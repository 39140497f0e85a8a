import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.geometry import pinhole_rays, posenc_ddpm, posenc_nerf
from diff3d_tpu.geometry.posenc import posenc_nerf_channels


def test_posenc_ddpm_shape_and_values():
    t = jnp.array([0.0, 10.0])
    emb = posenc_ddpm(t, emb_ch=64, max_time=1.0)
    assert emb.shape == (2, 64)
    # t=0: sin part 0, cos part 1.
    np.testing.assert_allclose(emb[0, :32], np.zeros(32), atol=1e-6)
    np.testing.assert_allclose(emb[0, 32:], np.ones(32), atol=1e-6)
    # first frequency is 1.0 -> emb[...,0] = sin(1000 * t)
    np.testing.assert_allclose(emb[1, 0], np.sin(10.0 * 1000.0), rtol=1e-3)


def test_posenc_ddpm_max_time_scaling():
    t = jnp.array([500.0])
    a = posenc_ddpm(t, 32, max_time=1000.0)
    b = posenc_ddpm(jnp.array([0.5]), 32, max_time=1.0)
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_posenc_nerf_channels():
    x = jnp.zeros((2, 2, 4, 4, 3))
    assert posenc_nerf(x, 0, 15).shape[-1] == 93 == posenc_nerf_channels(0, 15)
    assert posenc_nerf(x, 0, 8).shape[-1] == 51 == posenc_nerf_channels(0, 8)
    assert posenc_nerf(x, 3, 3).shape[-1] == 3


def test_posenc_nerf_values_scale_major():
    # One pixel, x = (0.1, 0.2, 0.3): first 3 sin entries must be
    # sin(2^0 * x) (scale-major flatten, reference einops "(c d)").
    x = jnp.array([0.1, 0.2, 0.3])
    out = np.asarray(posenc_nerf(x[None], 0, 2))[0]
    assert out.shape == (3 + 2 * 3 * 2,)
    np.testing.assert_allclose(out[:3], x, rtol=1e-6)
    np.testing.assert_allclose(out[3:6], np.sin(x), rtol=1e-5)
    np.testing.assert_allclose(out[6:9], np.sin(2 * np.asarray(x)), rtol=1e-5)
    # the +pi/2 half is cosine
    np.testing.assert_allclose(out[9:12], np.cos(x), rtol=1e-5)


# ---------------------------------------------------------------------------
# visu3d oracle: an independent numpy transcription of the EXACT pipeline the
# reference runs at /root/reference/xunet.py:311-318 —
#     v3d.Camera(spec=v3d.PinholeCamera(resolution=(H, W), K=K),
#                world_from_cam=v3d.Transform(R=R, t=t)).rays()
# transcribed step by step from visu3d's public sources (the library is not
# installable in this zero-egress image):
#   * ``PinholeCamera.px_centers``  (visu3d/dc_arrays/camera_spec.py):
#     ``np.meshgrid(arange(w), arange(h), indexing='xy')`` stacked as
#     ``(coord_w, coord_h)`` then ``+ 0.5`` — pixel CENTERS, u along width;
#   * ``PinholeCamera.cam_from_px``: append homogeneous 1, multiply by
#     ``K^-1`` — camera frame is OpenCV ``[right, down, fwd]``, giving
#     un-normalized directions on the z=1 plane;
#   * ``Transform.__matmul__(Ray)``  (visu3d/dc_arrays/transformation.py):
#     ``pos' = R @ pos + t``, ``dir' = R @ dir`` (rotation only on dir);
#     ray origin is the camera center, i.e. pos = 0 -> t;
#   * ``Camera.rays(normalize=True)`` then ``Ray.normalize()``: dir / |dir|.
# Everything runs in float64, uses np.linalg.solve (not inv), and never
# calls into diff3d_tpu — so agreement with pinhole_rays is a genuine
# two-implementation check of the convention, not self-reference.
# ---------------------------------------------------------------------------


def _visu3d_rays_oracle(R, t, K, h, w):
    R, t, K = (np.asarray(a, np.float64) for a in (R, t, K))
    # px_centers: meshgrid indexing='xy', stack (w-coord, h-coord), + 0.5
    coord_w, coord_h = np.meshgrid(np.arange(w), np.arange(h),
                                   indexing="xy")
    points2d = np.stack([coord_w, coord_h], axis=-1) + 0.5      # [h, w, 2]
    # cam_from_px: homogeneous, K^-1 (solve against the stacked points)
    ones = np.ones(points2d.shape[:-1] + (1,))
    points2d_h = np.concatenate([points2d, ones], axis=-1)      # [h, w, 3]
    cam_dir = np.linalg.solve(
        K[None, None], points2d_h[..., None])[..., 0]           # [h, w, 3]
    # Transform @ Ray: pos = R @ 0 + t; dir = R @ cam_dir
    world_dir = np.einsum("ij,hwj->hwi", R, cam_dir)
    # Ray.normalize()
    world_dir = world_dir / np.linalg.norm(world_dir, axis=-1,
                                           keepdims=True)
    pos = np.broadcast_to(t, world_dir.shape)
    return pos, world_dir


def _srn_lookat_pose(position, up=(0.0, 0.0, 1.0)):
    """SRN-style world-from-camera pose: camera at ``position`` on the
    object sphere, optical axis (+z, OpenCV convention) through the
    origin — the geometry of SRN's ``pose/*.txt`` cam2world matrices."""
    p = np.asarray(position, np.float64)
    z = -p / np.linalg.norm(p)                    # forward: toward origin
    x = np.cross(np.asarray(up, np.float64), z)
    x = x / np.linalg.norm(x)
    y = np.cross(z, x)
    return np.stack([x, y, z], axis=-1), p        # columns = cam axes


# SRN-realistic rig: cameras on the r=1.3 view sphere (SRN cars layout),
# intrinsics f=131.25, c=64 at 128^2 (the SRN intrinsics.txt scale).
_SRN_POSITIONS = [
    (1.3, 0.0, 0.0),
    (0.0, -1.3, 0.0),
    (0.919, 0.919, 0.0),
    (0.75, -0.65, 0.86),      # elevated view
    (-0.4, 1.1, -0.55),       # below the equator
]
_SRN_K = np.array([[131.25, 0.0, 64.0],
                   [0.0, 131.25, 64.0],
                   [0.0, 0.0, 1.0]])


@pytest.mark.parametrize("position", _SRN_POSITIONS)
def test_pinhole_rays_match_visu3d_oracle(position):
    """Golden check against the transcribed visu3d pipeline (SURVEY.md §7
    'hard part #1'): a convention slip (pixel corner vs center, K^T,
    row-vs-column camera axes, unnormalized dirs) shifts every ray and
    fails here, independently of diff3d_tpu's own derivation."""
    from jax import enable_x64

    R, t = _srn_lookat_pose(position)
    oracle_pos, oracle_dir = _visu3d_rays_oracle(R, t, _SRN_K, 128, 128)

    with enable_x64():
        pos, dirs = pinhole_rays(jnp.asarray(R, jnp.float64),
                                 jnp.asarray(t, jnp.float64),
                                 jnp.asarray(_SRN_K, jnp.float64), 128, 128)
        np.testing.assert_allclose(np.asarray(pos), oracle_pos, atol=1e-9)
        np.testing.assert_allclose(np.asarray(dirs), oracle_dir, atol=1e-9)

    # The production path runs float32 on-device; it must sit on the same
    # convention to float32 accuracy.
    pos32, dirs32 = pinhole_rays(jnp.asarray(R, jnp.float32),
                                 jnp.asarray(t, jnp.float32),
                                 jnp.asarray(_SRN_K, jnp.float32), 128, 128)
    np.testing.assert_allclose(np.asarray(dirs32), oracle_dir, atol=2e-5)


def test_visu3d_oracle_sanity():
    """The oracle itself: center-of-image ray of a look-at camera points
    at the origin (the look-at construction and the +0.5 center offset
    compose correctly)."""
    R, t = _srn_lookat_pose((1.3, 0.0, 0.0))
    _, d = _visu3d_rays_oracle(R, t, _SRN_K, 128, 128)
    # principal point (u=v=64) lies between pixels 63 and 64; the mean of
    # the 4 center pixels' dirs points along -t (toward the origin).
    center = d[63:65, 63:65].mean((0, 1))
    center /= np.linalg.norm(center)
    np.testing.assert_allclose(center, -t / np.linalg.norm(t), atol=1e-4)


@pytest.fixture
def simple_cam():
    K = jnp.array([[100.0, 0.0, 32.0], [0.0, 100.0, 32.0], [0.0, 0.0, 1.0]])
    R = jnp.eye(3)
    t = jnp.array([1.0, 2.0, 3.0])
    return R, t, K


def test_pinhole_rays_identity_cam(simple_cam):
    R, t, K = simple_cam
    pos, dirs = pinhole_rays(R, t, K, 64, 64)
    assert pos.shape == (64, 64, 3) and dirs.shape == (64, 64, 3)
    # origins are the camera position everywhere
    np.testing.assert_allclose(np.asarray(pos), np.broadcast_to(t, (64, 64, 3)))
    # unit directions
    np.testing.assert_allclose(np.linalg.norm(dirs, axis=-1), 1.0, rtol=1e-5)
    # the pixel whose center hits the principal point looks along +z:
    # u = j + 0.5 = cx = 32 -> j = 31.5 — not integral, so check the ray
    # at pixel (31, 31): direction ((31.5-32)/100, (31.5-32)/100, 1)/norm
    expect = np.array([-0.005, -0.005, 1.0])
    expect /= np.linalg.norm(expect)
    np.testing.assert_allclose(np.asarray(dirs[31, 31]), expect, atol=1e-5)


def test_pinhole_rays_rotation(simple_cam):
    R0, t, K = simple_cam
    # 90-degree rotation about y: +z_cam -> +x_world
    Ry = jnp.array([[0.0, 0.0, 1.0], [0.0, 1.0, 0.0], [-1.0, 0.0, 0.0]])
    _, d0 = pinhole_rays(R0, t, K, 8, 8)
    _, d1 = pinhole_rays(Ry, t, K, 8, 8)
    np.testing.assert_allclose(
        np.asarray(d1), np.einsum("ij,hwj->hwi", np.asarray(Ry),
                                  np.asarray(d0)), atol=1e-5)


def test_pinhole_rays_batched(simple_cam):
    R, t, K = simple_cam
    Rb = jnp.broadcast_to(R, (4, 2, 3, 3))
    tb = jnp.broadcast_to(t, (4, 2, 3))
    Kb = jnp.broadcast_to(K, (4, 1, 3, 3))
    pos, dirs = pinhole_rays(Rb, tb, Kb, 16, 16)
    assert pos.shape == (4, 2, 16, 16, 3)
    assert dirs.shape == (4, 2, 16, 16, 3)
    single = pinhole_rays(R, t, K, 16, 16)[1]
    np.testing.assert_allclose(np.asarray(dirs[2, 1]), np.asarray(single),
                               atol=1e-6)
