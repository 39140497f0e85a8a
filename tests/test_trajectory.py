"""Trajectory service: camera-path math, the multi-view consistency
metric, TrajectoryRequest streaming semantics, and the serving e2e.

Four layers, cheapest first:

* **Pose math** — property-style checks over radii/elevations: every
  generated pose is exactly SO(3) with det +1, orbits close seamlessly
  (the virtual frame ``n`` coincides with frame 0), look-at centers the
  target on the principal point, and the convention matches
  ``data/synthetic.py::_look_at`` bit-for-bit.
* **Consistency metric** — ray-traced sphere scenes (exact multi-view
  geometry by construction) rendered along a 16-pose orbit: the
  plane-homography reprojection score must rank the ordered sequence
  strictly better than shuffled frames and per-frame identity drift.
* **TrajectoryRequest units** — the commit buffer: in-order commits,
  out-of-order drops, blocking ``wait_frames``, backfill on resolve,
  error delivery only after committed frames are drained.
* **Serving e2e** on the CPU backend — frames streamed in commit order
  and bit-identical to ``Sampler.synthesize``; incremental HTTP poll
  (``?from=K``) and chunked NDJSON streaming; typed backpressure; and
  the acceptance run: a 3-replica fleet serves an 8-pose orbit whose
  frames are bit-identical to the sequential prefix oracle, with zero
  record migration across the per-replica ledgers.
"""

import dataclasses
import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from diff3d_tpu.config import MeshConfig, ServingConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import SyntheticDataset
from diff3d_tpu.data.synthetic import _look_at, _rays_np, render_spheres
from diff3d_tpu.evaluation import (plane_homography,
                                   reprojection_consistency, warp_frame)
from diff3d_tpu.models import XUNet
from diff3d_tpu.parallel import make_mesh
from diff3d_tpu.sampling import Sampler
from diff3d_tpu.serving import (FleetService, QueueFullError,
                                ServingService, TrajectoryRequest,
                                ViewRequest)
from diff3d_tpu.serving.scheduler import Scheduler
from diff3d_tpu.train.trainer import init_params
from diff3d_tpu.trajectory import (PATH_KINDS, keyframe_path, look_at,
                                   orbit_path, path_from_spec, spiral_path,
                                   trajectory_views)

RADII = (0.5, 2.0, 7.5)
ELEVATIONS = (-45.0, 0.0, 20.0, 70.0)


def _assert_so3(R, atol=1e-5):
    R = np.asarray(R, np.float64)
    eye = np.broadcast_to(np.eye(3), R.shape)
    np.testing.assert_allclose(R @ np.swapaxes(R, -1, -2), eye, atol=atol)
    np.testing.assert_allclose(np.linalg.det(R), 1.0, atol=atol)


def _project(K, R, T, point):
    """Pixel coordinates of a world point (OpenCV convention)."""
    x_cam = np.asarray(R, np.float64).T @ (np.asarray(point, np.float64)
                                           - np.asarray(T, np.float64))
    px = np.asarray(K, np.float64) @ x_cam
    return px[:2] / px[2], x_cam[2]


def _K(size):
    return np.array([[size * 1.2, 0, size / 2],
                     [0, size * 1.2, size / 2],
                     [0, 0, 1]], np.float32)


# ---------------------------------------------------------------------------
# Pose math
# ---------------------------------------------------------------------------


def test_orbit_poses_are_so3_over_parameter_grid():
    for radius in RADII:
        for elev in ELEVATIONS:
            R, T = orbit_path(7, radius=radius, elevation_deg=elev,
                              azimuth0_deg=33.0)
            assert R.shape == (7, 3, 3) and T.shape == (7, 3)
            assert R.dtype == np.float32 and T.dtype == np.float32
            _assert_so3(R)
            np.testing.assert_allclose(np.linalg.norm(T, axis=-1),
                                       radius, rtol=1e-5)


def test_orbit_closes_seamlessly_without_duplicated_endpoint():
    """A one-turn orbit's virtual frame ``n`` is frame 0 (loops as
    video), and frame ``n-1`` is NOT frame 0 (no duplicated endpoint)."""
    for n in (4, 9, 16):
        for radius, elev in ((0.5, -45.0), (2.0, 20.0), (7.5, 70.0)):
            R, T = orbit_path(n, radius=radius, elevation_deg=elev)
            Rn, Tn = orbit_path(1, radius=radius, elevation_deg=elev,
                                azimuth0_deg=360.0)
            np.testing.assert_allclose(Rn[0], R[0], atol=1e-6)
            np.testing.assert_allclose(Tn[0], T[0], atol=1e-5)
            assert not np.allclose(T[n - 1], T[0], atol=1e-3)


def test_paths_center_the_target_on_the_principal_point():
    K = _K(16)
    target = (0.3, -0.2, 0.1)
    paths = [
        orbit_path(5, radius=2.0, elevation_deg=15.0, target=target),
        spiral_path(5, radius=3.0, target=target),
        keyframe_path([[2.0, 0, 0.5], [0, 2.0, 0.5], [-2.0, 0, 1.0]], 5,
                      targets=[target] * 3),
    ]
    for R, T in paths:
        _assert_so3(R)
        for i in range(R.shape[0]):
            uv, depth = _project(K, R[i], T[i], target)
            assert depth > 0, "target must be in front (+z forward)"
            np.testing.assert_allclose(uv, [K[0, 2], K[1, 2]], atol=1e-3)


def test_look_at_matches_synthetic_dataset_convention():
    """The serving path generators and the training data pipeline must
    agree on what a camera pose means."""
    r = np.random.RandomState(0)
    for _ in range(20):
        eye = r.uniform(-3, 3, 3)
        if np.linalg.norm(eye) < 0.5:
            continue
        np.testing.assert_allclose(look_at(eye), _look_at(eye), atol=1e-6)


def test_look_at_degenerate_inputs():
    with pytest.raises(ValueError):
        look_at((1.0, 2.0, 3.0), target=(1.0, 2.0, 3.0))
    # Straight-down view: the fallback up-vector keeps the frame
    # non-degenerate (same escape hatch as data/synthetic.py).
    R = look_at((0.0, 0.0, 2.0))
    assert np.all(np.isfinite(R))
    _assert_so3(R[None])


def test_spiral_sweeps_and_clamps_elevation():
    R, T = spiral_path(9, radius=2.0, elevation_start_deg=-10.0,
                       elevation_end_deg=45.0)
    el = np.rad2deg(np.arcsin(T[:, 2] / np.linalg.norm(T, axis=-1)))
    assert np.all(np.diff(el) > 0)                   # monotone rise
    np.testing.assert_allclose(el[0], -10.0, atol=1e-3)
    np.testing.assert_allclose(el[-1], 45.0, atol=1e-3)
    _, T2 = spiral_path(3, elevation_start_deg=-89.0,
                        elevation_end_deg=89.0)
    el2 = np.rad2deg(np.arcsin(T2[:, 2] / np.linalg.norm(T2, axis=-1)))
    assert np.all(np.abs(el2) <= 80.0 + 1e-3)        # pole clamp


def test_keyframe_path_interpolates_and_validates():
    keys = np.array([[2.0, 0, 0], [0, 2.0, 0], [0, 0, 2.0]])
    R, T = keyframe_path(keys, 5)
    _assert_so3(R)
    np.testing.assert_allclose(T[0], keys[0], atol=1e-6)
    np.testing.assert_allclose(T[2], keys[1], atol=1e-6)  # mid keyframe
    np.testing.assert_allclose(T[-1], keys[2], atol=1e-6)
    with pytest.raises(ValueError):
        keyframe_path(keys[:1], 5)                   # k < 2
    with pytest.raises(ValueError):
        keyframe_path(keys, 5, targets=keys)         # eye == target


def test_path_from_spec_grammar():
    R, T = path_from_spec({"kind": "orbit", "frames": 6, "radius": 3.0,
                           "elevation_deg": 10.0})
    Rd, Td = orbit_path(6, radius=3.0, elevation_deg=10.0)
    np.testing.assert_array_equal(R, Rd)
    np.testing.assert_array_equal(T, Td)
    path_from_spec({"kind": "keyframes", "frames": 4,
                    "keyframes": [[2, 0, 0], [0, 2, 0]]})
    assert set(PATH_KINDS) == {"orbit", "spiral", "keyframes"}
    with pytest.raises(ValueError, match="kind"):
        path_from_spec({"kind": "helix", "frames": 4})
    with pytest.raises(ValueError, match="frames"):
        path_from_spec({"kind": "orbit"})
    with pytest.raises(ValueError, match="unknown"):
        path_from_spec({"kind": "orbit", "frames": 4, "elevation": 10})
    with pytest.raises(ValueError):
        path_from_spec(["orbit", 4])


def test_trajectory_views_assembly():
    img = np.zeros((8, 8, 3), np.float32)
    R, T = orbit_path(3, radius=2.0)
    cond_R, cond_T = look_at((2.0, 0.1, 0.8)), np.array([2.0, 0.1, 0.8],
                                                        np.float32)
    v = trajectory_views(img, cond_R, cond_T, _K(8), R, T)
    assert v["imgs"].shape == (1, 8, 8, 3)
    assert v["R"].shape == (4, 3, 3) and v["T"].shape == (4, 3)
    np.testing.assert_array_equal(v["R"][0], cond_R)
    np.testing.assert_array_equal(v["R"][1:], R)
    with pytest.raises(ValueError):
        trajectory_views(np.zeros((8, 8)), cond_R, cond_T, _K(8), R, T)


# ---------------------------------------------------------------------------
# Multi-view consistency metric (exact geometry via ray-traced spheres)
# ---------------------------------------------------------------------------


def _sphere_orbit_frames(n, size=32, radius=2.6, elevation=20.0,
                         scene_seed=0):
    """Frames of a fixed sphere scene along an orbit: geometrically
    consistent by construction (one 3D scene, exact ray tracing)."""
    r = np.random.RandomState(scene_seed)
    centers = r.uniform(-0.35, 0.35, (3, 3))
    radii = r.uniform(0.25, 0.5, 3)
    colors = r.uniform(-0.6, 0.9, (3, 3))
    K = _K(size).astype(np.float64)
    R, T = orbit_path(n, radius=radius, elevation_deg=elevation)
    frames = [render_spheres(*_rays_np(R[i].astype(np.float64),
                                       T[i].astype(np.float64),
                                       K, size, size),
                             centers, radii, colors) for i in range(n)]
    return np.stack(frames).astype(np.float32), R, T, K.astype(np.float32)


def test_consistency_identical_views_score_near_zero():
    frames, R, T, K = _sphere_orbit_frames(2)
    score = reprojection_consistency(frames[[0, 0]], R[[0, 0]], T[[0, 0]],
                                     K)
    assert score["num_pairs"] == 1
    # Round-off at the exact image border may invalidate one row/col.
    assert score["valid_frac"] > 0.9
    assert score["consistency_l1"] < 1e-6
    assert score["consistency_psnr"] > 60.0


def test_consistency_ranks_ordered_above_shuffled_and_drift():
    """The regression-gate property: frames that do not share one 3D
    scene must score strictly worse.  16-pose orbits keep the adjacent
    baseline small enough for the plane approximation to discriminate."""
    n = 16
    frames, R, T, K = _sphere_orbit_frames(n)
    good = reprojection_consistency(frames, R, T, K)
    assert good["num_pairs"] == n - 1
    assert good["valid_frac"] > 0.5

    perm = np.random.RandomState(1).permutation(n)
    bad = reprojection_consistency(frames[perm], R, T, K)
    # Per-frame identity drift: frames alternate between two different
    # scenes under the same poses.
    other, _, _, _ = _sphere_orbit_frames(n, scene_seed=9)
    drifted = np.where((np.arange(n) % 2 == 0)[:, None, None, None],
                       frames, other)
    drift = reprojection_consistency(drifted, R, T, K)

    for worse in (bad, drift):
        assert good["consistency_l1"] < 0.8 * worse["consistency_l1"], (
            good["consistency_l1"], worse["consistency_l1"])
        assert good["consistency_psnr"] > worse["consistency_psnr"]


def test_consistency_guidance_axis_and_custom_pairs():
    frames, R, T, K = _sphere_orbit_frames(4)
    with_b = np.repeat(frames[:, None], 2, axis=1)   # [N, B, H, W, 3]
    a = reprojection_consistency(frames, R, T, K)
    b = reprojection_consistency(with_b, R, T, K)
    assert a["consistency_l1"] == b["consistency_l1"]  # lane 0 scored
    c = reprojection_consistency(frames, R, T, K, pairs=[(0, 2), (1, 3)])
    assert [(p["i"], p["j"]) for p in c["pairs"]] == [(0, 2), (1, 3)]


def test_consistency_validation_and_behind_camera():
    frames, R, T, K = _sphere_orbit_frames(3)
    with pytest.raises(ValueError, match="2 frames"):
        reprojection_consistency(frames[:1], R[:1], T[:1], K)
    with pytest.raises(ValueError, match="poses"):
        reprojection_consistency(frames, R[:2], T[:2], K)
    # Camera looking away from the target: the plane is behind it.
    eye = np.array([2.0, 0.0, 0.0])
    R_away = look_at(eye, target=2 * eye)
    with pytest.raises(ValueError, match="behind"):
        plane_homography(K, R_away, eye, R[1], T[1])


def test_warp_identity_homography_is_a_noop():
    frames, _, _, _ = _sphere_orbit_frames(1)
    warped, valid = warp_frame(frames[0], np.eye(3))
    assert valid.all()
    np.testing.assert_allclose(warped, frames[0], atol=1e-6)


# ---------------------------------------------------------------------------
# TrajectoryRequest commit-buffer semantics (no device work)
# ---------------------------------------------------------------------------


def _traj_req(n_frames=3, size=4, **kw):
    R, T = orbit_path(n_frames, radius=2.0)
    v = trajectory_views(np.zeros((size, size, 3), np.float32),
                         look_at((2.0, 0.0, 0.7)),
                         np.array([2.0, 0.0, 0.7], np.float32),
                         _K(size), R, T)
    return TrajectoryRequest(v, **kw)


def test_trajectory_request_commit_order_and_backfill():
    req = _traj_req(3)
    assert req.is_trajectory and req.n_frames == 3 and req.n_views == 4
    plain = ViewRequest({"imgs": np.zeros((2, 4, 4, 3), np.float32),
                         "R": np.stack([np.eye(3, dtype=np.float32)] * 2),
                         "T": np.zeros((2, 3), np.float32),
                         "K": _K(4)})
    assert not plain.is_trajectory
    plain._commit_frame(1, np.zeros(1))              # no-op, no error

    f0, f1 = np.full((1, 4, 4, 3), 0.1), np.full((1, 4, 4, 3), 0.2)
    req._commit_frame(1, f0)
    req._commit_frame(3, np.full((1, 4, 4, 3), 9.0))  # out of order: drop
    req._commit_frame(1, np.full((1, 4, 4, 3), 9.0))  # duplicate: drop
    assert req.frames_done() == 1
    np.testing.assert_array_equal(req.wait_frames(0, timeout=0)[0], f0)
    req._commit_frame(2, f1)
    got = req.frames_since(0)
    assert len(got) == 2
    np.testing.assert_array_equal(got[1], f1)

    # Resolve with the full result: frame 3 is backfilled, the already
    # streamed frames keep their identity.
    result = np.stack([f0[0], f1[0], np.full((4, 4, 3), 0.3)])
    req._resolve(result)
    assert req.frames_done() == 3
    np.testing.assert_array_equal(req.frames_since(2)[0], result[2])
    assert req.wait_frames(3, timeout=0) == []       # past the end, done


def test_trajectory_request_wait_blocks_until_commit():
    req = _traj_req(2)
    got = {}

    def consumer():
        got["frames"] = req.wait_frames(0, timeout=30)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    req._commit_frame(1, np.full((1, 4, 4, 3), 0.5))
    t.join(30)
    assert not t.is_alive() and len(got["frames"]) == 1
    assert req.wait_frames(1, timeout=0.01) == []    # timeout, not done


def test_trajectory_request_error_after_draining_committed_frames():
    req = _traj_req(3)
    f0 = np.full((1, 4, 4, 3), 0.1)
    req._commit_frame(1, f0)
    req._reject(RuntimeError("replica died"))
    # Frames that committed are still deliverable...
    np.testing.assert_array_equal(req.wait_frames(0, timeout=0)[0], f0)
    # ...and the error surfaces once the stream is drained.
    with pytest.raises(RuntimeError, match="replica died"):
        req.wait_frames(1, timeout=0)


def test_trajectory_backpressure_and_validation():
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        port=0, max_queue=1, max_views=4))
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    sampler = Sampler(model, params, cfg)
    stalled = ServingService(sampler, cfg)           # engine NOT started
    try:
        ds = SyntheticDataset(num_objects=1, num_views=2, imgsize=8)
        v = ds.all_views(0)
        payload = {"cond": {"img": v["imgs"][0], "R": v["R"][0],
                            "T": v["T"][0], "K": v["K"]},
                   "path": {"kind": "orbit", "frames": 3}}
        stalled.submit_trajectory(payload)
        with pytest.raises(QueueFullError):          # typed backpressure
            stalled.submit_trajectory(dict(payload, seed=2))
        with pytest.raises(ValueError, match="ceiling"):
            stalled.submit_trajectory(
                {**payload, "path": {"kind": "orbit", "frames": 9}})
        with pytest.raises(ValueError, match="kind"):
            stalled.submit_trajectory(
                {**payload, "path": {"kind": "helix", "frames": 3}})
        with pytest.raises(ValueError, match="cond"):
            stalled.submit_trajectory({"path": {"kind": "orbit",
                                                "frames": 3}})
    finally:
        stalled.scheduler.close()


# ---------------------------------------------------------------------------
# Serving e2e on the CPU backend
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traj_env():
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    sampler = Sampler(model, params, cfg)
    ds = SyntheticDataset(num_objects=2, num_views=3, imgsize=8)
    return cfg, model, params, sampler, ds


def _orbit_views(ds, obj, n_frames):
    """Trajectory views for an orbit around ``ds``'s object, conditioned
    on its view 0 (radius/elevation match the dataset's camera shell)."""
    v = ds.all_views(obj)
    T0 = np.asarray(v["T"][0], np.float64)
    radius = float(np.linalg.norm(T0))
    elevation = float(np.rad2deg(np.arcsin(T0[2] / radius)))
    path_R, path_T = orbit_path(n_frames, radius=radius,
                                elevation_deg=elevation,
                                azimuth0_deg=17.0)
    return trajectory_views(v["imgs"][0], v["R"][0], v["T"][0], v["K"],
                            path_R, path_T)


def _tile_imgs(tviews):
    """synthesize sizes the record from imgs.shape[0]: tile the
    conditioning image across all views (only imgs[0] is consumed)."""
    n = tviews["R"].shape[0]
    out = dict(tviews)
    out["imgs"] = np.broadcast_to(tviews["imgs"][:1],
                                  (n,) + tviews["imgs"].shape[1:])
    return out


def _serving(cfg, **over):
    serving = dict(port=0, max_batch=4, max_queue=8, max_wait_ms=50.0,
                   max_views=10, default_timeout_s=120.0,
                   result_cache_entries=0)
    serving.update(over)
    return dataclasses.replace(cfg, serving=ServingConfig(**serving))


@pytest.mark.lock_witness
def test_trajectory_streams_bit_identical_frames(traj_env, lock_witness):
    """Unsharded e2e: frames stream through ``wait_frames`` in commit
    order, and the assembled trajectory is bit-identical to the offline
    sampler with the same seed."""
    cfg, model, params, sampler, ds = traj_env
    service = ServingService(sampler, _serving(cfg)).start(
        serve_http=False)
    try:
        tviews = _orbit_views(ds, 0, 3)
        req = service.submit_trajectory({"views": tviews, "seed": 21,
                                         "session_id": "stream-0"})
        assert req.is_trajectory and req.n_frames == 3
        streamed, sent = [], 0
        while True:
            chunk = req.wait_frames(sent, timeout=120)
            if not chunk:
                break
            streamed.extend(chunk)
            sent += len(chunk)
        result = req.result(timeout=0)
        assert req.done() and sent == 3

        direct = sampler.synthesize(_tile_imgs(tviews),
                                    jax.random.PRNGKey(21))
        np.testing.assert_array_equal(result, direct)
        for k, frame in enumerate(streamed):         # commit order
            np.testing.assert_array_equal(frame, direct[k])

        snap = service.metrics_snapshot()
        assert snap["counters"]["serving_trajectory_requests_total"] == 1
        assert snap["counters"]["serving_trajectory_frames_total"] == 3
        assert snap["gauges"]["serving_active_trajectories"] == 0
        assert snap["engine"]["trajectories"] == []  # nothing in flight
    finally:
        service.stop()


def test_trajectory_sharded_engine_matches_sharded_sampler(traj_env):
    """Sharded e2e (data=2 mesh): the engine pads the trajectory to the
    lane multiple and the result still matches the sampler bitwise."""
    cfg, model, params, sampler, ds = traj_env
    env = make_mesh(MeshConfig(data_parallel=2, model_parallel=1),
                    devices=jax.devices()[:2])
    sh_sampler = Sampler(model, params, cfg, mesh=env)
    service = ServingService(sh_sampler, _serving(cfg)).start(
        serve_http=False)
    try:
        assert service.engine.lane_multiple == 2
        tviews = _orbit_views(ds, 1, 3)
        req = service.submit_trajectory({"views": tviews, "seed": 5})
        out = req.result(timeout=180)
        direct = sh_sampler.synthesize(_tile_imgs(tviews),
                                       jax.random.PRNGKey(5))
        np.testing.assert_array_equal(out, direct)
        assert req.frames_done() == 3
    finally:
        service.stop()


@pytest.mark.lock_witness
def test_trajectory_http_poll_and_ndjson_stream(traj_env, lock_witness):
    """The two HTTP streaming surfaces: incremental poll
    (``GET /result/<id>?from=K`` — gapless, repeat-free via ``next``)
    and chunked NDJSON (``POST /trajectory`` with ``stream: true``)."""
    cfg, model, params, sampler, ds = traj_env
    service = ServingService(sampler, _serving(cfg)).start(serve_http=True)
    try:
        base = f"http://127.0.0.1:{service.port}"
        tviews = _orbit_views(ds, 0, 3)
        wire_views = {k: np.asarray(v).tolist() for k, v in tviews.items()}

        def post(path, payload, timeout=180):
            req = urllib.request.Request(
                f"{base}{path}", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=timeout)

        # Async submit + incremental poll.
        with post("/trajectory", {"views": wire_views, "seed": 31,
                                  "block": False}) as r:
            assert r.status == 202
            body = json.loads(r.read())
            assert body["n_frames"] == 3
            rid = body["id"]
        polled, nxt = [], 0
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                    f"{base}/result/{rid}?from={nxt}", timeout=30) as r:
                assert r.status == 200
                poll = json.loads(r.read())
            assert poll["from"] == nxt
            assert poll["next"] == nxt + len(poll["frames"])
            polled.extend(poll["frames"])
            nxt = poll["next"]
            if poll["status"] == "done":
                break
            assert poll["status"] == "running"
            time.sleep(0.05)
        assert nxt == 3 and poll["frames_committed"] == 3
        direct = sampler.synthesize(_tile_imgs(tviews),
                                    jax.random.PRNGKey(31))
        np.testing.assert_array_equal(
            np.asarray(polled, np.float32), direct)
        # Terminal body carries trajectory progress too.
        with urllib.request.urlopen(f"{base}/result/{rid}",
                                    timeout=30) as r:
            final = json.loads(r.read())
        assert final["n_frames"] == final["frames_committed"] == 3
        np.testing.assert_array_equal(
            np.asarray(final["views"], np.float32), direct)

        # Chunked NDJSON stream: header, then one line per frame in
        # order, then the terminal done line.  Same seed as the polled
        # request, so `direct` is the expected payload again.
        with post("/trajectory", {"views": wire_views, "seed": 31,
                                  "stream": True}) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/x-ndjson"
            lines = [json.loads(l) for l in r.read().splitlines() if l]
        assert lines[0]["status"] == "streaming"
        assert lines[0]["n_frames"] == 3
        assert [l["frame"] for l in lines[1:-1]] == [0, 1, 2]
        assert lines[-1]["status"] == "done"
        assert lines[-1]["frames_committed"] == 3
        np.testing.assert_array_equal(
            np.asarray([l["view"] for l in lines[1:-1]], np.float32),
            direct)
    finally:
        service.stop()


@pytest.mark.lock_witness
def test_trajectory_cobatches_with_view_requests(traj_env, lock_witness):
    """Interleaving: a trajectory and a plain view request in the same
    bucket share compiled scan launches (occupancy > 1) and both stay
    bit-identical to their offline counterparts."""
    cfg, model, params, sampler, ds = traj_env
    service = ServingService(
        sampler, _serving(cfg, max_wait_ms=300.0)).start(serve_http=False)
    try:
        tviews = _orbit_views(ds, 0, 3)              # 4 views, capacity 4
        plain_views = ds.all_views(1)
        traj = service.submit_trajectory({"views": tviews, "seed": 41})
        plain = service.submit({"views": plain_views, "seed": 42,
                                "n_views": 4})
        t_out = traj.result(timeout=180)
        p_out = plain.result(timeout=180)
        np.testing.assert_array_equal(
            t_out, sampler.synthesize(_tile_imgs(tviews),
                                      jax.random.PRNGKey(41)))
        np.testing.assert_array_equal(
            p_out, sampler.synthesize(plain_views, jax.random.PRNGKey(42),
                                      max_views=4))
        occ = service.metrics_snapshot()["histograms"][
            "serving_batch_occupancy"]
        assert occ["max"] > 1, f"never co-batched: {occ}"
    finally:
        service.stop()


@pytest.mark.lock_witness
def test_fleet_8pose_orbit_oracle_parity_zero_migration(traj_env,
                                                        lock_witness):
    """Acceptance e2e: a 3-replica fleet serves an 8-pose orbit through
    the router.  Frames stream in commit order (incrementally — the
    consumer observes partial progress), the trajectory is bit-identical
    to the sequential prefix oracle (request k renders the first k path
    poses with the same seed; its last view equals trajectory frame
    k-1), everything lands on one owning replica (zero record
    migration), and per-trajectory progress rides the fleet snapshot."""
    cfg, model, params, sampler, ds = traj_env
    svc = FleetService.build(sampler, _serving(cfg, replicas=3),
                             n=3).start(serve_http=False)
    sid, seed, n_frames = "orbit-e2e", 77, 8
    try:
        tviews = _orbit_views(ds, 0, n_frames)       # 9 views

        # Sequential single-view oracle, sticky to the same session:
        # request k conditions on view 0 and renders path poses 1..k.
        # One oracle per record-capacity bucket (2, 4, 8, 16) keeps the
        # tier-1 budget: the prefix property is transitive, so matching
        # frames 0, 1, 3 and 7 pins the whole shared RNG stream.
        oracle_last = {}
        for k in (1, 2, 4, 8):
            req = svc.router.submit(ViewRequest(
                _tile_imgs(tviews), seed=seed, n_views=k + 1,
                session_id=sid))
            oracle_last[k] = req.result(timeout=300)[-1]

        traj = svc.submit_trajectory({"views": tviews, "seed": seed,
                                      "session_id": sid})
        batches, progress_seen, sent = [], set(), 0
        while True:
            chunk = traj.wait_frames(sent, timeout=300)
            if not chunk:
                break
            batches.append(len(chunk))
            sent += len(chunk)
            for rep in svc.replicas:
                for t in rep.snapshot()["trajectories"]:
                    progress_seen.add((t["session_id"], t["frames_done"]))
        result = traj.result(timeout=0)
        assert sent == n_frames

        # Streamed incrementally, not one terminal burst.
        assert len(batches) >= 2, batches
        # /fleet exposed mid-flight progress for this trajectory.
        assert any(s == sid and 0 < done < n_frames
                   for s, done in progress_seen), progress_seen

        # Bit-parity: frame k-1 == the prefix oracle's last view (the
        # autoregressive record + per-view key-split stream are shared).
        for k, last in oracle_last.items():
            np.testing.assert_array_equal(result[k - 1], last)

        # Zero migration: one ledger holds the session, with every
        # request (4 oracles + 1 trajectory) on it.
        ledgers = {r.name: r.session_records() for r in svc.replicas}
        holders = [n for n, led in ledgers.items() if sid in led]
        assert len(holders) == 1, f"{sid} migrated across {holders}"
        assert ledgers[holders[0]][sid] == 5
        # The owning replica's engine did all the trajectory work.
        owner = next(r for r in svc.replicas if r.name == holders[0])
        snap = owner.metrics.snapshot()
        assert snap["counters"][
            "serving_trajectory_requests_total"] == 1
        assert snap["counters"][
            "serving_trajectory_frames_total"] == n_frames
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Frame-sequence writer (utils/frames.py)
# ---------------------------------------------------------------------------


def test_save_frame_sequence_writes_frames_and_contact_sheet(tmp_path):
    import os

    from PIL import Image

    from diff3d_tpu.utils import save_frame_sequence

    frames = np.linspace(-1, 1, 5 * 8 * 8 * 3, dtype=np.float32)
    frames = frames.reshape(5, 8, 8, 3)
    out = save_frame_sequence(str(tmp_path / "seq"), frames, columns=3)
    assert len(out["frames"]) == 5
    assert [os.path.basename(p) for p in out["frames"]] == [
        f"frame_{k:03d}.png" for k in range(5)]
    for p in out["frames"]:
        assert Image.open(p).size == (8, 8)
    sheet = Image.open(out["contact_sheet"])
    assert sheet.size == (3 * 8, 2 * 8)              # 3 cols x 2 rows

    # Guidance axis: lane 0 is written; no contact sheet on request.
    out2 = save_frame_sequence(str(tmp_path / "seq_b"),
                               np.repeat(frames[:, None], 2, axis=1),
                               contact_sheet=False)
    assert out2["contact_sheet"] is None
    a = np.asarray(Image.open(out["frames"][0]))
    b = np.asarray(Image.open(out2["frames"][0]))
    np.testing.assert_array_equal(a, b)

    with pytest.raises(ValueError):
        save_frame_sequence(str(tmp_path / "e"), frames[:0])
    with pytest.raises(ValueError):
        save_frame_sequence(str(tmp_path / "e"), frames[..., :2])


# ---------------------------------------------------------------------------
# eval_cli --orbit (slow: trains a checkpoint first)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_eval_cli_orbit_consistency_readout(tmp_path):
    """--orbit N renders a turntable per object and lands the
    reprojection-consistency numbers (plus frame PNGs under --save_dir)
    in the eval record."""
    import os

    from diff3d_tpu.cli import eval_cli, train_cli

    wd = str(tmp_path)
    train_cli.main(["--synthetic", "--config", "test", "--steps", "2",
                    "--batch", "8", "--workdir", wd, "--num_workers", "0"])
    out = str(tmp_path / "eval.jsonl")
    save = str(tmp_path / "art")
    eval_cli.main(["--model", os.path.join(wd, "checkpoints"),
                   "--synthetic_scenes", "--config", "test",
                   "--objects", "2", "--steps", "2", "--max_views", "2",
                   "--orbit", "3", "--orbit_objects", "1",
                   "--save_dir", save, "--out", out])
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    oc = rec["orbit_consistency"]
    assert oc["frames"] == 3 and oc["objects"] == 1
    assert oc["consistency_l1"] is None or np.isfinite(
        oc["consistency_l1"])
    (entry,) = oc["per_object"]
    assert entry["radius"] > 0
    assert os.path.exists(os.path.join(entry["frames_dir"],
                                       "frame_000.png"))
    assert os.path.exists(os.path.join(entry["frames_dir"],
                                       "contact_sheet.png"))
