"""Procedural stand-in dataset with the same sample contract as
:class:`diff3d_tpu.data.srn.SRNDataset`.

No reference counterpart — the reference has no test fixtures at all
(SURVEY.md §4).  Used by unit tests, the benchmark, and smoke training when
the real SRN zips are absent.  Cameras are placed on a sphere looking at the
origin with SRN-like intrinsics, and images are a deterministic function of
the object id and view angle (a shaded gradient), so two views of the same
"object" are geometrically consistent enough to overfit.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _look_at(cam_pos: np.ndarray) -> np.ndarray:
    """World-from-camera rotation for a camera at ``cam_pos`` looking at the
    origin (OpenCV convention: +z forward, +y down)."""
    fwd = -cam_pos / np.linalg.norm(cam_pos)
    up = np.array([0.0, 0.0, 1.0])
    if abs(fwd @ up) > 0.99:
        up = np.array([0.0, 1.0, 0.0])
    right = np.cross(fwd, up)
    right /= np.linalg.norm(right)
    down = np.cross(fwd, right)
    return np.stack([right, down, fwd], axis=1)


class SyntheticDataset:
    """``sample(idx, rng)`` matches :class:`SRNDataset`'s contract."""

    def __init__(self, num_objects: int = 8, num_views: int = 16,
                 imgsize: int = 16, seed: int = 0, sample_views: int = 2):
        self.num_objects = num_objects
        self.num_views = num_views
        self.imgsize = imgsize
        self.sample_views = sample_views
        self.ids = list(range(num_objects))   # SRNDataset contract
        s = imgsize
        # SRN-style intrinsics: focal ~ s, principal point at the center.
        self.K = np.array([[s * 1.2, 0.0, s / 2],
                           [0.0, s * 1.2, s / 2],
                           [0.0, 0.0, 1.0]], np.float32)
        rng = np.random.default_rng(seed)
        self._phases = rng.uniform(0, 2 * np.pi, size=(num_objects, 3))

    def __len__(self) -> int:
        return self.num_objects

    def _view(self, obj: int, view: int):
        theta = 2 * np.pi * view / self.num_views
        phi = 0.3 + 0.2 * np.sin(self._phases[obj, 0] + view)
        r = 2.0
        cam = r * np.array([np.cos(theta) * np.cos(phi),
                            np.sin(theta) * np.cos(phi),
                            np.sin(phi)], np.float32)
        R = _look_at(cam).astype(np.float32)
        s = self.imgsize
        yy, xx = np.meshgrid(np.linspace(-1, 1, s), np.linspace(-1, 1, s),
                             indexing="ij")
        ph = self._phases[obj]
        img = np.stack([np.sin(3 * xx + theta + ph[0]),
                        np.cos(2 * yy - theta + ph[1]),
                        np.sin(xx * yy + ph[2] + phi)], axis=-1)
        return img.astype(np.float32), R, cam

    def sample(self, idx: int, rng: np.random.Generator) -> Dict[str, np.ndarray]:
        views = rng.choice(self.num_views, size=self.sample_views,
                           replace=False)
        imgs, Rs, Ts = zip(*(self._view(idx, v) for v in views))
        return {"imgs": np.stack(imgs), "R": np.stack(Rs),
                "T": np.stack(Ts), "K": self.K}

    def all_views(self, obj: int) -> Dict[str, np.ndarray]:
        imgs, Rs, Ts = zip(*(self._view(obj, v)
                             for v in range(self.num_views)))
        return {"imgs": np.stack(imgs), "R": np.stack(Rs),
                "T": np.stack(Ts), "K": self.K}


def _rays_np(R: np.ndarray, t: np.ndarray, K: np.ndarray, H: int, W: int):
    """Numpy mirror of :func:`diff3d_tpu.geometry.pinhole_rays` (same
    pixel-center + world-from-camera convention; equality is asserted in
    tests/test_data.py so the renderer and the model's conditioning always
    agree on camera geometry)."""
    u = np.arange(W, dtype=np.float64) + 0.5
    v = np.arange(H, dtype=np.float64) + 0.5
    uu, vv = np.meshgrid(u, v)
    px = np.stack([uu, vv, np.ones_like(uu)], axis=-1)        # [H, W, 3]
    dir_cam = np.einsum("ij,hwj->hwi", np.linalg.inv(K), px)
    dirs = np.einsum("ij,hwj->hwi", R, dir_cam)
    dirs /= np.linalg.norm(dirs, axis=-1, keepdims=True)
    pos = np.broadcast_to(t, dirs.shape)
    return pos, dirs


def render_spheres(pos: np.ndarray, dirs: np.ndarray,
                   centers: np.ndarray, radii: np.ndarray,
                   colors: np.ndarray) -> np.ndarray:
    """Lambertian-shaded ray-traced spheres; returns ``[H, W, 3]`` in
    [-1, 1].  Nearest positive ray-sphere intersection wins; misses get a
    view-direction gradient background."""
    oc = pos[None] - centers[:, None, None]                   # [S, H, W, 3]
    b = 2.0 * np.einsum("shwc,hwc->shw", oc, dirs)
    c = np.einsum("shwc,shwc->shw", oc, oc) - radii[:, None, None] ** 2
    disc = b * b - 4.0 * c
    hit = disc > 0
    t_hit = np.where(hit, (-b - np.sqrt(np.maximum(disc, 0.0))) / 2.0,
                     np.inf)
    t_hit = np.where(t_hit > 1e-6, t_hit, np.inf)             # behind cam
    nearest = np.argmin(t_hit, axis=0)                        # [H, W]
    depth = np.take_along_axis(t_hit, nearest[None], axis=0)[0]
    any_hit = np.isfinite(depth)
    depth = np.where(any_hit, depth, 1.0)     # keep the miss math finite

    p = pos + depth[..., None] * dirs                         # hit points
    ctr = centers[nearest]                                    # [H, W, 3]
    n = p - ctr
    n /= np.maximum(np.linalg.norm(n, axis=-1, keepdims=True), 1e-9)
    light = np.array([0.577, 0.577, 0.577])
    lam = 0.35 + 0.65 * np.clip(n @ light, 0.0, 1.0)
    col = colors[nearest] * lam[..., None]

    bg = np.stack([0.15 * dirs[..., 2] - 0.55,
                   0.15 * dirs[..., 2] - 0.45,
                   0.25 * dirs[..., 2] - 0.35], axis=-1)
    img = np.where(any_hit[..., None], col, bg)
    return np.clip(img, -1.0, 1.0).astype(np.float32)


class SyntheticScenesDataset:
    """True-3D procedural dataset: each object is a handful of colored
    spheres, views are ray-traced renders from the SAME pinhole geometry
    the model conditions on.  Unlike :class:`SyntheticDataset`'s angle-
    parameterised patterns, these images ARE projections of a consistent
    3D scene, so novel-view synthesis on them is the real task at toy
    scale — used for the quality-evidence training runs when
    the SRN zips are absent.  Same ``sample``/``all_views`` contract as
    :class:`diff3d_tpu.data.srn.SRNDataset`.
    """

    def __init__(self, num_objects: int = 16, num_views: int = 24,
                 imgsize: int = 64, seed: int = 0, sample_views: int = 2,
                 spheres_per_object: int = 4):
        self.num_objects = num_objects
        self.num_views = num_views
        self.imgsize = imgsize
        self.sample_views = sample_views
        self.ids = list(range(num_objects))   # SRNDataset contract
        s = imgsize
        self.K = np.array([[s * 1.2, 0.0, s / 2],
                           [0.0, s * 1.2, s / 2],
                           [0.0, 0.0, 1.0]], np.float32)
        # Per-object generators keyed (seed, obj): object i's scene is
        # invariant to num_objects, so eval sets of different sizes score
        # the SAME scenes (a single (num_objects, ...) draw would shift
        # every object after a size change).
        n_sph = spheres_per_object
        per_obj = [np.random.default_rng((seed, i))
                   for i in range(num_objects)]
        self._centers = np.stack(
            [r.uniform(-0.55, 0.55, (n_sph, 3)) for r in per_obj])
        self._radii = np.stack(
            [r.uniform(0.18, 0.4, n_sph) for r in per_obj])
        self._colors = np.stack(
            [r.uniform(-0.2, 1.0, (n_sph, 3)) for r in per_obj])
        self._phase = np.array([r.uniform(0, 2 * np.pi) for r in per_obj])

    def __len__(self) -> int:
        return self.num_objects

    def _view(self, obj: int, view: int):
        theta = 2 * np.pi * view / self.num_views + self._phase[obj]
        phi = 0.25 + 0.2 * np.sin(self._phase[obj] + 2.1 * view)
        cam = 2.6 * np.array([np.cos(theta) * np.cos(phi),
                              np.sin(theta) * np.cos(phi),
                              np.sin(phi)])
        R = _look_at(cam)
        pos, dirs = _rays_np(R, cam, self.K.astype(np.float64),
                             self.imgsize, self.imgsize)
        img = render_spheres(pos, dirs, self._centers[obj],
                             self._radii[obj], self._colors[obj])
        return img, R.astype(np.float32), cam.astype(np.float32)

    def sample(self, idx: int, rng: np.random.Generator) -> Dict[str, np.ndarray]:
        views = rng.choice(self.num_views, size=self.sample_views,
                           replace=False)
        imgs, Rs, Ts = zip(*(self._view(idx, v) for v in views))
        return {"imgs": np.stack(imgs), "R": np.stack(Rs),
                "T": np.stack(Ts), "K": self.K}

    def all_views(self, obj: int) -> Dict[str, np.ndarray]:
        imgs, Rs, Ts = zip(*(self._view(obj, v)
                             for v in range(self.num_views)))
        return {"imgs": np.stack(imgs), "R": np.stack(Rs),
                "T": np.stack(Ts), "K": self.K}
