"""The one general generator of inputs: posed views of procedural objects,
made from ``--seed`` and the parameters of a traffic file
(``benchmark/traffic/<name>.json``).  A later cell brings a new data file,
never new code here.

``ViewDataset`` follows the sample contract the program's loaders read
(``ids``, ``len``, ``sample(idx, rng)``, ``all_views(obj)``): ``imgs
[V,H,W,3]`` float32 in [-1, 1], ``R [V,3,3]`` world-from-camera, ``T
[V,3]`` camera position, ``K [3,3]``.  Cameras sit on a sphere and look
at the origin; an image is a smooth function of object and view, so every
row of a batch differs.
"""

from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """Parameters of traffic mix ``name``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _look_at(cam: np.ndarray) -> np.ndarray:
    fwd = -cam / np.linalg.norm(cam)
    up = np.array([0.0, 0.0, 1.0])
    if abs(fwd @ up) > 0.99:
        up = np.array([0.0, 1.0, 0.0])
    right = np.cross(fwd, up)
    right /= np.linalg.norm(right)
    return np.stack([right, np.cross(fwd, right), fwd], axis=1)


class ViewDataset:
    def __init__(self, seed: int, num_objects: int, num_views: int,
                 imgsize: int, sample_views: int = 2):
        self.num_objects, self.num_views = num_objects, num_views
        self.imgsize, self.sample_views = imgsize, sample_views
        self.ids = list(range(num_objects))
        s = imgsize
        self.K = np.array([[1.2 * s, 0, s / 2], [0, 1.2 * s, s / 2],
                           [0, 0, 1]], np.float32)
        rng = np.random.default_rng([int(seed), 0x76696577])
        self._phase = rng.uniform(0, 2 * np.pi, (num_objects, 6))
        self._freq = rng.uniform(1.0, 4.0, (num_objects, 3))
        lin = np.linspace(-1, 1, s)
        self._yy, self._xx = np.meshgrid(lin, lin, indexing="ij")

    def __len__(self) -> int:
        return self.num_objects

    def _view(self, obj: int, view: int):
        ph, fr = self._phase[obj], self._freq[obj]
        theta = 2 * np.pi * view / self.num_views + ph[3]
        phi = 0.3 + 0.25 * np.sin(ph[4] + 1.7 * view)
        cam = 2.0 * np.array([np.cos(theta) * np.cos(phi),
                              np.sin(theta) * np.cos(phi), np.sin(phi)])
        xx, yy = self._xx, self._yy
        img = np.stack([np.sin(fr[0] * xx + theta + ph[0]),
                        np.cos(fr[1] * yy - theta + ph[1]),
                        np.sin(fr[2] * xx * yy + ph[2] + phi)], axis=-1)
        return (img.astype(np.float32), _look_at(cam).astype(np.float32),
                cam.astype(np.float32))

    def _pack(self, obj: int, views) -> Dict[str, np.ndarray]:
        imgs, Rs, Ts = zip(*(self._view(obj, int(v)) for v in views))
        return {"imgs": np.stack(imgs), "R": np.stack(Rs),
                "T": np.stack(Ts), "K": self.K}

    def sample(self, idx: int, rng: np.random.Generator):
        return self._pack(idx, rng.choice(self.num_views,
                                          size=self.sample_views,
                                          replace=False))

    def all_views(self, obj: int):
        return self._pack(obj, range(self.num_views))
