"""Input-pipeline throughput: can the host loader feed the device?

Measures the REAL data path — SRN-format PNGs on disk, decoded by the
native C++ pool (``native/decoder.cpp``), 2-view sampling, uint8
quantization, collate — with no device in the loop, so the number is
the host's alone (see DESIGN.md §3).  Compare ``loader_examples_per_sec``
against the train step's device demand (the ledger's train cells, once
they exist): the pipeline sustains the step rate iff loader >= device
demand.

A synthetic SRN directory (objects x views of 64^2 PNGs, poses,
intrinsics) is generated under ``--workdir`` on first run and reused.

Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np


def make_srn_dir(root: str, n_objects: int, n_views: int, size: int) -> str:
    from PIL import Image

    d = os.path.join(root, f"srn_bench_{n_objects}x{n_views}_{size}")
    marker = os.path.join(d, ".complete")
    if os.path.exists(marker):
        return d
    rng = np.random.default_rng(0)
    K = np.array([[size * 1.2, 0, size / 2], [0, size * 1.2, size / 2],
                  [0, 0, 1.0]])
    for o in range(n_objects):
        obj = os.path.join(d, f"obj{o:04d}")
        for sub in ("rgb", "pose", "intrinsics"):
            os.makedirs(os.path.join(obj, sub), exist_ok=True)
        for v in range(n_views):
            name = f"{v:06d}"
            img = rng.integers(0, 255, (size, size, 3), dtype=np.uint8)
            Image.fromarray(img).save(
                os.path.join(obj, "rgb", f"{name}.png"))
            pose = np.eye(4)
            pose[:3, 3] = rng.normal(0, 1, 3)
            np.savetxt(os.path.join(obj, "pose", f"{name}.txt"),
                       pose.reshape(1, 16))
            np.savetxt(os.path.join(obj, "intrinsics", f"{name}.txt"),
                       K.reshape(1, 9))
    open(marker, "w").close()
    return d


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workdir", default="/tmp")
    p.add_argument("--objects", type=int, default=32)
    p.add_argument("--views", type=int, default=16)
    p.add_argument("--imgsize", type=int, default=64)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--batches", type=int, default=20)
    p.add_argument("--num_workers", type=int, default=8)
    args = p.parse_args()

    from diff3d_tpu.data import InfiniteLoader, SRNDataset

    d = make_srn_dir(args.workdir, args.objects, args.views, args.imgsize)
    ds = SRNDataset("train", d, None, imgsize=args.imgsize,
                    train_fraction=1.0)
    loader = InfiniteLoader(ds, args.batch, num_workers=args.num_workers)

    next(loader)                        # warm (index, pools, page cache)
    t0 = time.perf_counter()
    for _ in range(args.batches):
        b = next(loader)
    dt = time.perf_counter() - t0
    assert b["imgs"].dtype == np.uint8 and b["imgs"].shape[0] == args.batch

    from diff3d_tpu import native

    print(json.dumps({
        "metric": "input_pipeline_examples_per_sec",
        "value": round(args.batches * args.batch / dt, 1),
        "unit": "examples/s",
        "imgsize": args.imgsize,
        "batch": args.batch,
        "num_workers": args.num_workers,
        "native_decoder": native.available(),
        "n_cores": os.cpu_count(),
    }))


if __name__ == "__main__":
    main()
