"""128^2 sampler execution: compile + time s/view.

The sampler (16384-token attention inside the compiled scan, reference
hot spot /root/reference/xunet.py:199-208) at the flagship resolution,
with random-init params at a given width; reports steady-state s/view.

Usage: python tools/smoke_srn128_sampler.py [--full_width] [--views 3]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--full_width", action="store_true",
                   help="paper width ch=256 (default: the reduced "
                        "ch64/emb512/nrb2 quality-run width)")
    p.add_argument("--views", type=int, default=3)
    p.add_argument("--timesteps", type=int, default=256)
    p.add_argument("--scan_chunks", type=int, default=4,
                   help="device executions per view scan (must divide "
                        "timesteps; bit-identical to 1 — several shorter "
                        "executions per view instead of one long one)")
    args = p.parse_args()

    import dataclasses

    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.data import SyntheticScenesDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = config_lib.srn128_config()
    if not args.full_width:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(
                cfg.model, ch=64, emb_ch=512, num_res_blocks=2))
    cfg = dataclasses.replace(
        cfg, diffusion=dataclasses.replace(cfg.diffusion,
                                           timesteps=args.timesteps))

    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params / 1e6:.1f}M  H={cfg.model.H}  "
          f"timesteps={args.timesteps}")

    ds = SyntheticScenesDataset(num_objects=1, num_views=args.views + 1,
                                imgsize=cfg.model.H, seed=0)
    views = ds.all_views(0)
    sampler = Sampler(model, params, cfg,
                      scan_chunks=args.scan_chunks)

    # The record buffer is sized to the next power of two of max_views, so
    # a DIFFERENT max_views can mean a fresh jit signature.  Warm up at
    # the SAME capacity as the timed run, or the "steady" numbers would
    # silently include minutes of 128^2 recompile.
    n = args.views + 1
    t0 = time.time()
    out = sampler.synthesize(views, jax.random.PRNGKey(1), max_views=n)
    # graftlint: disable-next-line=GL106(synthesize fetches the record to host before returning - value-synced)
    t_first = time.time() - t0
    print(f"{args.views} views (incl. compile): {t_first:.1f}s  "
          f"out {out.shape}")

    t0 = time.time()
    out = sampler.synthesize(views, jax.random.PRNGKey(2), max_views=n)
    # graftlint: disable-next-line=GL106(synthesize fetches the record to host before returning - value-synced)
    dt = time.time() - t0
    print(f"steady: {args.views} views in {dt:.1f}s -> "
          f"{dt / args.views:.2f} s/view")
    import numpy as np
    assert np.isfinite(np.asarray(out)).all(), "non-finite sampler output"
    print("OK: finite output at 128^2")


if __name__ == "__main__":
    main()
