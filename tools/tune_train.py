"""Measure train-step throughput across config variants on the attached
accelerator, to pick the fastest default for ``bench.py``.

Usage: python tools/tune_train.py [--config srn64|srn128] [variant ...]

Each variant is ``batch,accum,remat,policy,attn`` e.g. ``128,2,1,nothing,auto``.
With no args, runs a standard sweep at the srn64 config.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

CONFIG = "srn64"


def run_variant(global_batch: int, accum: int, remat: bool, policy: str,
                attn: str, n_steps: int = 10) -> float:
    import jax

    from diff3d_tpu import config as config_mod

    srn64_config = getattr(config_mod, f"{CONFIG}_config")
    from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import create_train_state, make_train_step
    from diff3d_tpu.train.trainer import init_params

    cfg = srn64_config()
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, remat=remat,
                                  remat_policy=policy, attn_impl=attn),
        train=dataclasses.replace(cfg.train, global_batch=global_batch,
                                  accum_steps=accum))

    env = make_mesh(cfg.mesh)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    state = jax.device_put(state, env.state_shardings(state))

    ds = SyntheticDataset(num_objects=8, num_views=16,
                          imgsize=cfg.model.H, seed=0)
    raw = next(InfiniteLoader(ds, global_batch, seed=0))
    batch = jax.device_put(
        {"imgs": raw["imgs"], "R": raw["R"], "T": raw["T"], "K": raw["K"]},
        env.batch())

    step_fn = make_train_step(model, cfg, env)
    for _ in range(2):
        state, metrics = step_fn(state, batch, rng)
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step_fn(state, batch, rng)
    float(metrics["loss"])
    return n_steps / (time.perf_counter() - t0)


def main() -> None:
    global CONFIG
    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    if len(sys.argv) > 2 and sys.argv[1] == "--config":
        CONFIG = sys.argv[2]
        del sys.argv[1:3]
    if len(sys.argv) > 1:
        variants = []
        for arg in sys.argv[1:]:
            b, a, r, p, at = arg.split(",")
            variants.append((int(b), int(a), bool(int(r)), p, at))
    else:
        variants = [
            (128, 2, True, "nothing", "auto"),   # current bench default
            (128, 2, True, "dots", "auto"),
            (128, 1, True, "nothing", "auto"),
            (128, 2, True, "nothing", "xla"),
            (64, 1, True, "dots", "auto"),
            (64, 1, False, "nothing", "auto"),
        ]

    for (b, a, r, p, at) in variants:
        tag = f"b{b} accum{a} remat={int(r)} policy={p} attn={at}"
        try:
            sps = run_variant(b, a, r, p, at)
            print(f"{tag}: {sps:.3f} steps/s = {sps * b:.1f} examples/s",
                  flush=True)
        except Exception as e:
            msg = str(e).splitlines()[0][:160]
            print(f"{tag}: FAILED {msg}", flush=True)


if __name__ == "__main__":
    main()
