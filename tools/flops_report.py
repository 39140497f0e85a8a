"""Report the compiled train step's FLOPs (XLA cost analysis) and the
achieved TFLOP/s at the measured step time — how much of the chip the
bench configs actually use.

    python tools/flops_report.py [--config srn64|srn128] [--ceiling 136.6]

srn64 runs the headline bench shape (batch 128, accum 2); srn128 the
north-star paper config shape (batch 16, accum 4 — the per-device
microbatch that fits one chip's HBM, bench.py).  ``--ceiling`` is the
sustained TFLOP/s to quote utilisation against (default 136.6: the bf16
8192³-matmul ceiling MEASURED on this chip by ``tools/roofline.py``,
committed as ``runs/roofline_r4.json``; v5e datasheet peak is ~197).
NOTE the model's own conv shapes cap near 35-38 TFLOP/s on this chip
(roofline.py conv sweep), so a step at ~38 is at its op-mix ceiling even
though it is far from the matmul ceiling — see docs/DESIGN.md §2.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# (global_batch, accum) per config — the shapes bench.py measures.
BENCH_SHAPE = {"srn64": (128, 2), "srn128": (16, 4)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", choices=["srn64", "srn128"],
                    default="srn64")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--ceiling", type=float, default=136.6,
                    help="sustained TFLOP/s to quote utilisation against")
    ap.add_argument("--attn_impl", default=None,
                    choices=["auto", "pallas", "xla"])
    args = ap.parse_args()

    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import create_train_state, make_train_step
    from diff3d_tpu.train.trainer import init_params

    global_batch, accum = BENCH_SHAPE[args.config]
    if args.batch is not None:
        global_batch = args.batch
    if args.accum is not None:
        accum = args.accum
    cfg = {"srn64": config_lib.srn64_config,
           "srn128": config_lib.srn128_config}[args.config]()
    model_over = {"remat": True}
    if args.attn_impl:
        model_over["attn_impl"] = args.attn_impl
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, **model_over),
        train=dataclasses.replace(cfg.train, global_batch=global_batch,
                                  accum_steps=accum))
    env = make_mesh(cfg.mesh)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    state = jax.device_put(state, env.state_shardings(state))
    ds = SyntheticDataset(num_objects=8, num_views=16, imgsize=cfg.model.H)
    raw = next(InfiniteLoader(ds, global_batch, seed=0))
    batch = jax.device_put(
        {"imgs": raw["imgs"], "R": raw["R"], "T": raw["T"], "K": raw["K"]},
        env.batch())

    # Donated state: the timed loop holds ONE live copy of the train
    # state (donate=False would double it and OOM the full-width srn128
    # state on a 16G chip).
    step_fn = make_train_step(model, cfg, env)
    for _ in range(2):
        state, metrics = step_fn(state, batch, rng)
    float(metrics["loss"])

    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step_fn(state, batch, rng)
    float(metrics["loss"])
    dt = (time.perf_counter() - t0) / n

    # Cost/comms extraction rides the shared analysis/ir.py path (the
    # shardcheck engine), on ABSTRACT args (ShapeDtypeStructs — no
    # device_get of the multi-GB state).
    # FLOPs come from the unsharded variant (same math modulo
    # collectives — the global-batch number, not a per-device shard);
    # the collective footprint comes from the REAL sharded step via its
    # ``.lower`` hook.
    from diff3d_tpu.analysis import ir as ir_lib

    fn = make_train_step(model, cfg, env=None, donate=False)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (state, batch))
    report = ir_lib.analyze_jitted(
        f"train_step_{args.config}", fn, abstract[0], abstract[1], rng)
    flops = float("nan") if report.flops is None else report.flops
    tflops = flops / dt / 1e12
    print(f"config: {args.config}  batch {global_batch} x accum {accum}  "
          f"attn_impl {cfg.model.attn_impl}")
    print(f"step time: {dt*1e3:.1f} ms  ({global_batch / dt:.1f} examples/s)")
    print(f"XLA cost-analysis flops/step: {flops:.3e}")
    print(f"achieved: {tflops:.1f} TFLOP/s "
          f"({100 * tflops / args.ceiling:.0f}% of the "
          f"{args.ceiling:.0f}-TFLOP/s ceiling)")
    try:
        sharded = ir_lib.analyze_lowered(
            f"train_step_{args.config}_sharded",
            step_fn.lower(abstract[0], abstract[1], rng))
        comms = ir_lib.comms_summary(sharded)
        per_op = ", ".join(
            f"{op} x{c['count']} ({c['bytes'] / 1e6:.1f} MB)"
            for op, c in comms["collectives"].items()) or "none"
        print(f"sharded-step collectives: {per_op}")
        print(f"sharded-step collective bytes/device/step: "
              f"{comms['total_collective_bytes'] / 1e6:.1f} MB")
    except Exception as e:  # comms are advisory; never kill the report
        print(f"sharded-step comms report unavailable: "
              f"{str(e).splitlines()[0]}")


if __name__ == "__main__":
    main()
