"""Why does the srn128 train step sit well below the chip's matmul
ceiling?  Measures, on the attached accelerator:

  1. the full-width srn128 train step (bench config) under each
     attention-engine assignment (global auto / all-xla / deep-pallas)
     and under larger microbatches (the HBM freed by ema_bf16 training
     states makes these feasible) — median-of-3 windows each;
  2. a per-site ATTENTION microbench: every (level, L, D) attention
     shape the 128^2 X-UNet actually runs, timed standalone for both
     engines — the per-level timing breakdown that either finds a
     faster engine assignment or proves the op-mix-ceiling argument
     the way srn64's was proven (runs/roofline_r4.json).

Writes one JSON to --out (default runs/profile128_r5.json).

Usage:  python -m tools.profile128 [--steps 6] [--out PATH]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


def _median_window(fn, sync, windows=3):
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        sync(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2], times


def time_train_step(cfg, n_steps: int):
    """Median seconds/step of the jitted srn128 train step."""
    import jax

    from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import create_train_state, make_train_step
    from diff3d_tpu.train.trainer import init_params

    env = make_mesh(cfg.mesh)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    state = jax.device_put(state, env.state_shardings(state))
    ds = SyntheticDataset(num_objects=8, num_views=16,
                          imgsize=cfg.model.H, seed=0)
    raw = next(InfiniteLoader(ds, cfg.train.global_batch, seed=0))
    batch = jax.device_put(
        {"imgs": raw["imgs"], "R": raw["R"], "T": raw["T"], "K": raw["K"]},
        env.batch())
    step_fn = make_train_step(model, cfg, env)

    def run():
        nonlocal state
        for _ in range(n_steps):
            state, metrics = step_fn(state, batch, rng)
        return metrics["loss"]

    float(run())                     # compile + warm
    med, times = _median_window(lambda: run(), lambda l: float(l))
    return med / n_steps, [t / n_steps for t in times]


def attention_sites(cfg_model):
    """Every distinct (level, L, D) self/cross-attention shape the
    X-UNet runs at this config.  ``blocks`` = XUNetBlocks with attention
    at the level (down + up); ``sdpa_calls`` = blocks x 2, since each
    block runs a self AND a cross attention (models/layers.py:205-208)
    — use sdpa_calls for any per-step cost attribution."""
    sites = []
    num_res = len(cfg_model.ch_mult)
    for lvl in range(num_res):
        if lvl not in cfg_model.attn_levels:
            continue
        h = cfg_model.H // (2 ** lvl)
        dim = cfg_model.ch * cfg_model.ch_mult[lvl]
        blocks = cfg_model.num_res_blocks + (cfg_model.num_res_blocks + 1)
        sites.append({"level": lvl, "L": h * h, "dim": dim,
                      "D": dim // cfg_model.attn_heads,
                      "blocks": blocks, "sdpa_calls": 2 * blocks})
    if num_res in cfg_model.attn_levels:    # middle block
        h = cfg_model.H // (2 ** (num_res - 1))
        dim = cfg_model.ch * cfg_model.ch_mult[-1]
        sites.append({"level": num_res, "L": h * h, "dim": dim,
                      "D": dim // cfg_model.attn_heads, "blocks": 1,
                      "sdpa_calls": 2})
    return sites


def microbench_site(B, L, heads, D, impl: str, n_iters: int = 8):
    """Seconds per sdpa call of one attention shape under one engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from diff3d_tpu.ops.attention import sdpa

    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(B, L, heads, D) * 0.1, jnp.bfloat16)
               for _ in range(3))

    @jax.jit
    def many(q, k, v):
        out = q
        for _ in range(n_iters):
            out = sdpa(out, k, v, impl=impl)
        return out

    sync = lambda o: float(jnp.sum(o.astype(jnp.float32)))
    sync(many(q, k, v))
    med, _ = _median_window(lambda: many(q, k, v), sync)
    return med / n_iters


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--out", default="runs/profile128_r5.json")
    p.add_argument("--skip_microbench", action="store_true")
    args = p.parse_args(argv)

    import jax

    from diff3d_tpu.config import srn128_config

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()
    platform = jax.devices()[0].platform
    base = srn128_config()

    # FLOPs/step from the compiled step's own cost analysis is not
    # reliable on all backends; reuse roofline_r4's measured figure
    # instead: bench srn128 b16x4 measured 33.6 TFLOP/s at 0.636 s/step
    # => ~21.4 TFLOP per b16 step.  Throughput comparisons
    # below are RELATIVE (sec/step), which needs no flop model.
    results = {"platform": platform, "sites": attention_sites(base.model),
               "train_variants": [], "attn_microbench": []}

    def _flush():
        # written after every measurement: a fault or window kill
        # mid-run still leaves every completed datapoint on disk.
        # tmp + rename so a kill mid-write can't truncate earlier data.
        import os
        with open(args.out + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
        os.replace(args.out + ".tmp", args.out)

    def variant(name, global_batch, accum, attn_impl_levels=None):
        cfg = dataclasses.replace(
            base,
            model=dataclasses.replace(
                base.model, remat=True,
                attn_impl_levels=attn_impl_levels),
            train=dataclasses.replace(base.train,
                                      global_batch=global_batch,
                                      accum_steps=accum))
        try:
            sec, windows = time_train_step(cfg, args.steps)
            rec = {"name": name, "global_batch": global_batch,
                   "accum": accum, "attn_impl_levels": attn_impl_levels,
                   "sec_per_step": round(sec, 4),
                   "examples_per_sec": round(global_batch / sec, 2),
                   "windows_sec_per_step": [round(t, 4) for t in windows]}
        except Exception as e:
            rec = {"name": name, "global_batch": global_batch,
                   "accum": accum,
                   "error": str(e).splitlines()[0][:200]}
        results["train_variants"].append(rec)
        print(json.dumps(rec), file=sys.stderr)
        _flush()

    # Baseline = bench's srn128 config, then the two levers.
    variant("b16x4_auto", 16, 4)
    variant("b16x2_auto", 16, 2)          # microbatch 8
    variant("b32x4_auto", 32, 4)          # microbatch 8, more examples
    variant("b32x2_auto", 32, 2)          # microbatch 16
    n_lvl = base.model.num_resolutions
    variant("b16x4_allxla", 16, 4, tuple(["xla"] * n_lvl))
    # index n_lvl-1 covers BOTH level-3 and the middle block (the two
    # D=256 sites) — see ModelConfig.attn_impl_at's middle clamping.
    variant("b16x4_deep_pallas", 16, 4,
            tuple(["auto"] * (n_lvl - 1) + ["pallas"]))
    # level 2 separately: D=128 at L=1024, below auto's L>=4096 pallas
    # threshold — the site the measured auto policy might be wrong about
    variant("b16x4_lvl2_pallas", 16, 4,
            tuple(["auto", "auto", "pallas", "auto"][:n_lvl]))

    if not args.skip_microbench:
        # B_eff = microbatch * 2 frames at the bench baseline (16/4=4 -> 8)
        for B_eff in (8, 16):
            for s in results["sites"]:
                for impl in ("xla", "pallas"):
                    try:
                        sec = microbench_site(B_eff, s["L"],
                                              base.model.attn_heads,
                                              s["D"], impl)
                        rec = {"B": B_eff, **s, "impl": impl,
                               "sec_per_call": round(sec, 6)}
                    except Exception as e:
                        rec = {"B": B_eff, **s, "impl": impl,
                               "error": str(e).splitlines()[0][:200]}
                    results["attn_microbench"].append(rec)
                    print(json.dumps(rec), file=sys.stderr)
                    _flush()

    _flush()
    print(json.dumps({"wrote": args.out,
                      "variants": len(results["train_variants"])}))


if __name__ == "__main__":
    main()
