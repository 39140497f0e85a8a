"""Microbench the fused GroupNorm->FiLM/SiLU Pallas kernels against the
unfused XLA composition, roofline-anchored.

    python tools/bench_kernels.py [--out runs/bench_kernels.json]
                                  [--dtype bf16|f32] [--interpret]
                                  [--backward]

Shapes are the X-UNet's REAL GroupNorm sites: one point per
(level tokens, level width) pair of the srn64 and srn128 configs at the
train-step's flattened batch (``N = global_batch/8 * 2 frames`` per
chip), in both "fire" variants the model uses (the ResnetBlock entry
GroupNorm->SiLU and the GroupNorm->FiLM->SiLU epilogue).

The fused kernel is memory-bound (~10 flops/element vs 8-16 bytes
moved), so the honest headline is achieved HBM bandwidth and its
fraction of the chip's datasheet peak — reported NEXT TO the measured
compute ceiling imported from ``runs/roofline_r4.json`` (the same
anchoring DESIGN.md §13 uses for MFU claims): ``speedup_vs_xla`` says
whether fusion won, ``pct_of_hbm_peak`` says how close to the roof the
win sits, and the roofline block says what roof the numbers were scored
against.

``--interpret`` (forced on CPU) runs the kernels through the Pallas
interpreter: timings are then compile-path smoke only — the mode exists
to commit a parity-checked artifact (``max_abs_err`` per point) from
hosts with no TPU attached, and the record says so.
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

# Datasheet HBM bandwidth (GB/s) by ``device_kind``; quoted (not
# measured) — the denominator for pct_of_hbm_peak on TPU.  A TPU that is
# not in the table is an error, not a v5e.  Non-TPU platforms get null.
HBM_GBPS_BY_KIND = {"TPU v5 lite": 819.0}   # Google Cloud docs, "TPU v5e"

ROOFLINE_PATH = "runs/roofline_r4.json"

#: (label, N, L, C): flattened [B*F, H*W, C] GroupNorm sites per level.
#: N = 16 flattened frames/chip (global batch 128 / 8 way * 2 frames at
#: srn64; srn128's per-chip batch is smaller but the site shapes are
#: what matter).  srn128's shallow levels hit the same C at 4x L.
SHAPES = [
    ("srn64_L0", 16, 4096, 128, 32),
    ("srn64_L1", 16, 1024, 256, 32),
    ("srn64_L2", 16, 256, 256, 32),
    ("srn64_L3", 16, 64, 512, 32),
    ("srn128_L0", 4, 16384, 256, 32),
    ("srn128_L3", 4, 256, 1024, 32),
]

VARIANTS = [
    ("gn_silu", False, True),       # ResnetBlock entry GroupNorm->SiLU
    ("gn_film_silu", True, True),   # FiLM epilogue (scale/shift fire)
]


def _time_windows(fn, sync, windows=3, reps=8):
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        sync(out)
        # graftlint: disable-next-line=GL106(sync() concretizes via float fetch - value-synced)
        times.append((time.perf_counter() - t0) / reps)
    return sorted(times)


def _roofline_ref():
    try:
        with open(ROOFLINE_PATH) as f:
            r = json.load(f)
        return {
            "path": ROOFLINE_PATH,
            "device": r.get("device"),
            "measured_ceiling_bf16_tflops":
                r.get("measured_ceiling_bf16_tflops"),
            "datasheet_peak_bf16_tflops":
                r.get("datasheet_peak_bf16_tflops"),
        }
    except Exception as e:
        return {"path": ROOFLINE_PATH,
                "error": str(e).splitlines()[0][:200]}


def _bench_point(label, N, L, C, G, film, silu, dtype_name, interpret,
                 backward):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from diff3d_tpu.ops.pallas_film import fused_groupnorm, xla_groupnorm

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
    dsize = jnp.dtype(dtype).itemsize
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(N, L, C), dtype)
    gamma = jnp.asarray(rs.randn(C), jnp.float32)
    beta = jnp.asarray(rs.randn(C), jnp.float32)
    kw = dict(num_groups=G, silu=silu)
    if film:
        kw["scale"] = jnp.asarray(0.3 * rs.randn(N, L, C), dtype)
        kw["shift"] = jnp.asarray(0.3 * rs.randn(N, L, C), dtype)

    def call(fn, extra):
        if backward:
            def loss(x, gamma, beta):
                return jnp.mean(fn(x, gamma, beta, **kw,
                                   **extra).astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss))
        return jax.jit(
            lambda x, gamma, beta: fn(x, gamma, beta, **kw, **extra))

    jp = call(fused_groupnorm, {"interpret": interpret})
    jx = call(xla_groupnorm, {})
    f_pallas = lambda: jp(x, gamma, beta)
    f_xla = lambda: jx(x, gamma, beta)
    sync = lambda y: float(jnp.sum(y.astype(jnp.float32)))
    out_p, out_x = f_pallas(), f_xla()
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32)
                                - out_x.astype(jnp.float32))))
    sync(out_p)

    t_pallas = _time_windows(f_pallas, sync)
    t_xla = _time_windows(f_xla, sync)
    med_p = t_pallas[len(t_pallas) // 2]
    med_x = t_xla[len(t_xla) // 2]

    # Fused-path HBM traffic: x in + y out (+ scale/shift in when the
    # FiLM port fires); backward reads x/g and writes dx (+ds/dt).
    # gamma/beta and the group stats live in VMEM — that's the point.
    streams = (2 + 2 * int(film)) * (1 + 2 * int(backward))
    bytes_moved = streams * N * L * C * dsize
    gbps = bytes_moved / med_p / 1e9
    return {
        "site": label,
        "shape": [N, L, C],
        "num_groups": G,
        "dtype": dtype_name,
        "variant": ("gn_film_silu" if film else "gn_silu")
                   + ("_bwd" if backward else ""),
        "pallas_ms": round(med_p * 1e3, 4),
        "xla_ms": round(med_x * 1e3, 4),
        "speedup_vs_xla": round(med_x / med_p, 3) if med_p else None,
        "bytes_moved": bytes_moved,
        "achieved_gbps": round(gbps, 2),
        "max_abs_err": err,
        "windows_ms": {
            "pallas": [round(t * 1e3, 4) for t in t_pallas],
            "xla": [round(t * 1e3, 4) for t in t_xla],
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default=None,
                   help="write the JSON record here (default: stdout)")
    p.add_argument("--dtype", default="bf16", choices=("bf16", "f32"))
    p.add_argument("--interpret", action="store_true",
                   help="Pallas interpreter (parity smoke; forced on "
                        "non-TPU platforms)")
    p.add_argument("--backward", action="store_true",
                   help="also time the fwd+bwd (custom_vjp) path")
    p.add_argument("--shapes", default=None,
                   help="comma list of site labels to run (default all)")
    args = p.parse_args(argv)

    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    dev = jax.devices()[0]
    interpret = args.interpret or dev.platform != "tpu"
    # Interpret mode at the real 4096/16384-token sites is minutes per
    # point for numbers nobody reads; shrink to the smallest sites and
    # a scaled-down big-L so the smoke still crosses row-tile bounds.
    shapes = SHAPES
    if interpret:
        shapes = [("srn64_L3", 2, 64, 512, 32),
                  ("srn64_L2_small", 2, 200, 256, 32)]
    if args.shapes:
        want = {s.strip() for s in args.shapes.split(",")}
        shapes = [s for s in shapes if s[0] in want]

    points = []
    passes = [False] + ([True] if args.backward else [])
    for label, N, L, C, G in shapes:
        for vname, film, silu in VARIANTS:
            for backward in passes:
                pt = _bench_point(label, N, L, C, G, film, silu,
                                  args.dtype, interpret, backward)
                points.append(pt)
                print(f"bench_kernels: {label} {pt['variant']} "
                      f"pallas {pt['pallas_ms']}ms xla {pt['xla_ms']}ms "
                      f"({pt['speedup_vs_xla']}x)", file=sys.stderr)

    hbm = None
    if dev.platform == "tpu":
        if dev.device_kind not in HBM_GBPS_BY_KIND:
            raise SystemExit(
                f"bench_kernels: no HBM peak on file for device_kind "
                f"{dev.device_kind!r}; add it to HBM_GBPS_BY_KIND with "
                "its source")
        hbm = HBM_GBPS_BY_KIND[dev.device_kind]
    for pt in points:
        pt["pct_of_hbm_peak"] = (round(100 * pt["achieved_gbps"] / hbm, 1)
                                 if hbm else None)
    record = {
        "metric": "fused_groupnorm_kernels",
        "device": str(dev.device_kind if hasattr(dev, "device_kind")
                      else dev),
        "platform": dev.platform,
        "mode": "interpret" if interpret else "compiled",
        "note": ("interpret-mode smoke: parity evidence only, timings "
                 "are the interpreter's, not the chip's"
                 if interpret else None),
        "hbm_gbps_datasheet": hbm,
        "roofline_ref": _roofline_ref(),
        "points": points,
    }
    out = json.dumps(record, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
        print(f"bench_kernels: wrote {args.out}", file=sys.stderr)
    else:
        print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
