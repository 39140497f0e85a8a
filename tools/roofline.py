"""Measure the attached chip's ACHIEVABLE compute ceiling: bf16 (and f32)
matmul sweep plus one conv shape, synced per window, median-of-windows.

    python tools/roofline.py [--out runs/roofline.json]

Why this exists: utilisation claims need a measured denominator, not an
assumed one.  The best sustained TFLOP/s any shape reaches here IS the
measured ceiling of the machine it ran on, to be quoted next to the
chip's datasheet peak (keyed by ``device_kind``) so MFU claims are
anchored to evidence at both ends.

Method: for each (M, N, K) a jitted chain of ``steps`` dependent matmuls
(each output feeds the next via a cheap elementwise touch, defeating CSE
while keeping the chain's FLOPs = steps * 2MNK) is timed over >=3 windows;
per-shape TFLOP/s = median window.  The dependent chain means device-side
back-to-back execution — host latency amortises across the chain
exactly as it does across a train step's layers.
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


def _time_windows(fn, sync, windows: int = 3):
    """Call ``fn()`` (device work) ``windows`` times, value-syncing via
    ``sync(result)``; returns per-window seconds."""
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        sync(fn())
        # graftlint: disable-next-line=GL106(sync() concretizes via float(jnp.sum) - value-synced by the caller-supplied closure)
        times.append(time.perf_counter() - t0)
    return times


def _matmul_chain(M, N, K, dtype, steps, b_std: float):
    import jax
    import jax.numpy as jnp

    # One c@b step scales magnitudes by ~ b_std * sqrt(K) (sum of K
    # iid products); damp by its inverse so chain values stay in a
    # NORMAL float range for all 64 steps.  The old fixed 1e-3 drove
    # bf16 activations to zero within ~20 steps at large K — harmless
    # on the MXU (timing is data-independent) but not the 'bounded
    # magnitudes' the chain intends, and a backend with zero/denormal
    # fast paths would skew the number (ADVICE r4).  The multiply still
    # fuses into the matmul epilogue.
    damp = 1.0 / (b_std * (K ** 0.5))

    def chain(a, b):
        def body(c, _):
            c = jax.lax.dot(c, b, precision=None,
                            preferred_element_type=dtype)
            return c * jnp.asarray(damp, dtype), None

        c, _ = jax.lax.scan(body, a, None, length=steps)
        return c

    return jax.jit(chain)


def measure_matmul(M, N, K, dtype_name: str, steps: int = 64):
    import jax
    import jax.numpy as jnp
    import numpy as np

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randn(M, K) * 0.1, dtype)
    b = jnp.asarray(rs.randn(K, N) * 0.1, dtype)
    fn = _matmul_chain(M, N, K, dtype, steps, b_std=0.1)
    sync = lambda c: float(jnp.sum(c.astype(jnp.float32)))
    sync(fn(a, b))                                  # compile + warm
    times = _time_windows(lambda: fn(a, b), sync)
    flops = 2.0 * M * N * K * steps
    per_window = sorted(flops / t / 1e12 for t in times)
    return {
        "shape": [M, N, K], "dtype": dtype_name, "chain_steps": steps,
        "tflops_median": round(per_window[len(per_window) // 2], 2),
        "tflops_windows": [round(v, 2) for v in per_window],
    }


def measure_conv(B, H, W, Cin, Cout, k, dtype_name: str, steps: int = 32):
    """One NHWC conv shape (the X-UNet stem/block shape class)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(B, H, W, Cin) * 0.1, dtype)
    w = jnp.asarray(rs.randn(k, k, Cin, Cout) * 0.1, dtype)

    if Cin != Cout:
        raise ValueError("chain needs Cin == Cout")

    # Same normalising damping as _matmul_chain: one conv step scales
    # magnitudes by ~ w_std * sqrt(k*k*Cin) (sum over the receptive
    # field), so damp by its inverse to keep chain values in a normal
    # float range instead of flushing bf16 activations to zero.
    damp = 1.0 / (0.1 * (k * k * Cin) ** 0.5)

    def chain(x, w):
        def body(c, _):
            c = jax.lax.conv_general_dilated(
                c, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=dtype)
            return c * jnp.asarray(damp, dtype), None

        c, _ = jax.lax.scan(body, x, None, length=steps)
        return c

    fn = jax.jit(chain)
    sync = lambda c: float(jnp.sum(c.astype(jnp.float32)))
    sync(fn(x, w))
    times = _time_windows(lambda: fn(x, w), sync)
    flops = 2.0 * B * H * W * k * k * Cin * Cout * steps
    per_window = sorted(flops / t / 1e12 for t in times)
    return {
        "conv": [B, H, W, Cin, Cout, k], "dtype": dtype_name,
        "chain_steps": steps,
        "tflops_median": round(per_window[len(per_window) // 2], 2),
        "tflops_windows": [round(v, 2) for v in per_window],
    }


# MXU-saturating square shapes + one tall batch-like shape.  (Chained
# timing needs output shape == input shape, so K == N throughout.)
MATMUL_SHAPES = [
    (1024, 1024, 1024),
    (2048, 2048, 2048),
    (4096, 4096, 4096),
    (8192, 8192, 8192),
    (16384, 4096, 4096),
]
# X-UNet conv shape classes (B = microbatch * 2 frames folded together,
# as the model runs them): srn64's level-0 / level-1 shapes at a
# microbatch of 64 and two 256-channel shapes of the srn128 class.
CONV_SHAPES = [
    (128, 64, 64, 128, 128, 3),    # srn64 level 0 (ch=128) @ microbatch 64
    (128, 32, 32, 256, 256, 3),    # srn64 level 1
    (128, 64, 64, 256, 256, 3),    # srn128-class wide shallow conv
    (32, 64, 64, 256, 256, 3),     # same at small batch (latency-bound)
]


def datasheet_peak_bf16_tflops(device_kind: str) -> float:
    """The chip's datasheet bf16 peak from ``benchmark/peaks.json``, the
    one table (keyed by ``device_kind``, with its source).  A TPU that
    is not in it is an error, not a v5e."""
    with open(os.path.join(_REPO_ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(
            f"roofline: no peak on file for device_kind {device_kind!r}; "
            "add it to benchmark/peaks.json with its source")
    return peaks[device_kind]["flops_per_s"] / 1e12


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write JSON here too")
    ap.add_argument("--dtypes", default="bf16,f32")
    args = ap.parse_args()

    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    dev = jax.devices()[0]
    peak = (datasheet_peak_bf16_tflops(dev.device_kind)
            if dev.platform == "tpu" else None)
    result = {
        "device": str(dev), "platform": dev.platform,
        "device_kind": dev.device_kind,
        "datasheet_peak_bf16_tflops": peak,
        "matmul": [], "conv": [],
    }
    for dtype in args.dtypes.split(","):
        for M, N, K in MATMUL_SHAPES:
            try:
                r = measure_matmul(M, N, K, dtype)
            except Exception as e:  # OOM on the biggest shapes is fine
                r = {"shape": [M, N, K], "dtype": dtype,
                     "error": str(e).splitlines()[0][:120]}
            result["matmul"].append(r)
            print(json.dumps(r), file=sys.stderr)
        for conv_shape in CONV_SHAPES:
            try:
                r = measure_conv(*conv_shape, dtype)
            except Exception as e:
                r = {"conv": list(conv_shape), "dtype": dtype,
                     "error": str(e).splitlines()[0][:120]}
            result["conv"].append(r)
            print(json.dumps(r), file=sys.stderr)

    best = max((r["tflops_median"] for r in result["matmul"]
                if "tflops_median" in r and r["dtype"] == "bf16"),
               default=None)
    result["measured_ceiling_bf16_tflops"] = best
    if best and peak:
        result["ceiling_vs_datasheet"] = round(best / peak, 3)
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
