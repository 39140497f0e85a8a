"""Readings that a cell's limits are set from, many seeds in one process
(set-up is long, the compiled programs are shared):

    python3 benchmark/calibrate.py --workload <cell> --seeds 101,102,... \\
        [--control-seeds 3] [--out chiprun_out/calib_<cell>.jsonl]

For every seed: the program against the float32 reference (the lower
reading).  For the first ``--control-seeds`` seeds also the control, the
reference computed in fp8 in the program's place, and (training) the
planted fault of half the batch left out, each against the float32
reference (the upper readings).  One JSON line per seed.  Not run by the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import run as harness
    harness.configure_cache()
    import jax

    bench, cell, config, mix = harness.load_cell(args.workload,
                                                 args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("calibrate: no accelerator", file=sys.stderr)
        return 3
    mod = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    out = open(args.out, "a") if args.out else None
    first = None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        d = mod.Driver(config=config, mix=mix, seed=seed,
                       chips=cell["chips"], spans=harness.Spans())
        d.setup(reuse=first)
        first = first or d
        t1 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed,
                "setup_s": t1 - t0,
                "memory_after_setup": harness.device_peak_bytes(
                    jax.devices()[0]),
                "readings": d.readings(args.seconds,
                                       control=n < args.control_seeds)}
        line["total_s"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    first.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
