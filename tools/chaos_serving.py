"""Injected-fault soak for the serving layer.

Drives the in-process service (scheduler + engine, no HTTP) with
synthetic requests while a :class:`diff3d_tpu.testing.faults.FaultInjector`
randomly fails and stalls device dispatches, then clears the faults and
checks the engine recovers.  The survival report counts every submitted
request into exactly one terminal bucket:

  * ``completed``        — resolved with a result,
  * ``failed_retryable`` — rejected with a typed RetryableError (the
    client could resubmit: EngineStepError, EngineOverloaded, ...),
  * ``failed_other``     — any non-retryable error (a contract breach
    under pure transient faults),
  * ``hung``             — future unresolved within the client budget,
  * ``lost``             — future STILL unresolved after a final drain.

Exit status is 0 iff ``failed_other == hung == lost == 0`` and the
engine's health is back to ``ok`` after the recovery window — the
fault-tolerance contract of DESIGN.md §7.

Usage (CPU):
    JAX_PLATFORMS=cpu python tools/chaos_serving.py \
        --requests 24 --fault-rate 0.3 --slow-rate 0.1 --json

Set ``--slow-s`` above ``--watchdog-s`` to exercise watchdog trips
instead of mere latency.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def _synthetic_views(n_views: int, size: int, seed: int):
    import numpy as np

    r = np.random.RandomState(seed)
    return {
        "imgs": r.randn(n_views, size, size, 3).astype(np.float32),
        "R": np.broadcast_to(np.eye(3, dtype=np.float32),
                             (n_views, 3, 3)).copy(),
        "T": r.randn(n_views, 3).astype(np.float32),
        "K": np.array([[size * 1.2, 0, size / 2],
                       [0, size * 1.2, size / 2],
                       [0, 0, 1]], np.float32),
    }


def _build(args):
    import jax

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.config import ServingConfig
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.serving import ServingService
    from diff3d_tpu.testing.faults import FaultInjector, wrap_sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": config_lib.srn64_config,
           "srn128": config_lib.srn128_config,
           "test": config_lib.test_config}[args.config]()
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        max_batch=4, max_queue=max(32, args.requests),
        max_wait_ms=30.0, max_views=6,
        default_timeout_s=args.timeout_s,
        watchdog_timeout_s=args.watchdog_s,
        step_retry_attempts=2, step_retry_backoff_s=0.05,
        degraded_recovery_steps=2, retry_after_s=1.0,
        result_cache_entries=0))     # a soak must not replay results
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    sampler = Sampler(model, params, cfg)
    inj = FaultInjector(seed=args.seed)
    service = ServingService(wrap_sampler(sampler, inj), cfg)
    return service, inj, cfg, int(sampler.w.shape[0])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", choices=["srn64", "srn128", "test"],
                   default="test")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--fault-rate", type=float, default=0.3,
                   help="per-dispatch probability of an injected step "
                        "exception")
    p.add_argument("--slow-rate", type=float, default=0.1,
                   help="per-dispatch probability of an injected stall")
    p.add_argument("--slow-s", type=float, default=0.4,
                   help="injected stall duration; set above --watchdog-s "
                        "to force watchdog trips")
    p.add_argument("--watchdog-s", type=float, default=2.0)
    p.add_argument("--timeout_s", type=float, default=120.0,
                   help="per-request deadline and client wait budget")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", action="store_true",
                   help="emit the survival report as one JSON line on "
                        "stdout")
    args = p.parse_args(argv)

    service, inj, cfg, guidance_B = _build(args)
    service.start(serve_http=False)

    from diff3d_tpu.runtime.retry import RetryableError
    from diff3d_tpu.sampling import record_capacity
    from diff3d_tpu.serving.engine import lane_count
    from diff3d_tpu.serving.scheduler import ViewRequest

    # Pre-compile every (bucket, lanes) shape traffic can launch so an
    # XLA compile can't masquerade as a stuck step under the watchdog.
    # The injector has no specs yet, so warmup dispatches run clean.
    eng = service.engine
    n_views_cycle = (3, 4, 5)
    t0 = time.perf_counter()
    for nv in sorted(set(n_views_cycle)):
        bucket = (cfg.model.H, cfg.model.W, record_capacity(nv))
        for lanes in {lane_count(n, eng.max_batch, eng.lane_multiple)
                      for n in (1, 2, eng.max_batch)}:
            eng.programs.warmup(bucket, lanes, guidance_B)
    print(f"chaos_serving: warmed programs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    # Health-transition recorder (sampled, 20ms).
    transitions, stop_poll = [], threading.Event()

    def _poll():
        last = None
        while not stop_poll.is_set():
            h = eng.health
            if h != last:
                transitions.append(h)
                last = h
            time.sleep(0.02)

    poller = threading.Thread(target=_poll, daemon=True)
    poller.start()

    inj.add("engine.step", prob=args.fault_rate)
    inj.add("engine.step", prob=args.slow_rate, kind="slow",
            delay_s=args.slow_s)

    views = [_synthetic_views(n_views_cycle[i % len(n_views_cycle)],
                              cfg.model.H, i)
             for i in range(args.requests)]
    counts = {"submitted": 0, "completed": 0, "failed_retryable": 0,
              "failed_other": 0, "hung": 0}
    errors = []
    lock = threading.Lock()
    reqs, waiters = [], []

    def waiter(req):
        try:
            req.result(timeout=args.timeout_s + 30)
            with lock:
                counts["completed"] += 1
        except Exception as e:
            with lock:
                if not req.done():
                    counts["hung"] += 1
                elif isinstance(e, RetryableError):
                    counts["failed_retryable"] += 1
                else:
                    counts["failed_other"] += 1
                errors.append(f"{type(e).__name__}: {e}")

    wall0 = time.perf_counter()
    for i, v in enumerate(views):
        req = ViewRequest(v, seed=1000 + i,
                          n_views=n_views_cycle[i % len(n_views_cycle)])
        try:
            eng.submit(req)
        except Exception as e:
            with lock:
                if isinstance(e, RetryableError):
                    counts["failed_retryable"] += 1
                else:
                    counts["failed_other"] += 1
                errors.append(f"submit {type(e).__name__}: {e}")
            counts["submitted"] += 1
            continue
        counts["submitted"] += 1
        reqs.append(req)
        w = threading.Thread(target=waiter, args=(req,), daemon=True)
        w.start()
        waiters.append(w)
        time.sleep(0.01)
    for w in waiters:
        w.join()
    wall = time.perf_counter() - wall0

    # Recovery window: faults off, a couple of clean probes, health must
    # return to ok.
    inj.clear("engine.step")
    probe_fail = 0
    for i in range(2):
        try:
            eng.submit(ViewRequest(_synthetic_views(3, cfg.model.H, 9000 + i),
                                   seed=9000 + i, n_views=3)
                       ).result(timeout=args.timeout_s)
        except Exception as e:
            probe_fail += 1
            errors.append(f"probe {type(e).__name__}: {e}")
    deadline = time.monotonic() + 60.0
    while eng.health != "ok" and time.monotonic() < deadline:
        time.sleep(0.05)

    lost = sum(1 for r in reqs if not r.done())
    snap = service.metrics_snapshot()
    stop_poll.set()
    poller.join(2)
    final_health = eng.health
    service.stop()

    c = snap["counters"]
    record = {
        "soak": "chaos_serving",
        "seed": args.seed,
        "fault_rate": args.fault_rate,
        "slow_rate": args.slow_rate,
        "slow_s": args.slow_s,
        "watchdog_s": args.watchdog_s,
        "wall_s": round(wall, 2),
        **counts,
        "lost": lost,
        "probe_failures": probe_fail,
        "injected_faults": inj.fired.get("engine.step", 0),
        "step_faults": c.get("serving_engine_step_faults_total", 0),
        "watchdog_trips": c.get("serving_engine_watchdog_trips_total", 0),
        "engine_restarts": c.get("serving_engine_restarts_total", 0),
        "shed": c.get("serving_requests_shed_total", 0),
        "health_transitions": transitions,
        "final_health": final_health,
        "error_sample": errors[:5],
    }
    ok = (counts["failed_other"] == 0 and counts["hung"] == 0
          and lost == 0 and probe_fail == 0 and final_health == "ok")
    record["survived"] = ok
    print(f"chaos_serving: {counts['completed']}/{counts['submitted']} "
          f"completed, {counts['failed_retryable']} retryable-failed, "
          f"{counts['failed_other']} other, {counts['hung']} hung, "
          f"{lost} lost; {record['injected_faults']} faults injected, "
          f"{record['watchdog_trips']} watchdog trips, final health "
          f"{final_health} -> {'SURVIVED' if ok else 'FAILED'}",
          file=sys.stderr)
    if args.json:
        print(json.dumps(record))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
