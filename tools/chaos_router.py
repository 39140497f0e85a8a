"""Injected-fault soak for the fleet router.

Drives an in-process :class:`~diff3d_tpu.serving.router.FleetService`
(N replicas, no HTTP) with concurrent multi-view *sessions* plus
sessionless traffic, then mid-run:

  * kills one session-owning replica through a seeded
    :class:`~diff3d_tpu.testing.faults.FaultInjector` ``kill`` spec
    (:func:`~diff3d_tpu.testing.faults.arm_replica`), and
  * runs a blue/green params rollout on an operator thread.

Every submitted request lands in exactly one terminal bucket
(completed / failed_retryable / failed_other / hung / lost, as in
``tools/chaos_serving.py``), and the router contract is checked on top:

  * zero record migration — each session's ledger entries live on
    exactly ONE replica (``Replica.session_records``),
  * sessions orphaned by the kill end in a typed
    :class:`~diff3d_tpu.serving.scheduler.SessionLost` naming the lost
    replica — never a hang, never a silent re-place,
  * sessionless traffic keeps completing on the survivors
    (``router_failover_total`` > 0 once a replica is dead),
  * surviving replicas report ``ok`` after the rollout + recovery
    window.

Exit status is 0 iff ``failed_other == hung == lost == migrations == 0``
and every surviving replica is healthy — the fleet contract of
DESIGN.md §14.

``--remote`` runs the same soak against a *cross-process* fleet
(DESIGN.md §19): each replica is a real ``worker_cli`` subprocess
pinned to a disjoint CPU device slice, fronted over the socket
transport, and the kill is a real ``SIGKILL`` of the victim's process
— the in-process kill sites only simulate death; this one delivers it.
The contract checked is identical: typed ``SessionLost`` naming the
victim, sessionless failover to the survivors, zero hung requests,
zero migrations (the dead worker's ledger survives in the router's
last-heartbeat cache, so the audit still sees its sessions).

Usage (CPU):
    JAX_PLATFORMS=cpu python tools/chaos_router.py \
        --replicas 3 --sessions 6 --views 3 --json
    JAX_PLATFORMS=cpu python tools/chaos_router.py \
        --remote --replicas 2 --sessions 4 --views 2 --json
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
    from diff3d_tpu.serving import FleetService
    from diff3d_tpu.testing.faults import FaultInjector
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": config_lib.srn64_config,
           "srn128": config_lib.srn128_config,
           "test": config_lib.test_config}[args.config]()
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        max_batch=4, max_queue=max(16, args.sessions * args.views),
        max_wait_ms=20.0, max_views=6,
        default_timeout_s=args.timeout_s,
        step_retry_attempts=2, step_retry_backoff_s=0.05,
        degraded_recovery_steps=2, retry_after_s=0.2,
        replicas=args.replicas,
        heartbeat_interval_s=0.1, heartbeat_timeout_s=2.0,
        result_cache_entries=0))     # a soak must not replay results
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    sampler = Sampler(model, params, cfg)
    inj = FaultInjector(seed=args.seed)
    if args.remote:
        service, procs = _build_remote_fleet(args, cfg)
    else:
        service = FleetService.build(sampler, cfg, params_version="v0")
        procs = {}
    return service, inj, cfg, sampler, procs


def _build_remote_fleet(args, cfg):
    """Spawn ``--replicas`` worker_cli subprocesses on disjoint CPU
    device slices and front them with RemoteReplicas — the fleet shape
    the in-process soak simulates, made real."""
    import json as json_lib
    import subprocess

    from diff3d_tpu.serving import FleetService
    from diff3d_tpu.serving.transport import RemoteReplica

    n = args.replicas
    host_devices = 8
    if n > host_devices:
        raise SystemExit(
            f"--remote --replicas {n}: at most {host_devices} workers "
            f"(one device each on the {host_devices}-virtual-device "
            "CPU backend)")
    per = host_devices // n
    # The children are pinned to the CPU on purpose, whatever this
    # process runs on: a TPU belongs to one process at a time, so worker
    # processes with device slices exist on the virtual CPU mesh only.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # workers pick their own device count
    procs = {}
    for i in range(n):
        lo, hi = i * per, (i + 1) * per - 1
        cmd = [sys.executable, "-m", "diff3d_tpu.cli.worker_cli",
               "--config", args.config, "--init", "random",
               "--devices", f"{lo}-{hi}", "--port", "0",
               "--name", f"w{i}", "--host_device_count",
               str(host_devices), "--timeout_s", str(args.timeout_s),
               "--max_views", "6"]
        if args.compile_cache:
            cmd += ["--compile_cache", args.compile_cache]
        procs[f"w{i}"] = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True)
    remotes = []
    for name, proc in procs.items():
        line = proc.stdout.readline()
        if not line:
            raise SystemExit(f"worker {name} died before its ready line")
        ready = json_lib.loads(line)
        print(f"chaos_router: worker {ready['name']} ready on "
              f"port {ready['port']}", file=sys.stderr)
        remotes.append(RemoteReplica(
            "127.0.0.1", ready["port"], name=ready["name"],
            heartbeat_interval_s=cfg.serving.heartbeat_interval_s,
            heartbeat_timeout_s=cfg.serving.heartbeat_timeout_s))
    return FleetService(remotes, cfg), procs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", choices=["srn64", "srn128", "test"],
                   default="test")
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--sessions", type=int, default=6,
                   help="concurrent multi-view object sessions")
    p.add_argument("--views", type=int, default=3,
                   help="sequential views per session (each waits for "
                        "the previous view's result — the autoregressive "
                        "record contract)")
    p.add_argument("--sessionless", type=int, default=6,
                   help="sessionless one-shot requests (may fail over)")
    p.add_argument("--timeout_s", type=float, default=120.0)
    p.add_argument("--retries", type=int, default=20,
                   help="client resubmits per view on a retryable "
                        "rejection (FleetOverloaded / ReplicaDraining)")
    p.add_argument("--no-kill", action="store_true",
                   help="skip the replica kill (rollout-only soak)")
    p.add_argument("--remote", action="store_true",
                   help="cross-process fleet: each replica is a real "
                        "worker_cli subprocess on a disjoint CPU device "
                        "slice; the kill is a real SIGKILL of the "
                        "victim's process")
    p.add_argument("--compile_cache", default=None,
                   help="with --remote: shared persistent XLA "
                        "compile-cache dir for the workers")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", action="store_true",
                   help="emit the survival report as one JSON line on "
                        "stdout")
    args = p.parse_args(argv)

    service, inj, cfg, sampler, worker_procs = _build(args)
    service.start(serve_http=False)
    router = service.router

    from diff3d_tpu.runtime.retry import RetryableError
    from diff3d_tpu.sampling import record_capacity
    from diff3d_tpu.serving.engine import lane_count
    from diff3d_tpu.serving.scheduler import SessionLost, ViewRequest
    from diff3d_tpu.testing.faults import arm_replica, replica_site

    # Pre-compile the program shapes traffic will launch.  In-process
    # replicas share the sampler's jit cache, so only the first warmup
    # compiles; remote workers compile in their own process on first
    # traffic (or reuse --compile_cache).
    n_views = 3
    bucket = (cfg.model.H, cfg.model.W, record_capacity(n_views))
    t0 = time.perf_counter()
    for rep in service.replicas:
        if not hasattr(rep, "engine"):
            continue
        for lanes in {lane_count(n, rep.engine.max_batch,
                                 rep.engine.lane_multiple)
                      for n in (1, 2, rep.engine.max_batch)}:
            rep.engine.programs.warmup(bucket, lanes,
                                       int(sampler.w.shape[0]))
    print(f"chaos_router: warmed programs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    for rep in service.replicas:
        if hasattr(rep, "engine"):    # fault sites live in-process
            arm_replica(rep, inj)

    counts = {"submitted": 0, "completed": 0, "failed_retryable": 0,
              "failed_other": 0, "hung": 0, "sessions_lost": 0}
    errors = []
    lock = threading.Lock()
    live_reqs = []

    def _bump(key, err=None):
        with lock:
            counts[key] += 1
            if err is not None:
                errors.append(err)

    def run_view(sid, view_idx, seed):
        """Submit one view (resubmitting on retryable rejections) and
        wait for its result.  Returns 'done', 'session_lost' or a
        terminal failure bucket already counted."""
        for attempt in range(args.retries + 1):
            req = ViewRequest(_synthetic_views(n_views, cfg.model.H, seed),
                              seed=seed, n_views=n_views, session_id=sid)
            try:
                router.submit(req)
                _bump("submitted")
            except SessionLost as e:
                _bump("submitted")
                _bump("sessions_lost",
                      f"{sid}/v{view_idx}: {type(e).__name__}: {e}")
                return "session_lost"
            except RetryableError as e:
                _bump("submitted")
                time.sleep(max(getattr(e, "retry_after_s", None) or 0.1,
                               0.05))
                continue
            except Exception as e:
                _bump("submitted")
                _bump("failed_other",
                      f"{sid}/v{view_idx}: submit {type(e).__name__}: {e}")
                return "failed"
            with lock:
                live_reqs.append(req)
            try:
                req.result(timeout=args.timeout_s + 30)
                _bump("completed")
                return "done"
            except RetryableError:
                if not req.done():
                    _bump("hung", f"{sid}/v{view_idx}: hung")
                    return "failed"
                # In-flight work died (kill / drain race) — resubmit;
                # a dead owner surfaces SessionLost on the next submit.
                time.sleep(0.05)
                continue
            except Exception as e:
                _bump("failed_other",
                      f"{sid}/v{view_idx}: {type(e).__name__}: {e}")
                return "failed"
        _bump("failed_retryable", f"{sid}: retries exhausted")
        return "failed"

    def run_session(si):
        sid = f"sess-{si}"
        for v in range(args.views):
            if run_view(sid, v, seed=1000 + si * 100 + v) != "done":
                return

    def run_sessionless(i):
        run_view(None, i, seed=9000 + i)

    threads = [threading.Thread(target=run_session, args=(i,), daemon=True)
               for i in range(args.sessions)]
    threads += [threading.Thread(target=run_sessionless, args=(i,),
                                 daemon=True)
                for i in range(args.sessionless)]
    wall0 = time.perf_counter()
    for t in threads:
        t.start()
        time.sleep(0.01)

    # Mid-run chaos, once at least one session has pinned an owner.
    deadline = time.monotonic() + 30.0
    victim = None
    while time.monotonic() < deadline:
        per = service.fleet_snapshot()["sessions"]["per_replica"]
        if per:
            victim = max(per, key=per.get)
            break
        time.sleep(0.02)
    if victim is not None and not args.no_kill:
        if args.remote:
            # The real thing: SIGKILL the victim's worker process.  The
            # router's heartbeat declares it dead within
            # heartbeat_timeout_s; until then sticky submits surface
            # retryable TransportErrors, after it typed SessionLost.
            import signal
            worker_procs[victim].send_signal(signal.SIGKILL)
            print(f"chaos_router: SIGKILLed worker {victim} "
                  f"(pid {worker_procs[victim].pid})", file=sys.stderr)
        else:
            # Fire on the victim's next step dispatch, exactly once.
            inj.add(replica_site(victim), kind="kill", first_n=1 << 30,
                    max_fires=1)
            print(f"chaos_router: kill armed on {victim}",
                  file=sys.stderr)

    rollout_box = {}

    def _rollout():
        time.sleep(0.3)
        try:
            rollout_box.update(service.rollout(sampler.params,
                                               version="v1",
                                               drain_timeout_s=60.0))
        except Exception as e:  # SIGKILL between drain-ok and swap:
            # the worker died mid-rollout; record it instead of leaving
            # the box empty (which reads as "rollout never ran").
            rollout_box.update(
                {"ok": False, "error": f"{type(e).__name__}: {e}"})

    ro = threading.Thread(target=_rollout, daemon=True)
    ro.start()

    for t in threads:
        t.join()
    ro.join()
    wall = time.perf_counter() - wall0

    # Recovery window: surviving replicas must settle back to ok.
    survivors = [r for r in service.replicas if r.health != "dead"]
    deadline = time.monotonic() + 60.0
    while (any(r.health != "ok" for r in survivors)
           and time.monotonic() < deadline):
        time.sleep(0.05)

    # Zero-migration audit: each session's ledger lives on one replica.
    owners = {}
    migrations = []
    for rep in service.replicas:
        for sid in rep.session_records():
            if sid in owners:
                migrations.append(f"{sid}: {owners[sid]} AND {rep.name}")
            owners[sid] = rep.name

    lost = sum(1 for r in live_reqs if not r.done())
    snap = service.metrics_snapshot()
    final_health = {r.name: r.health for r in service.replicas}
    service.stop()
    for proc in worker_procs.values():
        if proc.poll() is None:
            proc.terminate()
    for proc in worker_procs.values():
        try:
            proc.wait(timeout=15)
        except Exception:
            proc.kill()
            proc.wait()
        if proc.stdout is not None:
            proc.stdout.close()

    c = snap["counters"]
    kill_armed = victim is not None and not args.no_kill
    record = {
        "soak": "chaos_router",
        "seed": args.seed,
        "replicas": args.replicas,
        "sessions": args.sessions,
        "views": args.views,
        "wall_s": round(wall, 2),
        **counts,
        "lost": lost,
        "migrations": migrations,
        "victim": victim if kill_armed else None,
        "rollout": rollout_box,
        "router_requests_total": c.get("router_requests_total", 0),
        "router_rejected_total": c.get("router_rejected_total", 0),
        "router_failover_total": c.get("router_failover_total", 0),
        "router_sessions_lost_total": c.get("router_sessions_lost_total",
                                            0),
        "final_health": final_health,
        "error_sample": errors[:8],
    }
    survivors_ok = all(h == "ok" for n, h in final_health.items()
                       if h != "dead")
    ok = (counts["failed_other"] == 0 and counts["hung"] == 0
          and lost == 0 and not migrations and survivors_ok
          and bool(rollout_box) and counts["completed"] > 0)
    if kill_armed:
        # The kill must be visible: a dead replica and, if it owned
        # sessions at death, typed SessionLost rejections for them.
        ok = ok and "dead" in final_health.values()
    record["survived"] = ok
    print(f"chaos_router: {counts['completed']} completed, "
          f"{counts['sessions_lost']} sessions lost (typed), "
          f"{counts['failed_retryable']} retryable-failed, "
          f"{counts['failed_other']} other, {counts['hung']} hung, "
          f"{lost} lost, {len(migrations)} migrations; "
          f"victim={record['victim']}, rollout ok={rollout_box.get('ok')},"
          f" final={final_health} -> "
          f"{'SURVIVED' if ok else 'FAILED'}", file=sys.stderr)
    if args.json:
        print(json.dumps(record))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
