"""Long-running novel-view inference service.

Loads a checkpoint and serves ``POST /synthesize`` — concurrent requests
are microbatched into shared compiled scans (``diff3d_tpu/serving``), so
the chip stays occupied under live load instead of running one request's
underfilled guidance sweep at a time.

Usage:
    python -m diff3d_tpu.cli.serve_cli --model ./checkpoints \
        [--config srn64] [--port 8080] [--max_batch 8] [--max_wait_ms 50]

    # smoke-serve random-init params (no checkpoint; CPU-friendly):
    python -m diff3d_tpu.cli.serve_cli --init random --config test

Endpoints: ``POST /synthesize``, ``GET /result/<id>``, ``GET /healthz``,
``GET /metrics`` (text; ``?format=json`` for the structured snapshot).
With ``--cascade``, ``POST /cascade`` serves progressive previews: draft
frames stream first, refined frames replace them (DESIGN.md §20).
"""

from __future__ import annotations

import argparse
import logging
import signal
import threading

from diff3d_tpu.cli._common import (add_model_width_args,
                                    apply_model_width_overrides,
                                    build_abstract_state,
                                    load_eval_params)
from diff3d_tpu.config import NAMED_CONFIGS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default=None,
                   help="checkpoint directory (Orbax root); omit with "
                        "--init random")
    p.add_argument("--init", choices=["checkpoint", "random"],
                   default="checkpoint",
                   help="'random' serves freshly initialised params — "
                        "for smoke tests and load benches, no --model "
                        "needed")
    p.add_argument("--config",
                   choices=list(NAMED_CONFIGS),
                   default="srn64")
    p.add_argument("--host", default=None,
                   help="bind address (default: config, 127.0.0.1)")
    p.add_argument("--port", type=int, default=None,
                   help="bind port (default: config, 8080; 0 = ephemeral)")
    p.add_argument("--max_batch", type=int, default=None,
                   help="device-batch lane ceiling per shape bucket")
    p.add_argument("--max_wait_ms", type=float, default=None,
                   help="microbatch flush deadline after the first "
                        "request of a bucket arrives")
    p.add_argument("--max_queue", type=int, default=None,
                   help="bounded queue size; beyond it submissions get "
                        "HTTP 429")
    p.add_argument("--timeout_s", type=float, default=None,
                   help="default per-request deadline")
    p.add_argument("--watchdog_s", type=float, default=None,
                   help="watchdog deadline per device step: past it the "
                        "engine rejects the stuck batch with a retryable "
                        "error and degrades instead of hanging futures "
                        "(0 disables)")
    p.add_argument("--drain_s", type=float, default=10.0,
                   help="on SIGTERM/SIGINT, stop admitting work and wait "
                        "up to this long for in-flight requests to finish "
                        "before stopping (0 = immediate stop)")
    p.add_argument("--steps", type=int, default=None,
                   help="diffusion steps per view (reference: 256) — the "
                        "DENSE training grid; see --sampler_steps for the "
                        "few-step sampling subset")
    p.add_argument("--sampler", choices=["ancestral", "ddim"],
                   default="ancestral",
                   help="default reverse-process update: 'ancestral' "
                        "(paper's stochastic sampler) or 'ddim' "
                        "(deterministic eta=0)")
    p.add_argument("--sampler_steps", type=int, default=None,
                   help="few-step schedule for the default sampler: "
                        "reverse steps per view, a divisor of the dense "
                        "grid (e.g. 16 with 256 timesteps); default = "
                        "full grid")
    p.add_argument("--schedules", default=None,
                   help="extra compiled schedules to serve beyond the "
                        "default, as 'kind:steps,...' (e.g. "
                        "'ddim:16,ancestral:256'); requests naming any "
                        "other schedule get a typed 503 with this list. "
                        "With --replicas N, prefix an entry with 'i@' to "
                        "give it to replica i only (e.g. "
                        "'0@ddim:8,ancestral:256' = distilled-student "
                        "schedule on replica 0, ancestral everywhere) — "
                        "the router places requests on a replica that "
                        "compiled their schedule")
    p.add_argument("--replicas", type=int, default=None,
                   help="in-process engine replicas behind the fleet "
                        "router front door (default: config, 1 = plain "
                        "single-engine service).  Sessions "
                        "(payload 'session_id') pin to a replica; "
                        "adds GET /fleet and router counters to "
                        "GET /metrics")
    p.add_argument("--workers", default=None,
                   help="front pre-started worker processes "
                        "(diff3d_tpu.cli.worker_cli) as remote replicas: "
                        "'host:port,host:port'.  Mixes with --replicas: "
                        "N in-process replicas plus the listed workers "
                        "form one fleet (sessions pin across both kinds"
                        "); with --workers alone no local engine is "
                        "built, so this process needs no devices")
    p.add_argument("--scan_chunks", type=int, default=1,
                   help="split each view's diffusion scan into this many "
                        "device executions (must divide the per-view "
                        "step count)")
    p.add_argument("--cascade", default=None, metavar="PLAN",
                   help="serve progressive-preview cascades "
                        "(POST /cascade): 'draft=RES:kind:steps,"
                        "refine=RES:kind:steps@tSTART', e.g. "
                        "'draft=64:ddim:8,refine=128:ancestral:64@t0.4'"
                        " — the draft streams first at RES, then a "
                        "truncated refine pass (from t=START) replaces "
                        "each frame in place; refine RES must equal the "
                        "config's image size")
    p.add_argument("--mesh", action="store_true",
                   help="shard serving over a device mesh (cfg.mesh): "
                        "the request batch's object axis rides the data "
                        "axis, params follow the configured "
                        "replicated/fsdp policy; lane counts round up to "
                        "the data-axis size")
    p.add_argument("--pallas", action="store_true",
                   help="route the GroupNorm->FiLM/SiLU epilogues through "
                        "the fused Pallas kernels (ops/pallas_film.py; "
                        "interpret mode on a CPU process).  Equivalent to "
                        "model.kernels='pallas'")
    p.add_argument("--raw_params", action="store_true",
                   help="serve raw params instead of EMA")
    p.add_argument("--warmup", action="store_true",
                   help="pre-compile the single-lane program for the "
                        "max_views bucket before accepting traffic")
    add_model_width_args(p)
    return p


def build_service(args):
    """Config + params + sampler(s) -> ServingService (not started), or
    a FleetService when --replicas > 1."""
    import dataclasses

    import jax

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.models import build_xunet
    from diff3d_tpu.sampling import Sampler, record_capacity
    from diff3d_tpu.serving import FleetService, ServingService
    from diff3d_tpu.serving.fleet import build_fleet

    cfg = config_lib.named_config(args.config)
    # serving is the X-UNet's: refuse another denoiser before anything loads
    build_xunet(cfg, "serve_cli")
    if args.steps:
        cfg = dataclasses.replace(
            cfg, diffusion=dataclasses.replace(cfg.diffusion,
                                               timesteps=args.steps))
    cfg = apply_model_width_overrides(cfg, args)
    if args.pallas:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, kernels="pallas"))
    over = {k: getattr(args, k) for k in
            ("host", "port", "max_batch", "max_queue")
            if getattr(args, k) is not None}
    if args.replicas:            # 0 = remote-only fleet, keep cfg valid
        over["replicas"] = args.replicas
    if args.max_wait_ms is not None:
        over["max_wait_ms"] = args.max_wait_ms
    if args.timeout_s is not None:
        over["default_timeout_s"] = args.timeout_s
    if args.watchdog_s is not None:
        over["watchdog_timeout_s"] = args.watchdog_s
    if over:
        cfg = dataclasses.replace(
            cfg, serving=dataclasses.replace(cfg.serving, **over))
    cfg.validate()

    worker_addrs = []
    if getattr(args, "workers", None):
        for spec in args.workers.split(","):
            spec = spec.strip()
            if not spec:
                continue
            host, _, port_s = spec.rpartition(":")
            try:
                worker_addrs.append((host or "127.0.0.1", int(port_s)))
            except ValueError:
                raise SystemExit(
                    f"--workers entry {spec!r}: expected 'host:port'")
    # Local in-process replicas: with --workers present, default to a
    # pure-remote fleet unless --replicas asks for locals too.
    n_local = args.replicas if args.replicas is not None else (
        0 if worker_addrs else cfg.serving.replicas)
    if n_local == 0 and not worker_addrs:
        raise SystemExit("--replicas 0 needs --workers")

    def _remotes():
        from diff3d_tpu.serving.transport import (RemoteReplica,
                                                  TransportError)

        reps = []
        for host, port in worker_addrs:
            try:
                reps.append(RemoteReplica(
                    host, port,
                    heartbeat_interval_s=cfg.serving.heartbeat_interval_s,
                    heartbeat_timeout_s=cfg.serving.heartbeat_timeout_s,
                    max_frame_bytes=cfg.serving.max_frame_bytes))
            except TransportError as e:
                raise SystemExit(
                    f"--workers {host}:{port}: worker unreachable "
                    f"({e}) — start it first with "
                    f"'python -m diff3d_tpu.cli.worker_cli'")
        return reps

    if n_local == 0:
        # Remote-only front door: no local engine, no devices touched.
        logging.info("fronting %d remote workers, no local replicas",
                     len(worker_addrs))
        return FleetService(_remotes(), cfg)

    model = build_xunet(cfg, "serve_cli")
    if args.init == "random":
        from diff3d_tpu.train.trainer import init_params

        params = init_params(model, cfg, jax.random.PRNGKey(0))
        step, version = 0, "random-init"
    else:
        if not args.model:
            raise SystemExit("--model is required unless --init random")
        try:
            step, params = load_eval_params(args.model,
                                            build_abstract_state(cfg),
                                            args.raw_params)
        except ValueError as e:
            raise SystemExit(str(e))
        version = f"{args.model}@step{step}"
    logging.info("serving %s params (step %d)", version, step)

    mesh_env = None
    if getattr(args, "mesh", False):
        from diff3d_tpu.parallel import make_mesh

        mesh_env = make_mesh(cfg.mesh)
        logging.info("serving on mesh %s (lane multiple %d)",
                     dict(mesh_env.mesh.shape), mesh_env.data_size)
    sampler = Sampler(model, params, cfg, scan_chunks=args.scan_chunks,
                      mesh=mesh_env, sampler_kind=args.sampler,
                      steps=args.sampler_steps)
    cascade = None
    if args.cascade:
        from diff3d_tpu.cascade import CascadePlan, CascadeSampler

        try:
            plan = CascadePlan.parse(args.cascade)
        except ValueError as e:
            raise SystemExit(f"--cascade: {e}")
        if plan.refine.resolution != cfg.model.H:
            raise SystemExit(
                f"--cascade: refine resolution {plan.refine.resolution} "
                f"must equal the config's image size {cfg.model.H} "
                f"(--config {args.config})")
        cascade = CascadeSampler(model, params, cfg, plan, mesh=mesh_env)
        logging.info("cascade plan %s (draft %d^2 -> refine %d^2 from "
                     "t=%.2f)", plan.spec(), plan.draft.resolution,
                     plan.refine.resolution, plan.refine.start_t)
    n_replicas = n_local
    extra_samplers = {}
    per_replica_extra = {}
    made = {}                  # one Sampler per distinct extra schedule

    def _sampler_for(sched):
        if sched not in made:
            made[sched] = Sampler(
                model, params, cfg, scan_chunks=args.scan_chunks,
                mesh=mesh_env, sampler_kind=sched[0], steps=sched[1])
        return made[sched]

    if args.schedules:
        for spec in args.schedules.split(","):
            spec = spec.strip()
            target, at, rest = spec.partition("@")
            idx = None
            if at:
                try:
                    idx = int(target)
                except ValueError:
                    raise SystemExit(
                        f"--schedules entry {spec!r}: replica prefix "
                        "must be an integer index ('i@kind:steps')")
                if not 0 <= idx < n_replicas:
                    raise SystemExit(
                        f"--schedules entry {spec!r}: replica index "
                        f"{idx} outside --replicas {n_replicas}")
            else:
                rest = spec
            kind, _, steps_s = rest.partition(":")
            try:
                sched = (kind, int(steps_s))
            except ValueError:
                raise SystemExit(
                    f"--schedules entry {spec!r}: expected "
                    "'[i@]kind:steps'")
            if sched == (sampler.sampler_kind, sampler.steps):
                continue                    # already the default sampler
            if idx is None:
                extra_samplers[sched] = _sampler_for(sched)
            else:
                per_replica_extra.setdefault(idx, {})[sched] = (
                    _sampler_for(sched))
    if worker_addrs:
        # Mixed fleet: local in-process replicas + remote workers
        # behind one router (sessions pin across both kinds).
        local = build_fleet(
            sampler, cfg, n_replicas,
            extra_samplers=extra_samplers or None,
            per_replica_extra=per_replica_extra or None,
            params_version=version, cascade=cascade)
        service = FleetService(local + _remotes(), cfg)
    elif n_replicas > 1:
        service = FleetService.build(
            sampler, cfg, n=n_replicas,
            extra_samplers=extra_samplers or None,
            per_replica_extra=per_replica_extra or None,
            params_version=version, cascade=cascade)
    else:
        if per_replica_extra:
            raise SystemExit(
                "per-replica 'i@kind:steps' schedules require "
                "--replicas > 1")
        service = ServingService(sampler, cfg, params_version=version,
                                 extra_samplers=extra_samplers or None,
                                 cascade=cascade)
    if args.warmup:
        from diff3d_tpu.serving import Bucket

        cap = record_capacity(cfg.serving.max_views)
        # Remote replicas warm their own programs at worker boot; only
        # local engines can be warmed from this process.
        engines = ([service.engine] if hasattr(service, "engine")
                   else [rep.engine for rep in service.replicas
                         if hasattr(rep, "engine")])
        for eng in engines:
            for s in eng.samplers.values():
                bucket = Bucket(cfg.model.H, cfg.model.W, cap,
                                s.steps, s.sampler_kind)
                secs = eng.programs.warmup(bucket, s.lane_multiple,
                                           s.w.shape[0])
                logging.info("warmed bucket %s in %.1fs",
                             tuple(bucket), secs)
            if eng.cascade is not None:
                for phase, s in (("draft", eng.cascade.draft),
                                 ("refine", eng.cascade.refine)):
                    bucket = Bucket(s.cfg.model.H, s.cfg.model.W, cap,
                                    s.steps, s.sampler_kind, phase)
                    secs = eng.programs.warmup(bucket, s.lane_multiple,
                                               s.w.shape[0])
                    logging.info("warmed cascade %s bucket %s in %.1fs",
                                 phase, tuple(bucket), secs)
    return service


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    configure_compile_cache()

    service = build_service(args)
    service.start(serve_http=True)
    fleet = " , GET /fleet" if hasattr(service, "fleet_snapshot") else ""
    logging.info("listening on http://%s:%d (POST /synthesize, "
                 "GET /healthz, GET /metrics%s)",
                 service.cfg.serving.host, service.port, fleet)

    done = threading.Event()

    def _sig(signum, frame):
        logging.info("signal %d: shutting down", signum)
        done.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    try:
        done.wait()
    finally:
        service.stop(drain_s=args.drain_s)
        logging.info("stopped")


if __name__ == "__main__":
    main()
