"""Offered-load sweep for the serving layer.

Drives the in-process service (scheduler + engine, no HTTP overhead) with
synthetic requests at a sweep of arrival rates and reports, per rate:

  * throughput (synthesised views/s),
  * end-to-end latency p50/p99,
  * mean batch occupancy and padding fraction (how well the microbatcher
    filled the device batch at that load).

The interesting curve is occupancy vs. latency: at low offered load every
request rides alone (occupancy 1, minimal latency); as load rises the
microbatcher amortises the compiled scan across requests (occupancy ->
max_batch) and throughput climbs at bounded latency cost until the queue
saturates.  A fresh service per rate keeps the metrics windows clean.

With ``--replicas N`` the sweep runs against the fleet router
(``serving/router.py``) instead of a bare engine: sessionless requests
take the least-loaded path, and each rate point additionally reports
per-replica view counts and the utilization skew (hottest replica /
even-split share; 1.0 = perfectly balanced).

With ``--trajectory_lens L1,L2,...`` the bench switches to the
trajectory sweep: each point submits ``--requests`` concurrent
orbit-path trajectories of that length (one object session each — the
interleaved multi-object load the shared compiled scan co-batches) and
a streaming client drains each request's commit buffer, reporting
frames/s, time-to-first-frame vs. path length, end-to-end latency and
(with a fleet) the per-replica utilization skew plus a
``sessions_migrated`` count asserting the zero-migration contract
(must be 0).

Usage (CPU smoke):
    JAX_PLATFORMS=cpu python tools/bench_serving.py --config test \
        --rates 2,8,32 --requests 12 --out runs/bench_serving.json
    JAX_PLATFORMS=cpu python tools/bench_serving.py --config test \
        --trajectory_lens 3,5 --requests 4 --replicas 3 \
        --out runs/bench_trajectory.json

On a real chip, use the model config the service will run
(``--config srn64``) and rates around the measured per-view service time.
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


def _build_service(args):
    import jax

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.config import ServingConfig
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.serving import ServingService
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": config_lib.srn64_config,
           "srn128": config_lib.srn128_config,
           "test": config_lib.test_config}[args.config]()
    if args.steps:
        cfg = dataclasses.replace(
            cfg, diffusion=dataclasses.replace(cfg.diffusion,
                                               timesteps=args.steps))
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        max_batch=args.max_batch, max_queue=args.max_queue,
        max_wait_ms=args.max_wait_ms, default_timeout_s=args.timeout_s,
        max_views=max(16, args.n_views),
        result_cache_entries=0))     # load bench must not replay results
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    mesh_env = None
    if args.mesh:
        from diff3d_tpu.parallel import make_mesh

        mesh_env = make_mesh(cfg.mesh)
        print(f"bench_serving: mesh {dict(mesh_env.mesh.shape)} "
              f"(lane multiple {mesh_env.data_size})", file=sys.stderr)
    sampler = Sampler(model, params, cfg, mesh=mesh_env,
                      sampler_kind=args.sampler, steps=args.sampler_steps)
    return sampler, cfg


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


def _aggregate_snaps(snaps):
    """Sum counters / count-weight histogram means across replica
    metric snapshots (one replica = the single-service case)."""
    counters, hists = {}, {}
    for snap in snaps:
        for k, v in snap["counters"].items():
            counters[k] = counters.get(k, 0) + v
        for k, h in snap["histograms"].items():
            agg = hists.setdefault(k, {"count": 0, "_wsum": 0.0,
                                       "p50": 0.0})
            n = h.get("count", 0)
            agg["count"] += n
            agg["_wsum"] += h.get("mean", 0.0) * n
            agg["p50"] = max(agg["p50"], h.get("p50", 0.0))
    for h in hists.values():
        h["mean"] = h["_wsum"] / h["count"] if h["count"] else 0.0
    return counters, hists


def _build_fleet_or_single(sampler, cfg, args, cascade=None):
    """Fresh service per sweep point (clean metrics windows).  Returns
    ``(service, replicas_or_None, engines)``."""
    from diff3d_tpu.serving import FleetService, ServingService

    if args.replicas > 1:
        service = FleetService.build(sampler, cfg, n=args.replicas,
                                     cascade=cascade)
        service.start(serve_http=False)
        return service, service.replicas, [rep.engine
                                           for rep in service.replicas]
    service = ServingService(sampler, cfg,
                             cascade=cascade).start(serve_http=False)
    return service, None, [service.engine]


def _warmup(engines, sampler, cfg, n_views: int, n_requests: int) -> None:
    # Warm the fullest lane count so the first request doesn't pay the
    # compile (every sweep point would otherwise time one compile each).
    # Lane counts go through the engine's rounding (power of two, then up
    # to the mesh's lane multiple) so the warmed shapes are exactly the
    # ones traffic will launch.  Fleet replicas share the sampler's jit
    # cache, so only the first replica's warmup compiles.
    from diff3d_tpu.sampling import record_capacity
    from diff3d_tpu.serving import Bucket
    from diff3d_tpu.serving.engine import lane_count
    bucket = Bucket(cfg.model.H, cfg.model.W, record_capacity(n_views),
                    sampler.steps, sampler.sampler_kind)
    for eng in engines:
        for lanes in {lane_count(1, eng.max_batch, eng.lane_multiple),
                      lane_count(min(eng.max_batch, n_requests or 1),
                                 eng.max_batch, eng.lane_multiple)}:
            eng.programs.warmup(bucket, lanes, sampler.w.shape[0])


def _run_rate(sampler, cfg, rate: float, args) -> dict:
    import numpy as np

    service, replicas, engines = _build_fleet_or_single(sampler, cfg, args)
    fleet = replicas is not None
    submit = service.router.submit if fleet else service.engine.submit
    views = [_synthetic_views(args.n_views, cfg.model.H, i)
             for i in range(args.requests)]
    _warmup(engines, sampler, cfg, args.n_views, args.requests)

    from diff3d_tpu.serving.scheduler import ViewRequest
    reqs, latencies, errors = [], [], []
    lock = threading.Lock()

    def waiter(req):
        try:
            req.result(timeout=args.timeout_s + 30)
            with lock:
                latencies.append(req.done_time - req.submit_time)
        except Exception as e:
            with lock:
                errors.append(str(e))

    t0 = time.perf_counter()
    waiters = []
    for i in range(args.requests):
        req = ViewRequest(views[i], seed=i, n_views=args.n_views)
        try:
            submit(req)
        except Exception as e:
            errors.append(str(e))
            continue
        reqs.append(req)
        w = threading.Thread(target=waiter, args=(req,), daemon=True)
        w.start()
        waiters.append(w)
        if rate > 0:
            time.sleep(1.0 / rate)
    for w in waiters:
        w.join()
    wall = time.perf_counter() - t0
    if fleet:
        per_replica_views = {
            rep.name: rep.metrics.snapshot()["counters"].get(
                "serving_views_completed_total", 0) for rep in replicas}
        counters, hists = _aggregate_snaps(
            [rep.metrics.snapshot() for rep in replicas])
        router_snap = service.metrics_snapshot()["counters"]
    else:
        per_replica_views, router_snap = None, {}
        snap = service.metrics_snapshot()
        counters, hists = snap["counters"], snap["histograms"]
    service.stop()

    lat = np.asarray(sorted(latencies)) if latencies else np.zeros(0)
    views_done = counters.get("serving_views_completed_total", 0)
    occ = hists.get("serving_batch_occupancy", {})
    padf = hists.get("serving_batch_padding_fraction", {})
    up_bytes = counters.get("serving_host_upload_bytes_total", 0)
    fetch_bytes = counters.get("serving_host_fetch_bytes_total", 0)
    point = {
        "chips_used": engines[0].lane_multiple,
        "lane_multiple": engines[0].lane_multiple,
        "host_upload_bytes_per_view": (round(up_bytes / views_done)
                                       if views_done else None),
        "host_fetch_bytes_per_view": (round(fetch_bytes / views_done)
                                      if views_done else None),
        "offered_rate_rps": rate,
        "requests": args.requests,
        "completed": len(latencies),
        "errors": len(errors),
        "error_sample": errors[:3],
        "wall_s": round(wall, 3),
        "views_per_sec": round(views_done / wall, 3) if wall else None,
        "latency_p50_s": (round(float(np.percentile(lat, 50)), 3)
                          if lat.size else None),
        "latency_p99_s": (round(float(np.percentile(lat, 99)), 3)
                          if lat.size else None),
        "occupancy_mean": round(occ.get("mean", 0.0), 3),
        "padding_fraction_mean": round(padf.get("mean", 0.0), 3),
        "ttfv_p50_s": round(hists.get(
            "serving_time_to_first_view_seconds", {}).get("p50", 0.0), 3),
    }
    if fleet:
        vals = list(per_replica_views.values())
        mean = sum(vals) / len(vals) if vals else 0.0
        point.update({
            "replicas": args.replicas,
            "per_replica_views": per_replica_views,
            # Utilization skew: hottest replica's share of a perfectly
            # even split (1.0 = balanced; R = everything on one of R).
            "utilization_skew": (round(max(vals) / mean, 3)
                                 if mean else None),
            "router_failover_total": router_snap.get(
                "router_failover_total", 0),
            "router_rejected_total": router_snap.get(
                "router_rejected_total", 0),
        })
    return point


def _trajectory_payload(n_frames: int, size: int, seed: int) -> dict:
    """An orbit trajectory over a synthetic object: random conditioning
    image, conditioning camera on the same orbit shell (one azimuth
    back), path compiled server-side from the JSON spec — exactly the
    ``POST /trajectory`` wire shape."""
    import numpy as np

    from diff3d_tpu.trajectory import orbit_path

    r = np.random.RandomState(seed)
    radius, elevation = 2.6, 20.0
    step = 360.0 / max(1, n_frames)
    cond_R, cond_T = orbit_path(1, radius=radius, elevation_deg=elevation,
                                azimuth0_deg=-step)
    return {
        "cond": {
            "img": r.randn(size, size, 3).astype(np.float32),
            "R": cond_R[0], "T": cond_T[0],
            "K": np.array([[size * 1.2, 0, size / 2],
                           [0, size * 1.2, size / 2],
                           [0, 0, 1]], np.float32),
        },
        "path": {"kind": "orbit", "frames": n_frames, "radius": radius,
                 "elevation_deg": elevation},
        "seed": seed,
        "session_id": f"bench-obj-{seed}",
    }


def _run_trajectory(sampler, cfg, n_frames: int, args) -> dict:
    """One trajectory sweep point: ``args.requests`` concurrent orbit
    trajectories of ``n_frames`` frames, one object session each, every
    request drained by a streaming client as frames commit."""
    import numpy as np

    service, replicas, engines = _build_fleet_or_single(sampler, cfg, args)
    fleet = replicas is not None
    payloads = [_trajectory_payload(n_frames, cfg.model.H, i)
                for i in range(args.requests)]
    _warmup(engines, sampler, cfg, n_frames + 1, args.requests)

    lock = threading.Lock()
    ttffs, latencies, errors = [], [], []

    def drain(req, t_submit):
        # Streaming client: consume the commit buffer as the engine
        # fills it, like the chunked-HTTP reader would.
        try:
            sent, first = 0, None
            while True:
                chunk = req.wait_frames(sent,
                                        timeout=args.timeout_s + 30)
                if chunk and first is None:
                    first = time.perf_counter() - t_submit
                sent += len(chunk)
                if not chunk:
                    break
            req.result(timeout=args.timeout_s + 30)
            with lock:
                ttffs.append(first)
                latencies.append(req.done_time - req.submit_time)
        except Exception as e:
            with lock:
                errors.append(str(e))

    t0 = time.perf_counter()
    drainers = []
    for payload in payloads:
        t_submit = time.perf_counter()
        try:
            req = service.submit_trajectory(payload)
        except Exception as e:
            errors.append(str(e))
            continue
        th = threading.Thread(target=drain, args=(req, t_submit),
                              daemon=True)
        th.start()
        drainers.append(th)
    for th in drainers:
        th.join()
    wall = time.perf_counter() - t0

    if fleet:
        snaps = [rep.metrics.snapshot() for rep in replicas]
        counters, hists = _aggregate_snaps(snaps)
        per_replica_views = {
            rep.name: snap["counters"].get(
                "serving_views_completed_total", 0)
            for rep, snap in zip(replicas, snaps)}
        ledgers = [rep.session_records() for rep in replicas]
    else:
        snap = service.metrics_snapshot()
        counters, hists = snap["counters"], snap["histograms"]
        per_replica_views, ledgers = None, None
    service.stop()

    frames_done = counters.get("serving_trajectory_frames_total", 0)
    lat = np.asarray(sorted(latencies)) if latencies else np.zeros(0)
    tf = np.asarray(sorted(t for t in ttffs if t is not None))
    occ = hists.get("serving_batch_occupancy", {})
    point = {
        "trajectory_frames": n_frames,
        "requests": args.requests,
        "completed": len(latencies),
        "errors": len(errors),
        "error_sample": errors[:3],
        "wall_s": round(wall, 3),
        "frames_committed": frames_done,
        "frames_per_sec": (round(frames_done / wall, 3)
                           if wall else None),
        "ttff_p50_s": (round(float(np.percentile(tf, 50)), 3)
                       if tf.size else None),
        "ttff_max_s": (round(float(tf[-1]), 3) if tf.size else None),
        "latency_p50_s": (round(float(np.percentile(lat, 50)), 3)
                          if lat.size else None),
        "latency_p99_s": (round(float(np.percentile(lat, 99)), 3)
                          if lat.size else None),
        "occupancy_mean": round(occ.get("mean", 0.0), 3),
    }
    if fleet:
        vals = list(per_replica_views.values())
        mean = sum(vals) / len(vals) if vals else 0.0
        owners = {}
        for ledger in ledgers:
            for sid in ledger:
                owners[sid] = owners.get(sid, 0) + 1
        point.update({
            "replicas": args.replicas,
            "per_replica_views": per_replica_views,
            "utilization_skew": (round(max(vals) / mean, 3)
                                 if mean else None),
            # Sessions whose records appear on >1 replica's ledger —
            # any non-zero value is a broken zero-migration contract.
            "sessions_migrated": sum(
                1 for n in owners.values() if n > 1),
        })
    return point


def _warmup_cascade(engines, cascade, n_views: int,
                    n_requests: int) -> None:
    """Warm both phase buckets at the lane counts cascade traffic will
    launch (same rounding contract as :func:`_warmup`)."""
    from diff3d_tpu.sampling import record_capacity
    from diff3d_tpu.serving import Bucket
    from diff3d_tpu.serving.engine import lane_count

    cap = record_capacity(n_views)
    buckets = []
    for phase, s in (("draft", cascade.draft), ("refine", cascade.refine)):
        H = s.cfg.model.H
        buckets.append((Bucket(H, H, cap, s.steps, s.sampler_kind, phase),
                        s.w.shape[0]))
    for eng in engines:
        for bucket, guidance_B in buckets:
            for lanes in {lane_count(1, eng.max_batch, eng.lane_multiple),
                          lane_count(min(eng.max_batch, n_requests or 1),
                                     eng.max_batch, eng.lane_multiple)}:
                eng.programs.warmup(bucket, lanes, guidance_B)


def _run_cascade(sampler, cascade, cfg, rate: float, args) -> dict:
    """One cascade sweep point: ``args.requests`` progressive-preview
    requests at ``rate`` offered load, each drained by a streaming
    client walking the phase-tagged event buffer — reporting
    time-to-first-DRAFT-frame (the preview latency the cascade exists
    for) and time-to-first-REFINED-frame percentiles next to the usual
    end-to-end numbers."""
    import numpy as np

    service, replicas, engines = _build_fleet_or_single(
        sampler, cfg, args, cascade=cascade)
    fleet = replicas is not None
    payloads = [{"views": _synthetic_views(args.n_views, cfg.model.H, i),
                 "seed": i, "n_views": args.n_views}
                for i in range(args.requests)]
    _warmup_cascade(engines, cascade, args.n_views, args.requests)

    lock = threading.Lock()
    ttfds, ttfrs, latencies, errors = [], [], [], []

    def drain(req, t_submit):
        try:
            sent, first_draft, first_refined = 0, None, None
            while True:
                events = req.wait_events(sent,
                                         timeout=args.timeout_s + 30)
                now = time.perf_counter() - t_submit
                for e in events:
                    if e["phase"] == "draft" and first_draft is None:
                        first_draft = now
                    if e["phase"] == "refine" and first_refined is None:
                        first_refined = now
                sent += len(events)
                if not events:
                    break
            req.result(timeout=args.timeout_s + 30)
            with lock:
                ttfds.append(first_draft)
                ttfrs.append(first_refined)
                latencies.append(req.done_time - req.submit_time)
        except Exception as e:
            with lock:
                errors.append(str(e))

    t0 = time.perf_counter()
    drainers = []
    for payload in payloads:
        t_submit = time.perf_counter()
        try:
            req = service.submit_cascade(payload)
        except Exception as e:
            errors.append(str(e))
            continue
        th = threading.Thread(target=drain, args=(req, t_submit),
                              daemon=True)
        th.start()
        drainers.append(th)
        if rate > 0:
            time.sleep(1.0 / rate)
    for th in drainers:
        th.join()
    wall = time.perf_counter() - t0

    if fleet:
        counters, hists = _aggregate_snaps(
            [rep.metrics.snapshot() for rep in replicas])
    else:
        snap = service.metrics_snapshot()
        counters, hists = snap["counters"], snap["histograms"]
    service.stop()

    def _pcts(xs):
        a = np.asarray(sorted(x for x in xs if x is not None))
        if not a.size:
            return None, None
        return (round(float(np.percentile(a, 50)), 3),
                round(float(a[-1]), 3))

    lat = np.asarray(sorted(latencies)) if latencies else np.zeros(0)
    ttfd_p50, ttfd_max = _pcts(ttfds)
    ttfr_p50, ttfr_max = _pcts(ttfrs)
    occ = hists.get("serving_batch_occupancy", {})
    return {
        "offered_rate_rps": rate,
        "requests": args.requests,
        "completed": len(latencies),
        "errors": len(errors),
        "error_sample": errors[:3],
        "wall_s": round(wall, 3),
        "cascade_requests": counters.get(
            "serving_cascade_requests_total", 0),
        "cascade_frames": counters.get(
            "serving_cascade_frames_total", 0),
        "ttfd_p50_s": ttfd_p50,       # time to first DRAFT frame
        "ttfd_max_s": ttfd_max,
        "ttfr_p50_s": ttfr_p50,       # time to first REFINED frame
        "ttfr_max_s": ttfr_max,
        "latency_p50_s": (round(float(np.percentile(lat, 50)), 3)
                          if lat.size else None),
        "latency_p99_s": (round(float(np.percentile(lat, 99)), 3)
                          if lat.size else None),
        "occupancy_mean": round(occ.get("mean", 0.0), 3),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", choices=["srn64", "srn128", "test"],
                   default="test")
    p.add_argument("--rates", default="2,8,32",
                   help="comma-separated offered loads in requests/s "
                        "(0 = submit everything at once)")
    p.add_argument("--requests", type=int, default=8,
                   help="requests per rate point")
    p.add_argument("--n_views", type=int, default=3,
                   help="views per request (incl. the conditioning view)")
    p.add_argument("--steps", type=int, default=None,
                   help="diffusion steps per view (test config: 4)")
    p.add_argument("--sampler", choices=["ancestral", "ddim"],
                   default="ancestral",
                   help="reverse-process update served by the engine")
    p.add_argument("--sampler_steps", type=int, default=None,
                   help="few-step schedule: reverse steps per view, a "
                        "divisor of the dense grid (default = full grid) "
                        "— e.g. --sampler ddim --sampler_steps 16 vs the "
                        "256-step default for an end-to-end comparison")
    p.add_argument("--max_batch", type=int, default=8)
    p.add_argument("--max_queue", type=int, default=256)
    p.add_argument("--max_wait_ms", type=float, default=50.0)
    p.add_argument("--timeout_s", type=float, default=600.0)
    p.add_argument("--mesh", action="store_true",
                   help="shard the sampler over cfg.mesh (lane counts "
                        "round up to the data-axis size)")
    p.add_argument("--replicas", type=int, default=1,
                   help="run the sweep against the fleet router over "
                        "this many in-process replicas (sessionless "
                        "least-loaded placement); reports "
                        "per_replica_views + utilization_skew per rate")
    p.add_argument("--trajectory_lens", default="",
                   help="comma-separated orbit lengths (frames per "
                        "path); when set the bench runs the trajectory "
                        "sweep instead of the offered-load sweep: "
                        "--requests concurrent single-object "
                        "trajectories per point, streaming clients, "
                        "frames/s + time-to-first-frame vs. length")
    p.add_argument("--cascade", default="",
                   help="cascade plan spec, e.g. "
                        "'draft=64:ddim:8,refine=128:ancestral:64@t0.4' "
                        "(refine resolution must equal the config's); "
                        "when set the bench runs the progressive-preview "
                        "sweep over --rates: time-to-first-DRAFT-frame "
                        "and time-to-first-REFINED-frame percentiles vs "
                        "offered load")
    p.add_argument("--out", default="runs/bench_serving.json")
    args = p.parse_args(argv)

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    traj_lens = [int(v) for v in args.trajectory_lens.split(",")
                 if v.strip()]
    if traj_lens:
        # The service's n_views ceiling must clear the longest path
        # (+1 for the conditioning view).
        args.n_views = max(args.n_views, max(traj_lens) + 1)
    sampler, cfg = _build_service(args)
    cascade = None
    if args.cascade:
        from diff3d_tpu.cascade import CascadePlan, CascadeSampler

        plan = CascadePlan.parse(args.cascade)
        cascade = CascadeSampler(sampler.model, sampler.params, cfg,
                                 plan, mesh=sampler.mesh)
    points = []
    if cascade is not None:
        rates = [float(r) for r in args.rates.split(",") if r.strip()]
        for rate in rates:
            print(f"bench_serving: cascade rate={rate} rps ...",
                  file=sys.stderr)
            pt = _run_cascade(sampler, cascade, cfg, rate, args)
            print(f"bench_serving:   -> ttfd_p50={pt['ttfd_p50_s']}s "
                  f"ttfr_p50={pt['ttfr_p50_s']}s "
                  f"p50={pt['latency_p50_s']}s errors={pt['errors']}",
                  file=sys.stderr)
            points.append(pt)
    elif traj_lens:
        for n_frames in traj_lens:
            print(f"bench_serving: trajectory {n_frames} frames x "
                  f"{args.requests} objects ...", file=sys.stderr)
            pt = _run_trajectory(sampler, cfg, n_frames, args)
            print(f"bench_serving:   -> {pt['frames_per_sec']} frames/s, "
                  f"ttff_p50={pt['ttff_p50_s']}s "
                  f"p50={pt['latency_p50_s']}s "
                  f"occupancy={pt['occupancy_mean']}", file=sys.stderr)
            points.append(pt)
    else:
        rates = [float(r) for r in args.rates.split(",") if r.strip()]
        for rate in rates:
            print(f"bench_serving: rate={rate} rps ...", file=sys.stderr)
            pt = _run_rate(sampler, cfg, rate, args)
            print(f"bench_serving:   -> {pt['views_per_sec']} views/s, "
                  f"p50={pt['latency_p50_s']}s p99={pt['latency_p99_s']}s "
                  f"occupancy={pt['occupancy_mean']}", file=sys.stderr)
            points.append(pt)

    import jax

    record = {
        "bench": ("serving_cascade_sweep" if cascade is not None
                  else "serving_trajectory_sweep" if traj_lens
                  else "serving_offered_load"),
        "cascade": args.cascade or None,
        "config": args.config,
        "platform": jax.devices()[0].platform,
        "num_devices": len(jax.devices()),
        "mesh": bool(args.mesh),
        "lane_multiple": sampler.lane_multiple,
        "diffusion_steps": cfg.diffusion.timesteps,
        "sampler": sampler.sampler_kind,
        "sampler_steps": sampler.steps,
        "n_views": args.n_views,
        "max_batch": args.max_batch,
        "max_wait_ms": args.max_wait_ms,
        "replicas": args.replicas,
        "points": points,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
