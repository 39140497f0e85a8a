"""Headline benchmark: ALL THREE of PARITY.md's performance claims in one
JSON line.

Primary metric — training throughput on the reference's own config.
Reference baseline (``BASELINE.md``): 101K steps in 120h on 8x RTX 3090 at
SRN Cars 64x64, global batch 128 — 0.2338 train steps/s = 29.9 examples/s.
This bench times the same workload — X-UNet(H=64, W=64, ch=128), full
train step (loss, grad, Adam, EMA), bf16 compute + per-block remat — on
the attached TPU (one chip under the driver; the mesh scales the same
program to a pod).  There is no CPU mode: a process whose platform is not
``tpu`` prints an error record and exits non-zero, and so does a run in
which any phase recorded an error.

``vs_baseline`` compares **examples/s** against the reference's 29.9: the
hardware differs (8 GPUs there, TPU chips here), so throughput,
not step cadence, is the comparable quantity.  The global batch adapts
downward (128 -> 64 -> 32 per try) if the attached HBM can't hold the
reference's 128 — a single v5e is ~1/8 the memory of the reference's 8-GPU
rig that the 128-batch config was sized for.

The same JSON line also carries:

  * ``srn128`` — train examples/s at the paper's 128^2 config, which the
    reference could not run at all (OOM on 8x3090, README.md:39);
    ``vs_baseline`` is null because the reference has no number to beat.
  * ``sampler`` — seconds per synthesised novel view at the reference
    sampler's exact config (256 steps x 2-in-1 CFG forwards x 8-weight
    guidance sweep, ``/root/reference/sampling.py:130-158``); the
    reference published no timing, so ``vs_baseline`` is null.
  * ``sampler128`` — the same sampler protocol at the full-width 128^2
    config (16384-token attention inside the compiled scan); the
    reference could not sample at 128^2 at all.

Robustness: every train metric is the MEDIAN of >=3 independently timed
windows (per-window values + step-time stats embedded under ``windows``),
with one automatic full retry if the windows disagree by >3x — a single
timed window is one transient stall away from a badly wrong record.
Sub-benches that fail degrade to an ``error`` note in the record instead
of killing the primary metric — and make the exit code non-zero.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import sys
import time

BASELINE_STEPS_PER_SEC = 101_000 / (120 * 3600)   # 8x3090, README.md:39
BASELINE_EXAMPLES_PER_SEC = BASELINE_STEPS_PER_SEC * 128


#: Last phase the bench entered, and the partial payload accumulated so
#: far.  Any death — harness SIGTERM, unexpected exception — emits a
#: structured partial record carrying the phase reached and every
#: sub-metric already measured, so a failed round is diagnosable.
_PHASE = {"reached": "start"}
_PARTIAL: dict = {}

_PHASE_SEQUENCE = (
    "start", "train_srn64", "train_srn128", "sampler_srn64",
    "sampler_srn64_sharded", "sampler_steps_sweep", "sampler_srn128",
    "sampler_srn128_sharded", "sampler128_steps_sweep", "cascade_sweep",
    "kernels_ab", "complete",
)

#: Kernel backends this round was asked to measure (``--kernels``).
#: ``requested[0]`` is the primary — every phase runs with it; extra
#: entries trigger the ``kernels_ab`` phase.  Module-level so partial
#: records stamp WHICH kernel path was live when the round died.
_KERNELS = {"requested": ["xla"]}


def _enter_phase(name: str) -> None:
    _PHASE["reached"] = name


def _partial_record(reason: str) -> dict:
    """A parseable record of an incomplete round: what phase it reached
    and every metric already in hand."""
    return {
        "metric": "bench_partial",
        "value": None,
        "unit": None,
        "vs_baseline": None,
        "error": reason,
        "phase_reached": _PHASE["reached"],
        "kernels": list(_KERNELS["requested"]),
        "partial": dict(_PARTIAL),
    }


def _run(global_batch: int, n_steps: int, accum: int = 1,
         config: str = "srn64", windows: int = 3,
         kernels: str | None = None):
    import jax

    from diff3d_tpu.config import srn64_config, srn128_config
    from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import create_train_state, make_train_step
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": srn64_config, "srn128": srn128_config}[config]()
    model_over = {"remat": True}
    if kernels is not None:
        model_over["kernels"] = kernels     # groupnorm dispatch backend
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

    ds = SyntheticDataset(num_objects=8, num_views=16,
                          imgsize=cfg.model.H, seed=0)
    raw = next(InfiniteLoader(ds, global_batch, seed=0))
    batch = jax.device_put(
        {"imgs": raw["imgs"], "R": raw["R"], "T": raw["T"], "K": raw["K"]},
        env.batch())

    step_fn = make_train_step(model, cfg, env)

    # Warmup: compile + 2 steps.
    for _ in range(2):
        state, metrics = step_fn(state, batch, rng)
    jax.block_until_ready(metrics["loss"])

    # A single timed window is one transient stall away from a badly
    # wrong number.  Time `windows` independent windows, each ended by
    # block_until_ready on the last step's loss (the whole dependent
    # step chain has then run), and report the MEDIAN; if the windows
    # disagree by >3x (a stall hit at least one of them), run one full
    # extra set before taking the median, and embed per-window stats so
    # an anomalous capture is self-evident in the recorded JSON.
    def _window() -> float:
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step_fn(state, batch, rng)
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    times = [_window() for _ in range(windows)]
    retried = max(times) / min(times) > 3.0
    if retried:
        print(f"bench[{config}]: windows disagree >3x "
              f"({[round(t, 2) for t in times]}s); retrying once",
              file=sys.stderr)
        times += [_window() for _ in range(windows)]
    per_window = sorted(n_steps / t for t in times)
    median = per_window[len(per_window) // 2]
    stats = {
        "windows_steps_per_sec": [round(v, 3) for v in per_window],
        "step_ms_min": round(1e3 * min(times) / n_steps, 1),
        # Derived from the SAME window the headline median comes from, so
        # the recorded stats are internally consistent.
        "step_ms_median": round(1e3 / median, 1),
        "steps_per_window": n_steps,
        "retried": retried,
        "kernels": cfg.model.kernels,
    }
    # shardcheck comms report of the program just timed, so perf numbers
    # and collective counts travel in one JSON record (docs/DESIGN.md
    # §10).  Lowered on ABSTRACT args via the sharded step's .lower hook
    # (no extra buffers); best-effort — a report failure must never void
    # the headline metric.
    try:
        from diff3d_tpu.analysis import ir as ir_lib

        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (state, batch))
        # rngcheck stream digest from the SAME trace: determinism
        # provenance travels with the perf number (docs/DESIGN.md §17).
        from diff3d_tpu.analysis.rngflow import install_rng_witness

        witness, uninstall = install_rng_witness()
        try:
            lowered = step_fn.lower(abstract[0], abstract[1], rng)
        finally:
            uninstall()
        stats["rng_stream"] = {"digest": witness.digest(),
                               "n_events": len(witness.events)}
        report = ir_lib.analyze_lowered(f"train_step_{config}", lowered)
        stats["comms"] = ir_lib.comms_summary(report)
        # memcheck memory block from the SAME lower+compile pass: peak
        # HBM, donation effectiveness, hoistable scan-invariant FLOPs
        # (docs/DESIGN.md §13).
        from diff3d_tpu.analysis import mem as mem_lib

        stats["mem"] = (mem_lib.memory_summary(report.memory)
                        if report.memory is not None else None)
        # equivcheck semantic fingerprint from the SAME lowering: the
        # canonical digest travels with the perf number, so a recorded
        # regression can be split into "same program, slower" vs "the
        # program itself changed" (docs/DESIGN.md §18).
        from diff3d_tpu.analysis import equiv as equiv_lib

        stats["semantic_fingerprint"] = (
            equiv_lib.semantic_summary(report.semantic)
            if report.semantic is not None else None)
    except Exception as e:
        stats["comms"] = {"error": str(e).splitlines()[0][:200]}
    return median, stats


def _train_bench(configs, n_steps: int, config: str,
                 kernels: str | None = None):
    """Try ``(global_batch, accum)`` configs in order; returns
    ``(examples_per_sec, global_batch, accum, window_stats)``."""
    steps_per_sec, stats, global_batch, accum, err = None, None, None, 1, None
    for global_batch, accum in configs:
        # OOM (RESOURCE_EXHAUSTED) is deterministic — straight to the
        # next config.  Any other error is a real failure and propagates.
        try:
            steps_per_sec, stats = _run(global_batch, n_steps, accum,
                                        config, kernels=kernels)
            break
        except Exception as e:
            msg = str(e)
            if not ("RESOURCE_EXHAUSTED" in msg
                    or "memory" in msg.lower()):
                raise
            # Keep only the message: holding the exception would pin
            # the failed attempt's traceback frames (train state,
            # batch) and their HBM buffers across the next try.
            err = msg.splitlines()[0]
            print(f"bench[{config}]: b{global_batch}x{accum} failed "
                  f"({err}); trying next config", file=sys.stderr)
    if steps_per_sec is None:
        raise RuntimeError(f"all batch sizes failed: {err}")
    return steps_per_sec * global_batch, global_batch, accum, stats


def _sampler_bench(config: str = "srn64", n_views: int = 4,
                   object_batch: int = 1, use_mesh: bool = False,
                   sampler_kind: str = "ancestral",
                   steps: int | None = None,
                   kernels: str | None = None,
                   comms_out: dict | None = None,
                   mem_out: dict | None = None,
                   rng_out: dict | None = None,
                   sem_out: dict | None = None):
    """Seconds per synthesised view, reference sampler config (256 steps,
    8-weight guidance sweep, ``/root/reference/sampling.py:130-158``) —
    one compiled lax.scan per view.  ``srn128`` runs the full-resolution
    model the reference could never sample (OOM before training,
    README.md:39).

    ``object_batch > 1`` times the object-batched path
    (``Sampler.synthesize_many``) — the configuration ``eval_cli`` ships
    with, where N independent objects share each compiled scan; reported
    cost is per *effective* synthesised view (total time / N*(n_views-1)).

    ``use_mesh`` compiles the sampler with the config's device mesh
    (object axis sharded over the data axis — the sharded serving/eval
    runtime); ``object_batch`` should then be a multiple of the data-axis
    size or padding lanes dilute the per-view number.

    ``sampler_kind`` / ``steps`` select the reverse-process update and
    schedule subset (``diffusion/core.py``): the default is the
    reference protocol above; ``("ddim", 16)`` times the few-step
    deterministic path the serving layer exposes.

    ``kernels`` overrides the groupnorm dispatch backend
    (``ops/dispatch.py``): ``"pallas"`` times the fused
    GroupNorm->FiLM/SiLU Pallas path, ``"xla"`` the unfused reference;
    ``None`` keeps the config default.

    ``comms_out``, when given a dict, is filled with the shardcheck
    comms summary of the batched view-step program (collective counts /
    bytes / upcasts — ``analysis/ir.py``), so the recorded JSON carries
    comms next to the perf number.  Best-effort: on failure (e.g. the
    chunked srn128 path has no single program to lower) the dict gets
    an ``error`` note instead.  ``mem_out`` is the same contract for the
    memcheck memory summary (peak HBM / donation table / hoistable
    scan-invariant FLOPs — ``analysis/mem.py``), extracted from the
    same lower+compile pass.  ``rng_out`` is the same contract for the
    rngcheck stream digest (ordered key-derivation events witnessed
    during the lower — ``analysis/rngflow.py``), so bench rounds carry
    determinism provenance next to comms and memory.  ``sem_out`` is
    the same contract for the equivcheck semantic fingerprint (the
    canonical-form digest and dead/duplicate estimates —
    ``analysis/equiv.py``), pinning WHAT program was timed next to how
    fast it ran.
    """
    import jax
    import numpy as np

    from diff3d_tpu.config import srn64_config, srn128_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.sampling.runtime import Sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": srn64_config, "srn128": srn128_config}[config]()
    if kernels is not None:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, kernels=kernels))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    # srn128 full width: the 256-step scan is split into 4 device
    # executions (bit-identical result, test_sampling pins it; chunks=1
    # elsewhere).
    chunks = 4 if config == "srn128" else 1
    if steps is not None:
        chunks = min(chunks, steps)    # chunks must divide the schedule
    mesh_env = make_mesh(cfg.mesh) if use_mesh else None
    sampler = Sampler(model, init_params(model, cfg, rng), cfg,
                      scan_chunks=chunks, mesh=mesh_env,
                      sampler_kind=sampler_kind, steps=steps)

    if (comms_out is not None or mem_out is not None
            or rng_out is not None or sem_out is not None):
        try:
            from diff3d_tpu.analysis import equiv as equiv_lib
            from diff3d_tpu.analysis import ir as ir_lib
            from diff3d_tpu.analysis import mem as mem_lib
            from diff3d_tpu.analysis.rngflow import install_rng_witness
            from diff3d_tpu.sampling.runtime import record_capacity

            lanes = max(object_batch, sampler.lane_multiple)
            witness, uninstall = install_rng_witness()
            try:
                lowered = sampler.lower_step_many(
                    lanes, record_capacity(n_views))
            finally:
                uninstall()
            if rng_out is not None:
                rng_out.update({"digest": witness.digest(),
                                "n_events": len(witness.events)})
            report = ir_lib.analyze_lowered(
                f"step_many_{config}", lowered)
            if comms_out is not None:
                comms_out.update(ir_lib.comms_summary(report))
            if mem_out is not None and report.memory is not None:
                mem_out.update(mem_lib.memory_summary(report.memory))
            if sem_out is not None and report.semantic is not None:
                sem_out.update(
                    equiv_lib.semantic_summary(report.semantic))
        except Exception as e:
            for d in (comms_out, mem_out, rng_out, sem_out):
                if d is not None:
                    d["error"] = str(e).splitlines()[0][:200]

    s = cfg.model.H

    def _views(seed):
        r = np.random.RandomState(seed)
        return {
            "imgs": r.randn(n_views, cfg.model.H, cfg.model.W,
                            3).astype(np.float32),
            "R": np.broadcast_to(np.eye(3, dtype=np.float32),
                                 (n_views, 3, 3)).copy(),
            "T": r.randn(n_views, 3).astype(np.float32),
            "K": np.array([[s * 1.2, 0, s / 2], [0, s * 1.2, s / 2],
                           [0, 0, 1]], np.float32),
        }

    # Warmup (compile) at the SAME record-buffer capacity as the timed run;
    # synthesize block_until_ready-syncs and fetches the record to host.
    if object_batch == 1:
        views = _views(0)
        sampler.synthesize(views, rng, max_views=n_views)
        t0 = time.perf_counter()
        sampler.synthesize(views, rng, max_views=n_views)
        # graftlint: disable-next-line=GL106(synthesize block_until_ready-syncs the record before returning)
        raw = time.perf_counter() - t0
        return raw / (n_views - 1), raw, n_views - 1
    views_list = [_views(i) for i in range(object_batch)]
    rngs = list(jax.random.split(rng, object_batch))
    sampler.synthesize_many(views_list, rngs, max_views=n_views)
    t0 = time.perf_counter()
    sampler.synthesize_many(views_list, rngs, max_views=n_views)
    # graftlint: disable-next-line=GL106(synthesize_many block_until_ready-syncs the record before returning)
    raw = time.perf_counter() - t0
    return raw / (object_batch * (n_views - 1)), raw, (object_batch
                                                       * (n_views - 1))


def _sampler_steps_sweep(config: str = "srn64",
                         steps_list=(256, 64, 16, 8), n_views: int = 4,
                         object_batch: int = 1, use_mesh: bool = False,
                         kernels: str | None = None,
                         bench_fn=None) -> dict:
    """Few-step sampling sweep: s/view of the deterministic DDIM sampler
    at each schedule subset, plus speedup relative to the first (full
    256-step) point.  Model calls scale linearly with the schedule
    (``Sampler.model_calls_per_view == steps``, pinned by test_ddim), so
    the sweep quantifies how much of the 32x fewer-calls headroom the
    runtime actually converts into wall-clock speedup (per-step overhead,
    warmup amortisation, and host sync eat the rest).

    ``bench_fn`` (default :func:`_sampler_bench`) is injectable so the
    guard test can validate the sweep's structure without compiling four
    full-width samplers.
    """
    bench_fn = bench_fn or _sampler_bench
    points = []
    for steps in steps_list:
        spv, raw, n_eff = bench_fn(config, n_views=n_views,
                                   object_batch=object_batch,
                                   use_mesh=use_mesh,
                                   sampler_kind="ddim", steps=steps,
                                   kernels=kernels)
        points.append({
            "steps": steps,
            "sampler": "ddim",
            "sec_per_view": round(spv, 3),
            "raw_seconds": round(raw, 3),
            "effective_views": n_eff,
            "model_calls_per_view": steps,
        })
    base = points[0]["sec_per_view"]
    for pt in points:
        pt["speedup_vs_256"] = (round(base / pt["sec_per_view"], 2)
                                if pt["sec_per_view"] else None)
    return {
        "metric": f"sampler_steps_sweep_{config}",
        "unit": "s/view",
        "vs_baseline": None,   # reference has no few-step sampler at all
        "n_views": n_views,
        "object_batch": object_batch,
        "kernels": kernels or "default",
        "points": points,
    }


def _cascade_bench(config: str = "srn128", n_views: int = 2,
                   plan_spec: str | None = None):
    """Times the two cascade phases against the matched single-pass
    sampler (DESIGN.md §20): one warmed run each of the draft pass, the
    truncated refine pass, and the full-schedule single pass, same
    views and key stream.  Returns ``(plan_spec, draft_s, refine_s,
    single_s, n_eff)`` — raw seconds per phase plus the effective view
    count the sweep divides by.
    """
    import jax
    import numpy as np

    from diff3d_tpu.cascade import CascadePlan, CascadeSampler
    from diff3d_tpu.config import srn64_config, srn128_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling.runtime import Sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = {"srn64": srn64_config, "srn128": srn128_config}[config]()
    H = cfg.model.H
    if plan_spec is None:
        plan_spec = (f"draft={H // 2}:ddim:8,"
                     f"refine={H}:ancestral:64@t0.5")
    plan = CascadePlan.parse(plan_spec)
    rng = jax.random.PRNGKey(0)
    model = build_model(cfg)
    params = init_params(model, cfg, rng)
    cascade = CascadeSampler(model, params, cfg, plan)
    single = Sampler(model, params, cfg)

    s = cfg.model.H

    def _views(seed):
        r = np.random.RandomState(seed)
        return {
            "imgs": r.randn(n_views, cfg.model.H, cfg.model.W,
                            3).astype(np.float32),
            "R": np.broadcast_to(np.eye(3, dtype=np.float32),
                                 (n_views, 3, 3)).copy(),
            "T": r.randn(n_views, 3).astype(np.float32),
            "K": np.array([[s * 1.2, 0, s / 2], [0, s * 1.2, s / 2],
                           [0, 0, 1]], np.float32),
        }

    views = _views(0)
    k_draft, k_refine = jax.random.split(rng)
    # Warmup (compile) each phase, then time synced reruns.
    drafts = cascade.synthesize_draft(views, k_draft, max_views=n_views)
    t0 = time.perf_counter()
    drafts = cascade.synthesize_draft(views, k_draft, max_views=n_views)
    # graftlint: disable-next-line=GL106(synthesize block_until_ready-syncs the record before returning)
    draft_s = time.perf_counter() - t0
    cascade.refine_views(views, drafts, k_refine, max_views=n_views)
    t0 = time.perf_counter()
    cascade.refine_views(views, drafts, k_refine, max_views=n_views)
    # graftlint: disable-next-line=GL106(refine_views block_until_ready-syncs its result)
    refine_s = time.perf_counter() - t0
    single.synthesize(views, rng, max_views=n_views)
    t0 = time.perf_counter()
    single.synthesize(views, rng, max_views=n_views)
    # graftlint: disable-next-line=GL106(synthesize block_until_ready-syncs the record before returning)
    single_s = time.perf_counter() - t0
    return plan_spec, draft_s, refine_s, single_s, n_views - 1


def _cascade_sweep(config: str = "srn128", n_views: int = 2,
                   bench_fn=None) -> dict:
    """Cascade serving economics: draft latency (time to first preview
    frame), refine latency, and end-to-end s/view against the
    single-pass full-schedule sampler at the same resolution.

    ``bench_fn`` (default :func:`_cascade_bench`) is injectable so the
    guard test can validate the record's structure without compiling
    three samplers.
    """
    bench_fn = bench_fn or _cascade_bench
    plan_spec, draft_s, refine_s, single_s, n_eff = bench_fn(
        config, n_views=n_views)
    e2e = draft_s + refine_s
    return {
        "metric": f"cascade_sweep_{config}",
        "unit": "s/view",
        "vs_baseline": None,   # reference has no cascade at all
        "plan": plan_spec,
        "n_views": n_views,
        "effective_views": n_eff,
        "draft_sec_per_view": round(draft_s / n_eff, 3),
        "refine_sec_per_view": round(refine_s / n_eff, 3),
        "end_to_end_sec_per_view": round(e2e / n_eff, 3),
        "single_pass_sec_per_view": round(single_s / n_eff, 3),
        "draft_raw_seconds": round(draft_s, 3),
        "refine_raw_seconds": round(refine_s, 3),
        "single_pass_raw_seconds": round(single_s, 3),
        "speedup_vs_single_pass": (round(single_s / e2e, 2)
                                   if e2e else None),
        "preview_speedup": (round(single_s / draft_s, 2)
                            if draft_s else None),
    }


def _kernels_ab(kernels_list, *, config: str = "srn64",
                configs=((8, 1),), n_steps: int = 3, n_views: int = 4,
                train_fn=None, sampler_fn=None) -> dict:
    """Head-to-head kernel-backend sweep: the SAME train step and the
    SAME 256-step ancestral sampler timed once per requested backend
    (``xla`` = unfused reference graph, ``pallas`` = fused
    GroupNorm->FiLM/SiLU epilogues, ``ops/pallas_film.py``).  Variant 0
    is the comparison base; later variants carry speedups relative to
    it (train: higher examples/s is better; sampler: lower s/view is
    better — both reported as >1 == variant wins).  A variant that
    fails records a per-variant ``*_error`` note instead of voiding the
    others — the A/B is diagnosable even when one backend can't compile.

    ``train_fn`` / ``sampler_fn`` (default the real benches) are
    injectable so the guard test can validate the record's structure
    without compiling anything.
    """
    train_fn = train_fn or _train_bench
    sampler_fn = sampler_fn or _sampler_bench
    variants = []
    for k in kernels_list:
        v: dict = {"kernels": k}
        try:
            eps, gb, ac, stats = train_fn(list(configs), n_steps, config,
                                          kernels=k)
            v["train_examples_per_sec"] = round(eps, 2)
            v["train_global_batch"] = gb
            v["train_step_ms_median"] = stats.get("step_ms_median")
        except Exception as e:
            v["train_error"] = str(e).splitlines()[0][:200]
        try:
            spv, raw, n_eff = sampler_fn(config, n_views=n_views,
                                         kernels=k)
            v["sampler_sec_per_view"] = round(spv, 3)
            v["sampler_raw_seconds"] = round(raw, 3)
        except Exception as e:
            v["sampler_error"] = str(e).splitlines()[0][:200]
        variants.append(v)
    base = variants[0]
    for v in variants[1:]:
        b_eps = base.get("train_examples_per_sec")
        v_eps = v.get("train_examples_per_sec")
        if b_eps and v_eps:
            v[f"train_speedup_vs_{base['kernels']}"] = round(
                v_eps / b_eps, 3)
        b_spv = base.get("sampler_sec_per_view")
        v_spv = v.get("sampler_sec_per_view")
        if b_spv and v_spv:
            v[f"sampler_speedup_vs_{base['kernels']}"] = round(
                b_spv / v_spv, 3)
    return {
        "metric": f"kernels_ab_{config}",
        "dimension": "kernels",
        "unit": None,
        "vs_baseline": None,   # reference has a single (unfused) path
        "variants": variants,
    }


def _parse_args(argv):
    """``--kernels`` is the only flag: a comma list of groupnorm dispatch
    backends.  Entry 0 runs every phase; extra entries add the
    ``kernels_ab`` head-to-head phase."""
    import argparse

    p = argparse.ArgumentParser(
        prog="bench.py",
        description="Headline benchmark (see module docstring).")
    p.add_argument(
        "--kernels", default="xla",
        help="comma list of groupnorm kernel backends to measure "
             "(xla|pallas|auto); first entry drives all phases, extra "
             "entries run the kernels_ab A/B sweep (e.g. 'xla,pallas')")
    args = p.parse_args(list(argv))
    ks = [k.strip() for k in args.kernels.split(",") if k.strip()]
    bad = [k for k in ks if k not in ("xla", "pallas", "auto")]
    if bad:
        p.error(f"unknown kernel backend(s) {bad}; "
                f"choose from xla, pallas, auto")
    return ks or ["xla"]


def _recorded_errors(obj, path="") -> list:
    """Paths of every ``error`` / ``*_error`` note in a (nested) record."""
    found = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            here = f"{path}.{k}" if path else str(k)
            if k == "error" or str(k).endswith("_error"):
                found.append(here)
            else:
                found.extend(_recorded_errors(v, here))
    elif isinstance(obj, (list, tuple)):
        for n, v in enumerate(obj):
            found.extend(_recorded_errors(v, f"{path}[{n}]"))
    return found


def main(argv=()) -> int:
    """Run the bench with an always-parseable exit: a SIGTERM from the
    harness (``timeout`` sends TERM before KILL) or an unexpected
    exception both emit a structured partial-result record instead of
    nothing — and a non-zero exit code, as does a platform that is not
    ``tpu`` or any phase that recorded an error.  The previous SIGTERM
    disposition is restored on return so an embedding process (tests, a
    driving trainer) keeps its own handlers."""
    _PHASE["reached"] = "start"
    _PARTIAL.clear()
    _KERNELS["requested"] = _parse_args(argv)

    def _on_term(signum, frame):  # pragma: no cover - signal path
        print(json.dumps(_partial_record(
            "sigterm: killed before completion")), flush=True)
        os._exit(1)

    prev_term = None
    try:
        prev_term = signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # pragma: no cover - non-main thread
        prev_term = None
    try:
        return _bench_main()
    except BaseException as e:
        msg = str(e).splitlines()[0][:300] if str(e) else ""
        print(json.dumps(_partial_record(
            f"{type(e).__name__}: {msg}" if msg else type(e).__name__)),
            flush=True)
        return 1
    finally:
        if prev_term is not None:
            try:
                signal.signal(signal.SIGTERM, prev_term)
            except ValueError:  # pragma: no cover
                pass


def _bench_main() -> int:
    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    devices = jax.devices()     # no backend -> main()'s partial record
    platform = devices[0].platform
    ndev = len(devices)
    if platform != "tpu":
        # No CPU mode: a number from a CPU run is not a device metric.
        print(json.dumps(_partial_record(
            f"platform is {platform!r}, not 'tpu': bench.py measures "
            "the chip and does not fall back")))
        return 1
    kernels_list = list(_KERNELS["requested"])
    primary = kernels_list[0]
    # srn64 configs in preference order: the reference's exact global batch
    # 128 (2 accumulation microbatches fit one 16G chip), then direct
    # smaller batches.
    configs = [(128, 2), (64, 1), (32, 1)]
    n_steps = 10

    _enter_phase("train_srn64")
    try:
        examples_per_sec, global_batch, accum, stats = _train_bench(
            configs, n_steps, "srn64", kernels=primary)
    except Exception as e:
        print(json.dumps({
            "metric": f"train_examples_per_sec_srn64_{platform}_x{ndev}",
            "value": None,
            "unit": "examples/s",
            "vs_baseline": None,
            "error": str(e).splitlines()[0][:300],
            "phase_reached": _PHASE["reached"],
        }))
        return 1
    name = f"b{global_batch}" + (f"x{accum}accum" if accum > 1 else "")
    payload = _PARTIAL     # alias: a partial record carries it verbatim
    payload.update({
        "metric": f"train_examples_per_sec_srn64_{name}_{platform}"
                  f"_x{ndev}",
        "value": round(examples_per_sec, 2),
        "unit": "examples/s",
        "vs_baseline": round(examples_per_sec / BASELINE_EXAMPLES_PER_SEC,
                             4),
        "kernels": primary,
        "windows": stats,
    })

    # Secondary headline metrics ride in the same JSON line.
    _enter_phase("train_srn128")
    try:
        eps128, gb128, ac128, stats128 = _train_bench(
            [(16, 4), (8, 4)], 5, "srn128", kernels=primary)
        payload["srn128"] = {
            "metric": f"train_examples_per_sec_srn128_b{gb128}x"
                      f"{ac128}accum_{platform}_x{ndev}",
            "value": round(eps128, 2),
            "unit": "examples/s",
            "vs_baseline": None,   # reference OOMs at 128^2
            "windows": stats128,
        }
    except Exception as e:
        payload["srn128"] = {"error": str(e).splitlines()[0][:200]}
    _enter_phase("sampler_srn64")
    try:
        comms: dict = {}
        mem: dict = {}
        rng_stream: dict = {}
        sem: dict = {}
        sec_per_view, raw_s, n_eff = _sampler_bench(
            kernels=primary, comms_out=comms, mem_out=mem,
            rng_out=rng_stream, sem_out=sem)
        payload["sampler"] = {
            "metric": f"sampler_sec_per_view_srn64_{platform}",
            "value": round(sec_per_view, 2),
            "unit": "s/view",
            "vs_baseline": None,   # reference published no timing
            "kernels": primary,
            "raw_seconds": round(raw_s, 2),
            "effective_views": n_eff,
            "chips_used": 1,
            "comms": comms,
            "mem": mem,
            "rng_stream": rng_stream,
            "semantic_fingerprint": sem,
        }
    except Exception as e:
        payload["sampler"] = {"error": str(e).splitlines()[0][:200]}
    if ndev > 1 and isinstance(payload.get("sampler"), dict) \
            and "value" in payload["sampler"]:
        # Sharded runtime: one object per chip on the data axis.  The
        # unsharded block above keeps its longitudinal metric name;
        # per-chip scaling = value / sharded.sec_per_view.
        _enter_phase("sampler_srn64_sharded")
        try:
            sh_comms: dict = {}
            sh_mem: dict = {}
            sh_rng: dict = {}
            sh_sem: dict = {}
            sh_spv, sh_raw, sh_eff = _sampler_bench(
                object_batch=ndev, use_mesh=True, kernels=primary,
                comms_out=sh_comms, mem_out=sh_mem,
                rng_out=sh_rng, sem_out=sh_sem)
            payload["sampler"]["sharded"] = {
                "chips_used": ndev,
                "sec_per_view": round(sh_spv, 2),
                "raw_seconds": round(sh_raw, 2),
                "effective_views": sh_eff,
                "object_batch": ndev,
                "speedup_vs_single": round(
                    payload["sampler"]["value"] / sh_spv, 2)
                if sh_spv else None,
                "comms": sh_comms,
                "mem": sh_mem,
                "rng_stream": sh_rng,
                "semantic_fingerprint": sh_sem,
            }
        except Exception as e:
            payload["sampler"]["sharded"] = {
                "error": str(e).splitlines()[0][:200]}
    _enter_phase("sampler_steps_sweep")
    try:
        # Few-step DDIM sweep at srn64: how wall-clock tracks the
        # 256 -> 8 model-call reduction on real hardware.
        payload["sampler_steps"] = _sampler_steps_sweep(
            kernels=primary)
    except Exception as e:
        payload["sampler_steps"] = {"error": str(e).splitlines()[0][:200]}
    _enter_phase("sampler_srn128")
    try:
        # Object-batch 2, 2 views each = 2 effective synthesised views
        # per batched 256-step scan at 16384 tokens/frame, full-width
        # srn128 — the configuration eval_cli ships with (the unbatched
        # worst case was r3's 107 s/view; the shipping path amortises
        # the scan across objects).  raw_seconds/effective_views keep
        # the longitudinal record comparable across metric semantics
        # (ADVICE r4): raw_seconds is the wall time of ONE batched
        # scan pass, value = raw_seconds / effective_views.
        sec_per_view128, raw_s128, n_eff128 = _sampler_bench(
            "srn128", n_views=2, object_batch=2, kernels=primary)
        payload["sampler128"] = {
            "metric": f"sampler_sec_per_view_srn128_objbatch2_"
                      f"{platform}",
            "value": round(sec_per_view128, 2),
            "unit": "s/view",
            "vs_baseline": None,   # reference cannot run 128^2 at all
            "kernels": primary,
            "raw_seconds": round(raw_s128, 2),
            "effective_views": n_eff128,
            "chips_used": 1,
        }
    except Exception as e:
        payload["sampler128"] = {"error": str(e).splitlines()[0][:200]}
    if ndev > 1 and isinstance(payload.get("sampler128"), dict) \
            and "value" in payload["sampler128"]:
        _enter_phase("sampler_srn128_sharded")
        try:
            sh_spv, sh_raw, sh_eff = _sampler_bench(
                "srn128", n_views=2, object_batch=ndev, use_mesh=True,
                kernels=primary)
            payload["sampler128"]["sharded"] = {
                "chips_used": ndev,
                "sec_per_view": round(sh_spv, 2),
                "raw_seconds": round(sh_raw, 2),
                "effective_views": sh_eff,
                "object_batch": ndev,
                "speedup_vs_single": round(
                    payload["sampler128"]["value"] / sh_spv, 2)
                if sh_spv else None,
            }
        except Exception as e:
            payload["sampler128"]["sharded"] = {
                "error": str(e).splitlines()[0][:200]}
    _enter_phase("sampler128_steps_sweep")
    try:
        # Same sweep at the full-width 128^2 config (object-batched
        # like the sampler128 block so the scan stays amortised).
        payload["sampler128_steps"] = _sampler_steps_sweep(
            "srn128", n_views=2, object_batch=2, kernels=primary)
    except Exception as e:
        payload["sampler128_steps"] = {
            "error": str(e).splitlines()[0][:200]}
    _enter_phase("cascade_sweep")
    try:
        # Cascade serving economics at full width: 64²-draft preview
        # latency, truncated 128² refine latency, end-to-end s/view
        # vs the single-pass 256-step sampler (DESIGN.md §20).
        payload["cascade"] = _cascade_sweep("srn128", n_views=2)
    except Exception as e:
        payload["cascade"] = {"error": str(e).splitlines()[0][:200]}

    if len(kernels_list) > 1:
        _enter_phase("kernels_ab")
        try:
            # Re-time the srn64 train step and sampler per backend at
            # the batch config the primary phase settled on, so the
            # A/B rides one known-good config instead of re-walking
            # the fallback ladder per variant.
            payload["kernels_ab"] = _kernels_ab(
                kernels_list, configs=[(global_batch, accum)],
                n_steps=n_steps)
        except Exception as e:
            payload["kernels_ab"] = {
                "error": str(e).splitlines()[0][:200]}

    _enter_phase("complete")
    payload["phase_reached"] = "complete"
    failed = _recorded_errors(payload)
    if failed:
        payload["failed_phases"] = failed
    print(json.dumps(payload))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
