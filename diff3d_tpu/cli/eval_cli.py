"""Evaluation entry point: PSNR / SSIM / FID of synthesised novel views.

The reference has NO evaluation code (``SURVEY.md`` §5.5) despite FID/PSNR
being the paper's headline metrics; this closes that gap.  For each of the
first ``--objects`` val-split objects, the trained model synthesises every
view autoregressively from view 0 (the reference sampler's protocol,
``/root/reference/sampling.py:158-184``), and the generated views are
scored against ground truth:

  * PSNR / SSIM per view at the sampler's guidance weight ``--w_index``
    (default 1, i.e. w=1 in the reference's 0..7 sweep), averaged.
  * FID between the pooled generated views and the pooled GT views.
    With ``--feature_weights <local VGG16 state dict>`` the real
    VGG16-fc2 extractor is used and the number is reported as ``fid``;
    without it the seeded random-projection fallback is used and the
    number is reported as ``fid_randfeat`` — the key always says which
    extractor produced the value (``evaluation/features.py``).

Evaluation is OUTAGE-PROOF: synthesis and scoring are separate phases.
Each object's generated views are written to ``--resume_dir`` (default
``<out>.objdir``) the moment its batch finishes; re-running the same
command skips already-synthesised objects and proceeds straight to
scoring, so a link failure N objects in costs nothing but the partial
batch.  Scoring always recomputes every metric from the on-disk records,
so the final JSON is identical whether the run completed in one pass or
five.

``--w_select K`` adds validation-selected guidance: K EXTRA objects
(drawn after the eval set — disjoint from it) are synthesised, the
guidance weight with the best mean PSNR on them is chosen, and the eval
set is additionally scored at that weight (``*_w_selected`` fields).
The fixed ``--w_index`` headline is unchanged; selection never sees an
eval object.  This is the methodologically clean version of the
reference's w=0..7 sweep (``/root/reference/sampling.py:158``), whose
point is that the best w is data-dependent.

Writes one JSON line to stdout and (optionally) ``--out`` JSONL.

Usage:
    python -m diff3d_tpu.cli.eval_cli --model ./checkpoints \
        --val_data ./data/SRN/cars_train [--objects 8]
"""

from __future__ import annotations

import argparse
import json
import logging
import os

from diff3d_tpu.cli._common import (add_model_width_args,
                                    apply_model_width_overrides,
                                    build_abstract_state,
                                    load_eval_params)
from diff3d_tpu.config import NAMED_CONFIGS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", required=True,
                   help="checkpoint directory (Orbax root)")
    p.add_argument("--val_data", default=None,
                   help="SRN split dir (val objects are drawn from the "
                        "same 90/10 split the trainer used)")
    p.add_argument("--synthetic_scenes", action="store_true",
                   help="evaluate on ray-traced sphere scenes instead of "
                        "--val_data (default seed 1 = the held-out set "
                        "train_cli --synthetic_scenes validates on)")
    p.add_argument("--scenes_seed", type=int, default=1,
                   help="scene generator seed for --synthetic_scenes "
                        "(0 = the training scenes, 1 = held-out)")
    p.add_argument("--scene_objects", type=int, default=None,
                   help="the --scene_objects count the model was TRAINED "
                        "with; with --scenes_seed 0 ('the training "
                        "scenes'), --objects beyond it were never seen in "
                        "training and would skew a train-vs-heldout "
                        "comparison, so that combination errors out")
    p.add_argument("--object_batch", type=int, default=None,
                   help="objects synthesised concurrently as one batched "
                        "program (objects are independent; batching fills "
                        "the chip — per-object scores match --object_batch "
                        "1 to float tolerance).  Default: 8 at <=64^2, 2 "
                        "above (the batched model call and the record "
                        "buffer both scale with it; lower if OOM)")
    p.add_argument("--mesh", action="store_true",
                   help="shard synthesis over a device mesh (cfg.mesh): "
                        "the object batch rides the data axis, params "
                        "follow the configured replicated/fsdp policy; "
                        "--object_batch rounds up to the data-axis size")
    add_model_width_args(p)
    p.add_argument("--picklefile", default=None)
    p.add_argument("--config",
                   choices=list(NAMED_CONFIGS),
                   default="srn64")
    p.add_argument("--objects", type=int, default=8,
                   help="number of val objects to evaluate")
    p.add_argument("--max_views", type=int, default=None,
                   help="cap views per object (full object if omitted)")
    p.add_argument("--steps", type=int, default=None,
                   help="diffusion steps (reference: 256) — the DENSE "
                        "training grid; see --sampler_steps for the "
                        "few-step sampling subset")
    p.add_argument("--sampler", choices=["ancestral", "ddim"],
                   default="ancestral",
                   help="reverse-process update: 'ancestral' (paper's "
                        "stochastic sampler) or 'ddim' (deterministic "
                        "eta=0, enables few-step sampling)")
    p.add_argument("--sampler_steps", type=int, default=None,
                   help="few-step schedule: reverse steps per view, a "
                        "divisor of the dense grid (e.g. 16 with 256 "
                        "timesteps); default = full grid")
    p.add_argument("--parity_objects", type=int, default=0,
                   help="ALSO synthesise this many eval objects with the "
                        "full-grid ancestral oracle at matched seeds and "
                        "report PSNR/SSIM of the evaluated sampler "
                        "against it (sampler_parity in the output JSON) — "
                        "quantifies few-step quality degradation")
    p.add_argument("--scan_chunks", type=int, default=1,
                   help="split each view's diffusion scan into this many "
                        "device executions (must divide --steps; "
                        "bit-identical to 1 — several shorter "
                        "executions per view instead of one long one)")
    p.add_argument("--w_index", type=int, default=1,
                   help="guidance-sweep index scored for PSNR/SSIM/FID")
    p.add_argument("--w_select", type=int, default=0,
                   help="ALSO score at a validation-selected guidance "
                        "weight: synthesise this many extra selection "
                        "objects (disjoint from the eval set, drawn after "
                        "it), pick the w with the best mean PSNR on them, "
                        "and report *_w_selected fields at that w")
    p.add_argument("--feature_weights", default=None,
                   help="local VGG16 state-dict file (.pth/.pt/.npz, "
                        "torchvision key names) for real-feature FID; "
                        "omitted -> random-feature fallback, reported as "
                        "fid_randfeat")
    p.add_argument("--raw_params", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None, help="append final JSONL here")
    p.add_argument("--resume_dir", default=None,
                   help="per-object synthesis records live here (one .npz "
                        "per object, written as each object completes); "
                        "re-running skips objects already present.  "
                        "Default: <--out>.objdir when --out is given, "
                        "else a fresh temp dir (no resumability)")
    p.add_argument("--save_dir", default=None,
                   help="dump gt/generated view PNGs here "
                        "(<obj>/view{V}_{gt,gen}.png)")
    p.add_argument("--orbit", type=int, default=0,
                   help="ALSO render an N-frame orbit turntable per "
                        "--orbit_objects eval object (radius/elevation "
                        "derived from its GT poses) and report the "
                        "multi-view reprojection-consistency metric "
                        "(orbit_consistency in the output JSON); with "
                        "--save_dir the frames land in "
                        "<obj>/orbit/frame_%%03d.png + a contact sheet")
    p.add_argument("--orbit_objects", type=int, default=1,
                   help="eval objects to render orbits for (first K)")
    return p


def _record_path(resume_dir: str, obj, step: int) -> str:
    # checkpoint step is part of the NAME, not the settings stamp: after
    # more training, the same longitudinal eval command simply finds no
    # records for the new step and re-synthesises (stale-step records are
    # ignored, not a fatal protocol conflict) — while a dataset/model/
    # seed/timesteps mismatch against a same-step record stays a hard
    # error, since silently mixing those corrupts the aggregate.
    return os.path.join(resume_dir, f"obj_s{step}_{obj}.npz")


def _save_object_record(resume_dir: str, obj, gen, meta: dict) -> None:
    """Atomically persist one object's generated views (all guidance
    weights, float16 — ~2.4 MB at 128^2) plus the synthesis settings
    they were produced under."""
    import numpy as np

    path = _record_path(resume_dir, obj, meta["checkpoint_step"])
    tmp = path + ".tmp"
    np.savez_compressed(tmp, gen=gen.astype(np.float16),
                        meta=json.dumps(meta))
    # np.savez appends .npz to names it doesn't recognise
    if os.path.exists(tmp + ".npz"):
        tmp += ".npz"
    os.replace(tmp, path)


def _load_object_record(resume_dir: str, obj, expect_meta: dict):
    """Return (gen float32, True) if a valid record exists, else
    (None, False).  A record whose synthesis settings don't match the
    current flags is a hard error — silently mixing protocols would
    corrupt the aggregate."""
    import numpy as np

    path = _record_path(resume_dir, obj, expect_meta["checkpoint_step"])
    if not os.path.exists(path):
        return None, False
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        gen = z["gen"]                   # float16, cast per-use
    if meta != expect_meta:
        raise SystemExit(
            f"resume record {path} was synthesised under different "
            f"settings ({meta} != {expect_meta}); clear --resume_dir or "
            "point it elsewhere")
    return gen, True


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    configure_compile_cache()

    # Dataset-choice errors fire BEFORE model init + checkpoint restore.
    if args.synthetic_scenes and args.val_data:
        raise SystemExit(
            "--synthetic_scenes and --val_data are mutually exclusive")
    if not (args.synthetic_scenes or args.val_data):
        raise SystemExit("pass --val_data or --synthetic_scenes")
    if (args.synthetic_scenes and args.scenes_seed == 0
            and args.scene_objects is not None
            and args.objects + args.w_select > args.scene_objects):
        raise SystemExit(
            f"--scenes_seed 0 scores training scenes, but --objects "
            f"{args.objects} + --w_select {args.w_select} exceeds the "
            f"trained --scene_objects {args.scene_objects}: objects "
            "beyond the trained count were never seen in training and "
            "would be mislabeled as 'train' scores — lower --objects or "
            "drop --scene_objects")
    if args.object_batch is not None and args.object_batch < 1:
        raise SystemExit("--object_batch must be >= 1")

    import dataclasses

    import jax
    import numpy as np

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.data.srn import SRNDataset
    from diff3d_tpu.evaluation import (fid_from_stats, gaussian_stats, psnr,
                                       ssim)
    from diff3d_tpu.evaluation.features import resolve_feature_fn
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = config_lib.named_config(args.config)
    if args.steps:
        cfg = dataclasses.replace(
            cfg, diffusion=dataclasses.replace(cfg.diffusion,
                                               timesteps=args.steps))
    cfg = apply_model_width_overrides(cfg, args)

    # Fail fast on a bad --feature_weights path/file BEFORE the expensive
    # sampling loop; jit once here so the gt and gen stats passes share
    # one compiled executable.
    feature_fn, fid_key = resolve_feature_fn(args.feature_weights)
    feature_fn = jax.jit(feature_fn)

    model = build_model(cfg)
    try:
        step, params = load_eval_params(args.model,
                                        build_abstract_state(cfg),
                                        args.raw_params)
    except ValueError as e:   # e.g. --raw_params on an ema_bf16 checkpoint
        raise SystemExit(str(e))

    n_dataset_objs = max(8, args.objects + args.w_select)
    if args.synthetic_scenes:
        from diff3d_tpu.data import SyntheticScenesDataset

        ds = SyntheticScenesDataset(num_objects=n_dataset_objs,
                                    imgsize=cfg.model.H,
                                    seed=args.scenes_seed)
    else:
        ds = SRNDataset("val", args.val_data, args.picklefile,
                        imgsize=cfg.model.H,
                        split_seed=cfg.data.split_seed,
                        train_fraction=cfg.data.train_fraction)
    mesh_env = None
    if args.mesh:
        from diff3d_tpu.parallel import make_mesh

        mesh_env = make_mesh(cfg.mesh)
        logging.info("sampling on mesh %s (object axis over '%s', "
                     "params %s)", dict(mesh_env.mesh.shape),
                     cfg.mesh.data_axis, cfg.mesh.param_sharding)
    sampler = Sampler(model, params, cfg,
                      scan_chunks=args.scan_chunks, mesh=mesh_env,
                      sampler_kind=args.sampler, steps=args.sampler_steps)

    if args.object_batch is None:
        # The batched model call (N*2B examples) and the [N, capacity, B,
        # H, W, 3] record buffer both scale with N; at 128^2 a full-width
        # no-max_views eval would OOM at N=8, so the default stays shy
        # there and the flag overrides.
        args.object_batch = 8 if cfg.model.H <= 64 else 2
        logging.info("object_batch auto -> %d (H=%d)", args.object_batch,
                     cfg.model.H)
    if args.object_batch % sampler.lane_multiple:
        # synthesize_many pads internally, but a non-multiple batch wastes
        # the padding lanes' FLOPs every chunk — round the batch itself.
        args.object_batch = (-(-args.object_batch // sampler.lane_multiple)
                             * sampler.lane_multiple)
        logging.info("object_batch rounded -> %d (mesh data-axis size %d)",
                     args.object_batch, sampler.lane_multiple)

    ephemeral_resume_dir = None
    if args.resume_dir is None:
        if args.out:
            args.resume_dir = args.out + ".objdir"
        else:
            import tempfile

            # no --out, no --resume_dir: records still go through disk
            # (one scoring path) but the dir is ours to delete at exit —
            # otherwise every throwaway eval leaks MBs of npz into /tmp
            args.resume_dir = tempfile.mkdtemp(prefix="diff3d_eval_")
            ephemeral_resume_dir = args.resume_dir
    os.makedirs(args.resume_dir, exist_ok=True)
    if ephemeral_resume_dir is not None:
        import atexit
        import shutil

        atexit.register(shutil.rmtree, ephemeral_resume_dir,
                        ignore_errors=True)

    # Per-object keys are split off in object order BEFORE batching —
    # eval objects first, then the w_select selection objects — so the
    # scores are invariant to --object_batch AND to resume boundaries
    # (same key -> same per-object stream; see Sampler.synthesize_many),
    # and adding --w_select never perturbs an eval object's stream.
    rng = jax.random.PRNGKey(args.seed)
    if len(ds.ids) < args.objects + args.w_select:
        raise SystemExit(
            f"dataset has {len(ds.ids)} val objects; --objects "
            f"{args.objects} + --w_select {args.w_select} requested")
    eval_objs = list(ds.ids[: args.objects])
    sel_objs = list(ds.ids[args.objects: args.objects + args.w_select])
    all_objs = eval_objs + sel_objs
    obj_views, obj_keys = {}, {}
    for obj in all_objs:
        obj_views[obj] = ds.all_views(obj)
        rng, k = jax.random.split(rng)
        obj_keys[obj] = k

    def n_views_of(v) -> int:
        n = int(v["imgs"].shape[0])
        return min(n, args.max_views) if args.max_views else n

    # Synthesis settings stamp: a resume record is valid only if it was
    # produced by an identical sampling protocol — including the model
    # directory and the DATASET identity (without it, a seed-0 and a
    # seed-1 eval sharing an --out would silently score each other's
    # generations against the wrong ground truth).
    dataset_id = (f"scenes:{args.scenes_seed}" if args.synthetic_scenes
                  else f"srn:{os.path.abspath(args.val_data)}")
    expect_meta = {
        "model": os.path.abspath(args.model),
        "dataset": dataset_id,
        "checkpoint_step": int(step),
        "timesteps": int(cfg.diffusion.timesteps),
        # The schedule changes every generated pixel: stale records from a
        # different sampler/step count must hard-error, not silently mix.
        "sampler": sampler.sampler_kind,
        "sampler_steps": int(sampler.steps),
        "seed": int(args.seed),
        "max_views": args.max_views,
        "H": int(cfg.model.H),
        # The guidance sweep is the record's B axis: a changed sweep must
        # invalidate stale records, or psnr_per_w / --w_index silently
        # mis-index into generations made under different weights.
        "guidance_weights": [float(w) for w in
                             cfg.diffusion.guidance_weights],
    }

    # ---- Phase 1: synthesis (resumable; each object lands on disk the
    # moment its batch completes) -------------------------------------
    gens = {}
    todo = []
    for obj in all_objs:
        gen, ok = _load_object_record(args.resume_dir, obj, expect_meta)
        if ok:
            gens[obj] = gen
        else:
            todo.append(obj)
    if gens:
        logging.info("resume: %d/%d objects already synthesised in %s",
                     len(gens), len(all_objs), args.resume_dir)

    progress_path = os.path.join(args.resume_dir, "progress.jsonl")
    i = 0
    while i < len(todo):
        # chunk of <= object_batch consecutive objects with equal view
        # counts (synthesize_many truncates to the batch minimum)
        j = i + 1
        nv = n_views_of(obj_views[todo[i]])
        while (j < len(todo) and j - i < args.object_batch
               and n_views_of(obj_views[todo[j]]) == nv):
            j += 1
        batch = todo[i:j]
        outs = sampler.synthesize_many([obj_views[o] for o in batch],
                                       [obj_keys[o] for o in batch],
                                       max_views=args.max_views)
        for obj, out in zip(batch, outs):
            # float16 in memory AND on disk: a fresh pass and a resumed
            # pass (which reads the float16 record back) score the SAME
            # pixels, and the resident full-sweep arrays cost half the
            # bytes (scoring casts one w column at a time to float32)
            gens[obj] = np.asarray(out, np.float16)
            _save_object_record(args.resume_dir, obj, gens[obj],
                                expect_meta)
            with open(progress_path, "a") as f:
                f.write(json.dumps({"object": str(obj),
                                    "views": int(out.shape[0])}) + "\n")
            logging.info("synthesised object %s (%d views) -> %s", obj,
                         out.shape[0],
                         _record_path(args.resume_dir, obj,
                                      expect_meta["checkpoint_step"]))
        i = j

    # ---- Phase 2: scoring (pure recomputation from the records; a
    # resumed run and a single-pass run produce the same JSON) ---------
    def score_object(obj):
        """Per-view PSNR at every w + copy baseline for one object.
        ``out`` stays float16 ([V-1, B, H, W, 3]); metric passes cast one
        w column at a time so the resident footprint is halved."""
        out = gens[obj]
        if out.shape[0] == 0:
            return None
        views = obj_views[obj]
        gt = views["imgs"][1: 1 + out.shape[0]]
        w_psnrs = [np.asarray(psnr(out[:, wi].astype(np.float32),
                                   gt)).tolist()
                   for wi in range(out.shape[1])]
        copy0 = np.broadcast_to(views["imgs"][:1], gt.shape)
        base = np.asarray(psnr(copy0, gt)).tolist()
        return {"out": out, "gt": gt, "w_psnrs": w_psnrs, "base": base}

    scored = {obj: score_object(obj) for obj in all_objs}
    eval_scored = [(o, scored[o]) for o in eval_objs if scored[o]]
    if not eval_scored:
        raise SystemExit(
            "no views generated: every object had < 2 usable views "
            "(check --max_views / the dataset)")

    # Guidance-weight selection on the DISJOINT selection objects: best
    # pooled mean PSNR across their views.  The copy baseline is
    # w-independent, so argmax-PSNR == argmax-margin.
    w_selected = None
    if args.w_select:
        sel_scored = [scored[o] for o in sel_objs if scored[o]]
        if not sel_scored:
            raise SystemExit("--w_select objects produced no views")
        n_w = len(sel_scored[0]["w_psnrs"])
        sel_per_w = [float(np.mean([v for s in sel_scored
                                    for v in s["w_psnrs"][wi]]))
                     for wi in range(n_w)]
        w_selected = int(np.argmax(sel_per_w))
        logging.info("w_select: per-w PSNR on %d selection objects: %s "
                     "-> w_selected=%d", len(sel_scored),
                     [round(v, 3) for v in sel_per_w], w_selected)

    # GT features never vary with w: one stats pass shared by every
    # aggregate() call (fixed-w headline AND w_selected).
    gt_stats = gaussian_stats([s["gt"] for _, s in eval_scored],
                              feature_fn)
    agg_cache = {}

    def aggregate(w_index):
        """Headline + per-object stats of the EVAL set at one w (cached:
        when selection picks the same w as the fixed headline, the
        second call is free instead of re-running SSIM + FID)."""
        if w_index in agg_cache:
            return agg_cache[w_index]
        per_object, psnrs, base_psnrs, ssims = [], [], [], []
        gen_views = []
        for obj, s in eval_scored:
            obj_psnrs = s["w_psnrs"][w_index]
            gen = s["out"][:, w_index].astype(np.float32)
            obj_ssims = np.asarray(ssim(gen, s["gt"])).tolist()
            psnrs.extend(obj_psnrs)
            ssims.extend(obj_ssims)
            base_psnrs.extend(s["base"])
            gen_views.append(gen)
            per_object.append({
                "id": str(obj),
                "views": len(obj_psnrs),
                "psnr": round(float(np.mean(obj_psnrs)), 3),
                "psnr_std": round(float(np.std(obj_psnrs)), 3),
                "psnr_copy_view0": round(float(np.mean(s["base"])), 3),
                "ssim": round(float(np.mean(obj_ssims)), 4),
            })
        fid = fid_from_stats(gt_stats,
                             gaussian_stats(gen_views, feature_fn))
        margins = [o["psnr"] - o["psnr_copy_view0"] for o in per_object]
        obj_means = [o["psnr"] for o in per_object]
        agg_cache[w_index] = {
            "objects": len(per_object),
            "views": len(psnrs),
            "psnr": round(float(np.mean(psnrs)), 3),
            "psnr_copy_view0_baseline": round(float(np.mean(base_psnrs)),
                                              3),
            "psnr_obj_mean": round(float(np.mean(obj_means)), 3),
            "psnr_obj_std": round(float(np.std(obj_means)), 3),
            "psnr_margin_mean": round(float(np.mean(margins)), 3),
            "psnr_margin_std": round(float(np.std(margins)), 3),
            "objects_above_baseline": int(sum(m > 0 for m in margins)),
            "ssim": round(float(np.mean(ssims)), 4),
            fid_key: round(float(fid), 3),
            "per_object": per_object,
        }
        return agg_cache[w_index]

    if fid_key == "fid_randfeat":
        logging.warning(
            "FID below uses the seeded random-projection fallback — "
            "reported as 'fid_randfeat', NOT comparable to paper FID. "
            "Pass --feature_weights <local VGG16 state dict> for "
            "real-feature FID.")

    # Per-w pooled PSNR over the eval set (the reference's 0..7 sweep
    # readout) — selection objects are excluded from every eval metric.
    n_w = len(eval_scored[0][1]["w_psnrs"])
    per_w_psnrs = [
        round(float(np.mean([v for _, s in eval_scored
                             for v in s["w_psnrs"][wi]])), 3)
        for wi in range(n_w)]

    record = {"checkpoint_step": step, **aggregate(args.w_index),
              "psnr_per_w": per_w_psnrs, "w_index": args.w_index,
              "timesteps": cfg.diffusion.timesteps,
              "sampler": sampler.sampler_kind,
              "sampler_steps": int(sampler.steps)}

    # Matched-seed parity vs the full-grid ancestral oracle: same
    # per-object keys, so the generations differ ONLY by the reverse
    # schedule — the quality cost of few-step sampling, isolated.
    if args.parity_objects:
        from diff3d_tpu.evaluation import matched_seed_parity

        par_objs = eval_objs[: args.parity_objects]
        oracle = Sampler(model, params, cfg,
                         scan_chunks=args.scan_chunks, mesh=mesh_env)
        oracle_outs = [oracle.synthesize(obj_views[o], obj_keys[o],
                                         max_views=args.max_views)
                       for o in par_objs]
        record["sampler_parity"] = {
            "oracle": f"ancestral:{cfg.diffusion.timesteps}",
            "sampler": f"{sampler.sampler_kind}:{sampler.steps}",
            "objects": len(par_objs),
            **matched_seed_parity([gens[o] for o in par_objs],
                                  oracle_outs, w_index=args.w_index),
        }
    if w_selected is not None:
        sel_agg = aggregate(w_selected)
        record["w_selected"] = w_selected
        record["w_select_objects"] = [str(o) for o in sel_objs]
        for key in ("psnr", "psnr_margin_mean", "psnr_margin_std",
                    "objects_above_baseline", "ssim", fid_key):
            record[f"{key}_w_selected"] = sel_agg[key]
        record["per_object_w_selected"] = sel_agg["per_object"]

    # Orbit turntables + 3D-consistency readout: the trajectory-service
    # workload, scored offline.  Radius/elevation come from each
    # object's own GT poses so the orbit stays on the data manifold the
    # model was trained on; frames are synthesised autoregressively
    # (same record contract as serving's TrajectoryRequest), then scored
    # with the plane-homography reprojection metric.
    if args.orbit:
        from diff3d_tpu.evaluation import reprojection_consistency
        from diff3d_tpu.trajectory import orbit_path, trajectory_views

        if args.orbit < 2:
            raise SystemExit("--orbit needs >= 2 frames to score "
                             "consistency")
        per_orbit = []
        for obj in eval_objs[: args.orbit_objects]:
            views = obj_views[obj]
            T_gt = np.asarray(views["T"], np.float64)
            radii = np.linalg.norm(T_gt, axis=-1)
            radius = float(radii.mean())
            elevation = float(np.rad2deg(np.arcsin(
                np.clip(T_gt[:, 2] / np.maximum(radii, 1e-9),
                        -1.0, 1.0)).mean()))
            path_R, path_T = orbit_path(args.orbit, radius=radius,
                                        elevation_deg=elevation)
            tviews = trajectory_views(views["imgs"][0], views["R"][0],
                                      views["T"][0], views["K"],
                                      path_R, path_T)
            # synthesize sizes the record from imgs.shape[0]: tile the
            # conditioning image across the path (only imgs[0] is read).
            tviews["imgs"] = np.broadcast_to(
                tviews["imgs"][:1], (args.orbit + 1,) +
                tviews["imgs"].shape[1:])
            rng, k = jax.random.split(rng)
            frames = sampler.synthesize(tviews, k)  # [N, B, H, W, 3]
            gen = frames[:, args.w_index].astype(np.float32)
            score = reprojection_consistency(gen, path_R, path_T,
                                             views["K"])
            entry = {"id": str(obj), "radius": round(radius, 3),
                     "elevation_deg": round(elevation, 2),
                     "consistency_l1": score["consistency_l1"],
                     "consistency_psnr": score["consistency_psnr"],
                     "valid_frac": round(score["valid_frac"], 4)}
            if args.save_dir:
                from diff3d_tpu.utils import save_frame_sequence

                art = save_frame_sequence(
                    os.path.join(args.save_dir, str(obj), "orbit"), gen)
                entry["frames_dir"] = art["dir"]
                logging.info("orbit frames for %s -> %s", obj,
                             art["dir"])
            per_orbit.append(entry)
        l1s = [o["consistency_l1"] for o in per_orbit
               if o["consistency_l1"] is not None]
        ps = [o["consistency_psnr"] for o in per_orbit
              if o["consistency_psnr"] is not None]
        record["orbit_consistency"] = {
            "frames": args.orbit,
            "objects": len(per_orbit),
            "w_index": args.w_index,
            "consistency_l1": (round(float(np.mean(l1s)), 5)
                               if l1s else None),
            "consistency_psnr": (round(float(np.mean(ps)), 3)
                                 if ps else None),
            "per_object": per_orbit,
        }

    if args.save_dir:
        from PIL import Image

        from diff3d_tpu.sampling.runtime import to_uint8

        for obj, s in eval_scored:
            gen = s["out"][:, args.w_index]
            d = os.path.join(args.save_dir, str(obj))
            os.makedirs(d, exist_ok=True)
            Image.fromarray(
                to_uint8(obj_views[obj]["imgs"][0])).save(
                    os.path.join(d, "view0_cond.png"))
            for v in range(gen.shape[0]):
                Image.fromarray(to_uint8(s["gt"][v])).save(
                    os.path.join(d, f"view{v + 1}_gt.png"))
                Image.fromarray(to_uint8(gen[v])).save(
                    os.path.join(d, f"view{v + 1}_gen.png"))

    print(json.dumps(record))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
