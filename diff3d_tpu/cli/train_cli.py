"""Training entry point.

Flag parity with both reference CLIs (``/root/reference/train.py:307-319``
and ``lightning/train.py:18-46``): ``--transfer`` resumes from the latest
checkpoint, ``--train_data`` points at the SRN split dir.  Everything the
reference hardcodes (batch, steps, image size — ``train.py:210-217``,
``lightning/train.py:26-28``) is a flag here, with the reference's values
as defaults.

Usage:
    python -m diff3d_tpu.cli.train_cli --train_data ./data/SRN/cars_train \
        --picklefile ./data/cars.pickle [--transfer] [--config srn64]
    python -m diff3d_tpu.cli.train_cli --synthetic --steps 10   # smoke run
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

from diff3d_tpu.cli._common import (add_model_width_args,
                                    apply_model_width_overrides)
from diff3d_tpu.config import NAMED_CONFIGS


def _step_range(text: str) -> tuple:
    """``"START:STOP"`` -> ``(start, stop)``, ``1 <= start < stop``."""
    try:
        start, stop = (int(x) for x in text.split(":"))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"{text!r} is not START:STOP (two integers)")
    if not 1 <= start < stop:
        raise argparse.ArgumentTypeError(
            f"need 1 <= START < STOP, got {start}:{stop}")
    return start, stop


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--transfer", action="store_true",
                   help="resume from the latest checkpoint (reference "
                        "train.py:310-312)")
    p.add_argument("--train_data", default="./data/SRN/cars_train")
    p.add_argument("--val_data", default=None,
                   help="directory of held-out objects for --eval_every's "
                        "in-training validation (used in full); without it, "
                        "the val split of --train_data is used.  (Reference "
                        "flag, lightning/train.py:22 — where it was "
                        "accepted and ignored.)")
    p.add_argument("--picklefile", default=None,
                   help="object index pickle; regenerated by globbing when "
                        "absent")
    p.add_argument("--config",
                   choices=list(NAMED_CONFIGS),
                   default="srn64")
    p.add_argument("--batch", type=int, default=None,
                   help="global batch (default from config; reference runs "
                        "128, train.py:213)")
    p.add_argument("--steps", type=int, default=None,
                   help="max optimizer steps (reference lightning caps at "
                        "100K, lightning/train.py:45)")
    p.add_argument("--accum", type=int, default=None,
                   help="gradient-accumulation microbatches per step")
    p.add_argument("--workdir", default=".")
    p.add_argument("--profile_steps", type=_step_range, default=None,
                   metavar="START:STOP",
                   help="capture a jax.profiler trace of optimizer steps "
                        "START+1..STOP into <workdir>/profile and reduce "
                        "it to profile/by_scope.json (device seconds by "
                        "block class, idle by host span); START >= 1 so "
                        "that the compile is not traced")
    p.add_argument("--param_sharding",
                   choices=["replicated", "fsdp", "tp", "fsdp+tp"],
                   default=None)
    p.add_argument("--model_parallel", type=int, default=None,
                   help="mesh model-axis size for tp/fsdp+tp sharding")
    p.add_argument("--context_parallel", action="store_true",
                   help="GSPMD context parallelism: shard the activations' "
                        "spatial/token axis over the model axis")
    p.add_argument("--remat", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="rematerialise UNet blocks in the backward pass")
    p.add_argument("--remat_policy", choices=["nothing", "dots"],
                   default=None)
    add_model_width_args(p)
    p.add_argument("--attn_impl", default=None,
                   choices=["auto", "pallas", "xla"],
                   help="attention backend.  (The ring:<axis>/ulysses:<axis> "
                        "sequence-parallel cores need the caller to bind the "
                        "mesh axis via shard_map, so they are for embedding "
                        "the model in custom steps — through this trainer "
                        "use --context_parallel instead.)")
    p.add_argument("--pallas", action="store_true",
                   help="route the GroupNorm->FiLM/SiLU epilogues through "
                        "the fused Pallas kernels (ops/pallas_film.py; "
                        "interpret mode on a CPU process).  Equivalent to "
                        "model.kernels='pallas'")
    p.add_argument("--synthetic", action="store_true",
                   help="train on the procedural dataset (no SRN data "
                        "needed; smoke/bench runs)")
    p.add_argument("--synthetic_scenes", action="store_true",
                   help="train on the ray-traced sphere-scene dataset "
                        "(true 3D novel-view task at toy scale; quality-"
                        "evidence runs when SRN data is absent)")
    p.add_argument("--scene_objects", type=int, default=64,
                   help="number of training scenes for --synthetic_scenes "
                        "(more scenes = better cross-scene "
                        "generalization)")
    p.add_argument("--num_workers", type=int, default=8)
    p.add_argument("--eval_every", type=int, default=None,
                   help="score EMA params on a held-out batch every N "
                        "steps (val split of --train_data, or --val_data "
                        "when given); logged as val_loss in metrics.jsonl")
    p.add_argument("--warmup_examples", type=int, default=None,
                   help="linear lr warmup span in examples (default: the "
                        "paper's 10M, lightning/diff3d.py:12 — override "
                        "for short runs or the lr never leaves ~0)")
    p.add_argument("--ema_halflife_examples", type=int, default=None,
                   help="EMA half-life in examples (paper: 500K, "
                        "diff3d.py:19-20).  Short runs should shrink it "
                        "proportionally or the EMA/val params stay "
                        "dominated by the random init.")
    p.add_argument("--ckpt_every", type=int, default=None,
                   help="checkpoint cadence in steps (reference saves "
                        "every 50, train.py:214; raise it when the "
                        "device->host link is slow — each save fetches "
                        "the full train state)")
    p.add_argument("--init_from", default=None,
                   help="seed params+EMA from this checkpoint dir "
                        "(EMA weights), trained at --init_res with the "
                        "same width: progressive resolution transfer — "
                        "pos_emb is bilinearly upsampled, all other "
                        "params are resolution-independent and copy. "
                        "Fresh optimizer/schedules (unlike --transfer).")
    p.add_argument("--init_res", type=int, default=64,
                   help="resolution the --init_from checkpoint was "
                        "trained at")
    p.add_argument("--ckpt_mode",
                   choices=["full", "ema_bf16", "full_sliced"],
                   default=None,
                   help="'ema_bf16' saves only bf16 EMA params (~1/16 the "
                        "bytes): eval-grade weights + warm restart, for "
                        "full-width models on constrained device->host "
                        "links.  'full_sliced' keeps exact resume but "
                        "streams the state leaf-by-leaf with per-leaf "
                        "retry + atomic commit — for links where one "
                        "monolithic save is a single point of failure.  "
                        "Default 'full' = exact resume via Orbax.")
    p.add_argument("--ckpt_async", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="write full_sliced checkpoints from a background "
                        "thread (snapshot on the training thread, commit "
                        "off it) so saves overlap training; "
                        "--no-ckpt_async forces the synchronous oracle "
                        "path.  No effect on other --ckpt_mode values.")
    p.add_argument("--elastic", action="store_true",
                   help="run under the elasticity supervisor: on "
                        "preemption (SIGTERM) or a transient backend "
                        "fault, re-initialise the distributed runtime, "
                        "re-mesh over the surviving devices, restore the "
                        "latest durable checkpoint (resharded into the "
                        "new mesh) and resume the input stream "
                        "deterministically (DESIGN.md §16).  Implies "
                        "resume-from-latest-checkpoint; in-training "
                        "eval (--eval_every) is not wired in this mode.")
    p.add_argument("--elastic_max_remesh", type=int, default=8,
                   help="give up (typed GAVE_UP) after this many "
                        "consecutive re-mesh cycles without forward "
                        "progress; any cycle that advances the step "
                        "refills the budget")
    return p


def build_config(args):
    """The :class:`~diff3d_tpu.config.Config` these flags describe — the
    preset plus every override.  Anything that resumes from a workdir
    this CLI wrote (``Trainer(..., transfer=True)``) rebuilds it here."""
    from diff3d_tpu import config as config_lib

    cfg = config_lib.named_config(args.config)
    train_over = {}
    if args.batch is not None:
        train_over["global_batch"] = args.batch
    if args.steps is not None:
        train_over["max_steps"] = args.steps
    if args.accum is not None:
        train_over["accum_steps"] = args.accum
    if args.eval_every is not None:
        train_over["eval_every"] = args.eval_every
    if args.warmup_examples is not None:
        train_over["warmup_examples"] = args.warmup_examples
    if args.ema_halflife_examples is not None:
        train_over["ema_halflife_examples"] = args.ema_halflife_examples
    if args.ckpt_every is not None:
        train_over["ckpt_every"] = args.ckpt_every
    if args.ckpt_mode is not None:
        train_over["ckpt_mode"] = args.ckpt_mode
    if args.ckpt_async is not None:
        train_over["ckpt_async"] = args.ckpt_async
    if train_over:
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **train_over))
    mesh_over = {}
    if args.param_sharding:
        mesh_over["param_sharding"] = args.param_sharding
    if args.model_parallel:
        mesh_over["model_parallel"] = args.model_parallel
    if args.context_parallel:
        mesh_over["context_parallel"] = True
    if mesh_over:
        cfg = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, **mesh_over))
    cfg = apply_model_width_overrides(cfg, args)
    model_over = {}
    if args.remat is not None:
        model_over["remat"] = args.remat
    if args.remat_policy is not None:
        model_over["remat_policy"] = args.remat_policy
    if args.attn_impl is not None:
        model_over["attn_impl"] = args.attn_impl
    if args.pallas:
        model_over["kernels"] = "pallas"
    if model_over:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, **model_over))
    return cfg


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    configure_compile_cache()
    if args.synthetic and args.synthetic_scenes:
        raise SystemExit(
            "--synthetic and --synthetic_scenes are mutually exclusive")
    if args.elastic and args.init_from:
        raise SystemExit(
            "--elastic and --init_from are mutually exclusive: seed the "
            "workdir with a plain --init_from run first, then relaunch "
            "--elastic (it resumes from the latest checkpoint)")

    # Must precede any other JAX use (backend init closes the window).
    from diff3d_tpu.parallel.multihost import maybe_initialize_distributed
    maybe_initialize_distributed()

    import jax

    from diff3d_tpu.data import (InfiniteLoader, SRNDataset, SyntheticDataset,
                                 SyntheticScenesDataset, prefetch_to_device)
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import Trainer

    cfg = build_config(args)

    if args.synthetic_scenes:
        ds = SyntheticScenesDataset(num_objects=args.scene_objects,
                                    num_views=24, imgsize=cfg.model.H)
        # different seed -> entirely different held-out scenes
        val_ds = (SyntheticScenesDataset(num_objects=8, num_views=24,
                                         imgsize=cfg.model.H, seed=1)
                  if cfg.train.eval_every else None)
    elif args.synthetic:
        ds = SyntheticDataset(num_objects=64, num_views=32,
                              imgsize=cfg.model.H)
        val_ds = (SyntheticDataset(num_objects=8, num_views=32,
                                   imgsize=cfg.model.H, seed=1)
                  if cfg.train.eval_every else None)
    else:
        ds = SRNDataset("train", args.train_data, args.picklefile,
                        imgsize=cfg.model.H,
                        split_seed=cfg.data.split_seed,
                        train_fraction=cfg.data.train_fraction)
        if not cfg.train.eval_every:
            val_ds = None
        elif args.val_data:
            # A dedicated val directory is entirely held-out — use ALL of
            # it (split='train' with train_fraction=1.0 keeps every id).
            val_ds = SRNDataset("train", args.val_data, None,
                                imgsize=cfg.model.H,
                                split_seed=cfg.data.split_seed,
                                train_fraction=1.0)
        else:
            # No dedicated dir: score the val split of the train data.
            val_ds = SRNDataset("val", args.train_data, args.picklefile,
                                imgsize=cfg.model.H,
                                split_seed=cfg.data.split_seed,
                                train_fraction=cfg.data.train_fraction)

    if args.elastic:
        from diff3d_tpu.runtime.retry import (RetryPolicy,
                                              is_transient_backend_error)
        from diff3d_tpu.train.trainer import ElasticSupervisor

        def make_loader(step, env):
            # Per re-mesh cycle: per-host share of the (constant) global
            # batch under the surviving process set, seeked to the
            # restored step — the loader's global stream is a pure
            # function of (seed, step), so the new partition neither
            # replays nor skips examples.
            per_host = cfg.train.global_batch // jax.process_count()
            inner = InfiniteLoader(
                ds, per_host, seed=cfg.train.seed,
                host_id=jax.process_index(),
                num_hosts=jax.process_count(),
                num_workers=args.num_workers, start_step=step)
            return prefetch_to_device(inner, env.batch(),
                                      depth=cfg.data.prefetch)

        supervisor = ElasticSupervisor(
            cfg, make_loader, workdir=args.workdir,
            retry=RetryPolicy(max_attempts=args.elastic_max_remesh,
                              base_delay_s=2.0, max_delay_s=60.0,
                              classify=is_transient_backend_error))
        supervisor.run()
        return

    env = make_mesh(cfg.mesh)
    trainer = Trainer(cfg, env=env, workdir=args.workdir,
                      transfer=args.transfer)
    if args.init_from and int(trainer.state.step) > 0:
        logging.info(
            "--init_from %s SKIPPED: the workdir already resumed at step "
            "%d (progressive-transfer seeding only applies to a fresh "
            "run; point --workdir somewhere new to re-seed)",
            args.init_from, int(trainer.state.step))
    if args.init_from and int(trainer.state.step) == 0:
        # Progressive resolution transfer (convert/progressive.py): seed
        # params+EMA from a checkpoint trained at --init_res with the
        # same width; pos_emb is bilinearly upsampled, everything else
        # copies.  Skipped after a --transfer resume (step > 0): the run
        # is already past its init.
        import jax.numpy as jnp

        from diff3d_tpu.cli._common import (build_abstract_state,
                                            load_eval_params)
        from diff3d_tpu.convert.progressive import (
            adapt_params_resolution, check_resolution_compatible)

        src_cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, H=args.init_res,
                                           W=args.init_res))
        src_step, src_params = load_eval_params(
            args.init_from, build_abstract_state(src_cfg),
            raw_params=False)
        src_params = adapt_params_resolution(
            src_params, (cfg.model.H, cfg.model.W))
        check_resolution_compatible(src_params, trainer.state.params)
        shardings = env.state_shardings(trainer.state)
        seeded = jax.device_put(src_params, shardings.params)
        trainer.state = trainer.state.replace(
            params=seeded,
            # On-device copy of the already-sharded tree: distinct
            # buffers (the step donates the state; aliased leaves fail
            # at execute time) without a second host->device transfer.
            ema_params=jax.tree.map(jnp.copy, seeded))
        logging.info(
            "seeded params+EMA from %s (step-%d %dx%d model) -> %dx%d",
            args.init_from, src_step, args.init_res, args.init_res,
            cfg.model.H, cfg.model.W)
    trainer.install_preemption_handler()

    # Build the loader AFTER restore so a --transfer run seeks the data
    # stream to the restored step (exact-replay resume; the reference only
    # restores the step counter, train.py:244-251).
    per_host = cfg.train.global_batch // jax.process_count()
    loader = InfiniteLoader(
        ds, per_host, seed=cfg.train.seed,
        host_id=jax.process_index(), num_hosts=jax.process_count(),
        num_workers=args.num_workers, start_step=int(trainer.state.step))
    trainer.loader = prefetch_to_device(loader, env.batch(),
                                        depth=cfg.data.prefetch)
    if val_ds is not None:
        trainer.val_loader = InfiniteLoader(
            val_ds, per_host, seed=cfg.train.seed + 1,
            host_id=jax.process_index(), num_hosts=jax.process_count(),
            num_workers=0, sample_mode="permute")
    trainer.train(profile_steps=args.profile_steps)


if __name__ == "__main__":
    main()
