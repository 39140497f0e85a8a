"""Convert a reference PyTorch checkpoint into this framework's format.

Takes the reference's ``.pt`` files (``{'model': state_dict, 'optim': ...,
'step': ...}`` — ``/root/reference/train.py:287-298``, incl. the published
pretrained weights) and writes an Orbax checkpoint that ``train_cli
--transfer``, ``sample_cli`` and ``eval_cli`` load directly.  The optimizer
state is NOT converted (torch Adam moments don't map onto optax's tree);
the step counter is preserved so schedules resume at the right point, and
the EMA is seeded from the converted weights (the reference never
implemented its documented EMA, SURVEY.md §2.3).

Usage:
    python -m diff3d_tpu.cli.convert_cli --torch_ckpt latest.pt \
        --out ./checkpoints [--config srn64]
"""

from __future__ import annotations

import argparse
import logging

from diff3d_tpu.config import NAMED_CONFIGS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--torch_ckpt", required=True, help="reference .pt file")
    p.add_argument("--out", required=True,
                   help="Orbax checkpoint root to write")
    p.add_argument("--config",
                   choices=list(NAMED_CONFIGS),
                   default="srn64")
    p.add_argument("--step", type=int, default=None,
                   help="override the step recorded in the checkpoint")
    p.add_argument("--verify", action="store_true",
                   help="verify-only dry run: reconstruct the expected "
                        "reference key set from --config, report every "
                        "missing/extra/shape-mismatched key, and exit "
                        "without writing (non-zero on mismatch).  The "
                        "same verification always runs before a real "
                        "conversion.")
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    configure_compile_cache()

    import jax
    import jax.numpy as jnp

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.train import CheckpointManager, create_train_state
    from diff3d_tpu.train.state import advance_schedule

    cfg = config_lib.named_config(args.config)
    # the reference's checkpoints are X-UNets: refuse another denoiser
    from diff3d_tpu.models import build_xunet
    model = build_xunet(cfg, "convert_cli")

    # Verify the INPUT key set first (torch keys + shapes reconstructed
    # from config): the real published .pt deserves a complete report of
    # what is wrong, not a KeyError mid-conversion.
    import torch

    from diff3d_tpu.convert import convert_state_dict, verify_state_dict

    raw = torch.load(args.torch_ckpt, map_location="cpu",
                     weights_only=True)
    if isinstance(raw, dict) and "model" in raw:
        sd, ckpt_step = raw["model"], int(raw.get("step", 0))
    else:
        sd, ckpt_step = raw, 0
    report = verify_state_dict(sd, cfg.model)
    n_bad = sum(map(len, report.values()))
    if n_bad:
        for kind, items in report.items():
            for it in items:
                logging.error("verify: %s: %s", kind, it)
        raise SystemExit(
            f"{args.torch_ckpt} does not match --config {args.config}: "
            f"{len(report['missing'])} missing, {len(report['extra'])} "
            f"extra, {len(report['shape_mismatch'])} shape-mismatched "
            "keys (full list above)")
    logging.info("verify: %s matches the expected %s key set "
                 "(%d tensors)", args.torch_ckpt, args.config, len(sd))
    if args.verify:
        return

    params = convert_state_dict(sd, cfg.model)
    step = args.step if args.step is not None else ckpt_step

    params = jax.tree.map(jnp.asarray, params)

    # Fail fast on config/checkpoint mismatch (e.g. a 64px .pt converted
    # with --config srn128): compare against the model's expected tree
    # BEFORE writing a checkpoint that would only blow up at restore time.
    from diff3d_tpu.train.trainer import init_params as _init_params
    expected = jax.eval_shape(
        lambda: _init_params(model, cfg, jax.random.PRNGKey(0)))
    exp_flat = dict(jax.tree_util.tree_flatten_with_path(expected)[0])
    got_flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    missing = exp_flat.keys() - got_flat.keys()
    extra = got_flat.keys() - exp_flat.keys()
    bad = [jax.tree_util.keystr(k) for k in exp_flat.keys() & got_flat.keys()
           if exp_flat[k].shape != got_flat[k].shape]
    if missing or extra or bad:
        raise SystemExit(
            f"checkpoint does not match --config {args.config}: "
            f"missing={sorted(map(jax.tree_util.keystr, missing))[:5]} "
            f"extra={sorted(map(jax.tree_util.keystr, extra))[:5]} "
            f"shape-mismatch={sorted(bad)[:5]}")
    state = create_train_state(params, cfg.train)
    # The lr schedule's position is optax's internal count, not
    # TrainState.step — advance it so a converted step-100K checkpoint
    # doesn't silently re-run warmup (Adam's own count stays 0: the zero
    # moments it bias-corrects ARE fresh).
    state = state.replace(step=jnp.asarray(step, jnp.int32),
                          opt_state=advance_schedule(state.opt_state, step))

    mgr = CheckpointManager(args.out, keep=1)
    mgr.save(state, force=True)
    mgr.wait()
    mgr.close()
    n = sum(int(p.size) for p in jax.tree.leaves(params))
    logging.info("converted %s (%.1fM params, step %d) -> %s",
                 args.torch_ckpt, n / 1e6, step, args.out)


if __name__ == "__main__":
    main()
