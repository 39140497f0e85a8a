"""Shared CLI plumbing.

The three checkpoint-consuming CLIs (train resume, sample, eval) must
rebuild the exact ``ModelConfig`` a checkpoint was trained with; the
width knobs that change the parameter tree's shape live here so a new
knob lands in every CLI at once.
"""

from __future__ import annotations

import argparse
import dataclasses

_WIDTH_KEYS = ("ch", "emb_ch", "num_res_blocks")


def add_model_width_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--ch", type=int, default=None,
                   help="base channel width — must match the trained "
                        "checkpoint (reference: 128 at 64^2, "
                        "xunet.py:229; smaller widths train/checkpoint "
                        "faster)")
    p.add_argument("--emb_ch", type=int, default=None,
                   help="conditioning embedding width (reference: 1024)")
    p.add_argument("--num_res_blocks", type=int, default=None,
                   help="res blocks per UNet level (reference: 3)")
    p.add_argument("--imgsize", type=int, default=None,
                   help="square image resolution H=W — overrides the "
                        "--config preset (must match the trained "
                        "checkpoint; must be divisible by 2^(levels-1))")


def apply_model_width_overrides(cfg, args):
    """Returns ``cfg`` with any of --ch/--emb_ch/--num_res_blocks applied,
    plus --imgsize (H=W resolution override)."""
    over = {k: getattr(args, k) for k in _WIDTH_KEYS
            if getattr(args, k) is not None}
    if getattr(args, "imgsize", None) is not None:
        over["H"] = over["W"] = args.imgsize
    if not over:
        return cfg
    return dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **over))


def build_abstract_state(cfg):
    """Abstract TrainState template (ShapeDtypeStructs, nothing
    materialised) for ``build_model(cfg)`` — the restore target every
    checkpoint-consuming CLI needs.  ``jax.eval_shape`` means no params,
    moments, or EMA are ever allocated just to describe the tree."""
    import jax

    from diff3d_tpu.models import build_model
    from diff3d_tpu.train import create_train_state
    from diff3d_tpu.train.trainer import init_params

    model = build_model(cfg)
    return jax.eval_shape(lambda: create_train_state(
        init_params(model, cfg, jax.random.PRNGKey(0)), cfg.train))


def load_eval_params(model_dir: str, state, raw_params: bool):
    """Load ``(step, params)`` for inference from a checkpoint directory of
    either save mode (full TrainState or ema_bf16 — see
    ``train/checkpoint.py``).  ``state`` is a template TrainState —
    abstract (:func:`build_abstract_state`) or concrete; ``raw_params``
    picks the non-EMA weights, which only full checkpoints carry."""
    import jax

    from diff3d_tpu.train import CheckpointManager

    mgr = CheckpointManager(model_dir)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    if mgr.mode == "ema_bf16":
        if raw_params:
            # Typed error at the library layer; the CLIs present argparse
            # problems as SystemExit themselves (ADVICE r4 — train_cli's
            # --init_from path also lands here, and a library misuse
            # should not look like a clean CLI exit).
            raise ValueError(
                f"{model_dir} is an ema_bf16 checkpoint: it has no raw "
                "params to score (--raw_params unavailable)")
        got = mgr.restore_ema(abstract.params)
        if got is None:
            raise FileNotFoundError(f"no checkpoint under {model_dir}")
        return got
    restored = mgr.restore(abstract)
    if restored is None:
        raise FileNotFoundError(f"no checkpoint under {model_dir}")
    params = restored.params if raw_params else restored.ema_params
    return int(restored.step), params
