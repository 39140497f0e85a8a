"""Novel-view sampling entry point.

Flag parity with the reference sampler (``/root/reference/sampling.py:
19-23``): ``--model`` is the checkpoint to load, ``--target`` the SRN
object directory whose views are synthesised autoregressively.  Output
layout matches ``sampling/{step}/{gt,0..7}.png`` (``sampling.py:179-182``).

Usage:
    python -m diff3d_tpu.cli.sample_cli --model ./checkpoints \
        --target ./data/SRN/cars_test/<object-id> [--out ./sampling]
"""

from __future__ import annotations

import argparse
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
    p.add_argument("--target", required=True,
                   help="SRN object dir with rgb/ pose/ intrinsics/")
    p.add_argument("--out", default="sampling")
    p.add_argument("--config",
                   choices=list(NAMED_CONFIGS),
                   default="srn64")
    p.add_argument("--steps", type=int, default=None,
                   help="diffusion steps (reference: 256)")
    p.add_argument("--max_views", type=int, default=None)
    p.add_argument("--scan_chunks", type=int, default=1,
                   help="split each view's diffusion scan into this many "
                        "device executions (must divide --steps; "
                        "bit-identical to 1 — several shorter "
                        "executions per view instead of one long one)")
    p.add_argument("--raw_params", action="store_true",
                   help="sample with raw params instead of EMA")
    p.add_argument("--seed", type=int, default=0)
    add_model_width_args(p)
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    logging.getLogger("absl").setLevel(logging.WARNING)
    from diff3d_tpu.runtime import configure_compile_cache
    configure_compile_cache()

    import dataclasses

    import jax

    from diff3d_tpu import config as config_lib
    from diff3d_tpu.data.srn import load_object_views
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = config_lib.named_config(args.config)
    if args.steps:
        cfg = dataclasses.replace(
            cfg, diffusion=dataclasses.replace(cfg.diffusion,
                                               timesteps=args.steps))
    cfg = apply_model_width_overrides(cfg, args)

    model = build_model(cfg)
    try:
        step, params = load_eval_params(args.model,
                                        build_abstract_state(cfg),
                                        args.raw_params)
    except ValueError as e:   # e.g. --raw_params on an ema_bf16 checkpoint
        raise SystemExit(str(e))
    logging.info("loaded step-%d checkpoint from %s", step, args.model)

    # Load every view of the target object dir (reference sampling.py:26-48).
    views = load_object_views(os.path.normpath(args.target), cfg.model.H)

    sampler = Sampler(model, params, cfg,
                      scan_chunks=args.scan_chunks)
    sampler.synthesize(views, jax.random.PRNGKey(args.seed),
                       out_dir=args.out, max_views=args.max_views)
    logging.info("wrote %s", args.out)


if __name__ == "__main__":
    main()
