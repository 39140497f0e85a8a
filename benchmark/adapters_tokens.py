"""Where the benchmark touches the program for the token denoiser: the
program's own config, model and sampler (``build_model`` -> ``Sampler``,
as ``eval_cli`` wires them) from a ``benchmark/configs`` file of that
model.  The generic parts (the timed call, the warm-up, the diffusion
settings) are ``benchmark/adapters.py``'s.
"""

from __future__ import annotations

from typing import Dict

from benchmark import adapters
from benchmark.adapters import diffusion_dict  # noqa: F401  (re-export)
from benchmark.reference import token_denoiser as rt
from benchmark.reference.xunet import flatten, nest


def build_config(config: dict):
    """``diff3d_tpu.config.Config`` with a ``TokenModelConfig`` from a
    configuration file that keeps the published key names."""
    from diff3d_tpu.config import (Config, DataConfig, DiffusionConfig,
                                   TokenModelConfig)

    m = rt.model_dict(config)
    m.update(mrope_section=tuple(m["mrope_section"]),
             experts_held=tuple(m["experts_held"]),
             dtype=config["dtype"], **config["tiles"])
    d = dict(config["diffusion"])
    d["guidance_weights"] = tuple(d["guidance_weights"])
    cfg = Config(model=TokenModelConfig(**m), diffusion=DiffusionConfig(**d),
                 data=DataConfig(imgsize=config["H"]))
    cfg.validate()
    return cfg


def check_tree(cfg, flat: Dict[str, object]) -> None:
    """The benchmark's parameter names and shapes against the program's
    own ``init`` (shapes only)."""
    import jax

    from diff3d_tpu.models import build_model
    from diff3d_tpu.train.trainer import init_params

    theirs = flatten(adapters._plain(jax.eval_shape(
        lambda: init_params(build_model(cfg), cfg, jax.random.PRNGKey(0)))))
    ours = {k: tuple(v.shape) for k, v in flat.items()}
    theirs = {k: tuple(v.shape) for k, v in theirs.items()}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise RuntimeError(f"parameter trees differ: {diff[:8]}")


class SampleProgram(adapters.SampleProgram):
    """``Sampler`` over ``build_model(cfg)``; ``warm``, ``call`` and
    ``free`` are the X-UNet cell's."""

    def __init__(self, cfg, flat: Dict[str, object], *, kind: str,
                 steps: int | None):
        from diff3d_tpu.models import build_model
        from diff3d_tpu.sampling import Sampler

        self.cfg = cfg
        self.sampler = Sampler(build_model(cfg), nest(flat), cfg,
                               sampler_kind=kind, steps=steps)

    def free(self) -> None:
        """As the X-UNet cell's, and harmless when called again: these
        parameters are 10 GB, and calibration frees them before the
        reference makes its own."""
        import jax

        for leaf in jax.tree.leaves(self.sampler.params):
            if not leaf.is_deleted():
                leaf.delete()
