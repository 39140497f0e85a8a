"""Where the benchmark touches the program for the hybrid token denoiser
with routed experts beside a shared expert: the program's own config from
a ``benchmark/configs`` file of that model.  The tree check, the sampler
program and the diffusion settings are ``benchmark/adapters_tokens.py``'s,
which read nothing of the model's kind.
"""

from __future__ import annotations

from benchmark.adapters_tokens import (SampleProgram, check_tree,  # noqa: F401
                                       diffusion_dict, nest)
from benchmark.reference import hybrid_moe_denoiser as rm


def build_config(config: dict):
    """``diff3d_tpu.config.Config`` with a ``TokenModelConfig`` from a
    configuration file that keeps the published key names: the router's
    width (``published.num_local_experts``) is the program's
    ``num_experts``, the file's ``intermediate_size`` its
    ``moe_intermediate_size``, and ``experts_held`` says which of the
    experts' weights exist here."""
    from diff3d_tpu.config import (Config, DataConfig, DiffusionConfig,
                                   TokenModelConfig)

    m = rm.model_dict(config)
    m.update(layer_types=tuple(m["layer_types"]),
             experts_held=tuple(m["experts_held"]),
             dtype=config["dtype"], **config["tiles"])
    d = dict(config["diffusion"])
    d["guidance_weights"] = tuple(d["guidance_weights"])
    cfg = Config(model=TokenModelConfig(**m), diffusion=DiffusionConfig(**d),
                 data=DataConfig(imgsize=config["H"]))
    cfg.validate()
    return cfg
