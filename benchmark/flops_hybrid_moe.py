"""Operations and bytes the algorithm of the hybrid token denoiser with
routed experts beside a shared expert needs (``reference/
hybrid_moe_denoiser.py model_dict``), in closed form from a
configuration's shapes: ``benchmark/flops_hybrid.py``'s count of the
mixers and of the dense gated MLP (here the shared expert, class ``mlp``,
at ``shared_intermediate_size``), and per layer two classes more:

  ``moe_router``  ``2 L D E`` over all ``E = num_experts`` the router
                  scores, whatever is held here.
  ``experts``     the held experts' three matmuls, ``6 D F`` a row, on
                  the rows an **even load** sends here: ``L k held / E``
                  of an example's ``L k`` assignments (10 240 of 81 920 at
                  L 8192, top-10, 9 of 72).  The routing of a run sends
                  more or fewer (the run's ``notes`` have the share the
                  reference saw); rows of padding, and blocks of the
                  layout's static bound that hold none, are not counted,
                  so a padded block reads low.

Nothing is read off a jaxpr or a compiled program.  Bytes as
``flops_hybrid.py`` counts them: every operand read once, every result
written once, activations at 2 bytes, parameters as stored (4 bytes);
``experts`` reads its rows in and writes them back (``D`` wide) and reads
the held experts' matrices once for an example.
"""

from __future__ import annotations

from typing import Dict

from benchmark import flops_hybrid
from benchmark.flops_hybrid import row_flops  # noqa: F401  (re-export)
from benchmark.flops_tokens import ACT_BYTES, PARAM_BYTES


def _expert_rows(cfg: dict) -> float:
    """Rows of one example that an even load routes to the held experts."""
    L = flops_hybrid._sizes(cfg)[0]
    return (L * cfg["num_experts_per_tok"] * cfg["experts_held"][1]
            / cfg["num_experts"])


def layer_flops(cfg: dict, kind: str) -> Dict[str, float]:
    """FLOPs of one decoder layer of type ``kind`` on one example, by
    class (the scope tags of ``diff3d_tpu/utils/profiling.py``)."""
    L = flops_hybrid._sizes(cfg)[0]
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    out = flops_hybrid.layer_flops(cfg, kind)
    out["moe_router"] = 2.0 * L * D * cfg["num_experts"]
    out["experts"] = _expert_rows(cfg) * 3 * 2.0 * D * F
    return out


def layer_bytes(cfg: dict, kind: str) -> Dict[str, float]:
    """Least HBM bytes of one decoder layer of type ``kind`` on one
    example, by class."""
    L = flops_hybrid._sizes(cfg)[0]
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E, held = cfg["num_experts"], cfg["experts_held"][1]
    out = flops_hybrid.layer_bytes(cfg, kind)
    out["moe_router"] = (ACT_BYTES * L * D + 4.0 * L * E
                         + PARAM_BYTES * D * E)
    out["experts"] = (2 * ACT_BYTES * _expert_rows(cfg) * D
                      + PARAM_BYTES * held * 3.0 * D * F)
    return out


def example_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class, without the
    conditioning branch."""
    out = {"patch_embed": flops_hybrid.example_flops(
        dict(cfg, layer_types=[]))["patch_embed"]}
    for kind in cfg["layer_types"]:
        for c, v in layer_flops(cfg, kind).items():
            out[c] = out.get(c, 0.0) + v
    return out


def forward_flops(cfg: dict, examples: int, rows: int) -> float:
    return (examples * sum(example_flops(cfg).values())
            + rows * row_flops(cfg))


def sample_view_flops(cfg: dict, steps: int, weights: int) -> float:
    """One synthesised view of one object: every reverse step calls the
    model on ``2 * weights`` examples at 2 conditioning rows."""
    return steps * forward_flops(cfg, 2 * weights, 2)
