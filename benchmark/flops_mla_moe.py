"""Operations and bytes the algorithm of the latent-attention token
denoiser with sigmoid-routed experts needs (``reference/mla_moe_denoiser.py
model_dict``), in closed form from a configuration's shapes, as
``benchmark/flops_hybrid.py`` counts its block: the multiply-adds of every
contraction as 2 FLOPs each and nothing else; nothing is read off a jaxpr
or a compiled program.  By class (the scope tags of
``diff3d_tpu/utils/profiling.py``), per layer and example, ``L`` tokens:

  ``mla_q``       ``W_qa`` and ``W_qb``: ``2 L (D q_r + q_r H (d_n + d_r))``
  ``mla_kv``      ``W_kva`` and ``W_kvb``: ``2 L (D (kv_r + d_r) + kv_r H
                  (d_n + d_v))``
  ``attention``   the core over all ``L`` keys, ``2 L^2 H (d_n + d_r)``
                  for the scores and ``2 L^2 H d_v`` for the values, and
                  ``W_o``, ``2 L H d_v D``
  ``mlp``         the dense layers' MLP, or the shared expert beside the
                  routed ones: ``3 x 2 L D F``
  ``moe_router``  ``2 L D E`` over all ``E`` experts the router scores
  ``experts``     the held experts' three matmuls, ``6 D F`` a row, on the
                  rows an **even load** sends here, ``L k held / E`` (2048
                  of an example's 65 536 assignments at L 8192, top-8, 8 of
                  256); padding rows and blocks of the layout's static
                  bound are not counted, so a padded layout reads low.

The rotary embedding and the norms are no contraction and count 0.  Bytes
(rooflines, PERF.md section 7): the least HBM traffic of one layer on one
example if every operand is read once and every result written once,
activations at 2 bytes, parameters as this configuration stores them
(``param_dtype``: 2 bytes in bfloat16).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops_tokens import ACT_BYTES, POSE_CH


def _sizes(cfg: dict):
    L = 2 * (cfg["H"] // cfg["patch"]) * (cfg["W"] // cfg["patch"])
    return (L, cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _param_bytes(cfg: dict) -> int:
    return 2 if cfg.get("param_dtype", "float32") == "bfloat16" else 4


def _expert_rows(cfg: dict) -> float:
    """Rows of one example that an even load routes to the held experts."""
    return (_sizes(cfg)[0] * cfg["num_experts_per_tok"]
            * cfg["experts_held"][1] / cfg["num_experts"])


def layer_flops(cfg: dict, dense: bool) -> Dict[str, float]:
    """FLOPs of one decoder layer on one example, by class; ``dense``:
    one of the leading dense layers."""
    L, D, H, dn, dr, dv = _sizes(cfg)
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    out = {"mla_q": 2.0 * L * (D * qr + qr * H * (dn + dr)),
           "mla_kv": 2.0 * L * (D * (kvr + dr) + kvr * H * (dn + dv)),
           "attention": (2.0 * L * L * H * (dn + dr) + 2.0 * L * L * H * dv
                         + 2.0 * L * H * dv * D)}
    if dense:
        out["mlp"] = 3 * 2.0 * L * D * cfg["dense_intermediate_size"]
        return out
    out["mlp"] = 3 * 2.0 * L * D * cfg["shared_intermediate_size"]
    out["moe_router"] = 2.0 * L * D * cfg["num_experts"]
    out["experts"] = _expert_rows(cfg) * 3 * 2.0 * D * cfg[
        "moe_intermediate_size"]
    return out


def layer_bytes(cfg: dict, dense: bool) -> Dict[str, float]:
    """Least HBM bytes of one decoder layer on one example, by class."""
    L, D, H, dn, dr, dv = _sizes(cfg)
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    A, P = ACT_BYTES, _param_bytes(cfg)
    out = {"mla_q": (A * L * (D + 2 * qr + H * (dn + dr))
                     + P * (D * qr + qr * H * (dn + dr))),
           "mla_kv": (A * L * (D + (kvr + dr) + kvr + H * (dn + dv))
                      + P * (D * (kvr + dr) + kvr * H * (dn + dv))),
           # q and k in, v in, the heads' outputs out and into W_o, D out
           "attention": (A * L * (2 * H * (dn + dr) + 3 * H * dv + D)
                         + P * H * dv * D)}
    F = (cfg["dense_intermediate_size"] if dense
         else cfg["shared_intermediate_size"])
    out["mlp"] = 2 * A * L * D + P * 3.0 * D * F
    if not dense:
        E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        out["moe_router"] = A * L * D + 4.0 * L * E + P * D * E
        out["experts"] = (2 * A * _expert_rows(cfg) * D
                          + P * cfg["experts_held"][1] * 3.0 * D * Fe)
    return out


def example_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class, without the
    conditioning branch."""
    L, D = _sizes(cfg)[:2]
    p = cfg["patch"]
    out = {"patch_embed": (2.0 * L * (p * p * 3) * D             # pixels in
                           + 2.0 * (L // 2) * D * (p * p * 3))}  # head
    for i in range(cfg["num_hidden_layers"]):
        for c, v in layer_flops(cfg, i < cfg["first_k_dense_replace"]
                                ).items():
            out[c] = out.get(c, 0.0) + v
    return out


def row_flops(cfg: dict) -> float:
    """FLOPs of the conditioning branch for one conditioning row."""
    L, D = _sizes(cfg)[:2]
    p = cfg["patch"]
    return (2.0 * L * (p * p * POSE_CH) * D                     # rays
            + 2 * (2.0 * cfg["emb_ch"] * D + 2.0 * D * D))      # logSNR MLP


def forward_flops(cfg: dict, examples: int, rows: int) -> float:
    return (examples * sum(example_flops(cfg).values())
            + rows * row_flops(cfg))


def sample_view_flops(cfg: dict, steps: int, weights: int) -> float:
    """One synthesised view of one object: every reverse step calls the
    model on ``2 * weights`` examples at 2 conditioning rows."""
    return steps * forward_flops(cfg, 2 * weights, 2)
