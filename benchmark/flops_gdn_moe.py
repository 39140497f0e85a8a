"""Operations and bytes the algorithm of the token denoiser on a
``qwen3_next`` block needs (``reference/gdn_moe_denoiser.py
model_dict``), in closed form from a configuration's shapes, as
``benchmark/flops_mla_moe.py`` counts its block: the multiply-adds of
every contraction as 2 FLOPs each and nothing else; nothing is read off a
jaxpr or a compiled program.  By class (the scope tags of
``diff3d_tpu/utils/profiling.py``), per layer and example, ``L`` tokens,
``kd = Hk dk`` and ``vd = Hv dv`` the Gated DeltaNet's key and value
widths, ``C`` its chunk (the configuration's ``tiles.delta_chunk``, which
the caller adds to the model's dict):

  ``delta_proj``  ``W_qkvz``, ``W_ba`` and ``W_out``: ``2 L D (2 kd + 2 vd
                  + 2 Hv) + 2 L vd D``
  ``delta_conv``  the depthwise conv's taps: ``2 L (2 kd + vd) K``
  ``delta_scan``  the chunked form's contractions (ops/delta.py), for
                  each chunk and value head: ``k . k``, ``q . k``, ``u =
                  T (beta v)`` and ``w = T (beta exp(gam) k)`` (``C^2`` each
                  times ``dk``, ``dk``, ``dv``, ``dk``), the inverse ``T``
                  by forward substitution (``C^3 / 6``), and in the chunks'
                  recurrence ``w S``, ``exp(gam) q S``, ``k^T n`` (``C dk
                  dv`` each) and ``P n`` (``C^2 dv``)
  ``delta_gate``  the gated norm: no contraction, 0
  ``attention``   ``W_q`` (queries and gates), ``W_k``, ``W_v``, the core
                  over all ``L`` keys, ``2 L^2 Hq d`` for the scores and as
                  much for the values, and ``W_o``
  ``mlp``         the shared expert, ``3 x 2 L D F``, and its gate ``2 L D``
  ``moe_router``  ``2 L D E`` over all ``E`` experts the router scores
  ``experts``     the held experts' three matmuls, ``6 D F`` a row, on the
                  rows an **even load** sends here, ``L k held / E``
                  (40 960 of an example's 81 920 assignments at L 8192,
                  top-10, 256 of 512); padding rows and blocks of the
                  layout's static bound are not counted, so a padded
                  layout reads low
  ``residual``    the two norms and residual adds: no contraction, 0

The rotary turn and the norms count 0.  Bytes (rooflines, PERF.md section
7): the least HBM traffic of one layer on one example if every operand is
read once and every result written once, activations at 2 bytes, float32
decays, write strengths and gates at 4, parameters as this configuration
stores them (``param_dtype``: 2 bytes in bfloat16).  These classes wait
for a harness that reads a scope's device time (PERF.md section 7); the
cell reads ``sample_mfu_pct`` through :func:`sample_view_flops`.
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops_tokens import ACT_BYTES, POSE_CH


def _tokens(cfg: dict) -> int:
    return 2 * (cfg["H"] // cfg["patch"]) * (cfg["W"] // cfg["patch"])


def _widths(cfg: dict):
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return Hk * dk, Hv * dv, Hv, dk, dv


def _param_bytes(cfg: dict) -> int:
    return 2 if cfg.get("param_dtype", "float32") == "bfloat16" else 4


def _expert_rows(cfg: dict) -> float:
    """Rows of one example that an even load routes to the held experts."""
    return (_tokens(cfg) * cfg["num_experts_per_tok"]
            * cfg["experts_held"][1] / cfg["num_experts"])


def _scan_macs(cfg: dict) -> float:
    """Multiply-adds of the delta rule's chunked form on one example."""
    L, C = _tokens(cfg), cfg["delta_chunk"]
    _, _, Hv, dk, dv = _widths(cfg)
    chunks = -(-L // C)
    per_chunk = (C * C * (3 * dk + 2 * dv) + C ** 3 / 6
                 + 3 * C * dk * dv)
    return chunks * Hv * per_chunk


def _ffn_flops(cfg: dict) -> Dict[str, float]:
    L, D = _tokens(cfg), cfg["hidden_size"]
    return {"mlp": 3 * 2.0 * L * D * cfg["shared_intermediate_size"]
            + 2.0 * L * D,
            "moe_router": 2.0 * L * D * cfg["num_experts"],
            "experts": _expert_rows(cfg) * 3 * 2.0 * D * cfg[
                "moe_intermediate_size"],
            "residual": 0.0}


def layer_flops(cfg: dict, kind: str) -> Dict[str, float]:
    """FLOPs of one decoder layer of ``kind`` (``linear_attention`` or
    ``full_attention``) on one example, by class."""
    L, D = _tokens(cfg), cfg["hidden_size"]
    if kind == "linear_attention":
        kd, vd, Hv, _, _ = _widths(cfg)
        out = {"delta_proj": (2.0 * L * D * (2 * kd + 2 * vd + 2 * Hv)
                              + 2.0 * L * vd * D),
               "delta_conv": 2.0 * L * (2 * kd + vd)
               * cfg["linear_conv_kernel_dim"],
               "delta_scan": 2.0 * _scan_macs(cfg),
               "delta_gate": 0.0}
    else:
        Hq, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        out = {"attention": (2.0 * L * D * (2 * Hq * d + 2 * Hkv * d)
                             + 2 * 2.0 * L * L * Hq * d
                             + 2.0 * L * Hq * d * D)}
    out.update(_ffn_flops(cfg))
    return out


def layer_bytes(cfg: dict, kind: str) -> Dict[str, float]:
    """Least HBM bytes of one decoder layer of ``kind`` on one example,
    by class."""
    L, D = _tokens(cfg), cfg["hidden_size"]
    A, P = ACT_BYTES, _param_bytes(cfg)
    if kind == "linear_attention":
        kd, vd, Hv, _, _ = _widths(cfg)
        cw = 2 * kd + vd
        out = {"delta_proj": (A * L * (D + 2 * kd + 2 * vd + vd + D)
                              + 4 * L * 2 * Hv
                              + P * (D * (2 * kd + 2 * vd + 2 * Hv)
                                     + vd * D)),
               "delta_conv": (2 * A * L * cw
                              + P * cw * cfg["linear_conv_kernel_dim"]),
               # q, k, v in, the two float32 gates in, o out
               "delta_scan": A * L * (2 * kd + 2 * vd) + 4 * L * 2 * Hv,
               "delta_gate": 3 * A * L * vd}
    else:
        Hq, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        # the projections out, q / k / v into the core, its output out,
        # gated, into W_o, D out
        out = {"attention": (A * L * (D + 2 * Hq * d + 2 * Hkv * d
                                      + Hq * d + 2 * Hkv * d + 3 * Hq * d
                                      + D)
                             + P * (D * (2 * Hq * d + 2 * Hkv * d)
                                    + Hq * d * D))}
    E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    F = cfg["shared_intermediate_size"]
    out.update({"mlp": 2 * A * L * D + P * (3.0 * D * F + D),
                "moe_router": A * L * D + 4.0 * L * E + P * D * E,
                "experts": (2 * A * _expert_rows(cfg) * D
                            + P * cfg["experts_held"][1] * 3.0 * D * Fe),
                # each half: h in, u out; h and the half's output in, h out
                "residual": 2 * 5 * A * L * D})
    return out


def example_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class, without the
    conditioning branch."""
    L, D = _tokens(cfg), cfg["hidden_size"]
    p = cfg["patch"]
    out = {"patch_embed": (2.0 * L * (p * p * 3) * D             # pixels in
                           + 2.0 * (L // 2) * D * (p * p * 3))}  # head
    for kind in cfg["layer_types"]:
        for c, v in layer_flops(cfg, kind).items():
            out[c] = out.get(c, 0.0) + v
    return out


def row_flops(cfg: dict) -> float:
    """FLOPs of the conditioning branch for one conditioning row."""
    L, D = _tokens(cfg), cfg["hidden_size"]
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
