"""Operations and bytes the algorithm of the hybrid token denoiser needs
(``reference/hybrid_denoiser.py model_dict``), in closed form from a
configuration's shapes, as ``benchmark/flops_tokens.py`` counts Keye's
block: the multiply-adds of every contraction as 2 FLOPs each and nothing
else; nothing is read off a jaxpr or a compiled program.  Attention is
counted over all ``L`` keys (the layer selects none).  The state-space
scan is counted as the chunked form's four contractions at the published
chunk size ``Q = mamba_chunk_size`` -- per token ``2 Q N`` (``C B^T``
inside a chunk), ``2 H Q P`` (the decay-masked product with ``x``),
``2 H P N`` (the chunk's state) and ``2 N H P`` (``C S``): 4.26 MFLOP a
token at Q 256, H 64, P 64, N 128 -- not as the sequential recurrence's
``4 H P N`` (2.10 MFLOP), so an implementation that recurs token by token
reads high and one with a larger chunk reads low.  One "example" is one
pair of frames; the conditioning branch is counted per conditioning row.

Bytes (rooflines, PERF.md section 7): the least HBM traffic of one layer
on one example if every operand is read once and every result written
once, activations in the compute dtype (2 bytes), parameters as stored (4
bytes).  ``ssm_scan``: ``x``, ``B``, ``C``, ``dt`` and the gate ``z`` in,
``y`` out (a fused scan gates before it writes).
"""

from __future__ import annotations

from typing import Dict

from benchmark.flops_tokens import ACT_BYTES, PARAM_BYTES, POSE_CH


def _sizes(cfg: dict):
    L = 2 * (cfg["H"] // cfg["patch"]) * (cfg["W"] // cfg["patch"])
    H, N = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    return L, H, cfg["mamba_d_head"], N, H * cfg["mamba_d_head"]


def layer_flops(cfg: dict, kind: str) -> Dict[str, float]:
    """FLOPs of one decoder layer of type ``kind`` on one example, by
    class (the scope tags of ``diff3d_tpu/utils/profiling.py``)."""
    L, H, P, N, di = _sizes(cfg)
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    out = {"mlp": 3 * 2.0 * L * D * F}
    if kind == "mamba":
        Q = min(cfg["mamba_chunk_size"], L)
        out["ssm_proj"] = 2.0 * L * D * (2 * di + 2 * N + H + di)
        out["ssm_conv"] = 2.0 * L * cfg["mamba_d_conv"] * (di + 2 * N)
        out["ssm_scan"] = L * (2.0 * Q * N + 2.0 * H * Q * P
                               + 2 * 2.0 * H * P * N)
    elif kind == "attention":
        Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["head_dim"]
        out["attention"] = (2.0 * L * D * (2 * Hq * d + 2 * Hkv * d)
                            + 2 * 2.0 * L * L * Hq * d)     # qk^T, pv
    else:
        raise ValueError(kind)
    return out


def layer_bytes(cfg: dict, kind: str) -> Dict[str, float]:
    """Least HBM bytes of one decoder layer of type ``kind`` on one
    example, by class."""
    L, H, P, N, di = _sizes(cfg)
    D, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    out = {"mlp": 2 * ACT_BYTES * L * D + PARAM_BYTES * 3.0 * D * F}
    if kind == "mamba":
        wide = 2 * di + 2 * N + H
        conv = di + 2 * N
        out["ssm_proj"] = (ACT_BYTES * L * (D + wide + di + D)
                           + PARAM_BYTES * D * (wide + di))
        out["ssm_conv"] = (2 * ACT_BYTES * L * conv
                           + PARAM_BYTES * (cfg["mamba_d_conv"] + 1) * conv)
        out["ssm_scan"] = ACT_BYTES * L * (di + 2 * N + H + di + di)
    elif kind == "attention":
        Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg["head_dim"]
        out["attention"] = (2 * ACT_BYTES * L * D
                            + PARAM_BYTES * D * (2 * Hq * d + 2 * Hkv * d))
    else:
        raise ValueError(kind)
    return out


def example_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class, without the
    conditioning branch."""
    L = _sizes(cfg)[0]
    D, p = cfg["hidden_size"], cfg["patch"]
    out: Dict[str, float] = {}
    for kind in cfg["layer_types"]:
        for c, v in layer_flops(cfg, kind).items():
            out[c] = out.get(c, 0.0) + v
    out["patch_embed"] = (2.0 * L * (p * p * 3) * D             # pixels in
                          + 2.0 * (L // 2) * D * (p * p * 3))   # head
    return out


def row_flops(cfg: dict) -> float:
    """FLOPs of the conditioning branch for one conditioning row."""
    L = _sizes(cfg)[0]
    D, p = cfg["hidden_size"], cfg["patch"]
    return (2.0 * L * (p * p * POSE_CH) * D                     # rays
            + 2 * (2.0 * cfg["emb_ch"] * D + 2.0 * D * D))      # logSNR MLP


def forward_flops(cfg: dict, examples: int, rows: int) -> float:
    return (examples * sum(example_flops(cfg).values())
            + rows * row_flops(cfg))


def sample_view_flops(cfg: dict, steps: int, weights: int) -> float:
    """One synthesised view of one object: every reverse step calls the
    model on ``2 * weights`` examples at 2 conditioning rows."""
    return steps * forward_flops(cfg, 2 * weights, 2)
