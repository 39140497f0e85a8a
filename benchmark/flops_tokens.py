"""Operations and bytes the algorithm of the token denoiser needs, in
closed form from a configuration's shapes (``reference/token_denoiser.py
model_dict``).  Operations: the multiply-adds of every contraction as 2
FLOPs each and nothing else, as ``benchmark/flops.py`` counts the X-UNet:
attention over the ``topk`` selected keys (not over all ``L``), the
``num_experts_per_tok`` routed experts of a token (not all of them, and no
padding), so an implementation that masks a dense score tile or pads its
expert blocks reads low against these, and one that skips reads higher;
nothing is read off a jaxpr or a compiled program.  One "example" is one
pair of frames; the conditioning branch (ray projection, logSNR MLP) is
counted per conditioning row, which the sampler shares among examples.

Bytes, for the three classes that are new (rooflines, PERF.md section 5):
the least HBM traffic of one layer on one example if every operand is
read once and every result written once, activations in the compute
dtype (2 bytes), parameters as stored (4 bytes).
"""

from __future__ import annotations

from typing import Dict

ACT_BYTES = 2
PARAM_BYTES = 4
POSE_CH = 144


def _sizes(cfg: dict):
    L = 2 * (cfg["H"] // cfg["patch"]) * (cfg["W"] // cfg["patch"])
    k = min(cfg["indexer_topk"], L)
    return L, k


def layer_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one decoder layer on one example, by class."""
    L, k = _sizes(cfg)
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hi, di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    return {
        "attention": 2.0 * L * D * (2 * Hq * d + 2 * Hkv * d),  # q, o, k, v
        "indexer": (2.0 * L * D * (Hi * di + di + Hi)           # projections
                    + 2.0 * L * L * Hi * di),                   # I[t, s]
        "sparse_attention": 2 * 2.0 * L * k * Hq * d,           # qk^T, pv
        "moe_router": 2.0 * L * D * E,
        # a token's routed experts that are held here: all of them when
        # every expert is, their even share otherwise
        "experts": (3 * 2.0 * L * D * F * cfg["num_experts_per_tok"]
                    * held / E),
    }


def layer_bytes(cfg: dict) -> Dict[str, float]:
    """Least HBM bytes of one decoder layer on one example, for the
    classes whose roofline PERF.md reports."""
    L, k = _sizes(cfg)
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hi, di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    F = cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    return {
        # u in, the three projections' kernels, the selection out as
        # topk int32 indices per token
        "indexer": (ACT_BYTES * L * D
                    + PARAM_BYTES * D * (Hi * di + di + Hi) + 4.0 * L * k),
        # q, k, v in, the selection in, the heads' output
        "sparse_attention": (ACT_BYTES * L * d * (2 * Hq + 2 * Hkv)
                             + 4.0 * L * k),
        # tokens in and out, every held expert's three matrices once
        "experts": (2 * ACT_BYTES * L * D + PARAM_BYTES * held * 3.0 * D * F),
    }


def example_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class, without the
    conditioning branch."""
    L, _ = _sizes(cfg)
    D, p = cfg["hidden_size"], cfg["patch"]
    out = {c: v * cfg["num_hidden_layers"]
           for c, v in layer_flops(cfg).items()}
    out["patch_embed"] = (2.0 * L * (p * p * 3) * D             # pixels in
                          + 2.0 * (L // 2) * D * (p * p * 3))   # head
    return out


def row_flops(cfg: dict) -> float:
    """FLOPs of the conditioning branch for one conditioning row."""
    L, _ = _sizes(cfg)
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
