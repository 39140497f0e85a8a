"""Operations the algorithm needs, in closed form from a configuration's
shapes.  Counts the multiply-adds of every contraction (convolutions,
dense layers, attention) as 2 FLOPs each and nothing else: no
normalisation, activation or encoding arithmetic, no recomputation, and
nothing read off a jaxpr or compiled program, so the same work is counted
whatever implements an op.  One "example" is one model input: a pair of
frames (conditioning view and noisy target) at ``H x W``.
"""

from __future__ import annotations

from typing import Dict

POSE_CH = 144
FRAMES = 2


def forward_flops(cfg: dict) -> Dict[str, float]:
    """FLOPs of one forward pass of one example, by class of layer."""
    H, W = cfg["H"], cfg["W"]
    ch, emb = cfg["ch"], cfg["emb_ch"]
    dims = [ch * m for m in cfg["ch_mult"]]
    nres, nblk = len(dims), cfg["num_res_blocks"]
    attn_levels = set(cfg["attn_levels"])
    out = {"conv": 0.0, "film": 0.0, "attention": 0.0, "conditioning": 0.0}

    def conv(kind, k, cin, cout, h, w):
        out[kind] += 2.0 * k * k * cin * cout * h * w * FRAMES

    def resnet(cin, f, h, w):
        conv("conv", 3, cin, f, h, w)
        conv("conv", 3, f, f, h, w)
        if cin != f:
            conv("conv", 1, cin, f, h, w)
        out["film"] += 2.0 * emb * 2 * f * h * w * FRAMES

    def attn_pair(c, h, w):
        L = h * w
        for _ in ("self", "cross"):
            out["attention"] += FRAMES * (4 * 2.0 * L * c * c   # q, k, v, out
                                          + 2 * 2.0 * L * L * c  # qk^T, pv
                                          + 2.0 * L * c * c)     # 1x1 out conv

    def block(cin, f, h, w, use_attn):
        resnet(cin, f, h, w)
        if use_attn:
            attn_pair(f, h, w)

    out["conditioning"] += 2 * 2.0 * emb * emb * FRAMES   # two dense layers
    for lvl in range(nres):
        s = 2 ** lvl
        conv("conditioning", 3, POSE_CH, emb, H // s, W // s)

    conv("conv", 3, 3, ch, H, W)
    c, skips = ch, [ch]
    for lvl in range(nres):
        h, w = H >> lvl, W >> lvl
        for _ in range(nblk):
            block(c, dims[lvl], h, w, lvl in attn_levels)
            c = dims[lvl]
            skips.append(c)
        if lvl != nres - 1:
            resnet(c, dims[lvl], h, w)
            skips.append(c)
    h, w = H >> (nres - 1), W >> (nres - 1)
    block(c, dims[-1], h, w, nres in attn_levels)
    for lvl in reversed(range(nres)):
        h, w = H >> lvl, W >> lvl
        for _ in range(nblk + 1):
            block(c + skips.pop(), dims[lvl], h, w, lvl in attn_levels)
            c = dims[lvl]
        if lvl != 0:
            resnet(c, dims[lvl], h, w)
    assert not skips
    conv("conv", 3, c, 3, H, W)
    return out


def forward_total(cfg: dict) -> float:
    return sum(forward_flops(cfg).values())


def train_step_flops(cfg: dict, global_batch: int) -> float:
    """Forward and backward of the batch: the backward pass costs twice
    the forward's contractions (one for the inputs' gradient, one for the
    weights').  Rematerialised forwards are not counted."""
    return 3.0 * forward_total(cfg) * global_batch


def sample_view_flops(cfg: dict, steps: int, weights: int) -> float:
    """One synthesised view of one object: every reverse step calls the
    model on ``2 * weights`` examples (conditional and unconditional)."""
    return forward_total(cfg) * 2 * weights * steps
