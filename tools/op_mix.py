"""Static per-op FLOP inventory of one X-UNet forward pass.

Mirrors ``models/xunet.py``'s structure exactly (stem -> down blocks +
downsamples -> middle -> up blocks + upsamples -> head; ResnetBlock =
conv1/conv2 + optional 1x1 skip_proj, attention = q/k/v/out projections
+ the sdpa core + a 1x1 out_conv) and prints FLOPs grouped by op class
and UNet level.  Pure arithmetic — runs anywhere, no devices.

Counted: every conv (stem/blocks/resamples/head/ConditioningProcessor
per-level strided convs), every attention projection + sdpa core, and
every FiLM dense — FiLM's conditioning input is [B, F, h, w, emb_ch]
(full spatial extent, models/xunet.py:78-80), so its
emb_ch -> 2*features dense is real per-pixel matmul work, ~17%% of the
srn128 forward.  The fused-kernel sites (``ops/pallas_film.py``) are
inventoried as their own classes — ``fused_gn_silu`` (ResnetBlock entry
GroupNorm->SiLU + the head's last_gn) and ``fused_film`` (the
GroupNorm->FiLM->SiLU epilogue) — with elementwise FLOPs (~10-12 per
element), so the share the kernel layer covers is a number, not a
hand-wave; their HBM-traffic share is far larger than their FLOP share,
which is exactly why they are fused.  Still omitted: residual adds,
plain attention GroupNorms, and the two logsnr MLP denses (spatial
size 1).

Why it exists: the srn128 train step measures far
below the chip's big-matmul ceiling.  ``tools/roofline.py`` measures
what each conv SHAPE CLASS can sustain; this tool says how much of the
step's work sits in each shape class, so ceiling-x-share gives the
op-mix ceiling prediction without hand-waving.

Usage: python -m tools.op_mix [--config srn128] [--microbatch 4]
"""

from __future__ import annotations

import argparse
import json
from collections import defaultdict


def conv_flops(b, h, w, cin, cout, k):
    return 2.0 * b * h * w * cin * cout * k * k


def dense_flops(b, l, cin, cout):
    return 2.0 * b * l * cin * cout


def inventory(cfg_model, microbatch: int):
    """Returns a list of op records for ONE forward pass at
    ``microbatch`` examples (x2 frames folded into the batch axis,
    matching the model's reshape)."""
    ops = []
    BF = microbatch * 2
    num_res = cfg_model.num_resolutions
    dims = [cfg_model.ch * m for m in cfg_model.ch_mult]
    H = cfg_model.H

    def res_at(lvl):
        return H // (2 ** lvl)

    def add(kind, lvl, flops, shape):
        ops.append({"kind": kind, "level": lvl, "flops": flops,
                    "shape": shape})

    def resnet(lvl, cin, cout, tag):
        h = res_at(lvl)
        # entry GroupNorm->SiLU, fused (pallas_film): ~10 elementwise
        # flops/element (two-pass stats + normalize/affine + silu)
        add("fused_gn_silu", lvl, 10.0 * BF * h * h * cin,
            [BF, h, h, cin])
        add(f"conv3x3_{tag}", lvl, conv_flops(BF, h, h, cin, cout, 3),
            [BF, h, h, cin, cout, 3])
        add(f"conv3x3_{tag}", lvl, conv_flops(BF, h, h, cout, cout, 3),
            [BF, h, h, cout, cout, 3])
        # FiLM: Dense(emb_ch -> 2*cout) at EVERY spatial position (the
        # level emb carries pose information per pixel)
        add("film_dense", lvl,
            dense_flops(BF, h * h, cfg_model.emb_ch, 2 * cout),
            [BF, h * h, cfg_model.emb_ch, 2 * cout])
        # GroupNorm->FiLM(scale/shift)->SiLU epilogue, fused: the GN's
        # ~10 flops/element plus the modulate multiply-add
        add("fused_film", lvl, 12.0 * BF * h * h * cout,
            [BF, h, h, cout])
        if cin != cout:
            add(f"conv1x1_skip", lvl, conv_flops(BF, h, h, cin, cout, 1),
                [BF, h, h, cin, cout, 1])

    def attention(lvl, c):
        h = res_at(lvl)
        L = h * h
        for name in ("q", "k", "v", "out"):
            add("attn_proj", lvl, dense_flops(BF, L, c, c), [BF, L, c, c])
        # sdpa core: QK^T + PV, each 2*L*L*C
        add("attn_sdpa", lvl, 2 * (2.0 * BF * L * L * c), [BF, L, c])
        add("conv1x1_attnout", lvl, conv_flops(BF, h, h, c, c, 1),
            [BF, h, h, c, c, 1])

    def xunet_block(lvl, cin, cout, use_attn):
        resnet(lvl, cin, cout, "block")
        if use_attn:
            for _ in ("self", "cross"):
                attention(lvl, cout)

    # conditioning: one strided 3x3 conv per level, 144ch posenc ->
    # emb_ch at that level's resolution (models/conditioning.py:108-117)
    POSENC_CH = 144
    for lvl in range(num_res):
        h = res_at(lvl)
        add("cond_conv", lvl,
            conv_flops(BF, h, h, POSENC_CH, cfg_model.emb_ch, 3),
            [BF, h, h, POSENC_CH, cfg_model.emb_ch, 3])

    # stem
    add("conv3x3_stem", 0, conv_flops(BF, H, H, 3, cfg_model.ch, 3),
        [BF, H, H, 3, cfg_model.ch, 3])
    c = cfg_model.ch

    # down path (track the skip stack's channel dims like xunet.py's hs)
    hs = [c]
    for lvl in range(num_res):
        use_attn = lvl in cfg_model.attn_levels
        for _ in range(cfg_model.num_res_blocks):
            xunet_block(lvl, c, dims[lvl], use_attn)
            c = dims[lvl]
            hs.append(c)
        if lvl != num_res - 1:
            resnet(lvl, c, dims[lvl], "downsample")
            hs.append(c)

    # middle
    xunet_block(num_res - 1, c, dims[-1], num_res in cfg_model.attn_levels)
    c = dims[-1]

    # up path
    for lvl in reversed(range(num_res)):
        use_attn = lvl in cfg_model.attn_levels
        for _ in range(cfg_model.num_res_blocks + 1):
            cin = c + hs.pop()
            xunet_block(lvl, cin, dims[lvl], use_attn)
            c = dims[lvl]
        if lvl != 0:
            resnet(lvl, c, dims[lvl], "upsample")
    assert not hs

    # head: last_gn (GroupNorm->SiLU, fused) then the zero-init conv
    add("fused_gn_silu", 0, 10.0 * BF * H * H * dims[0],
        [BF, H, H, dims[0]])
    add("conv3x3_head", 0, conv_flops(BF, H, H, dims[0], 3, 3),
        [BF, H, H, dims[0], 3, 3])
    return ops


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", choices=["srn64", "srn128"],
                   default="srn128")
    p.add_argument("--microbatch", type=int, default=4,
                   help="examples per device program (bench srn128 runs "
                        "global 16 / accum 4 = 4)")
    p.add_argument("--out", default=None, help="write full JSON here")
    args = p.parse_args(argv)

    from diff3d_tpu.config import srn64_config, srn128_config

    cfg = {"srn64": srn64_config, "srn128": srn128_config}[args.config]()
    ops = inventory(cfg.model, args.microbatch)
    total = sum(o["flops"] for o in ops)

    by_level = defaultdict(float)
    by_class = defaultdict(float)
    by_level_class = defaultdict(float)
    for o in ops:
        by_level[o["level"]] += o["flops"]
        if o["kind"] == "attn_sdpa":
            cls = "attn_sdpa"
        elif o["kind"].startswith("attn"):
            cls = "attn_proj"
        elif o["kind"] == "film_dense":
            cls = "film"
        elif o["kind"] in ("fused_gn_silu", "fused_film"):
            cls = o["kind"]         # the pallas_film kernel classes
        elif o["kind"] == "cond_conv":
            cls = "cond_conv"
        else:
            # bucket convs by their widest channel count — the quantity
            # that sets MXU result-tile fill (tools/roofline.py classes)
            cls = f"conv_ch{max(o['shape'][3], o['shape'][4])}"
        by_class[cls] += o["flops"]
        by_level_class[(o["level"], cls)] += o["flops"]

    report = {
        "config": args.config,
        "microbatch": args.microbatch,
        "total_fwd_gflops": round(total / 1e9, 2),
        "note": "forward only; backward ~2x, remat adds ~1x fwd",
        "share_by_level": {
            str(l): round(v / total, 4) for l, v in sorted(by_level.items())},
        "share_by_class": {
            k: round(v / total, 4) for k, v in sorted(by_class.items())},
        "share_by_level_class": {
            f"L{l}/{c}": round(v / total, 4)
            for (l, c), v in sorted(by_level_class.items())},
    }
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"report": report, "ops": ops}, f, indent=1)


if __name__ == "__main__":
    main()
