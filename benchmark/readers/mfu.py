"""Whole-step share of the chip's peak: the FLOPs the algorithm needs for
the work the window finished (``benchmark/flops.py``; recomputation not
counted) over the window's seconds, over ``chips`` times the peak of
``benchmark/peaks.json``."""


def read(ctx):
    w = ctx["window"]
    peak = ctx["peak"]["flops_per_s"] * ctx["chips"]
    if not w.get("flops") or not w.get("window_s") or peak != peak:
        return None
    return 100.0 * w["flops"] / w["window_s"] / peak
