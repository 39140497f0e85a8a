"""A statistic of the durations of one of the benchmark's own spans:
``p50`` (median), ``mean`` or ``share`` (their sum over the window, %)."""

import statistics


def read(ctx, span, stat, scale=1.0):
    durs = [e - s for s, e in ctx["spans"].get(span, [])]
    if not durs:
        return None
    if stat == "p50":
        return scale * statistics.median(durs)
    if stat == "mean":
        return scale * statistics.fmean(durs)
    if stat == "share":
        return 100.0 * sum(durs) / ctx["window"]["window_s"]
    raise ValueError(stat)
