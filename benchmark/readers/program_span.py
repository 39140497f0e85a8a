"""A statistic of the program's own spans: those that
``diff3d_tpu.utils.profiling`` (imported here) records in memory at the
program's layer boundaries, on the same ``time.perf_counter`` clock as the
benchmark's spans.

Only spans inside the window count: from the start of the benchmark's first
``input_wait`` or ``call`` span to the end of its last ``step`` or ``call``.
``p50`` / ``mean`` are of the durations, ``share`` is their sum over the
window (%), ``per_id`` the mean over ids (one id = one call) of the summed
durations, and ``sum_until`` the seconds covered by the spans that ended
before the window's start (overlaps once: a function traced inside
another's trace is one stretch of time).  A program without the recorder,
or without such spans, reads nothing."""

import statistics


def window_of(spans: dict):
    starts = [s for n in ("input_wait", "call") for s, _ in spans.get(n, [])]
    ends = [e for n in ("step", "call") for _, e in spans.get(n, [])]
    if not starts or not ends:
        return None
    return min(starts), max(ends)


def read(ctx, spans, stat, scale=1.0):
    try:
        from diff3d_tpu.utils import profiling
        recorder = profiling.RECORDER
    except (ImportError, AttributeError):      # a program from before it
        return None
    window = window_of(ctx["spans"])
    if window is None:
        return None
    w0, w1 = window
    mine = [s for s in recorder.spans() if s.name in spans]
    if stat == "sum_until":
        before = [(s.start, s.end) for s in mine if s.end <= w0]
        return scale * profiling.union_seconds(before) if before else None
    mine = [s for s in mine if s.start >= w0 and s.end <= w1]
    if not mine:
        return None
    durs = [s.end - s.start for s in mine]
    if stat == "p50":
        return scale * statistics.median(durs)
    if stat == "mean":
        return scale * statistics.fmean(durs)
    if stat == "share":
        return 100.0 * sum(durs) / (w1 - w0)
    if stat == "per_id":
        by_id: dict = {}
        for s, d in zip(mine, durs):
            by_id[s.id] = by_id.get(s.id, 0.0) + d
        return scale * statistics.fmean(by_id.values())
    raise ValueError(stat)
