"""From a profiler trace to numbers: device busy seconds, the operations
that took most device time, and the idle gaps by what the host was doing.

The reduction works on a plain form, ``{"device": {plane: [[name, start_ns,
dur_ns], ...]}, "host": [[name, start_ns, dur_ns], ...]}``, which
``load_xplane`` extracts from the ``.xplane.pb`` that ``jax.profiler``
writes; a small recorded trace in that form is kept under
``benchmark/tests/data`` so that the arithmetic is checked without a chip.
Only spans that the benchmark itself annotates (names starting with
``bench:``) are read from the host plane.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "trace_window"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or [
                ln for ln in lines
                if ln.name not in ("Steps", "XLA Modules", "XLA TraceMe")]
            device[plane.name] = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(
                    [ev.name[len(SPAN_PREFIX):], float(ev.start_ns),
                     float(ev.duration_ns)]
                    for ev in ln.events if ev.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[32,64]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[32,64]``: the trace prints an operation's whole HLO
    line; its name and the shape it yields say which one it is."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return f"{head} {shape}".strip()[:120]


def self_seconds(events: Sequence[Sequence]) -> Dict[str, float]:
    """Seconds by operation, an operation that encloses others (a
    ``while`` around its body) counted without what it encloses."""
    out: Dict[str, float] = {}
    stack: List[list] = []                     # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(start)
        while stack and start + dur > stack[-1][1]:    # overlaps, not inside
            close(stack[-1][1])
        if stack:
            stack[-1][2] -= dur
        stack.append([short_name(name), start + dur, dur])
    close(float("inf"))
    return out


def clip(events: Sequence[Sequence], lo: float, hi: float) -> List[list]:
    return [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
            for n, s, d in events if s < hi and s + d > lo]


def reduce(trace: dict, window_s: float, top: int = 10) -> dict:
    """``busy_s`` (union of device-op intervals, averaged over the device
    planes), the ``top`` device operations by their own summed seconds (of
    the busiest plane) and the ``top`` kinds of idle gap: each gap between
    two device operations is given to the benchmark span that covers its
    middle on the host, or to ``unattributed``.

    Where the host plane has a ``trace_window`` span (the harness opens it
    once tracing is on and closes it before tracing stops), everything is
    clipped to it and its length is the window: the profiler starts and
    stops the device's trace a little outside the calls that ask for it."""
    planes = trace["device"]
    spans = sorted((ev for ev in trace["host"] if ev[0] != WINDOW_SPAN),
                   key=lambda ev: ev[1])
    marks = [ev for ev in trace["host"] if ev[0] == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0][1], marks[0][1] + marks[0][2]
        window_s = (hi - lo) / 1e9
        planes = {n: clip(evs, lo, hi) for n, evs in planes.items()}
    planes = {n: evs for n, evs in planes.items() if evs}
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "device_ops": [],
                "idle_gaps": []}
    busy, merged_by_plane = [], {}
    for name, events in planes.items():
        m = merge([(s, s + d) for _, s, d in events if d > 0])
        merged_by_plane[name] = m
        busy.append(sum(e - s for s, e in m) / 1e9)
    fullest = max(planes, key=lambda n: len(planes[n]))
    ops = sorted(self_seconds(planes[fullest]).items(),
                 key=lambda kv: -kv[1])[:top]

    gaps: Dict[str, float] = {}
    m = merged_by_plane[fullest]
    if marks:                      # idle at the window's two ends counts
        m = [(lo, lo)] + m + [(hi, hi)]
    for (_, e0), (s1, _) in zip(m, m[1:]):
        if s1 <= e0:
            continue
        mid = 0.5 * (e0 + s1)
        owner = "unattributed"
        best = None
        for name, s, d in spans:          # innermost covering span
            if s <= mid <= s + d and (best is None or d < best):
                owner, best = name, d
        gaps[owner] = gaps.get(owner, 0.0) + (s1 - e0) / 1e9
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
