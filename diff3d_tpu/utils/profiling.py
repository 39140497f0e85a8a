"""Observability: one vocabulary, one module (docs/DESIGN.md, "Observability").

The reference has no observability at all — two dead ``strt=time.time()``
assignments and tqdm bars (``/root/reference/train.py:136,150,264``;
SURVEY.md §5.1).  Here the measurement sits inside the program:

  * :func:`scope` — ``jax.named_scope("d3d.<class>")`` from the fixed
    vocabulary :data:`SCOPES`.  Every device op of the train step and of
    the sampler's view program carries one in its ``op_name``; metadata
    only, the compiled programs are unchanged.
  * :func:`span` / :func:`count` — host spans and counters at the layer
    boundaries (loader, prefetch, step dispatch, sampler), kept in a
    bounded in-memory ring and, under a profiler session, written into the
    profiler's host plane (``d3d:<name>``) on the device ops' clock.
    Always on: they sit at boundaries crossed a few times per step, never
    inside a traced function.
  * the compile clock — JAX's own trace / lower / backend-compile events
    as ``compile.*`` spans of the same recorder (:data:`COMPILE_CLOCK`),
    the backend compile split into persistent-cache reads and programs
    compiled afresh.
  * :func:`spanned` — the program's own build steps (``init.*``: model,
    parameters, optimizer state, train step, sampler) as one span a call.
  * :func:`profile_window` — the one capture path: a ``jax.profiler``
    trace of the ``with`` body, reduced by :func:`scope_seconds` to
    ``<logdir>/by_scope.json`` (device seconds by class, forward and
    backward, and idle gaps by host span).
  * :class:`StepTimer` — cheap wall-clock step timing with percentile
    summaries, no device syncs outside window boundaries.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import glob
import json
import os
import re
import struct
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

#: Prefix of a device scope in an op's ``op_name`` / of a host span in the
#: profiler's host plane.
SCOPE_PREFIX = "d3d."
SPAN_PREFIX = "d3d:"

#: The block classes a device op can belong to — the contract (DESIGN.md
#: has the table of where each is opened).  The innermost tag on an op's
#: path is its class; forward and backward share a tag.
SCOPES = ("conv", "film", "groupnorm", "attention", "conditioning",
          "dropout", "residual", "loss", "grad_accum", "optimizer", "ema",
          "metrics", "sampler", "record",
          # the token denoiser's own (models/token_denoiser.py); it
          # shares "attention" (projections), "residual", "conditioning"
          "patch_embed", "moe_router", "experts", "indexer",
          "sparse_attention", "rope",
          # a state-space mixer's four (models/mamba.py: projections,
          # causal conv, the scan with its steps and decays, the gated
          # norm) and the dense gated MLP (models/token_layers.py)
          "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate", "mlp",
          # latent attention's two projection paths (models/
          # latent_attention.py: W_qa, its norm, W_qb; W_kva, its norm,
          # W_kvb); its rotary turn is "rope", its core and W_o
          # "attention"
          "mla_q", "mla_kv",
          # a Gated DeltaNet mixer's four (models/gated_deltanet.py:
          # W_qkvz, W_ba and W_out; the causal conv; the l2 norms, beta,
          # the decays and the delta rule's scan; the gated norm)
          "delta_proj", "delta_conv", "delta_scan", "delta_gate")


def scope(tag: str):
    """``jax.named_scope("d3d.<tag>")`` for a tag of :data:`SCOPES`."""
    if tag not in SCOPES:
        raise ValueError(f"scope {tag!r} is not in the vocabulary {SCOPES}")
    return jax.named_scope(SCOPE_PREFIX + tag)


# --------------------------------------------------------------------------
# host spans and counters
# --------------------------------------------------------------------------


class Span(NamedTuple):
    """One finished host span: ``start`` / ``end`` by
    ``time.perf_counter``, ``parent`` the name of the span that enclosed
    it on its thread (or None), ``id`` what ties one step's or one call's
    spans together across threads, ``thread`` the recording thread."""

    name: str
    start: float
    end: float
    parent: Optional[str]
    id: Optional[object]
    thread: int


class _OpenSpan:
    __slots__ = ("_rec", "_name", "_id", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str, id):
        self._rec, self._name, self._id = rec, name, id

    def __enter__(self):
        # No session: TraceAnnotation is a flag check (< 1 us).  Under one
        # the span lands in the host plane on the device ops' clock.
        self._ann = jax.profiler.TraceAnnotation(
            SPAN_PREFIX + self._name,
            **({} if self._id is None else {"id": self._id}))
        self._ann.__enter__()
        self._rec._stack().append(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        stack = self._rec._stack()
        stack.pop()
        self._rec.add(self._name, self._t0, t1, id=self._id,
                      parent=stack[-1] if stack else None)
        self._ann.__exit__(*exc)
        return False


class Recorder:
    """Spans in a bounded ring (the newest ``capacity``), running totals
    per span name, and named counters.  One lock guards all three: it is
    taken once per span end, a few times per train step."""

    def __init__(self, capacity: int = 1 << 16):
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=capacity)  # guarded-by: self._lock
        self._totals: Dict[str, List[float]] = {}  # guarded-by: self._lock
        self._counters: Dict[str, float] = {}  # guarded-by: self._lock
        # per-thread stack of open span names: thread-local, so lock-free
        self._local = threading.local()

    def _stack(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, id=None) -> _OpenSpan:
        """Context manager around one crossing of a layer boundary."""
        return _OpenSpan(self, name, id)

    def add(self, name: str, start: float, end: float, *, id=None,
            parent: Optional[str] = None) -> None:
        """Record a span that has already ended (the compile clock's
        events arrive so)."""
        rec = Span(name, start, end, parent, id, threading.get_ident())
        with self._lock:
            self._ring.append(rec)
            tot = self._totals.get(name)
            if tot is None:
                self._totals[name] = [1, end - start]
            else:
                tot[0] += 1
                tot[1] += end - start

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def spans(self, name: Optional[str] = None) -> List[Span]:
        """The ring's spans, oldest first (those called ``name``)."""
        with self._lock:
            out = list(self._ring)
        return out if name is None else [s for s in out if s.name == name]

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """``{span name: (count, seconds)}`` since the start — unlike the
        ring these never forget, so differences over a log window are
        exact."""
        with self._lock:
            return {k: (int(n), s) for k, (n, s) in self._totals.items()}

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)


#: The program's recorder.  Module state on purpose: the boundaries that
#: record (loader threads, the step closure, the sampler) share no object
#: a recorder could ride on, and readers (``Trainer``'s log line, the
#: benchmark's ``program_span`` reader) need one place to look.
RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count

#: The spans recorded while the program is built, before its first
#: dispatch: the compile clock's and the build steps' of :func:`spanned`.
#: ``benchmark/readers/setup_split.py`` reads only names listed here.
SETUP_SPANS = ("compile.trace", "compile.lower", "compile.backend",
               "compile.cache_read", "compile.fresh", "init.model",
               "init.params", "init.state", "init.train_step",
               "init.sampler")


def spanned(name: str):
    """Decorator: each call of the function is one span ``name`` of
    :data:`SETUP_SPANS`, on the caller's thread.  For build steps, called
    once per process or per program; a call under a trace
    (``jax.eval_shape``) is timed at trace time, once."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return timed
    return wrap


# --------------------------------------------------------------------------
# the compile clock
# --------------------------------------------------------------------------

class CompileClock:
    """JAX's own compile-pipeline durations as ``compile.*`` spans of a
    recorder, each kept with its arrival time and the function's name as
    ``id``, so a phase's wall time splits into compile and run without
    guessing: ``compile.trace``, ``compile.lower``, ``compile.backend``
    and, inside each backend span, either ``compile.cache_read`` (the
    persistent cache's read, deserialisation and load, from JAX's
    retrieval-time event) or ``compile.fresh`` (the whole backend span of
    a program compiled rather than read).  What is left of a backend span
    on a read is the cache key and dispatch.  One per process (JAX keeps
    listeners for good): :data:`COMPILE_CLOCK`.
    """

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
        "/jax/core/compile/backend_compile_duration": "compile.backend",
    }
    #: fires in ``compile_or_get_cached`` on a hit alone, on the thread
    #: that compiles, before that thread's backend event ends
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self, recorder: Recorder):
        self._rec = recorder
        # the read seen on this thread since its last backend event
        self._local = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, secs: float, **kw) -> None:
        now = time.perf_counter()
        if event == self.CACHE_READ:
            self._local.read = (now - secs, now)
            return
        name = self.EVENTS.get(event)
        if name is None:
            return
        fun = kw.get("fun_name")
        start = now - secs
        self._rec.add(name, start, now, id=fun)
        if name != "compile.backend":
            return
        read = getattr(self._local, "read", None)
        self._local.read = None
        if read is None or read[1] < start:   # none, or a stray older one
            self._rec.add("compile.fresh", start, now, id=fun)
        else:       # two clocks: held inside the span it is part of
            self._rec.add("compile.cache_read", max(read[0], start),
                          read[1], id=fun)

    def snapshot(self) -> dict:
        """Seconds in each stage since the clock started, and how many
        programs: plain sums of JAX's events (a jitted function traced
        inside another's trace is in both; :func:`union_seconds` over the
        spans counts wall time once)."""
        tot = self._rec.totals()
        zero = (0, 0.0)
        return {"trace_s": tot.get("compile.trace", zero)[1],
                "lower_s": tot.get("compile.lower", zero)[1],
                "backend_compile_s": tot.get("compile.backend", zero)[1],
                "backend_compiles": tot.get("compile.backend", zero)[0],
                "cache_hits": tot.get("compile.cache_read", zero)[0]}


#: Registered once, at import: every module of the program that traces or
#: dispatches imports this one first, so the clock sees all of the
#: program's compilation.
COMPILE_CLOCK = CompileClock(RECORDER)


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps once."""
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += e - max(s, hi)
            hi = e
    return total


# --------------------------------------------------------------------------
# from a profiler trace to seconds by class
# --------------------------------------------------------------------------
#
# ``jax.profiler.ProfileData`` shows an event's own stats only; an op's
# ``op_name`` is a stat (``tf_op``) of its *event metadata* on the TPU
# (probe on the chip, PR 24), and on the CPU the op has only ``hlo_op`` /
# ``hlo_module``, to be joined with the HLO protos the trace carries in
# its ``/host:metadata`` plane.  So the ``.xplane.pb`` is read here by its
# wire format (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto;
# field numbers below), with nothing but the standard library.


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` over one serialized message;
    a length-delimited value is a memoryview, a varint an int.  The three
    varints are decoded in place: this loop reads every byte of a trace of
    tens of megabytes, and a call per varint doubles its time."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        wt = key & 7
        if wt == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield key >> 3, wt, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """One XStat -> ``(name, value)``; a ``ref_value`` is looked up."""
    key, val = None, None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            val = v
        elif num == 5:
            val = _text(v)
        elif num == 6:
            val = v                                  # bytes, kept as a view
        elif num == 7:
            val = stat_names.get(v, "")
    return stat_names.get(key, ""), val


def _map_entry(buf) -> Tuple[int, object]:
    key = val = None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _hlo_op_names(hlo_proto) -> Tuple[str, Dict[str, str]]:
    """HloProto -> ``(module name, {instruction name: op_name})``."""
    module, names = "", {}
    for num, _, mod in _fields(hlo_proto):
        if num != 1:                                 # HloProto.hlo_module
            continue
        for n2, _, v2 in _fields(mod):
            if n2 == 1:
                module = _text(v2)
            elif n2 == 3:                            # computations
                for n3, _, inst in _fields(v2):
                    if n3 != 2:                      # instructions
                        continue
                    iname = op_name = ""
                    for n4, _, v4 in _fields(inst):
                        if n4 == 1:
                            iname = _text(v4)
                        elif n4 == 7:                # OpMetadata
                            for n5, _, v5 in _fields(v4):
                                if n5 == 2:
                                    op_name = _text(v5)
                    names[iname] = op_name
    return module, names


def _plane_parts(plane) -> Tuple[str, list, dict, Dict[int, str]]:
    """XPlane -> ``(name, [XLine], {id: XEventMetadata}, {id: stat
    name})``, the messages still serialized."""
    pname, lines, emeta, stat_names = "", [], {}, {}
    for num, _, v in _fields(plane):
        if num == 2:
            pname = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            k, md = _map_entry(v)
            emeta[k] = md
        elif num == 5:
            k, md = _map_entry(v)
            stat_names[k] = next(
                (_text(x) for n, _, x in _fields(md) if n == 2), "")
    return pname, lines, emeta, stat_names


DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: XLA ops that enclose others (their ``flops`` are their bodies')
_ENCLOSING = ("while", "conditional", "call")


def load_xplane(path: str) -> dict:
    """The plain form the reduction works on::

        {"device": {plane: [[name, start_ns, dur_ns, op_name, flops,
                             bytes], ...]},
         "host": [[span name, start_ns, dur_ns], ...]}

    Device events are the TPU planes' ``XLA Ops`` lines; where the trace
    has none (a CPU capture) they are the host threads' events that carry
    an ``hlo_op``, named through the trace's own HLO protos.  Host events
    are this module's ``d3d:`` spans."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    device: Dict[str, list] = {}
    host: List[list] = []
    cpu_ops: List[list] = []
    hlo: Dict[str, Dict[str, str]] = {}
    for num, _, plane in _fields(data):
        if num != 1:
            continue
        pname, lines, emeta, stat_names = _plane_parts(plane)
        is_device = pname.startswith(DEVICE_PLANE)
        if not (is_device or pname.startswith("/host:")):
            continue
        meta: Dict[int, tuple] = {}    # id -> (name, op_name, flops, bytes)
        for k, buf in emeta.items():
            name, st = "", {}
            for n3, _, v3 in _fields(buf):
                if n3 == 2:
                    name = _text(v3)
                elif n3 == 5:
                    sk, sv = _stat(v3, stat_names)
                    st[sk] = sv
            if pname == "/host:metadata":
                if "Hlo Proto" in st:
                    module, names = _hlo_op_names(st["Hlo Proto"])
                    hlo.setdefault(module, {}).update(names)
                continue
            enclosing = st.get("hlo_category") in _ENCLOSING
            meta[k] = (name, st.get("tf_op", ""),
                       0 if enclosing else int(st.get("flops", 0) or 0),
                       0 if enclosing else int(st.get("bytes_accessed", 0)
                                               or 0))
        for line in lines:
            lname, t0_ns, events = "", 0, []
            for n3, _, v3 in _fields(line):
                if n3 == 2:
                    lname = _text(v3)
                elif n3 == 3:
                    t0_ns = v3
                elif n3 == 4:
                    events.append(v3)
            if is_device and lname != OPS_LINE:
                continue
            for ev in events:
                mid = off_ps = dur_ps = 0
                st = {}
                for n4, _, v4 in _fields(ev):
                    if n4 == 1:
                        mid = v4
                    elif n4 == 2:
                        off_ps = v4
                    elif n4 == 3:
                        dur_ps = v4
                    elif n4 == 4 and not is_device:
                        sk, sv = _stat(v4, stat_names)
                        st[sk] = sv
                name, op_name, flops, nbytes = meta.get(mid, ("", "", 0, 0))
                start, dur = t0_ns + off_ps / 1e3, dur_ps / 1e3
                if is_device:
                    device.setdefault(pname, []).append(
                        [name, start, dur, op_name, flops, nbytes])
                elif name.startswith(SPAN_PREFIX):
                    host.append([name[len(SPAN_PREFIX):], start, dur])
                elif "hlo_op" in st:
                    cpu_ops.append([st["hlo_op"], start, dur,
                                    st.get("hlo_module", ""), 0, 0])
    if not device and cpu_ops:
        for ev in cpu_ops:
            ev[3] = hlo.get(ev[3], {}).get(ev[0], "")
        device["/host:CPU"] = cpu_ops
    return {"device": device, "host": host}


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


_TAG = re.compile(re.escape(SCOPE_PREFIX) + r"(\w+)")


def op_class(op_name: str) -> Tuple[Optional[str], bool]:
    """``(innermost d3d. tag or None, is backward)`` of an ``op_name``
    such as ``jit(step_fn)/.../transpose(jvp(d3d.conv))/conv_general``."""
    tags = _TAG.findall(op_name)
    return (tags[-1] if tags else None), "transpose(" in op_name


def _short(name: str) -> str:
    """``%fusion.12 = bf16[32,64]{...} fusion(...)`` -> ``fusion.12
    bf16[32,64]``."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()[:120]


def reduce_scopes(trace: dict, top: int = 20) -> dict:
    """Device seconds by class from a trace in :func:`load_xplane`'s plain
    form (the arithmetic of :func:`scope_seconds`).

    Own time: an op that encloses others (a ``while`` around its body)
    counts without what it encloses.  Each op's own time goes to the
    innermost ``d3d.`` tag of its ``op_name``, forward or backward by the
    ``transpose(`` component; an op with no tag is ``unscoped`` and the
    ``top`` of those are listed by op.  The window is from the first
    device op's start to the last one's end; each idle gap between ops is
    given to the innermost ``d3d:`` host span over its middle, or to
    ``unattributed``.  With several device planes the fullest is read.
    ``flops`` / ``bytes`` per class are XLA's own cost model's, summed
    over the ops that ran."""
    planes = {n: evs for n, evs in trace["device"].items() if evs}
    out = {"by_class": {}, "unscoped_s": 0.0, "unscoped_top": [],
           "idle_by_span": {}, "busy_s": 0.0, "window_s": 0.0}
    if not planes:
        return out
    events = sorted(planes[max(planes, key=lambda n: len(planes[n]))],
                    key=lambda ev: (ev[1], -ev[2]))
    by_class: Dict[str, dict] = {}
    unscoped: Dict[str, float] = {}
    stack: List[list] = []                   # [event, end, own_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            ev, _, own = stack.pop()
            own = max(own, 0.0) / 1e9
            tag, bwd = op_class(ev[3])
            if tag is None:
                out["unscoped_s"] += own
                key = _short(ev[0])
                unscoped[key] = unscoped.get(key, 0.0) + own
                continue
            c = by_class.setdefault(tag, {"fwd_s": 0.0, "bwd_s": 0.0,
                                          "flops": 0, "bytes": 0})
            c["bwd_s" if bwd else "fwd_s"] += own
            c["flops"] += ev[4]
            c["bytes"] += ev[5]

    for ev in events:
        start, dur = ev[1], ev[2]
        close(start)
        while stack and start + dur > stack[-1][1]:  # overlaps, not inside
            close(stack[-1][1])
        if stack:
            stack[-1][2] -= dur
        stack.append([ev, start + dur, dur])
    close(float("inf"))

    merged: List[List[float]] = []
    for ev in events:
        s, e = ev[1], ev[1] + ev[2]
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    spans = trace["host"]
    idle: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = 0.5 * (e0 + s1)
        owner, best = "unattributed", None
        for name, s, d in spans:                     # innermost covering span
            if s <= mid <= s + d and (best is None or d < best):
                owner, best = name, d
        idle[owner] = idle.get(owner, 0.0) + (s1 - e0) / 1e9
    out["by_class"] = by_class
    out["unscoped_top"] = [[k, v] for k, v in sorted(
        unscoped.items(), key=lambda kv: -kv[1])[:top]]
    out["idle_by_span"] = idle
    out["busy_s"] = sum(e - s for s, e in merged) / 1e9
    out["window_s"] = (merged[-1][1] - merged[0][0]) / 1e9 if merged else 0.0
    return out


def scope_seconds(xplane_path: str) -> dict:
    """``{"by_class": {tag: {"fwd_s", "bwd_s", "flops", "bytes"}},
    "unscoped_s", "unscoped_top": [[op, s], ...], "idle_by_span": {name:
    s}, "busy_s", "window_s"}`` of one ``.xplane.pb``: see
    :func:`reduce_scopes`."""
    return reduce_scopes(load_xplane(xplane_path))


@contextlib.contextmanager
def profile_window(logdir: str, enabled: bool = True) -> Iterator[None]:
    """Trace everything inside the ``with`` body to ``logdir`` and reduce
    it to ``<logdir>/by_scope.json`` (:func:`scope_seconds`): the one
    capture path (``train_cli --profile_steps START:STOP`` ends here).

    Use around a few already-compiled steps (never the first — tracing a
    compile produces a useless giant trace) and block on the last result
    inside the body, or the window closes on the dispatch::

        with profile_window(os.path.join(workdir, "profile")):
            for _ in range(3):
                state, metrics = step_fn(state, batch, rng)
            jax.block_until_ready(metrics["loss"])
    """
    if not enabled:
        yield
        return
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # d3d: spans only, not every Python call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    # only where the body ended well: a failed reduction must not hide
    # the body's own exception
    with open(os.path.join(logdir, "by_scope.json"), "w") as f:
        json.dump(scope_seconds(find_xplane(logdir)), f, indent=1)


class StepTimer:
    """Wall-clock per-step timing.

    ``tick()`` marks a step boundary; ``summary()`` reports mean / p50 /
    p95 / max milliseconds over the retained window.  Pure host-side —
    call ``jax.block_until_ready`` yourself at window edges if you want
    device-inclusive times (the trainer does, at log boundaries).
    """

    def __init__(self, window: int = 512):
        self._window = window
        self._times: List[float] = []
        self._last: Optional[float] = None

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._times.append(now - self._last)
            if len(self._times) > self._window:
                self._times = self._times[-self._window:]
        self._last = now

    def reset(self) -> None:
        self._times.clear()
        self._last = None

    def summary(self) -> dict:
        if not self._times:
            return {}
        ms = np.asarray(self._times) * 1e3
        return {
            "step_ms_mean": float(ms.mean()),
            "step_ms_p50": float(np.percentile(ms, 50)),
            "step_ms_p95": float(np.percentile(ms, 95)),
            "step_ms_max": float(ms.max()),
        }
