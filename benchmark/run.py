"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name: ``configs/<config>.json``,
``traffic/<traffic>.json`` (which names its driver under ``drivers/``),
``metrics/<metric>.json`` (which names its reader under ``readers/``).  A
later cell or metric adds files and ``BENCHMARK.json`` entries; nothing
here is edited.

Order of a run: set-up (weights from the seed on the device, every shape
warmed, compilation counted) -> the measured window -> peak memory read
-> the program's state freed -> the plain reference and the comparison
that decides ``correct`` -> (``--trace 1``) the trace reduced and the
per-layer readers -> the result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from contextlib import contextmanager  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


class Spans:
    """Named host-clock spans, kept in memory; each is also a
    ``TraceAnnotation`` (``bench:<name>``) so that a profiler trace shows
    what the host was doing while the device idled."""

    def __init__(self):
        self.data: dict = {}

    @contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield
        self.data.setdefault(name, []).append((t0, time.perf_counter()))

    def add(self, name: str, start: float, end: float) -> None:
        self.data.setdefault(name, []).append((start, end))


class CompileCount:
    """Counts the backend compilations (persistent-cache reads included)
    that JAX's monitoring reports."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


class Tracer:
    """Profiles ``seconds`` of the window from a thread of its own, so that
    a call that lasts longer than a trace should is traced in part."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.window_s = 0.0
        self._thread = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:trace_window"):
            time.sleep(self.seconds)
        self.window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()

    def finish(self) -> dict:
        from benchmark import trace_reduce

        self._thread.join()
        trace = trace_reduce.load_xplane(trace_reduce.find_xplane(TRACE_DIR))
        out = trace_reduce.reduce(trace, self.window_s)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return out


def device_peak_bytes(device) -> int:
    """Peak bytes on one chip, read when the window has closed and its
    state is still live.  The TPU runtime keeps two books: arrays
    (``bytes_in_use``) and what a running program reserves for its
    temporaries (``bytes_reserved``), each with a peak of its own.  The
    window's peak is its live arrays plus the largest reservation; set-up
    may have held more arrays for a moment (``peak_bytes_in_use``)."""
    stats = device.memory_stats() or {}
    return int(max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0)))


def configure_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, with
    no size cap (the chip machine's own cap of 192 MiB evicts the train
    step's entry), for every program however small.  Called before JAX
    is first imported: JAX takes the directory from the environment then
    (the repo's rule, tests/test_bringup.py: only
    runtime/compile_cache.py sets it through ``jax.config``)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(CACHE_DIR, exist_ok=True)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_cell(name: str, rehearse: bool = False) -> tuple:
    """``BENCHMARK.json``, the cell's entry, its configuration and its
    traffic mix; ``rehearse`` swaps in the tiny configuration and the
    mix's ``rehearsal`` entries."""
    from benchmark import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    mix = traffic.load(cell["traffic"])
    path = os.path.join(ROOT, files[cell["config"]])
    if rehearse:
        path = os.path.join(HERE, "configs", "tiny.json")
        mix = dict(mix, **mix.get("rehearsal", {}))
    with open(path) as f:
        config = json.load(f)
    return bench, cell, config, mix


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def run_cell(driver, bench: dict, cell: dict, config: dict, mix: dict, *,
             seconds: float, trace: int, peak: dict, devices,
             rehearsal: bool = False) -> tuple:
    """Everything of a run after the look for a chip: set-up, window,
    peak memory, release, the comparison, the per-layer readers.  Returns
    the result line's object and the numbers compared."""
    compiles = CompileCount()
    spans = driver.spans
    driver.setup()
    compiles_before, compile_s_setup = compiles.n, compiles.seconds
    tracer = None
    if trace:
        tracer = Tracer(min(float(mix.get("trace_seconds", 5)), seconds))
    setup_s = time.perf_counter() - T_PROCESS
    window = driver.measure(seconds,
                            on_start=tracer.start if tracer else None)
    compiles_in_window = compiles.n - compiles_before

    dev = devices[0]
    memory_peak = max(device_peak_bytes(d) for d in devices[:cell["chips"]])
    reduced = tracer.finish() if tracer else None
    driver.release()

    t0 = time.perf_counter()
    numbers = driver.verify()
    verify_s = time.perf_counter() - t0
    numbers.append(("compiles_in_window", compiles_in_window, 0))
    numbers.append(("failed", window["failed"], 0))
    correct = all(lim is not None and v == v and v <= lim
                  for _, v, lim in numbers)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": memory_peak}
    metrics = {}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"spans": spans.data, "window": window, "trace": reduced,
               "peak": peak, "chips": cell["chips"], "config": config,
               "mix": mix}
        for m in metrics_of(bench, "per_layer", cell["name"]):
            with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
                spec = json.load(f)
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["setup"] = {"setup_s": setup_s, "compiles": compiles_before,
                       "compile_s": compile_s_setup,
                       "phases": {k[len("setup."):]: sum(e - s for s, e in v)
                                  for k, v in spans.data.items()
                                  if k.startswith("setup.")},
                       "window_s": window["window_s"],
                       "verify_s": verify_s}
    result["notes"] = driver.notes
    result["rehearsal"] = rehearsal
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in numbers}     # comes last
    return result, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny config: checks control "
                         "flow, reports no device number")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "diff3d_tpu")):
        print("benchmark: the program (diff3d_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 3
    configure_cache()
    bench, cell, config, mix = load_cell(args.workload, args.rehearse)

    import jax

    devices = jax.devices()
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    dev = devices[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"benchmark: no accelerator (platform {dev.platform!r})",
                  file=sys.stderr)
            return 3
        if len(devices) < cell["chips"]:
            print(f"benchmark: {cell['name']} needs {cell['chips']} chips, "
                  f"found {len(devices)}", file=sys.stderr)
            return 3
        if dev.device_kind not in peaks:
            print(f"benchmark: no peaks for device kind "
                  f"{dev.device_kind!r} in peaks.json", file=sys.stderr)
            return 3
    peak = peaks.get(dev.device_kind, {"flops_per_s": float("nan")})

    driver_mod = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    driver = driver_mod.Driver(config=config, mix=mix, seed=args.seed,
                               chips=cell["chips"], spans=Spans())
    result, numbers = run_cell(driver, bench, cell, config, mix,
                               seconds=args.seconds, trace=args.trace,
                               peak=peak, devices=devices,
                               rehearsal=args.rehearse)
    sys.stdout.flush()
    print(f"notes {json.dumps(driver.notes)}", file=sys.stderr)
    for name, v, lim in numbers:
        print(f"compared {name} = {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
