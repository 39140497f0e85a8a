"""The ``program_span`` reader: its arithmetic on spans planted in the
program's recorder, and through ``run.py --rehearse --trace 1`` on both
cells, where the seven metrics that read the program's own spans print in
the cells that list them and no file of the benchmark is touched."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import program_span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

IN_CELL = {
    "srn64_train": {"train_loader_batch_p50_ms", "train_h2d_put_p50_ms",
                    "train_prefetch_wait_pct", "train_dispatch_p50_ms",
                    "setup_trace_lower_s", "setup_backend_compile_s"},
    "srn64_sample": {"sample_host_ms_per_call", "setup_trace_lower_s",
                     "setup_backend_compile_s"},
}


def test_statistics_of_planted_spans():
    from diff3d_tpu.utils.profiling import RECORDER

    base = 1.0e9          # far from any real perf_counter reading
    ctx = {"spans": {"input_wait": [(base + 10.0, base + 10.1)],
                     "step": [(base + 10.0, base + 20.0)]}}
    add = RECORDER.add
    add("t.compile", base + 1.0, base + 4.0)
    add("t.compile", base + 2.0, base + 3.0)         # inside the first
    add("t.compile", base + 8.0, base + 9.0)
    add("t.compile", base + 12.0, base + 13.0)       # in the window: not set-up
    for k, (s, d) in enumerate([(10.5, 1.0), (12.0, 3.0), (16.0, 2.0)]):
        add("t.work", base + s, base + s + d, id=k // 2)
    add("t.work", base + 19.5, base + 21.0, id=9)    # ends after the window
    read = program_span.read
    assert read(ctx, ["t.compile"], "sum_until") == pytest.approx(4.0)
    assert read(ctx, ["t.work"], "p50", 1000.0) == pytest.approx(2000.0)
    assert read(ctx, ["t.work"], "mean") == pytest.approx(2.0)
    assert read(ctx, ["t.work"], "share") == pytest.approx(60.0)
    assert read(ctx, ["t.work"], "per_id") == pytest.approx(3.0)
    assert read(ctx, ["t.none"], "p50") is None
    assert read({"spans": {}}, ["t.work"], "p50") is None
    with pytest.raises(ValueError):
        read(ctx, ["t.work"], "p99")


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    from diff3d_tpu.utils import profiling

    monkeypatch.delattr(profiling, "RECORDER")
    ctx = {"spans": {"call": [(1.0, 2.0)]}}
    assert program_span.read(ctx, ["sampler.stage"], "mean") is None


@pytest.mark.parametrize("cell", sorted(IN_CELL))
def test_the_seven_metrics_print_in_their_cells(cell):
    bench_dir = os.path.join(ROOT, "benchmark")
    files = [os.path.join(d, f) for d, _, fs in os.walk(bench_dir)
             for f in fs if "__pycache__" not in d]
    before = {p: open(p, "rb").read() for p in files}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if m["source"] == "program_span" and "workloads" in m
              and cell in m["workloads"] and m["name"] in IN_CELL[cell]}
    assert listed == IN_CELL[cell]

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    got = line["metrics"]
    for name in IN_CELL[cell]:
        assert got[name]["value"] >= 0, name
    others = set().union(*IN_CELL.values()) - IN_CELL[cell]
    assert not others & set(got)
    if cell == "srn64_train":
        # the program's wait and the benchmark's span around it agree
        assert got["train_prefetch_wait_pct"]["value"] == pytest.approx(
            got["train_input_wait_pct"]["value"], abs=0.5)
    # what the compile clock saw before the window is the harness's count
    assert got["setup_backend_compile_s"]["value"] <= (
        line["setup"]["compile_s"] + 1e-6)
    assert line["compared"]["compiles_in_window"]["value"] == 0
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
