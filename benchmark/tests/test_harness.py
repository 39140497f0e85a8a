"""A configuration, a traffic mix, a per-layer metric and its reader are
added as new files and new ``BENCHMARK.json`` entries alone, and the
harness runs the new cell; and the harness refuses to run where it must.
Drives ``run.py --rehearse`` on the CPU in a copy of the benchmark."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=900)


@pytest.fixture()
def checkout(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "diff3d_tpu"), tmp_path / "diff3d_tpu")
    return tmp_path


def test_a_new_cell_config_and_metric_are_new_files_only(checkout):
    b = checkout / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "srn64.json").read_text())
    (b / "configs" / "newcfg.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "train_b128_a8.json").read_text())
    (b / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (b / "metrics" / "new_dispatch_ms.json").write_text(json.dumps(
        {"reader": "new_reader", "args": {"span": "dispatch"}}))
    (b / "readers" / "new_reader.py").write_text(
        "def read(ctx, span):\n"
        "    d = [e - s for s, e in ctx['spans'].get(span, [])]\n"
        "    return 1000.0 * sum(d) / len(d) if d else None\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "newcfg", "source": "test",
                             "file": "benchmark/configs/newcfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new_cell", "config": "newcfg",
                               "traffic": "newmix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_examples_per_s":
            m["workloads"].append("new_cell")
    bench["per_layer"].append(
        {"name": "new_dispatch_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "trainer",
         "moves": "train_examples_per_s", "workloads": ["new_cell"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    r = run(checkout, "--workload", "new_cell", "--seed", "2147483659",
            "--seconds", "1", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["metrics"]["new_dispatch_ms"]["value"] > 0
    assert "train_mfu_pct" not in line["metrics"]   # lists other cells only
    assert list(line)[-1] == "compared"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_no_accelerator_is_an_error_not_a_fallback(checkout):
    r = run(checkout, "--workload", "srn64_train", "--seed", "1",
            "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = run(tmp_path, "--workload", "srn64_train", "--seed", "1",
            "--seconds", "1", "--trace", "0", "--rehearse")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
