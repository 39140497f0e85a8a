"""bench.py robustness layer: every exit prints a parseable JSON record,
and every failure — no backend, a platform that is not ``tpu``, a phase
that recorded an error — exits non-zero instead of passing for a run."""

import sys

sys.path.insert(0, "/root/repo")

import jax
import pytest

import bench


def test_sampler_steps_sweep_structure():
    """The few-step sweep record: one DDIM point per schedule, speedups
    relative to the full-grid point, and the 16-step schedule showing at
    least 8x fewer model calls per view than the 256-step one (it is
    exactly 16x; the guard leaves slack only for future schedule
    changes)."""
    calls = []

    def fake_bench(config, n_views, object_batch, use_mesh,
                   sampler_kind, steps, kernels=None):
        calls.append((config, sampler_kind, steps))
        # Per-view time shrinking sub-linearly with the schedule, like
        # real hardware (per-step overhead doesn't vanish).
        return 0.004 * steps + 0.05, 1.0, 3

    rec = bench._sampler_steps_sweep("srn64", bench_fn=fake_bench)
    assert rec["metric"] == "sampler_steps_sweep_srn64"
    assert [c[2] for c in calls] == [256, 64, 16, 8]
    assert all(c[1] == "ddim" for c in calls)

    points = {p["steps"]: p for p in rec["points"]}
    assert set(points) == {256, 64, 16, 8}
    assert points[256]["speedup_vs_256"] == 1.0
    assert points[16]["speedup_vs_256"] > points[64]["speedup_vs_256"] > 1
    # The acceptance pin: 16-step DDIM costs >= 8x fewer model calls.
    assert (points[256]["model_calls_per_view"]
            >= 8 * points[16]["model_calls_per_view"])
    for p in rec["points"]:
        assert p["sampler"] == "ddim"
        assert p["sec_per_view"] > 0 and p["effective_views"] == 3


def test_cascade_sweep_structure():
    """The cascade record: draft/refine/end-to-end s/view against the
    matched single-pass sampler, with the preview speedup (single-pass
    over draft latency) being the progressive-preview win and the plan
    spec pinned next to the numbers."""
    calls = []

    def fake_bench(config, n_views):
        calls.append((config, n_views))
        # draft fast, refine mid, single-pass slowest — the shape a
        # working cascade must have.
        return ("draft=64:ddim:8,refine=128:ancestral:64@t0.5",
                0.2, 1.0, 4.0, n_views - 1)

    rec = bench._cascade_sweep("srn128", n_views=3, bench_fn=fake_bench)
    assert rec["metric"] == "cascade_sweep_srn128"
    assert calls == [("srn128", 3)]
    assert rec["plan"] == "draft=64:ddim:8,refine=128:ancestral:64@t0.5"
    assert rec["effective_views"] == 2
    assert rec["draft_sec_per_view"] == 0.1
    assert rec["refine_sec_per_view"] == 0.5
    assert rec["end_to_end_sec_per_view"] == 0.6
    assert rec["single_pass_sec_per_view"] == 2.0
    # End-to-end still beats single-pass, and the draft preview beats
    # it by much more — the whole point of the cascade.
    assert rec["speedup_vs_single_pass"] > 1
    assert rec["preview_speedup"] > rec["speedup_vs_single_pass"]
    assert rec["unit"] == "s/view" and rec["vs_baseline"] is None


def test_cascade_sweep_in_phase_sequence():
    """Cascade sweep and kernels A/B are real phases: a round dying
    inside either must report it as ``phase_reached`` in the partial
    record, in run order (cascade, then the A/B, then complete)."""
    seq = bench._PHASE_SEQUENCE
    assert "cascade_sweep" in seq
    assert seq.index("kernels_ab") == seq.index("cascade_sweep") + 1
    assert seq.index("kernels_ab") == seq.index("complete") - 1


def test_kernels_ab_structure():
    """The kernel A/B record: one variant per requested backend, timed
    by the SAME train/sampler benches with only ``kernels`` varying,
    speedups relative to variant 0, and per-variant error notes instead
    of a voided record when one backend fails."""
    calls = []

    def fake_train(configs, n_steps, config, kernels=None):
        calls.append(("train", config, kernels, tuple(configs)))
        eps = {"xla": 100.0, "pallas": 125.0}[kernels]
        return eps, configs[0][0], configs[0][1], {"step_ms_median": 9.0}

    def fake_sampler(config, n_views, kernels=None):
        calls.append(("sampler", config, kernels))
        return {"xla": 2.0, "pallas": 1.6}[kernels], 6.0, 3

    rec = bench._kernels_ab(["xla", "pallas"], configs=[(64, 1)],
                            n_steps=5, train_fn=fake_train,
                            sampler_fn=fake_sampler)
    assert rec["metric"] == "kernels_ab_srn64"
    assert rec["dimension"] == "kernels"
    assert [c[2] for c in calls] == ["xla", "xla", "pallas", "pallas"]
    assert all(c[3] == ((64, 1),) for c in calls if c[0] == "train")
    xla, pallas = rec["variants"]
    assert xla["kernels"] == "xla" and pallas["kernels"] == "pallas"
    assert xla["train_examples_per_sec"] == 100.0
    assert pallas["train_speedup_vs_xla"] == 1.25
    assert pallas["sampler_speedup_vs_xla"] == 1.25
    assert "train_speedup_vs_xla" not in xla    # base carries no ratio


def test_kernels_ab_survives_one_variant_failing():
    def fake_train(configs, n_steps, config, kernels=None):
        if kernels == "pallas":
            raise RuntimeError("RESOURCE_EXHAUSTED: vmem")
        return 100.0, 64, 1, {"step_ms_median": 9.0}

    def fake_sampler(config, n_views, kernels=None):
        return 2.0, 6.0, 3

    rec = bench._kernels_ab(["xla", "pallas"], train_fn=fake_train,
                            sampler_fn=fake_sampler)
    xla, pallas = rec["variants"]
    assert xla["train_examples_per_sec"] == 100.0
    assert "RESOURCE_EXHAUSTED" in pallas["train_error"]
    assert "train_speedup_vs_xla" not in pallas
    assert pallas["sampler_speedup_vs_xla"] == 1.0


def test_main_rejects_unknown_kernel_backend(capsys):
    import pytest as _pytest

    with _pytest.raises(SystemExit):
        bench.main(["--kernels", "cuda"])


def test_partial_record_stamps_kernels():
    bench._KERNELS["requested"] = ["xla", "pallas"]
    try:
        rec = bench._partial_record("test")
        assert rec["kernels"] == ["xla", "pallas"]
    finally:
        bench._KERNELS["requested"] = ["xla"]


def _last_record(capsys):
    import json

    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_main_emits_parseable_json_when_backend_never_comes_up(
        monkeypatch, capsys):
    def always_down():
        raise RuntimeError("UNAVAILABLE: no backend")

    monkeypatch.setattr(jax, "devices", always_down)
    assert bench.main() != 0
    rec = _last_record(capsys)          # MUST parse
    assert rec["value"] is None and "UNAVAILABLE" in rec["error"]
    assert rec["phase_reached"] == "start"


def test_main_refuses_a_platform_that_is_not_tpu(capsys):
    """The test process is CPU-pinned: bench.py must say so and exit
    non-zero — never bench a tiny batch on the CPU under a device
    metric's name."""
    assert jax.devices()[0].platform == "cpu"
    assert bench.main() != 0
    rec = _last_record(capsys)
    assert rec["value"] is None and "not 'tpu'" in rec["error"]


@pytest.mark.parametrize("payload,paths", [
    ({"value": 1.0, "windows": {"comms": {"collectives": 3}}}, []),
    ({"value": 1.0, "srn128": {"error": "RESOURCE_EXHAUSTED"}},
     ["srn128.error"]),
    ({"value": 1.0, "windows": {"comms": {"error": "lowering failed"}}},
     ["windows.comms.error"]),
    ({"kernels_ab": {"variants": [{"kernels": "xla"},
                                  {"train_error": "vmem"}]}},
     ["kernels_ab.variants[1].train_error"]),
])
def test_recorded_errors_finds_every_failed_phase(payload, paths):
    """What makes a completed round exit non-zero: any ``error`` /
    ``*_error`` note anywhere in the record."""
    assert bench._recorded_errors(payload) == paths
