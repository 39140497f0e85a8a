"""What decides ``correct``, shown to fail: the rest of a run driven
(``run.run_cell``, past the look for a chip) with the timed path broken
underneath, once for each fault a cell can have, and the control (the
reference in fp8 in the program's place).  At a size a CPU test holds:
32 px, 32 channels, the cells' own traffic shrunk by their ``rehearsal``
entries, the cells' own limits."""


import jax
import pytest

from benchmark import run as harness

CELLS = {"train": "srn64_train", "sample": "srn64_sample"}


def build(kind, seed, fault=None):
    import importlib
    bench, cell, config, mix = harness.load_cell(CELLS[kind], rehearse=True)
    config = dict(config, H=32, W=32, ch=32)
    mod = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    driver = mod.Driver(config=config, mix=mix, seed=seed,
                        chips=1, spans=harness.Spans())
    driver.fault = fault
    return driver, bench, cell, config, mix


def drive(kind, seed, fault=None):
    driver, bench, cell, config, mix = build(kind, seed, fault)
    result, _ = harness.run_cell(
        driver, bench, cell, config, mix, seconds=0.5, trace=0,
        peak={"flops_per_s": float("nan")}, devices=jax.devices())
    return result


@pytest.mark.parametrize("kind", ["train", "sample"])
def test_the_program_is_correct_at_test_size(kind):
    result = drive(kind, seed=2147483659)
    assert result["correct"], result["compared"]
    assert all(v["limit"] is not None for v in result["compared"].values())


@pytest.mark.parametrize("kind,fault", [
    ("train", "state_unchanged"), ("train", "half_batch"),
    ("train", "loss_altered"), ("sample", "answer_altered")])
def test_a_broken_timed_path_is_not_correct(kind, fault):
    result = drive(kind, seed=77, fault=fault)
    assert not result["correct"], result["compared"]
    over = [k for k, v in result["compared"].items()
            if v["value"] > v["limit"]]
    assert over and "compiles_in_window" not in over, result["compared"]


def test_the_control_fails_training():
    driver, *_ = build("train", seed=5)
    driver.setup()
    numbers = driver.compare(driver.reference("fp8"), driver.reference())
    driver.release()
    assert any(v > lim for _, v, lim in numbers), numbers


def test_the_control_fails_sampling():
    driver, *_ = build("sample", seed=5)
    driver.setup()
    driver.measure(0.1)
    call, obj, view = driver.picks()[0]
    gap = driver.image_gap(driver.reference_view(call, obj, view, "fp8"),
                           driver.reference_view(call, obj, view))
    driver.release()
    assert gap > driver.mix["limits"]["image_gap"], gap
