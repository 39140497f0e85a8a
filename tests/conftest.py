"""Test harness: force JAX onto CPU with 8 virtual devices, so
multi-device mesh tests run anywhere (the TPU-world equivalent of a fake
distributed backend — the reference has none, SURVEY.md §4).

The platform is pinned before any backend is initialised (backends are
created lazily, so XLA_FLAGS set here is still honoured), whatever the
caller's environment says: the suite must never open a real chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compile cache: the suite's cost is XLA CPU compiles of the
# (tiny) X-UNet variants; cached, a full run drops from ~10min to ~1min.
# Placed by the program's own rule: JAX_COMPILATION_CACHE_DIR where it is
# set, else the checkout's fixed directory.
from diff3d_tpu.runtime import configure_compile_cache  # noqa: E402

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh, got "
    f"{jax.devices()[0].platform}")
assert len(jax.devices()) == 8, len(jax.devices())

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run the slow end-to-end tests (test_cli, test_multiprocess)")


def _env_on(name):
    return os.environ.get(name, "").lower() in ("1", "true", "yes")


def pytest_collection_modifyitems(config, items):
    """Keep the default ``pytest -q`` under ~5 min: the two end-to-end
    files (train->sample CLI roundtrip, 2-process pod) are opt-in, as
    are the ``distill`` soaks (multi-round progressive-distillation
    ladders; the fast 2-round smoke stays in the default run)."""
    run_all = config.getoption("--runslow") or _env_on("RUN_SLOW")
    if not run_all:
        skip = pytest.mark.skip(
            reason="slow end-to-end test; pass --runslow (or RUN_SLOW=1)")
        for item in items:
            if "slow" in item.keywords:
                item.add_marker(skip)
    if not (run_all or _env_on("RUN_DISTILL")):
        skip_d = pytest.mark.skip(
            reason="distillation soak; pass --runslow (or RUN_DISTILL=1)")
        for item in items:
            if "distill" in item.keywords:
                item.add_marker(skip_d)
