"""Start-up rules every entry point follows on the chip machine (PR 21):
where the compile cache lives, that an explicit kernel request is honoured
or raises, that interpret mode is a CPU-process thing, that a single-host
start makes no distributed call, and that ``chip_smoke.py`` fails without
an accelerator.  CPU only; nothing here compiles a model."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import diff3d_tpu.ops.attention  # noqa: F401 - registers 'sdpa'
import diff3d_tpu.ops.pallas_film  # noqa: F401 - registers 'groupnorm'
from diff3d_tpu.ops import dispatch
from diff3d_tpu.parallel import multihost
from diff3d_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test (the live
    cache object was initialised long before and is not affected)."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.configure_compile_cache()
    second = compile_cache.configure_compile_cache()
    assert first == second == compile_cache.DEFAULT_CACHE_DIR
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_cache_dir_variable_set_is_left_untouched(
        monkeypatch, cache_config, tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # as JAX reads it
    assert compile_cache.configure_compile_cache() == placed
    # ... and an explicit caller choice (worker_cli --compile_cache)
    # yields to the variable too.
    assert compile_cache.configure_compile_cache(
        str(tmp_path / "flag")) == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert not os.path.exists(placed)       # nothing created, nothing moved


def test_cache_dir_variable_and_config_must_agree(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "code"))
    with pytest.raises(RuntimeError, match="only the variable"):
        compile_cache.configure_compile_cache()


def test_cache_dir_explicit_choice_when_variable_unset(
        monkeypatch, cache_config, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    flag = str(tmp_path / "flag")
    assert compile_cache.configure_compile_cache(flag) == flag


def test_no_file_sets_the_cache_dir_behind_the_helper():
    """Every site goes through runtime/compile_cache.py: no other file
    of the repo calls jax.config.update on the cache directory."""
    needle = 'config.update("jax_compilation_cache_dir"'
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d != "chiprun_out"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, REPO)
            if rel in ("diff3d_tpu/runtime/compile_cache.py",
                       "tests/test_bringup.py"):
                continue
            with open(path, encoding="utf-8") as f:
                if needle in f.read():
                    offenders.append(rel)
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# dispatch: explicit requests are honoured or raise; only 'auto' chooses
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op,operands,kwargs,names", [
    ("groupnorm", [((2, 256, 128), jnp.float16)], {"num_groups": 32},
     r"groupnorm.*float16\[2, 256, 128\].*num_groups=32"),
    ("groupnorm", [((2, 64, 96), jnp.float32)], {"num_groups": 7},
     r"groupnorm.*float32\[2, 64, 96\]"),
    ("sdpa", [((2, 64, 4, 1024), jnp.bfloat16)] * 3, {},
     r"sdpa.*bfloat16\[2, 64, 4, 1024\]"),
])
def test_explicit_pallas_request_that_cannot_be_honoured_raises(
        op, operands, kwargs, names):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in operands]
    with pytest.raises(ValueError, match=names):
        dispatch.resolve(op, "pallas", *args, **kwargs)
    # 'auto' is the only request that may choose — here, xla.
    assert dispatch.resolve(op, "auto", *args, **kwargs).name == "xla"


def test_interpret_mode_is_for_a_cpu_process_only(monkeypatch):
    assert dispatch.interpret_default() is True     # this process is CPU
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert dispatch.interpret_default() is False


def test_backend_error_is_not_read_as_cpu(monkeypatch):
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        dispatch.default_backend()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        dispatch.interpret_default()
    x = jax.ShapeDtypeStruct((2, 256, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        dispatch.resolve("groupnorm", "auto", x, num_groups=32)


# ---------------------------------------------------------------------------
# multihost: a single-host start makes no call; a configured one that
# fails, fails
# ---------------------------------------------------------------------------


def test_single_host_start_never_calls_distributed_initialize(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)

    def boom(**kw):
        raise AssertionError("jax.distributed.initialize was called")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    assert multihost.maybe_initialize_distributed() is False
    assert multihost.reinitialize_distributed() is False


@pytest.mark.parametrize("how", ["argument", "environment"])
def test_configured_multi_process_start_that_fails_fails(monkeypatch, how):
    from diff3d_tpu.runtime.retry import RetryPolicy

    calls = []

    def refuse(**kw):
        calls.append(kw)
        raise RuntimeError("INVALID_ARGUMENT: bad coordinator")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    kw = {}
    if how == "argument":
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        kw = dict(coordinator_address="localhost:1", num_processes=2,
                  process_id=0)
    else:
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1")
    with pytest.raises(RuntimeError, match="bad coordinator"):
        multihost.maybe_initialize_distributed(
            retry=RetryPolicy(max_attempts=2, sleep=lambda s: None), **kw)
    assert len(calls) == 1          # a config error is not retried


# ---------------------------------------------------------------------------
# chip_smoke.py without an accelerator
# ---------------------------------------------------------------------------


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_a_cpu_process():
    proc = _run_smoke(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail too (and print no result)."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env_path = os.environ.get("PYTHONPATH", "")
    assert REPO not in env_path.split(os.pathsep)
    proc = _run_smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# ---------------------------------------------------------------------------
# train_cli.build_config: what chip_smoke.py resumes with
# ---------------------------------------------------------------------------


def test_train_cli_build_config_applies_every_override():
    """``chip_smoke.py`` rebuilds the Config of its ``train_cli`` run from
    the same argv to resume from the workdir; the two must agree."""
    from diff3d_tpu.cli import train_cli

    args = train_cli.build_parser().parse_args(
        ["--config", "srn64", "--synthetic", "--batch", "24", "--steps",
         "4", "--ckpt_every", "2", "--param_sharding", "fsdp", "--pallas",
         "--remat"])
    cfg = train_cli.build_config(args)
    assert (cfg.train.global_batch, cfg.train.max_steps,
            cfg.train.ckpt_every) == (24, 4, 2)
    assert cfg.mesh.param_sharding == "fsdp"
    assert cfg.model.kernels == "pallas" and cfg.model.remat is True
    assert (cfg.model.H, cfg.model.ch, cfg.model.emb_ch,
            cfg.model.num_res_blocks) == (64, 128, 1024, 3)
