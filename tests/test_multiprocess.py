"""REAL multi-process distributed test: a 2-process CPU 'pod' (2 virtual
devices per process, 4 global) runs the sharded train step end-to-end.

This is the multi-host story the reference never tested anywhere
(README.md:14 'Yet to test'; SURVEY.md §4): here it runs in CI on any
machine.  Covers jax.distributed bring-up, cross-process gradient
all-reduce compiled from shardings, per-host global-batch assembly
(``shard_host_local``'s ``make_array_from_process_local_data`` branch),
and identical loss trajectories on every process.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

_WORKER = os.path.join(os.path.dirname(__file__), "_mp_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(port: int, tmp_path) -> tuple[list, list]:
    """Launch both workers against ``port``; returns (procs, log texts)."""
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_PLATFORMS="cpu",
        # No persistent cache in the workers: if one AOT-loads a cached
        # executable while the other compiles, they create different
        # gloo-context sequences and the collective rendezvous times
        # out.  With the cache off both workers compile, symmetrically.
        JAX_ENABLE_COMPILATION_CACHE="false",
    )
    # Workers write straight to files: PIPE capture with sequential
    # communicate() can deadlock (a worker blocking on a full unread pipe
    # stalls the other inside a cross-process collective), and a timeout
    # must still kill BOTH workers or they stay pinned on the rendezvous.
    log_paths = [tmp_path / f"out_{pid}.log" for pid in (0, 1)]
    logs = [open(p, "wb") for p in log_paths]
    procs = []
    try:
        for pid in (0, 1):
            procs.append(subprocess.Popen(
                [sys.executable, _WORKER, str(pid), "2",
                 f"localhost:{port}", str(tmp_path)],
                env=env, stdout=logs[pid], stderr=subprocess.STDOUT))
        for p in procs:
            p.wait(timeout=840)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    return procs, [p.read_text(errors="replace") for p in log_paths]


def test_two_process_distributed_train_step(tmp_path):
    # _free_port closes the probe socket before the coordinator rebinds it
    # (TOCTOU): another process can grab the port in between, so a bind
    # failure retries the whole launch on a fresh port instead of flaking.
    for attempt in range(3):
        procs, outs = _run_workers(_free_port(), tmp_path)
        if all(p.returncode == 0 for p in procs):
            break
        bind_race = any(
            marker in out.lower()
            for out in outs
            for marker in ("address already in use", "failed to bind",
                           "errno 98"))
        if not (bind_race and attempt < 2):
            break
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"

    losses = [json.load(open(tmp_path / f"loss_{pid}.json"))
              for pid in (0, 1)]
    # Both processes observe the SAME global loss (one global batch, one
    # all-reduced gradient) — the property the reference's DDP path lost.
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert all(np.isfinite(l) for l in losses[0]) and len(losses[0]) == 2
