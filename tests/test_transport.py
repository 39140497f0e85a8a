"""Cross-process fleet: framing, error codec, RemoteReplica, workers.

Four layers, cheapest first:

* **Framing + codec unit tests** — length-prefixed JSON frames over a
  socketpair: bit-exact ndarray round-trips, and every malformed input
  (oversized declared length, EOF mid-frame, non-JSON body) is a typed
  error, never a hung socket.  The typed retryable hierarchy crosses the
  wire by class name and comes back as the same class with the same
  payload fields.
* **RemoteReplica over an in-process Worker wrapping test_router.py's
  scripted fakes** — duck-type conformance with the in-process
  :class:`~diff3d_tpu.serving.fleet.Replica` surface (the router needs
  zero placement changes), trajectory frame cursors, rollout RPCs, and
  the heartbeat-death contract: a worker gone silent past the timeout
  is ``dead`` forever and its in-flight requests reject with a typed
  ``SessionLost`` naming it.
* **HBM-budgeted admission** — fire/silent pairs against a synthetic
  ``runs/memcheck/`` manifest: the gate's arithmetic (resident + record
  + program peak vs budget), rejection *at the door* with no ledger
  trace, and the counters surfacing through worker /stats and the
  router's ``fleet_admission_rejects_total{reason="hbm"}``.
* **The 2-worker subprocess e2e** — real ``worker_cli`` processes on
  disjoint 4-device slices of the 8-virtual-device CPU mesh, serving
  concurrent sticky sessions bit-identical to the in-process oracle,
  then a mid-run SIGKILL: typed ``SessionLost`` naming the victim,
  sessionless failover to the survivor, zero migration, zero hangs.
  The larger soak (``tools/chaos_router.py --remote``) is marked slow.
"""

import dataclasses
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from diff3d_tpu.analysis import membudgets
from diff3d_tpu.config import ServingConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.runtime.retry import RetryableError
from diff3d_tpu.serving.scheduler import (EngineStopped, QueueFullError,
                                          ReplicaDraining, ReplicaOverBudget,
                                          RequestTimeout, SessionLost,
                                          TrajectoryRequest,
                                          UnsupportedSchedule, ViewRequest)
from diff3d_tpu.serving.transport import (Connection, FrameGarbage,
                                          FrameTooLarge, FrameTruncated,
                                          RemoteReplica, TransportError,
                                          decode_error, decode_payload,
                                          encode_error, encode_payload,
                                          recv_frame, request_from_wire,
                                          request_wire, send_frame)
from diff3d_tpu.serving.worker import (HbmAdmission, Worker,
                                       program_for_schedule)

from test_router import FakeReplica

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LEN = struct.Struct("!I")


def _pair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


def _views(i, n_views=3, size=8):
    r = np.random.RandomState(100 + i)
    return {
        "imgs": r.randn(n_views, size, size, 3).astype(np.float32),
        "R": np.broadcast_to(np.eye(3, dtype=np.float32),
                             (n_views, 3, 3)).copy(),
        "T": r.randn(n_views, 3).astype(np.float32),
        "K": np.array([[size * 1.2, 0, size / 2],
                       [0, size * 1.2, size / 2],
                       [0, 0, 1]], np.float32),
    }


def _req(session_id=None, seed=0, trajectory=False, **kw):
    cls = TrajectoryRequest if trajectory else ViewRequest
    return cls(_views(seed), seed=seed, n_views=3,
               session_id=session_id, **kw)


# ---------------------------------------------------------------------------
# Framing: bit-exact round trips, typed faults, never a hung socket
# ---------------------------------------------------------------------------


def test_frame_roundtrip_bit_exact():
    a, b = _pair()
    try:
        msg = {
            "op": "submit",
            "args": {
                "f32": np.random.RandomState(0).randn(2, 3, 3).astype(
                    np.float32),
                "f16": np.arange(6, dtype=np.float16).reshape(2, 3),
                "i64": np.array([[-(1 << 40), 7]], np.int64),
                "bool": np.array([True, False]),
                "nested": [{"x": np.float32(1.5), "n": np.int64(-3)},
                           "str", None, 2.5],
            },
        }
        send_frame(a, msg)
        got = recv_frame(b)
        for key in ("f32", "f16", "i64", "bool"):
            want = msg["args"][key]
            have = got["args"][key]
            assert have.dtype == want.dtype
            assert have.tobytes() == want.tobytes()
        assert got["args"]["nested"][0] == {"x": 1.5, "n": -3}
        assert got["args"]["nested"][1:] == ["str", None, 2.5]
    finally:
        a.close()
        b.close()


def test_payload_codec_normalizes_big_endian():
    big = np.arange(4, dtype=">f4")
    back = decode_payload(encode_payload(big))
    assert back.dtype == np.dtype("<f4")
    np.testing.assert_array_equal(back, big.astype("<f4"))


def test_clean_eof_is_none_not_error():
    a, b = _pair()
    a.close()
    try:
        assert recv_frame(b) is None
    finally:
        b.close()


def test_declared_length_past_cap_is_frame_too_large():
    a, b = _pair()
    try:
        a.sendall(_LEN.pack(1 << 29))
        with pytest.raises(FrameTooLarge):
            recv_frame(b, max_bytes=1 << 16)
    finally:
        a.close()
        b.close()


def test_oversized_outgoing_frame_refused_before_send():
    a, b = _pair()
    try:
        with pytest.raises(FrameTooLarge):
            send_frame(a, {"blob": "x" * 4096}, max_bytes=64)
        a.close()             # nothing was written: peer sees clean EOF
        assert recv_frame(b) is None
    finally:
        b.close()


def test_eof_mid_frame_is_frame_truncated():
    a, b = _pair()
    try:
        a.sendall(_LEN.pack(100) + b'{"op": "tr')
        a.close()
        with pytest.raises(FrameTruncated):
            recv_frame(b)
    finally:
        b.close()


def test_eof_between_header_and_body_is_frame_truncated():
    a, b = _pair()
    try:
        a.sendall(_LEN.pack(64))
        a.close()
        with pytest.raises(FrameTruncated):
            recv_frame(b)
    finally:
        b.close()


@pytest.mark.parametrize("body", [b"not json at all", b"[1, 2, 3]",
                                  b'"a bare string"'])
def test_non_object_body_is_frame_garbage(body):
    a, b = _pair()
    try:
        a.sendall(_LEN.pack(len(body)) + body)
        with pytest.raises(FrameGarbage):
            recv_frame(b)
    finally:
        a.close()
        b.close()


def test_all_frame_faults_are_retryable():
    for cls in (TransportError, FrameTooLarge, FrameTruncated,
                FrameGarbage):
        assert issubclass(cls, RetryableError)


# ---------------------------------------------------------------------------
# Error codec: the typed hierarchy crosses the wire intact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exc", [
    QueueFullError("queue full"),
    RequestTimeout("req-1: timed out"),
    EngineStopped("stopped"),
    TransportError("socket reset"),
    ReplicaDraining("draining", replica="r2", retry_after_s=0.7),
    SessionLost("record gone", replica="r0", retry_after_s=1.5),
    UnsupportedSchedule("no ddim here", supported=["ancestral:4"],
                        retry_after_s=None),
    ReplicaOverBudget("over", replica="w1", retry_after_s=5.0,
                      budget_bytes=1000, resident_bytes=600,
                      program_peak_bytes=300),
])
def test_error_roundtrip_preserves_class_message_and_fields(exc):
    back = decode_error(encode_error(exc))
    assert type(back) is type(exc)
    assert str(back) == str(exc)
    for field in ("retry_after_s", "replica", "supported", "budget_bytes",
                  "resident_bytes", "program_peak_bytes"):
        assert getattr(back, field, None) == getattr(exc, field, None)


def test_over_budget_headroom_survives_the_wire():
    exc = ReplicaOverBudget("over", replica="w1", retry_after_s=1.0,
                            budget_bytes=1000, resident_bytes=600,
                            program_peak_bytes=300)
    back = decode_error(encode_error(exc))
    assert back.headroom_bytes == 400


def test_unknown_error_type_degrades_to_runtime_error():
    back = decode_error({"type": "SomeExoticError", "msg": "boom"})
    assert type(back) is RuntimeError
    assert "SomeExoticError" in str(back) and "boom" in str(back)


def test_non_retryable_stdlib_errors_rehydrate():
    for exc in (ValueError("bad shape"), KeyError("missing"),
                TypeError("nope")):
        back = decode_error(encode_error(exc))
        assert type(back) is type(exc)


def test_request_wire_roundtrip_plain_and_trajectory():
    for trajectory in (False, True):
        req = _req(session_id="obj-7", seed=3, trajectory=trajectory,
                   sampler_kind="ancestral", steps=4, timeout_s=9.0)
        back = request_from_wire(decode_payload(encode_payload(
            request_wire(req))))
        assert type(back) is type(req)
        assert (back.id, back.seed, back.n_views, back.session_id) == \
            (req.id, req.seed, req.n_views, req.session_id)
        assert (back.sampler_kind, back.steps, back.timeout_s) == \
            (req.sampler_kind, req.steps, req.timeout_s)
        np.testing.assert_array_equal(back.imgs0, req.imgs0)
        np.testing.assert_array_equal(back.R, req.R)
        np.testing.assert_array_equal(back.T, req.T)
        np.testing.assert_array_equal(back.K, req.K)


# ---------------------------------------------------------------------------
# RemoteReplica over an in-process Worker wrapping scripted fakes
# ---------------------------------------------------------------------------


class BootableFake(FakeReplica):
    """test_router's scripted replica + the lifecycle surface Worker
    drives and an optional scripted resolution for submitted requests."""

    def __init__(self, *a, resolve_with=None, commit_frames=None, **kw):
        super().__init__(*a, **kw)
        self.resolve_with = resolve_with      # callable(req) -> ndarray
        self.commit_frames = commit_frames    # list of frames to stream

    def start(self):
        return self

    def stop(self, timeout=None):
        self.events.append("stop")

    def submit(self, req):
        super().submit(req)
        if self.commit_frames is not None:
            for k, frame in enumerate(self.commit_frames):
                req._commit_frame(k + 1, frame)
        if self.resolve_with is not None:
            req._resolve(np.asarray(self.resolve_with(req)))
        return req


def _tiny_cfg(**serving_over):
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    serving = dict(port=0, max_batch=4, max_queue=8, max_wait_ms=20.0,
                   max_views=6, default_timeout_s=60.0,
                   retry_after_s=0.1, result_cache_entries=0)
    serving.update(serving_over)
    return dataclasses.replace(cfg, serving=ServingConfig(**serving))


def _seeded_result(req):
    return np.random.RandomState(req.seed).randn(2, 1, 8, 8, 3).astype(
        np.float32)


def _worker_pair(fake, cfg=None, admission=None, **remote_kw):
    worker = Worker(fake, cfg or _tiny_cfg(), admission=admission).start()
    remote_kw.setdefault("heartbeat_interval_s", 0.05)
    remote_kw.setdefault("heartbeat_timeout_s", 1.0)
    remote = RemoteReplica("127.0.0.1", worker.port, **remote_kw).start()
    return worker, remote


def _wait_for(pred, timeout=10.0, poll=0.01, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(poll)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.lock_witness
def test_remote_replica_duck_types_the_replica_surface(lock_witness):
    """Attribute-for-attribute conformance with the surface the router
    reads — RemoteReplica must be a drop-in for Replica."""
    fake = BootableFake("w-fake", depth=3,
                        schedules={("ancestral", 4), ("ddim", 2)})
    fake.sessions["s1"] = 2
    worker, remote = _worker_pair(fake)
    try:
        for attr in ("name", "health", "depth", "supports",
                     "supported_schedules", "params_version", "submit",
                     "session_records", "session_count", "drain",
                     "resume", "kill", "swap_params", "snapshot",
                     "start", "stop"):
            assert hasattr(remote, attr), f"RemoteReplica lacks {attr}"
        assert remote.name == fake.name     # adopted from the worker
        assert remote.health == fake.health
        assert remote.depth() == fake.depth()
        for kind, steps in (("ancestral", 4), ("ddim", 2), ("ddim", 99)):
            assert remote.supports(kind, steps) == fake.supports(kind,
                                                                 steps)
        assert remote.supported_schedules() == fake.supported_schedules()
        assert remote.params_version == fake.params_version
        assert remote.session_records() == fake.session_records()
        assert remote.session_count("s1") == 2
        snap = remote.snapshot()
        assert snap["name"] == fake.name
        assert snap["transport"]["connected"]
        assert snap["transport"]["remote"].endswith(str(worker.port))
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_remote_submit_resolves_bit_identical(lock_witness):
    fake = BootableFake("w-res", resolve_with=_seeded_result)
    worker, remote = _worker_pair(fake)
    try:
        req = remote.submit(_req(session_id="obj-1", seed=5))
        got = req.result(timeout=10)
        np.testing.assert_array_equal(got, _seeded_result(req))
        assert req.cached is False
        # The ledger entry landed on the worker-side replica.
        assert remote.session_records() == {"obj-1": 1}
        assert remote.transport_stats()["rtt_ms"] is not None
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_remote_submit_rehydrates_typed_rejections(lock_witness):
    fake = BootableFake("w-err")
    worker, remote = _worker_pair(fake)
    try:
        for exc in (QueueFullError("full"),
                    ReplicaDraining("draining", replica="w-err",
                                    retry_after_s=0.3),
                    UnsupportedSchedule("no ddim",
                                        supported=["ancestral:4"]),
                    SessionLost("gone", replica="w-err")):
            fake.submit_exc = exc
            with pytest.raises(type(exc)) as ei:
                remote.submit(_req(seed=1))
            assert str(ei.value) == str(exc)
            for field in ("replica", "supported", "retry_after_s"):
                assert getattr(ei.value, field, None) == \
                    getattr(exc, field, None)
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_remote_trajectory_streams_frames_through_cursors(lock_witness):
    frames = [np.full((1, 8, 8, 3), k, np.float32) for k in range(2)]
    fake = BootableFake("w-traj", commit_frames=frames,
                        resolve_with=lambda req: np.stack(frames))
    worker, remote = _worker_pair(fake)
    try:
        req = remote.submit(_req(seed=2, trajectory=True))
        np.testing.assert_array_equal(req.result(timeout=10),
                                      np.stack(frames))
        got = req.frames_since(0)
        assert len(got) == 2
        for want, have in zip(frames, got):
            np.testing.assert_array_equal(want, have)
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_remote_lifecycle_rpcs_reach_the_replica(lock_witness):
    fake = BootableFake("w-life")
    worker, remote = _worker_pair(fake)
    try:
        assert remote.drain(timeout=1.0) is True
        remote.resume()
        version = remote.swap_params({"w": np.ones(3, np.float32)},
                                     version="v9")
        assert version == "v9"
        _wait_for(lambda: {"drain", "resume", "swap"} <=
                  set(fake.events), what="lifecycle events")
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_heartbeat_timeout_is_terminal_death_with_typed_session_lost(
        lock_witness):
    """The connection-supervision contract: a worker gone silent past
    heartbeat_timeout_s is dead forever, in-flight requests reject with
    SessionLost naming it, and later submits are EngineStopped — never
    a hang."""
    fake = BootableFake("w-dead")          # never resolves
    # The death clock is the test's: no probe can time out on a busy
    # machine (30 s), and silence turns into death when the test says
    # the timeout has passed, not when wall time does.
    now = [0.0]
    worker, remote = _worker_pair(fake, heartbeat_interval_s=0.05,
                                  heartbeat_timeout_s=30.0,
                                  clock=lambda: now[0])
    try:
        req = remote.submit(_req(session_id="s-lost", seed=7))
        # The ledger the audit falls back on is the last heartbeat's
        # copy: let one probe see the session before the worker dies.
        _wait_for(lambda: remote._cached("session_records") == {"s-lost": 1},
                  what="a heartbeat that saw the session")
        worker.stop()                      # abrupt close: SIGKILL shape
        remote.depth()                     # the control connection fails too
        _wait_for(lambda: not remote.transport_stats()["connected"],
                  what="failed probes")
        assert remote.health != "dead"     # silent, not yet past the timeout
        assert not req.done()
        now[0] += 30.1
        with pytest.raises(SessionLost) as ei:
            req.result(timeout=10)
        assert ei.value.replica == "w-dead"
        _wait_for(lambda: remote.health == "dead", what="death")
        stats = remote.transport_stats()
        assert stats["heartbeat_timeouts"] == 1
        assert stats["connected"] is False
        with pytest.raises(EngineStopped):
            remote.submit(_req(seed=8))
        # Death is terminal: the cached ledger still shows the lost
        # session (the zero-migration audit needs the dead owner).
        assert remote.session_records() == {"s-lost": 1}
    finally:
        remote.stop()
        worker.stop()


def test_connection_call_times_out_instead_of_hanging():
    listener = socket.create_server(("127.0.0.1", 0))
    try:
        conn = Connection("127.0.0.1", listener.getsockname()[1],
                          timeout_s=0.3)
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            conn.call("ping")              # nobody ever answers
        assert time.monotonic() - t0 < 5.0
        conn.close()
    finally:
        listener.close()


# ---------------------------------------------------------------------------
# HBM-budgeted admission against a synthetic memcheck manifest
# ---------------------------------------------------------------------------

_PEAK = 50_000


def _manifest_dir(tmp_path, peak=_PEAK, programs=("step_many",)):
    d = str(tmp_path / "memcheck")
    for program in programs:
        membudgets.write_manifest(
            membudgets.manifest_path(program, d),
            membudgets.MemManifest(
                program=program,
                budgets=membudgets.MemBudget(peak_bytes=peak)))
    return d


def test_admission_fire_and_silent_pair(tmp_path):
    d = _manifest_dir(tmp_path)
    req_a, req_b = _req(seed=1), _req(seed=2)
    need = HbmAdmission.record_bytes(req_a)
    assert need > 0
    # Silent: exactly one request + the program peak fits.
    gate = HbmAdmission(budget_bytes=need + _PEAK, manifest_dir=d,
                        replica_name="wA", retry_after_s=2.5)
    gate.admit(req_a, default_kind="ancestral")
    snap = gate.snapshot()
    assert snap["resident_bytes"] == need
    assert snap["headroom_bytes"] == _PEAK
    assert snap["program_peaks"] == {"step_many": _PEAK}
    # Fire: the second identical request pushes past the budget, with
    # the full arithmetic on the exception — and no reservation leaks.
    with pytest.raises(ReplicaOverBudget) as ei:
        gate.admit(req_b, default_kind="ancestral")
    e = ei.value
    assert (e.replica, e.retry_after_s) == ("wA", 2.5)
    assert (e.budget_bytes, e.resident_bytes, e.program_peak_bytes) == \
        (need + _PEAK, need, _PEAK)
    assert e.headroom_bytes == _PEAK
    assert gate.snapshot()["rejects"] == 1
    # Releasing the first reservation lets the second in.
    gate.release(req_a.id)
    gate.admit(req_b, default_kind="ancestral")


def test_admission_unpinned_program_charged_the_largest_peak(tmp_path):
    d = _manifest_dir(tmp_path, programs=("step_many", "step_many_ddim"))
    membudgets.write_manifest(
        membudgets.manifest_path("step_many_ddim", d),
        membudgets.MemManifest(
            program="step_many_ddim",
            budgets=membudgets.MemBudget(peak_bytes=3 * _PEAK)))
    gate = HbmAdmission(budget_bytes=10 * _PEAK, manifest_dir=d)
    assert program_for_schedule(None) == "step_many"
    assert program_for_schedule("ancestral") == "step_many"
    assert gate.program_peak("ancestral") == _PEAK
    assert gate.program_peak("ddim") == 3 * _PEAK
    # A kind with no committed manifest is charged conservatively.
    assert gate.program_peak("exotic") == 3 * _PEAK


def test_admission_disabled_when_budget_unset(tmp_path):
    gate = HbmAdmission(0, manifest_dir=_manifest_dir(tmp_path))
    gate.admit(_req(seed=1))
    snap = gate.snapshot()
    assert snap["enabled"] is False
    assert snap["headroom_bytes"] is None
    assert snap["resident_bytes"] == 0      # disabled gate reserves nothing


@pytest.mark.lock_witness
def test_worker_rejects_at_the_door_before_any_replica_work(
        tmp_path, lock_witness):
    """The fire/silent pair through the wire: an over-budget submit is
    a typed 503-shaped ReplicaOverBudget with zero ledger trace, and
    raising the budget admits the identical request."""
    fake = BootableFake("w-hbm", resolve_with=_seeded_result)
    gate = HbmAdmission(budget_bytes=1, manifest_dir=_manifest_dir(tmp_path),
                        replica_name="w-hbm", retry_after_s=1.0)
    worker, remote = _worker_pair(fake, admission=gate)
    try:
        with pytest.raises(ReplicaOverBudget) as ei:
            remote.submit(_req(session_id="s-budget", seed=4))
        assert ei.value.replica == "w-hbm"
        assert ei.value.budget_bytes == 1
        assert ei.value.retry_after_s == 1.0
        assert fake.submitted == []        # rejected before the replica
        assert fake.sessions == {}         # ... and before the ledger
        assert worker.metrics.snapshot()["counters"][
            "worker_admission_rejects_hbm_total"] == 1
        # The reject count rides the heartbeat into transport_stats.
        _wait_for(lambda: remote.transport_stats()
                  ["admission_rejects_hbm"] == 1, what="hbm stat")
        # Silent half: same request shape under a real budget.
        worker.admission.budget_bytes = 1 << 30
        req = remote.submit(_req(session_id="s-budget", seed=4))
        req.result(timeout=10)
        assert fake.sessions == {"s-budget": 1}
        # /stats (HTTP, include_memory) surfaces the same arithmetic.
        hbm = worker.metrics_snapshot()["hbm"]
        assert hbm["enabled"] and hbm["budget_bytes"] == 1 << 30
    finally:
        remote.stop()
        worker.stop()


@pytest.mark.lock_witness
def test_router_surfaces_admission_rejects_and_remote_metrics(
        tmp_path, lock_witness):
    """Through the front door: the router re-raises the typed
    ReplicaOverBudget (no FleetOverloaded wrap) and folds the worker's
    reject counter into fleet_admission_rejects_total{reason="hbm"}."""
    from diff3d_tpu.serving.router import FleetService

    fake = BootableFake("w-gate", resolve_with=_seeded_result)
    gate = HbmAdmission(budget_bytes=1,
                        manifest_dir=_manifest_dir(tmp_path),
                        replica_name="w-gate", retry_after_s=1.0)
    worker = Worker(fake, _tiny_cfg(), admission=gate).start()
    cfg = _tiny_cfg(replicas=1, heartbeat_interval_s=0.05,
                    heartbeat_timeout_s=1.0)
    remote = RemoteReplica("127.0.0.1", worker.port,
                           heartbeat_interval_s=0.05,
                           heartbeat_timeout_s=1.0)
    svc = FleetService([remote], cfg).start(serve_http=False)
    try:
        with pytest.raises(ReplicaOverBudget) as ei:
            svc.router.submit(_req(session_id="s-r", seed=6))
        assert ei.value.replica == "w-gate"
        _wait_for(lambda: remote.transport_stats()
                  ["admission_rejects_hbm"] >= 1, what="hbm stat")
        snap = svc.metrics_snapshot()
        assert snap["counters"][
            'fleet_admission_rejects_total{reason="hbm"}'] >= 1
        assert snap["counters"]["router_rejected_total"] >= 1
        assert snap["gauges"]["fleet_remote_connected"] == 1.0
        # GET /fleet carries the per-replica transport block (RTT).
        fleet = svc.fleet_snapshot()
        transport = fleet["replicas"]["w-gate"]["transport"]
        assert transport["remote"].endswith(str(worker.port))
        assert transport["rtt_ms"] is not None
    finally:
        svc.stop()
        worker.stop()


def test_worker_http_front_door_exposes_hbm_stats(tmp_path):
    fake = BootableFake("w-http", resolve_with=_seeded_result)
    gate = HbmAdmission(budget_bytes=1 << 20,
                        manifest_dir=_manifest_dir(tmp_path),
                        replica_name="w-http")
    worker = Worker(fake, _tiny_cfg(), admission=gate)
    worker.start(http_port=0)
    try:
        base = f"http://127.0.0.1:{worker.http_port}"
        with urllib.request.urlopen(f"{base}/stats", timeout=5) as resp:
            stats = json.loads(resp.read())
        assert stats["hbm"]["budget_bytes"] == 1 << 20
        assert stats["hbm"]["headroom_bytes"] == 1 << 20
        assert stats["hbm"]["program_peaks"] == {"step_many": _PEAK}
        with urllib.request.urlopen(f"{base}/healthz", timeout=5) as resp:
            health = json.loads(resp.read())
        assert health["replica"] == "w-http"
        assert health["hbm"]["enabled"] is True
    finally:
        worker.stop()


# ---------------------------------------------------------------------------
# The 2-worker subprocess e2e on the split CPU mesh (tier-1: ONE instance)
# ---------------------------------------------------------------------------


def _spawn_worker(name, devices, tmp_path, logs):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # --host_device_count sets it pre-import
    log = open(tmp_path / f"{name}.err.log", "wb")
    logs.append(log)
    return subprocess.Popen(
        [sys.executable, "-m", "diff3d_tpu.cli.worker_cli",
         "--config", "test", "--init", "random",
         "--imgsize", "8", "--ch", "8", "--shallow",
         "--devices", devices, "--port", "0", "--name", name,
         "--host_device_count", "8", "--timeout_s", "120",
         "--max_views", "6",
         "--compile_cache", str(tmp_path / "xla_cache")],
        env=env, stdout=subprocess.PIPE, stderr=log, text=True)


def _read_ready(name, proc):
    line = proc.stdout.readline()
    assert line, f"worker {name} exited before its ready line " \
        f"(rc={proc.poll()})"
    ready = json.loads(line)
    assert ready["ready"] and ready["name"] == name
    return ready


@pytest.mark.lock_witness
def test_two_worker_fleet_serves_sessions_and_survives_sigkill(
        tmp_path, lock_witness):
    """The acceptance e2e (DESIGN.md §19): two real worker processes on
    disjoint 4-device slices of the 8-virtual-device CPU mesh serve
    concurrent sticky sessions bit-identical to the in-process oracle
    (zero migration), then one worker is SIGKILLed mid-request: the
    in-flight request rejects with a typed SessionLost naming the
    victim, later sticky submits for its sessions do too, sessionless
    traffic fails over to the survivor, and the router metrics record
    the heartbeat death.  The larger soak is the slow
    tools/chaos_router.py --remote run below."""
    import jax

    from diff3d_tpu.models import XUNet
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.serving.router import FleetService
    from diff3d_tpu.train.trainer import init_params

    logs, procs = [], {}
    service = None
    try:
        for name, devices in (("e2e-w0", "0-3"), ("e2e-w1", "4-7")):
            procs[name] = _spawn_worker(name, devices, tmp_path, logs)
        # The oracle compiles while the workers boot.
        cfg = _tiny_cfg(replicas=2, default_timeout_s=120.0,
                        heartbeat_interval_s=0.1,
                        heartbeat_timeout_s=2.0)
        model = XUNet(cfg.model)
        params = init_params(model, cfg, jax.random.PRNGKey(0))
        oracle = Sampler(model, params, cfg)
        remotes = []
        for name, proc in procs.items():
            ready = _read_ready(name, proc)
            remotes.append(RemoteReplica(
                "127.0.0.1", ready["port"], name=name,
                heartbeat_interval_s=cfg.serving.heartbeat_interval_s,
                heartbeat_timeout_s=cfg.serving.heartbeat_timeout_s))
        service = FleetService(remotes, cfg).start(serve_http=False)

        # Two concurrent sticky sessions, two views each; every result
        # must be bit-identical to the oracle (worker params come from
        # the same PRNGKey(0) random init; a 4-device slice changes
        # nothing about the math).
        reqs = {}
        for si, sid in enumerate(("s0", "s1")):
            for k in range(2):
                seed = 10 * (si + 1) + k
                reqs[(sid, k)] = service.router.submit(
                    ViewRequest(_views(seed), seed=seed, n_views=3,
                                session_id=sid))
        for (sid, k), req in reqs.items():
            seed = req.seed
            direct = oracle.synthesize(_views(seed),
                                       jax.random.PRNGKey(seed),
                                       max_views=3)
            np.testing.assert_array_equal(req.result(timeout=120), direct)

        # Zero migration: each session's ledger lives on ONE worker.
        owners = {}
        for rep in service.replicas:
            for sid, count in rep.session_records().items():
                assert sid not in owners, f"{sid} migrated"
                owners[sid] = rep.name
                assert count == 2
        assert set(owners) == {"s0", "s1"}

        # SIGKILL the owner of s0 while a request is in flight.
        victim = owners["s0"]
        survivor = next(r.name for r in service.replicas
                        if r.name != victim)
        inflight = service.router.submit(
            ViewRequest(_views(77), seed=77, n_views=3, session_id="s0"))
        os.kill(procs[victim].pid, signal.SIGKILL)
        with pytest.raises(SessionLost) as ei:
            inflight.result(timeout=30)
        assert ei.value.replica == victim
        assert inflight.done()             # terminal, not hung

        # Sticky resubmits for the lost session are typed SessionLost
        # too (the dying window surfaces retryable TransportErrors).
        deadline = time.monotonic() + 20.0
        while True:
            try:
                service.router.submit(
                    ViewRequest(_views(78), seed=78, n_views=3,
                                session_id="s0"))
                raise AssertionError("dead owner accepted a submit")
            except SessionLost as e:
                assert e.replica == victim
                break
            except RetryableError:
                assert time.monotonic() < deadline, "no typed SessionLost"
                time.sleep(0.1)

        # Sessionless traffic fails over to the survivor, bit-exact.
        free = service.router.submit(
            ViewRequest(_views(79), seed=79, n_views=3))
        direct = oracle.synthesize(_views(79), jax.random.PRNGKey(79),
                                   max_views=3)
        np.testing.assert_array_equal(free.result(timeout=120), direct)

        # The death is on the fleet surface: health, metrics, ledger.
        dead = service.router.replica(victim)
        assert dead.health == "dead"
        assert "s0" in dead.session_records()   # cached for the audit
        snap = service.metrics_snapshot()
        assert snap["counters"]["fleet_heartbeat_timeouts_total"] >= 1
        assert snap["gauges"]["fleet_remote_connected"] == 1.0
        transport = service.fleet_snapshot()["replicas"][survivor][
            "transport"]
        assert transport["connected"] and transport["rtt_ms"] is not None
    finally:
        if service is not None:
            service.stop()
        for proc in procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            if proc.stdout is not None:
                proc.stdout.close()
        for log in logs:
            log.close()


@pytest.mark.slow
def test_remote_chaos_soak(tmp_path):
    """Superseded in tier 1 by
    test_two_worker_fleet_serves_sessions_and_survives_sigkill (one
    SIGKILL, 2 sessions); this soak adds concurrent session churn,
    sessionless load and a mid-run rollout on the cross-process fleet.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable,
         os.path.join(_REPO_ROOT, "tools", "chaos_router.py"),
         "--remote", "--replicas", "2", "--sessions", "4",
         "--views", "2", "--sessionless", "6", "--json",
         "--compile_cache", str(tmp_path / "xla_cache")],
        env=env, capture_output=True, text=True, timeout=840)
    assert out.returncode == 0, out.stderr[-2000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])
    assert record["survived"] is True
    assert record["hung"] == 0 and record["lost"] == 0
    assert record["migrations"] == []
