"""Fleet router: session affinity, placement, backpressure, rollout.

Two layers, mirroring the router's own split:

* **Routing-core unit tests** drive :class:`~diff3d_tpu.serving.router.Router`
  against fake replicas (the router duck-types the
  :class:`~diff3d_tpu.serving.fleet.Replica` surface and compiles
  nothing, so the placement/affinity/backpressure logic is testable
  with zero device work): rendezvous stability under churn, sticky vs
  sessionless failover, claim release, the typed rejection hierarchy,
  and the blue/green rollout state machine.
* **Fleet integration tests** run real 3-replica fleets on the tiny
  shallow config — bit-parity through the router, schedule-aware
  placement, HTTP 503 + ``Retry-After``, ``GET /fleet``, the chaos
  kill/failover path, and the acceptance e2e: 8 concurrent multi-view
  sessions with a mid-run params rollout, zero dropped requests and
  zero record migration (asserted against the per-replica session
  ledgers).  Threaded paths run under ``@pytest.mark.lock_witness``.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from diff3d_tpu.config import ServingConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.models import XUNet
from diff3d_tpu.runtime.retry import RetryableError
from diff3d_tpu.sampling import Sampler
from diff3d_tpu.serving import (EngineDraining, FleetOverloaded,
                                FleetService, ProgramCache, QueueFullError,
                                ReplicaDraining, Router, SessionLost,
                                UnsupportedSchedule, ViewRequest)
from diff3d_tpu.testing.faults import FaultInjector, arm_replica
from diff3d_tpu.train.trainer import init_params


# ---------------------------------------------------------------------------
# Routing core against fake replicas (no device work)
# ---------------------------------------------------------------------------


class FakeReplica:
    """Just the Replica surface the router reads, fully scripted."""

    def __init__(self, name, depth=0, health="ok", schedules=None,
                 submit_exc=None):
        self.name = name
        self.health = health
        self._depth = depth
        self.schedules = schedules          # None = supports everything
        self.submit_exc = submit_exc        # raise this on submit
        self.submitted = []
        self.sessions = {}
        self.params_version = "v0"
        self.events = []                    # rollout choreography log
        self.drain_ok = True

    def depth(self):
        return self._depth

    def supports(self, kind=None, steps=None):
        return self.schedules is None or (kind, steps) in self.schedules

    def supported_schedules(self):
        return sorted(f"{k}:{s}" for k, s in (self.schedules or ()))

    def submit(self, req):
        if self.submit_exc is not None:
            raise self.submit_exc
        self.submitted.append(req)
        if req.session_id is not None:
            self.sessions[req.session_id] = (
                self.sessions.get(req.session_id, 0) + 1)
        return req

    def session_count(self, sid):
        return self.sessions.get(sid, 0)

    def session_records(self):
        return dict(self.sessions)

    def drain(self, timeout=None):
        self.events.append("drain")
        return self.drain_ok

    def resume(self):
        self.events.append("resume")

    def swap_params(self, params, version=None):
        self.events.append("swap")
        self.params_version = version or "swapped"
        return self.params_version

    def snapshot(self):
        return {"name": self.name, "health": self.health,
                "queue_depth": self._depth, "sessions": len(self.sessions)}


def _tiny_req(session_id=None, seed=0, sampler_kind=None, steps=None):
    views = {
        "imgs": np.zeros((2, 4, 4, 3), np.float32),
        "R": np.broadcast_to(np.eye(3, dtype=np.float32), (2, 3, 3)).copy(),
        "T": np.zeros((2, 3), np.float32),
        "K": np.eye(3, dtype=np.float32),
    }
    return ViewRequest(views, seed=seed, n_views=2, session_id=session_id,
                       sampler_kind=sampler_kind, steps=steps)


def test_rendezvous_stability_under_churn():
    """Removing one replica only remaps the sessions it owned; every
    other session keeps its argmax (the affinity-under-churn contract,
    which a mod-N hash would violate wholesale)."""
    reps = [FakeReplica(f"r{i}") for i in range(5)]
    sids = [f"sess-{i}" for i in range(200)]
    before = {sid: Router.rendezvous_order(sid, reps)[0].name
              for sid in sids}
    survivors = [r for r in reps if r.name != "r2"]
    after = {sid: Router.rendezvous_order(sid, survivors)[0].name
             for sid in sids}
    assert any(v == "r2" for v in before.values())  # r2 owned some
    for sid in sids:
        if before[sid] != "r2":
            assert after[sid] == before[sid], f"{sid} remapped needlessly"


def test_session_affinity_survives_fleet_churn():
    """The affinity table, not the hash, is the source of truth: adding
    a replica (which WOULD win the rendezvous for some sessions) and
    killing an unrelated one never moves an established session."""
    reps = [FakeReplica("r0"), FakeReplica("r1"), FakeReplica("r2")]
    router = Router(reps, retry_after_s=0.5)
    router.submit(_tiny_req(session_id="sess-A", seed=1))
    owner = router.fleet_snapshot()["sessions"]["per_replica"]
    (owner_name,) = owner
    # Churn: a newcomer joins, an unrelated replica dies.
    router.add_replica(FakeReplica("r9"))
    for r in reps:
        if r.name != owner_name:
            r.health = "dead"
            break
    for seed in range(2, 6):
        router.submit(_tiny_req(session_id="sess-A", seed=seed))
    by_name = {r.name: r for r in router.replica_list()}
    assert by_name[owner_name].session_count("sess-A") == 5
    assert sum(r.session_count("sess-A")
               for r in router.replica_list()) == 5  # zero migration


def test_sessionless_least_loaded_and_tiebreak():
    reps = [FakeReplica("r0", depth=5), FakeReplica("r1", depth=0),
            FakeReplica("r2", depth=2), FakeReplica("r3", depth=0)]
    router = Router(reps)
    router.submit(_tiny_req(seed=7))
    assert len(reps[1].submitted) == 1      # depth 0, name-tiebreak r1<r3
    assert not reps[0].submitted and not reps[3].submitted


def test_sessionless_fails_over_down_the_order():
    full = QueueFullError("full")
    reps = [FakeReplica("r0", depth=0, submit_exc=full),
            FakeReplica("r1", depth=1, submit_exc=full),
            FakeReplica("r2", depth=2)]
    router = Router(reps)
    router.submit(_tiny_req(seed=8))
    assert len(reps[2].submitted) == 1
    assert router.metrics.counter("router_failover_total", "").value == 1
    # All full -> FleetOverloaded carrying retry_after_s.
    reps[2].submit_exc = EngineDraining("draining", retry_after_s=0.1)
    with pytest.raises(FleetOverloaded) as ei:
        router.submit(_tiny_req(seed=9))
    assert ei.value.retry_after_s == router.retry_after_s


def test_sticky_capacity_never_fails_over():
    """A session at its owner's capacity gets FleetOverloaded — the
    record is on that replica, so routing elsewhere is never correct."""
    reps = [FakeReplica("r0"), FakeReplica("r1")]
    router = Router(reps, retry_after_s=0.25)
    router.submit(_tiny_req(session_id="s", seed=1))
    owner = next(r for r in reps if r.submitted)
    other = next(r for r in reps if not r.submitted)
    owner.submit_exc = QueueFullError("full")
    with pytest.raises(FleetOverloaded) as ei:
        router.submit(_tiny_req(session_id="s", seed=2))
    assert ei.value.retry_after_s == 0.25
    assert not other.submitted               # no silent re-place
    owner.submit_exc = None
    router.submit(_tiny_req(session_id="s", seed=3))
    assert owner.session_count("s") == 2     # still the owner


def test_new_session_claim_released_on_capacity():
    """A first view rejected for capacity leaves no claim behind — the
    session re-places (to the same rendezvous owner) once capacity
    frees, instead of pinning to a replica that never served it."""
    reps = [FakeReplica("r0"), FakeReplica("r1"), FakeReplica("r2")]
    chosen = Router.rendezvous_order("sess-N", reps)[0]
    chosen.submit_exc = QueueFullError("full")
    router = Router(reps)
    with pytest.raises(FleetOverloaded):
        router.submit(_tiny_req(session_id="sess-N", seed=1))
    assert router.fleet_snapshot()["sessions"]["active"] == 0
    chosen.submit_exc = None
    router.submit(_tiny_req(session_id="sess-N", seed=1))
    assert chosen.session_count("sess-N") == 1


def test_sticky_draining_and_dead_rejections():
    reps = [FakeReplica("r0"), FakeReplica("r1")]
    router = Router(reps, retry_after_s=0.5)
    router.submit(_tiny_req(session_id="s", seed=1))
    owner = next(r for r in reps if r.submitted)
    owner.health = "draining"
    with pytest.raises(ReplicaDraining) as ei:
        router.submit(_tiny_req(session_id="s", seed=2))
    assert ei.value.replica == owner.name
    assert ei.value.retry_after_s == 0.5
    owner.health = "dead"
    with pytest.raises(SessionLost) as ei:
        router.submit(_tiny_req(session_id="s", seed=3))
    assert ei.value.replica == owner.name    # names the lost replica
    assert router.fleet_snapshot()["sessions"]["active"] == 0
    m = router.metrics
    assert m.counter("router_sessions_lost_total", "").value == 1
    assert m.counter("router_rejected_total", "").value == 2


def test_schedule_aware_placement_and_union():
    """Requests land only on replicas that compiled their schedule; a
    schedule nobody serves is rejected with the fleet-wide union."""
    reps = [FakeReplica("r0", schedules={("ancestral", 4)}, depth=0),
            FakeReplica("r1", schedules={("ancestral", 4), ("ddim", 2)},
                        depth=9)]
    router = Router(reps)
    router.submit(_tiny_req(seed=1, sampler_kind="ddim", steps=2))
    assert len(reps[1].submitted) == 1       # despite the higher depth
    with pytest.raises(UnsupportedSchedule) as ei:
        router.submit(_tiny_req(seed=2, sampler_kind="ddim", steps=7))
    assert "ddim:2" in ei.value.supported
    assert "ancestral:4" in ei.value.supported


def test_rollout_state_machine():
    """Drain -> swap -> resume per live replica; a drain timeout resumes
    un-swapped and fails the rollout; dead replicas are skipped; the
    rollout flag is single-flight."""
    good = FakeReplica("r0")
    stuck = FakeReplica("r1")
    stuck.drain_ok = False
    dead = FakeReplica("r2", health="dead")
    router = Router([good, stuck, dead])
    out = router.rollout(params=None, version="v1", drain_timeout_s=0.1)
    assert out["ok"] is False
    assert good.events == ["drain", "swap", "resume"]
    assert good.params_version == "v1"
    assert stuck.events == ["drain", "resume"]       # never swapped
    assert stuck.params_version == "v0"
    assert dead.events == []
    statuses = {s["replica"]: s["status"] for s in out["steps"]}
    assert statuses == {"r0": "swapped", "r1": "drain-timeout",
                        "r2": "skipped-dead"}
    assert router.fleet_snapshot()["rollout_active"] is False
    # Single-flight: a rollout observing the active flag is rejected.
    with router._lock:
        router._rollout_active = True
    with pytest.raises(RuntimeError):
        router.rollout(params=None, version="v2")
    with router._lock:
        router._rollout_active = False


# ---------------------------------------------------------------------------
# Real fleets on the tiny shallow config
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_env():
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    sampler = Sampler(model, params, cfg)
    # Pre-compile the shapes fleet traffic launches; replicas share the
    # sampler's jit cache, so every fleet reuses these programs.
    pc = ProgramCache(sampler)
    gb = int(sampler.w.shape[0])
    for bucket, lanes in (((8, 8, 4), 1), ((8, 8, 4), 2)):
        pc.warmup(bucket, lanes, gb)
    return cfg, model, params, sampler


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


def make_fleet(cfg, sampler, n=3, per_replica_extra=None, **over):
    serving = dict(port=0, max_batch=4, max_queue=8, max_wait_ms=20.0,
                   max_views=6, default_timeout_s=60.0,
                   step_retry_backoff_s=0.02, retry_after_s=0.1,
                   replicas=n, result_cache_entries=0)
    serving.update(over)
    cfg2 = dataclasses.replace(cfg, serving=ServingConfig(**serving))
    return FleetService.build(sampler, cfg2,
                              per_replica_extra=per_replica_extra,
                              params_version="v0")


def _wait_for(pred, timeout=30.0, poll=0.01, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(poll)
    raise AssertionError(f"timed out waiting for {what}")


def _owner_of(svc, sid):
    per = svc.fleet_snapshot()["replicas"]
    owners = [n for n, snap in per.items()
              if svc.router.replica(n).session_count(sid)]
    assert len(owners) == 1, f"session {sid} on {owners}"
    return owners[0]


@pytest.mark.lock_witness
def test_router_results_bit_identical_to_direct(fleet_env, lock_witness):
    """Routing adds nothing to the math: a session view and a
    sessionless request through the 3-replica router are bit-equal to
    the sampler called directly."""
    cfg, model, params, sampler = fleet_env
    svc = make_fleet(cfg, sampler).start(serve_http=False)
    try:
        v = _views(0)
        a = svc.router.submit(ViewRequest(v, seed=11, n_views=3,
                                          session_id="obj-0"))
        b = svc.router.submit(ViewRequest(v, seed=11, n_views=3))
        direct = sampler.synthesize(v, jax.random.PRNGKey(11), max_views=3)
        np.testing.assert_array_equal(a.result(timeout=60), direct)
        np.testing.assert_array_equal(b.result(timeout=60), direct)
        assert _owner_of(svc, "obj-0")       # exactly one ledger entry
    finally:
        svc.stop()


@pytest.mark.lock_witness
def test_e2e_sessions_affinity_rollout_zero_drop(fleet_env, lock_witness):
    """Acceptance e2e: 3 replicas, 8 concurrent multi-view sessions,
    a mid-run blue/green rollout — every view of a session lands on its
    owning replica (zero migration, per-replica record counters), zero
    requests dropped (typed retryable rejections are retried by the
    client and all views complete), and every live replica finishes on
    the new params version."""
    cfg, model, params, sampler = fleet_env
    svc = make_fleet(cfg, sampler).start(serve_http=False)
    n_sessions, n_view_reqs = 8, 3
    completed, failures = [], []
    lock = threading.Lock()

    def run_session(si):
        sid = f"obj-{si}"
        for v in range(n_view_reqs):
            req = None
            for _ in range(200):             # client retry loop
                try:
                    req = svc.router.submit(
                        ViewRequest(_views(si * 10 + v), seed=si * 10 + v,
                                    n_views=3, session_id=sid))
                    break
                except RetryableError as e:
                    time.sleep(getattr(e, "retry_after_s", None) or 0.05)
            else:
                with lock:
                    failures.append(f"{sid}/v{v}: retries exhausted")
                return
            try:
                req.result(timeout=60)
                with lock:
                    completed.append((sid, v))
            except Exception as e:
                with lock:
                    failures.append(f"{sid}/v{v}: {type(e).__name__}: {e}")
                return

    try:
        threads = [threading.Thread(target=run_session, args=(i,))
                   for i in range(n_sessions)]
        for t in threads:
            t.start()
        time.sleep(0.2)                      # sessions pin mid-flight
        out = svc.rollout(params, version="v1", drain_timeout_s=60.0)
        for t in threads:
            t.join(120)
        assert not failures, failures
        assert len(completed) == n_sessions * n_view_reqs  # zero dropped
        assert out["ok"] is True
        assert all(s["status"] == "swapped" for s in out["steps"])
        # Zero migration: each session's ledger lives on one replica and
        # counts every one of its views.
        ledgers = {r.name: r.session_records() for r in svc.replicas}
        for si in range(n_sessions):
            sid = f"obj-{si}"
            holders = [n for n, led in ledgers.items() if sid in led]
            assert len(holders) == 1, f"{sid} migrated across {holders}"
            assert ledgers[holders[0]][sid] == n_view_reqs
        assert {r.params_version for r in svc.replicas} == {"v1"}
        snap = svc.metrics_snapshot()
        assert snap["counters"]["router_requests_total"] >= (
            n_sessions * n_view_reqs)
        assert snap["counters"]["router_rollouts_total"] == 1
        assert snap["fleet"]["sessions"]["active"] == n_sessions
    finally:
        svc.stop()


@pytest.mark.lock_witness
def test_http_backpressure_503_retry_after_and_fleet_route(fleet_env,
                                                           lock_witness):
    """The HTTP surface of the fleet contract: a fully-draining fleet
    503s with a ``Retry-After`` header (typed ReplicaDraining), GET
    /fleet exposes topology + sessions, and the router counters ride
    GET /metrics."""
    import json
    import urllib.error
    import urllib.request

    cfg, model, params, sampler = fleet_env
    svc = make_fleet(cfg, sampler, n=2).start(serve_http=True)
    try:
        base = f"http://127.0.0.1:{svc.port}"
        payload = {"views": {k: v.tolist() for k, v in _views(3).items()},
                   "seed": 3, "n_views": 3, "block": False,
                   "session_id": "http-sess"}
        body = json.dumps(payload).encode()

        def post():
            return urllib.request.urlopen(urllib.request.Request(
                f"{base}/synthesize", data=body,
                headers={"Content-Type": "application/json"}), timeout=30)

        for rep in svc.replicas:
            assert rep.drain(timeout=10)
        with pytest.raises(urllib.error.HTTPError) as ei:
            post()
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        assert "draining" in json.loads(ei.value.read())["error"]

        for rep in svc.replicas:
            rep.resume()
        with post() as resp:
            assert resp.status == 202
            rid = json.loads(resp.read())["id"]
        req = svc.get_request(rid)
        req.result(timeout=60)

        with urllib.request.urlopen(f"{base}/fleet", timeout=30) as resp:
            fleet = json.loads(resp.read())
        assert set(fleet["replicas"]) == {"r0", "r1"}
        assert fleet["sessions"]["active"] == 1
        owner = _owner_of(svc, "http-sess")
        assert fleet["sessions"]["per_replica"] == {owner: 1}

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert "router_requests_total" in text
        assert "router_rejected_total" in text
        for rep in svc.replicas:
            assert f"router_replica_depth_{rep.name}" in text
    finally:
        svc.stop()


def test_schedule_aware_routing_heterogeneous_fleet(fleet_env):
    """per-replica schedules: 2-step DDIM traffic lands on the one
    replica that compiled it (whatever the load), and a schedule nobody
    compiled is rejected with the fleet-wide union."""
    cfg, model, params, sampler = fleet_env
    student = Sampler(model, params, cfg, sampler_kind="ddim", steps=2)
    svc = make_fleet(cfg, sampler, n=3,
                     per_replica_extra={1: {("ddim", 2): student}})
    svc.start(serve_http=False)
    try:
        req = svc.router.submit(
            ViewRequest(_views(5), seed=5, n_views=3, session_id="distill",
                        sampler_kind="ddim", steps=2))
        req.result(timeout=120)              # one tiny 2-step compile
        assert _owner_of(svc, "distill") == "r1"
        with pytest.raises(UnsupportedSchedule) as ei:
            svc.router.submit(ViewRequest(_views(6), seed=6, n_views=3,
                                          sampler_kind="ddim", steps=7))
        assert "ddim:2" in ei.value.supported
        health = svc.health()
        assert "ddim:2" in health["supported_schedules"]
    finally:
        svc.stop()


@pytest.mark.chaos
@pytest.mark.lock_witness
def test_replica_kill_failover_and_session_lost(fleet_env, lock_witness):
    """Chaos: a replica dies mid-dispatch (seeded kill fault).  Its
    sticky sessions get a typed SessionLost NAMING the lost replica
    (never a hang, never a silent re-place); sessionless traffic fails
    over to the survivors and keeps completing."""
    cfg, model, params, sampler = fleet_env
    inj = FaultInjector(seed=0)
    svc = make_fleet(cfg, sampler).start(serve_http=False)
    try:
        sites = {rep.name: arm_replica(rep, inj) for rep in svc.replicas}
        # Pin a session and find its owner — that replica is the victim.
        first = svc.router.submit(ViewRequest(_views(7), seed=7, n_views=3,
                                              session_id="doomed"))
        first.result(timeout=60)
        victim = _owner_of(svc, "doomed")
        inj.add(sites[victim], kind="kill", first_n=1 << 30, max_fires=1)

        # The next sticky view triggers the kill mid-dispatch.
        dying = svc.router.submit(ViewRequest(_views(8), seed=8, n_views=3,
                                              session_id="doomed"))
        with pytest.raises(RetryableError):
            dying.result(timeout=60)
        _wait_for(lambda: svc.router.replica(victim).health == "dead",
                  what="victim death")

        with pytest.raises(SessionLost) as ei:
            svc.router.submit(ViewRequest(_views(9), seed=9, n_views=3,
                                          session_id="doomed"))
        assert ei.value.replica == victim
        assert ei.value.retry_after_s is not None

        ok = svc.router.submit(ViewRequest(_views(10), seed=10, n_views=3))
        ok.result(timeout=60)                # survivors still serve
        snap = svc.metrics_snapshot()
        assert snap["counters"]["router_sessions_lost_total"] == 1
        assert snap["counters"]["router_failover_total"] >= 1
        assert svc.health()["status"] == "ok"
        assert svc.health()["replicas"][victim] == "dead"
    finally:
        svc.stop()
