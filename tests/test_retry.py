"""Retry-policy and fault-injector units (diff3d_tpu/runtime/retry.py,
diff3d_tpu/testing/faults.py) — no device work, no JAX beyond imports.

These are the contracts every fault-tolerant layer leans on: the trainer
and serving engine wrap dispatches in :class:`RetryPolicy`, the async
checkpoint writer retries commits under it, and the chaos tests drive
all of them through :class:`FaultInjector`.  A behavioral drift here
(e.g. retrying a config error, or a nondeterministic backoff
sequence) silently changes every one of those layers at once.
"""

import pytest

from diff3d_tpu.runtime.retry import (RetryPolicy, RetryableError,
                                      is_transient_backend_error,
                                      is_transient_io_error)
from diff3d_tpu.testing.faults import (FaultInjected, FaultInjector,
                                       wrap_sampler)


def _policy(**kw):
    kw.setdefault("sleep", lambda s: None)       # tests never really sleep
    return RetryPolicy(**kw)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exc,expected", [
    (RuntimeError("UNAVAILABLE: TPU backend stalled"), True),
    (RuntimeError("DEADLINE_EXCEEDED while dialing"), True),
    (ConnectionResetError("connection reset by peer"), True),
    (RetryableError("typed transient"), True),
    (FaultInjected("injected"), True),           # injected == real transient
    (ValueError("bad shape"), False),
    (RuntimeError("XlaRuntimeError: INVALID_ARGUMENT"), False),
])
def test_transient_backend_classification(exc, expected):
    assert is_transient_backend_error(exc) is expected


def test_transient_io_classification():
    assert is_transient_io_error(OSError("disk quota exceeded"))
    assert is_transient_io_error(FaultInjected("injected"))
    assert not is_transient_io_error(ValueError("bad manifest"))
    assert not is_transient_io_error(KeyboardInterrupt())


# ---------------------------------------------------------------------------
# RetryPolicy.call
# ---------------------------------------------------------------------------


def test_retries_then_succeeds_and_logs_attempts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: blip")
        return "ok"

    log = []
    p = _policy(max_attempts=4, base_delay_s=0.5, jitter=0.0)
    assert p.call(flaky, attempts_log=log) == "ok"
    assert calls["n"] == 3
    assert [e["attempt"] for e in log] == [1, 2]
    assert all("UNAVAILABLE" in e["error"] for e in log)
    # exponential growth: 0.5, then 1.0
    assert [e["backoff_s"] for e in log] == [0.5, 1.0]


def test_nonretryable_raises_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("config error")

    with pytest.raises(ValueError, match="config error"):
        _policy(max_attempts=5).call(bad)
    assert calls["n"] == 1


def test_exhaustion_reraises_last_error_unchanged():
    sentinel = RuntimeError("UNAVAILABLE: still down")

    def always():
        raise sentinel

    with pytest.raises(RuntimeError) as ei:
        _policy(max_attempts=3, base_delay_s=0.0).call(always)
    assert ei.value is sentinel          # typed errors survive the policy


def test_backoff_caps_and_constant_growth():
    import random

    p = _policy(base_delay_s=1.0, max_delay_s=4.0, growth=2.0, jitter=0.0)
    rng = random.Random(0)
    assert [p.delay_for(a, rng) for a in (1, 2, 3, 4, 5)] == \
        [1.0, 2.0, 4.0, 4.0, 4.0]
    const = _policy(base_delay_s=7.0, max_delay_s=7.0, growth=1.0,
                    jitter=0.0)
    assert [const.delay_for(a, rng) for a in (1, 4)] == [7.0, 7.0]


def test_jitter_is_deterministic_per_seed():
    slept_a, slept_b, slept_c = [], [], []

    def run(seed, slept):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 4:
                raise RuntimeError("UNAVAILABLE")
            return None

        RetryPolicy(max_attempts=4, base_delay_s=1.0, jitter=0.5,
                    seed=seed, sleep=slept.append).call(flaky)

    run(0, slept_a)
    run(0, slept_b)
    run(1, slept_c)
    assert slept_a == slept_b            # same seed -> same schedule
    assert slept_a != slept_c            # different seed -> different


def test_on_retry_hook_sees_each_failure():
    seen = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE")
        return None

    _policy(max_attempts=3, base_delay_s=0.25, jitter=0.0).call(
        flaky, on_retry=lambda a, e, d: seen.append((a, str(e), d)))
    assert [(a, d) for a, _, d in seen] == [(1, 0.25), (2, 0.5)]


def test_broken_classifier_does_not_mask_the_fault():
    def bad_classify(exc):
        raise RuntimeError("classifier bug")

    with pytest.raises(RuntimeError, match="the real fault"):
        _policy(max_attempts=3, classify=bad_classify).call(
            lambda: (_ for _ in ()).throw(RuntimeError("the real fault")))


# ---------------------------------------------------------------------------
# FaultInjector
# ---------------------------------------------------------------------------


def test_injector_first_n_and_at_calls():
    inj = FaultInjector(seed=0)
    inj.add("site", first_n=2)
    inj.add("site", at_calls=(5,))
    fired = []
    for i in range(1, 7):
        try:
            inj.fire("site")
            fired.append(False)
        except FaultInjected:
            fired.append(True)
    assert fired == [True, True, False, False, True, False]
    assert inj.calls["site"] == 6 and inj.fired["site"] == 3


def test_injector_probabilistic_schedule_replays_exactly():
    def schedule(seed):
        inj = FaultInjector(seed=seed)
        inj.add("s", prob=0.5)
        out = []
        for _ in range(20):
            try:
                inj.fire("s")
                out.append(0)
            except FaultInjected:
                out.append(1)
        return out

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)
    assert 0 < sum(schedule(7)) < 20     # actually mixed


def test_injector_max_fires_and_clear():
    inj = FaultInjector()
    inj.add("s", first_n=100, max_fires=1)
    with pytest.raises(FaultInjected):
        inj.fire("s")
    inj.fire("s")                        # capped: second call clean
    inj.add("s", first_n=100)
    with pytest.raises(FaultInjected):
        inj.fire("s")
    inj.clear("s")
    inj.fire("s")                        # specs gone, counters survive
    assert inj.calls["s"] == 4


def test_injector_custom_exception_and_wrap():
    inj = FaultInjector()
    inj.add("s", at_calls=(1,), exc=lambda: OSError("disk gone"))
    wrapped = inj.wrap("s", lambda x: x + 1)
    with pytest.raises(OSError, match="disk gone"):
        wrapped(1)
    assert wrapped(1) == 2


def test_wrap_sampler_proxies_attributes_and_instruments_step_many():
    class FakeSampler:
        lane_multiple = 2

        def step_many(self, *a, **kw):
            return "stepped"

    inj = FaultInjector()
    inj.add("engine.step", at_calls=(1,))
    s = wrap_sampler(FakeSampler(), inj)
    assert s.lane_multiple == 2          # passthrough
    with pytest.raises(FaultInjected):
        s.step_many()
    assert s.step_many() == "stepped"
    assert inj.calls["engine.step"] == 2
