"""Typed retry policy for transient backend and IO faults.

One policy object answers three questions the trainer and the serving
engine used to answer independently (and differently):

* **Is this error worth retrying?**  Typed classification: anything
  deriving from :class:`RetryableError` is, and for everything else a
  small set of transport-level message markers ("UNAVAILABLE",
  "DEADLINE_EXCEEDED", ...) decides.
* **How long do we wait?**  Exponential backoff with a cap and
  deterministic seeded jitter, so chaos tests replay exactly and a fleet
  of preempted workers does not re-dial in lockstep.
* **What happened?**  ``call(..., attempts_log=...)`` records every
  failed attempt and its backoff so callers (the checkpoint writer log)
  can report what the policy did.

This module deliberately imports no JAX at module scope — classifying
errors and sleeping must stay cheap and importable everywhere, including
before a backend exists.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import time
from typing import Any, Callable, List, Optional

log = logging.getLogger(__name__)


class RetryableError(RuntimeError):
    """A fault the *caller* may safely retry.

    Raised (or subclassed) wherever the system rejects work for a
    transient reason: a failed/stuck engine step, degraded-mode
    admission control, a draining replica.  ``retry_after_s`` is an
    advisory wait; the HTTP layer maps it to a ``Retry-After`` header.
    """

    def __init__(self, msg: str, *, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


#: Lower-cased substrings that mark an exception as a transient
#: transport/backend fault.  Sourced from gRPC status names.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "connection reset",
    "connection refused",
    "socket closed",
    "broken pipe",
    "transport closed",
    "failed to connect",
    "temporarily",
)


def is_transient_backend_error(exc: BaseException) -> bool:
    """True if ``exc`` looks like a transient backend/transport fault."""
    if isinstance(exc, RetryableError):
        return True
    if isinstance(exc, ConnectionError):
        # Reset/refused/aborted against a worker socket: the transport
        # layer retries or the heartbeat declares the peer dead.
        return True
    msg = str(exc).lower()
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


def is_transient_io_error(exc: BaseException) -> bool:
    """True if ``exc`` is a filesystem fault worth retrying.

    Checkpoint commits go to network filesystems in practice, where
    ``OSError`` is routinely transient.  Injected faults
    (:class:`RetryableError` subclasses) count so chaos tests exercise
    the same path.
    """
    return isinstance(exc, (OSError, RetryableError))


@dataclasses.dataclass
class RetryPolicy:
    """Bounded retry with capped exponential backoff and seeded jitter.

    ``classify`` decides retryability; a non-retryable error (or the
    final attempt's error) is re-raised as-is so callers keep their
    typed exceptions.  ``sleep`` is injectable so tests run at full
    speed, and jitter draws from ``random.Random(seed)`` per call so a
    given policy produces the same backoff sequence every time.
    """

    max_attempts: int = 3
    base_delay_s: float = 1.0
    max_delay_s: float = 60.0
    growth: float = 2.0         # 1.0 = constant backoff
    jitter: float = 0.25        # +/- fraction of the delay
    seed: int = 0
    classify: Callable[[BaseException], bool] = is_transient_backend_error
    sleep: Callable[[float], None] = time.sleep

    def delay_for(self, attempt: int, rng: random.Random) -> float:
        """Backoff after failed attempt number ``attempt`` (1-based)."""
        delay = min(self.max_delay_s,
                    self.base_delay_s * self.growth ** (attempt - 1))
        if self.jitter:
            delay *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(0.0, delay)

    def call(self, fn: Callable[[], Any], *,
             describe: str = "call",
             attempts_log: Optional[List[dict]] = None,
             on_retry: Optional[Callable[[int, BaseException, float], None]] = None) -> Any:
        """Run ``fn`` under this policy and return its result.

        Each failed-but-retried attempt appends
        ``{"attempt", "error", "backoff_s"}`` to ``attempts_log`` (if
        given) and invokes ``on_retry(attempt, exc, delay)`` before
        sleeping.  The last error is raised unchanged on exhaustion.
        """
        if self.max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, got {self.max_attempts}")
        rng = random.Random(self.seed)
        for attempt in range(1, self.max_attempts + 1):
            try:
                return fn()
            except Exception as exc:  # noqa: BLE001 - classifier decides
                try:
                    retryable = bool(self.classify(exc))
                except Exception:  # a broken classifier must not mask the fault
                    retryable = False
                if not retryable or attempt >= self.max_attempts:
                    raise
                delay = self.delay_for(attempt, rng)
                if attempts_log is not None:
                    attempts_log.append({
                        "attempt": attempt,
                        "error": str(exc).splitlines()[0][:200] if str(exc) else type(exc).__name__,
                        "backoff_s": round(delay, 4),
                    })
                log.warning("%s: attempt %d/%d failed (%s); retrying in %.2fs",
                            describe, attempt, self.max_attempts, exc, delay)
                if on_retry is not None:
                    on_retry(attempt, exc, delay)
                self.sleep(delay)
        raise AssertionError("unreachable")  # pragma: no cover


class RetryBudget:
    """Progress-aware failure budget for long-lived supervision loops.

    A plain ``RetryPolicy`` bounds *consecutive* attempts of one call; an
    elasticity loop instead needs "give up only after N failures *without
    forward progress*": a run that trains for an hour, gets preempted,
    re-meshes and trains on has earned a fresh budget, while a mesh that
    crashes at bring-up N times in a row is genuinely dead.

    ``spend()`` consumes one unit and returns True while budget remains;
    ``reset()`` refills it (call on observed progress, e.g. the step
    counter advanced past where the cycle started).  Not thread-safe —
    owned by a single supervisor loop.
    """

    def __init__(self, max_failures: int):
        if max_failures < 1:
            raise ValueError(f"max_failures must be >= 1, got {max_failures}")
        self.max_failures = max_failures
        self.spent = 0

    def spend(self) -> bool:
        """Consume one failure; True iff the budget is not yet exhausted."""
        self.spent += 1
        return self.spent < self.max_failures

    def reset(self) -> None:
        self.spent = 0

    @property
    def remaining(self) -> int:
        return max(0, self.max_failures - self.spent)
