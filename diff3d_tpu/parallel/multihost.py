"""Multi-host bring-up.

The reference hardcodes ``MASTER_ADDR=localhost`` and spawns one process
per GPU with gloo TCP rendezvous (``/root/reference/train.py:181-187``) —
single-node only.  Here ONE process drives every chip of its host, so a
single-host start never touches ``jax.distributed``; a multi-process job
names its coordinator (``JAX_COORDINATOR_ADDRESS``, which JAX itself
reads, or explicit arguments) and after the rendezvous ``jax.devices()``
spans every host and the mesh layer (``mesh.py``) scales unchanged.
"""

from __future__ import annotations

import logging
import os

import jax

from diff3d_tpu.runtime.retry import (RetryPolicy,
                                      is_transient_backend_error)

log = logging.getLogger(__name__)

#: Coordinator dial retry: at pod bring-up the coordinator process and
#: the workers race, so the first dial routinely lands before the
#: coordinator listens (UNAVAILABLE / connection refused).  Only
#: transient transport faults retry; config errors surface immediately.
_INIT_RETRY = RetryPolicy(max_attempts=4, base_delay_s=5.0,
                          max_delay_s=30.0,
                          classify=is_transient_backend_error)


def maybe_initialize_distributed(coordinator_address: str | None = None,
                                 num_processes: int | None = None,
                                 process_id: int | None = None,
                                 retry: RetryPolicy | None = None) -> bool:
    """Initialise JAX's multi-host runtime if a multi-process job was
    configured; returns whether this process is part of one.

    MUST run before any other JAX call (``jax.distributed.initialize``
    refuses once a backend exists) — call it first thing in ``main``.

    A job is configured when a coordinator is named: the
    ``coordinator_address`` argument or ``JAX_COORDINATOR_ADDRESS`` in
    the environment.  Without one this is a single-host start and NO call
    is made — a bare ``jax.distributed.initialize()`` would go looking
    for a cluster (metadata-server lookups that hang or retry for minutes
    on a machine without a network).  With one, what is still unset
    (``num_processes``, ``process_id``) is left to JAX's cluster
    detection, transient coordinator-dial faults (workers racing the
    coordinator at bring-up) are retried under ``retry`` (default: 4
    attempts with 5-30 s backoff), and any other failure propagates: a
    configured multi-process start that fails, fails.
    """
    if not (coordinator_address
            or os.environ.get("JAX_COORDINATOR_ADDRESS")):
        log.debug("single-host start: jax.distributed not initialised")
        return False
    policy = retry or _INIT_RETRY
    try:
        policy.call(
            lambda: jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id),
            describe="jax.distributed.initialize")
    except RuntimeError as e:
        if "only be called once" not in str(e):
            raise
        log.debug("jax.distributed already initialised")
    log.info("jax.distributed up: process %d/%d, %d global devices",
             jax.process_index(), jax.process_count(), jax.device_count())
    return jax.process_count() > 1


def shutdown_distributed() -> bool:
    """Tear down the multi-host runtime if one is up; True if it was.

    The elasticity path (``train/trainer.py::ElasticSupervisor``) calls
    this between re-mesh cycles: after a host-set change the old
    coordinator channel is stale, and ``jax.distributed.initialize``
    refuses while a previous client exists.  Safe to call when nothing
    was initialised (returns False) — single-process chaos tests drive
    the same code path as a real pod shrink.
    """
    try:
        from jax._src import distributed as _dist
        live = getattr(_dist.global_state, "client", None) is not None
    except Exception:  # pragma: no cover - private API moved
        live = True  # let shutdown() itself decide
    if not live:
        return False
    try:
        jax.distributed.shutdown()
    except Exception as e:  # pragma: no cover - best effort teardown
        log.warning("jax.distributed.shutdown failed: %s", e)
        return False
    log.info("jax.distributed torn down for re-mesh")
    return True


def reinitialize_distributed(coordinator_address: str | None = None,
                             num_processes: int | None = None,
                             process_id: int | None = None,
                             retry: RetryPolicy | None = None) -> bool:
    """Tear down and re-dial the multi-host runtime for a new host set.

    One re-mesh cycle of the elasticity loop: :func:`shutdown_distributed`
    drops the stale coordinator client, then
    :func:`maybe_initialize_distributed` re-dials under the usual
    bring-up retry policy (workers race the restarted coordinator exactly
    as at first launch).  Returns the new multi-process status.
    Single-host runs (no coordinator named) are a cheap no-op returning
    False, so the supervisor can call this unconditionally.
    """
    shutdown_distributed()
    return maybe_initialize_distributed(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id, retry=retry)


def is_primary() -> bool:
    """True on the process that owns checkpoint/metric writes (the
    reference gates these on rank 0, ``train.py:287-298``)."""
    return jax.process_index() == 0


def shard_host_local(tree, sharding):
    """Assemble per-host local batch arrays into global sharded arrays.

    Each host's loader yields its own ``global_batch / num_hosts`` slice
    (``InfiniteLoader(host_id=..., num_hosts=...)``); multi-process runs
    must go through ``jax.make_array_from_process_local_data`` so the
    global array's shards come from each host's slice — a plain
    ``device_put`` would treat every host's (different) local array as
    the same global value, which is undefined across processes.
    Single-process keeps the cheap ``device_put``.
    """
    import numpy as np

    if jax.process_count() > 1:
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), tree)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)
