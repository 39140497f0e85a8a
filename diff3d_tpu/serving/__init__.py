"""Batched novel-view inference service.

Turns the offline :class:`diff3d_tpu.sampling.Sampler` into a long-running
service: a bounded scheduler microbatches concurrent requests into
fixed-shape device batches (bucketed by image size and record capacity), a
device-executor engine drives the object-batched per-view scan and admits
new requests *between* views (continuous batching at view granularity —
3DiM's 256-step-per-view sampler makes per-request latency batch-bound,
not step-bound), and a stdlib HTTP frontend exposes submit/poll, health
and metrics endpoints.  Above the single engine, the fleet router
(``serving/router.py`` + ``serving/fleet.py``) runs N replicas behind one
front door with session affinity (device-resident records never migrate),
typed fleet backpressure, blue/green params rollout and schedule-aware
placement.  The cross-process fleet (``serving/transport.py`` +
``serving/worker.py``) puts the same replica surface behind a socket:
workers pin replicas to disjoint device slices, the router fronts them
through :class:`RemoteReplica` with zero placement changes, and
HBM-budgeted admission rejects at the door with a typed
:class:`ReplicaOverBudget`.
"""

from diff3d_tpu.serving.cache import (ParamsRegistry, ProgramCache,
                                      ResultCache)
from diff3d_tpu.serving.engine import (Engine, EngineStopTimeout,
                                       HEALTH_DEGRADED, HEALTH_DRAINING,
                                       HEALTH_OK)
from diff3d_tpu.serving.fleet import HEALTH_DEAD, Replica, build_fleet
from diff3d_tpu.serving.metrics import MetricsRegistry
from diff3d_tpu.serving.router import FleetService, Router
from diff3d_tpu.serving.scheduler import (Bucket, EngineDraining,
                                          EngineOverloaded, EngineStepError,
                                          EngineStopped, FleetOverloaded,
                                          QueueFullError, ReplicaDraining,
                                          ReplicaOverBudget,
                                          RequestCancelled, RequestTimeout,
                                          Scheduler, SessionLost,
                                          TrajectoryRequest,
                                          UnsupportedSchedule, ViewRequest)
from diff3d_tpu.serving.server import (ServingService, build_request,
                                       build_trajectory_request,
                                       make_http_server)
from diff3d_tpu.serving.transport import (FrameGarbage, FrameTooLarge,
                                          FrameTruncated, RemoteReplica,
                                          TransportError)
from diff3d_tpu.serving.worker import HbmAdmission, Worker, boot_worker

__all__ = [
    "Bucket", "Engine", "EngineDraining", "EngineOverloaded",
    "EngineStepError", "EngineStopTimeout", "EngineStopped",
    "FleetOverloaded", "FleetService", "FrameGarbage", "FrameTooLarge",
    "FrameTruncated", "HEALTH_DEAD", "HEALTH_DEGRADED",
    "HEALTH_DRAINING", "HEALTH_OK", "HbmAdmission", "MetricsRegistry",
    "ParamsRegistry", "ProgramCache", "QueueFullError", "RemoteReplica",
    "Replica", "ReplicaDraining", "ReplicaOverBudget", "RequestCancelled",
    "RequestTimeout", "ResultCache", "Router", "Scheduler",
    "ServingService", "SessionLost", "TransportError",
    "TrajectoryRequest", "UnsupportedSchedule", "ViewRequest",
    "Worker", "boot_worker", "build_fleet", "build_request",
    "build_trajectory_request", "make_http_server",
]
