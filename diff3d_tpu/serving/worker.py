"""Worker process: one replica behind the socket transport.

The far end of ``serving/transport.py``: a :class:`Worker` wraps one
:class:`~diff3d_tpu.serving.fleet.Replica` (touching ONLY the replica
duck-type surface, so tests can wrap scripted fakes) and serves the
framed RPC protocol — submit / poll / state / drain / resume / kill /
swap_params / snapshot / depth / supports / session ledger — plus an
optional HTTP front door (the single-replica surface: /healthz,
/metrics, /stats, /synthesize) for direct inspection of a worker.

Three things live here beyond plumbing (DESIGN.md §19):

**HBM-budgeted admission.**  The worker loads its programs' peak-HBM
manifests (the ``runs/memcheck/`` pins, ``memcheck --update``'s output)
at boot and rejects *at the door* — before any device work, before the
request even reaches the replica — when admitting a request would push
the slice past its budget::

    resident_record_bytes + request_record_bytes + program_peak_bytes
        > hbm_budget_bytes   ->  ReplicaOverBudget (503 + Retry-After)

``resident_record_bytes`` counts the device-resident record buffers of
every request still in flight on this worker (capacity × H × W × 3
float32 each — the autoregressive record the session conditions on);
``program_peak_bytes`` is the manifest pin for the request's compiled
program.  Budget, resident and headroom surface on the ``state`` RPC,
``health()`` and ``GET /stats`` so the router and operators see the
same arithmetic that rejected the request.

**Persistent compile cache.**  ``worker_cli`` places it through
:func:`diff3d_tpu.runtime.configure_compile_cache` before the first
trace, so replica scale-out and blue/green worker restarts reuse each
other's XLA compilations instead of paying a cold compile per process.

**Replica×mesh-slice placement.**  :func:`boot_worker` builds the
replica's :class:`~diff3d_tpu.parallel.mesh.MeshEnv` over an explicit
*device subset* (``jax.devices()[lo:hi]``).  The CPU tests split the
8-virtual-device mesh 2×4 between two worker processes; that layout is
for the virtual CPU mesh only.  A TPU belongs to one process at a time:
on a real host the second worker process cannot open the device, so ONE
process drives all local chips and in-process
:class:`~diff3d_tpu.serving.fleet.Replica` objects are the supported
single-host layout.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import threading
from http.server import ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from diff3d_tpu.analysis import membudgets
from diff3d_tpu.config import Config
from diff3d_tpu.serving.scheduler import (ReplicaOverBudget, RequestTimeout,
                                          ViewRequest)
from diff3d_tpu.serving.transport import (DEFAULT_MAX_FRAME_BYTES,
                                          FrameGarbage, FrameTooLarge,
                                          FrameTruncated, TransportError,
                                          encode_error, recv_frame,
                                          request_from_wire, send_frame)

log = logging.getLogger(__name__)

#: Programs whose manifests a worker preloads: the serving step
#: programs per sampler kind (the scan that renders views) plus the
#: warmup trace and the two cascade phase programs (DESIGN.md §20).
#: ``step_many`` is the ancestral sampler's program; other kinds append
#: their name (matching memcheck's registry).
SERVING_PROGRAMS = ("step_many", "step_many_ddim", "serving_warmup",
                    "step_many_cascade_draft", "step_many_cascade_refine")


def program_for_schedule(sampler_kind: Optional[str],
                         phase: Optional[str] = None) -> str:
    """memcheck program name for a request's (resolved) sampler kind.
    A cascade phase child maps to its phase program regardless of kind
    — the phase, not the schedule, names the compiled scan."""
    if phase is not None:
        return f"step_many_cascade_{phase}"
    if sampler_kind in (None, "ancestral"):
        return "step_many"
    return f"step_many_{sampler_kind}"


class HbmAdmission:
    """The admission gate: budget arithmetic over resident records.

    Tracks the record bytes of every in-flight request (reserved at
    admission, released when the request resolves) and the per-program
    peak pins from the memcheck manifests.  ``budget_bytes <= 0``
    disables the gate (the default for tests that only exercise the
    transport).
    """

    def __init__(self, budget_bytes: int = 0,
                 manifest_dir: str = membudgets.DEFAULT_MANIFEST_DIR,
                 replica_name: str = "?",
                 retry_after_s: float = 5.0):
        self.budget_bytes = int(budget_bytes)
        self.replica_name = replica_name
        self.retry_after_s = float(retry_after_s)
        self._lock = threading.Lock()
        self._reserved: Dict[str, int] = {}  # guarded-by: self._lock
        self._rejects = 0  # guarded-by: self._lock
        self._warned_unpinned: set = set()  # guarded-by: self._lock
        self.program_peaks: Dict[str, int] = {}
        self._load_manifests(manifest_dir)

    def _load_manifests(self, manifest_dir: str) -> None:
        for program in SERVING_PROGRAMS:
            path = membudgets.manifest_path(program, manifest_dir)
            if not os.path.exists(path):
                continue
            try:
                manifest = membudgets.load_manifest(path)
            except (ValueError, json.JSONDecodeError) as e:
                log.warning("hbm admission: unreadable manifest %s: %s",
                            path, e)
                continue
            self.program_peaks[program] = manifest.budgets.peak_bytes

    @staticmethod
    def record_bytes(req: ViewRequest) -> int:
        """Device-resident record footprint of one admitted request:
        the float32 record buffer the autoregressive sampler conditions
        on (capacity × H × W × 3 lanes of 4 bytes)."""
        b = req.bucket
        return b.capacity * b.H * b.W * 3 * 4

    def program_peak(self, sampler_kind: Optional[str],
                     phase: Optional[str] = None) -> int:
        """Manifest pin for the request's program; a kind with no
        committed manifest is charged the largest known pin (admission
        must stay conservative for unpinned programs, not free) — and
        warns once per program name, so an unpinned cascade phase
        riding the fallback is visible, not silent."""
        program = program_for_schedule(sampler_kind, phase)
        peak = self.program_peaks.get(program)
        if peak is not None:
            return peak
        fallback = max(self.program_peaks.values(), default=0)
        with self._lock:
            warn = program not in self._warned_unpinned
            if warn:
                self._warned_unpinned.add(program)
        if warn:
            log.warning(
                "hbm admission: program %r has no committed memcheck "
                "manifest pin — charging the largest known pin "
                "(%d bytes); run `python -m diff3d_tpu.analysis.memcheck "
                "--update` to pin it", program, fallback)
        return fallback

    def admit(self, req: ViewRequest,
              default_kind: Optional[str] = None) -> None:
        """Reserve the request's footprint or raise
        :class:`ReplicaOverBudget` — atomic under the gate's lock, so
        two concurrent submits can never both squeeze under the line.

        Cascade work is charged its actual phase pin: a phase child
        carries ``bucket.phase``, and a cascade parent (whose children
        have not been derived yet) is charged the refine pin — the
        full-resolution phase, i.e. the cascade's own peak — instead of
        the cross-program largest-pin fallback."""
        if self.budget_bytes <= 0:
            return
        kind = req.sampler_kind if req.sampler_kind is not None \
            else default_kind
        phase = getattr(req.bucket, "phase", None) \
            if req.bucket is not None else None
        if phase is None and getattr(req, "is_cascade", False):
            phase = "refine"
        need = self.record_bytes(req)
        peak = self.program_peak(kind, phase=phase)
        with self._lock:
            resident = sum(self._reserved.values())
            if resident + need + peak > self.budget_bytes:
                self._rejects += 1
                raise ReplicaOverBudget(
                    f"{req.id}: admitting {need} record bytes would "
                    f"exceed the slice HBM budget: resident {resident} "
                    f"+ record {need} + program peak {peak} > budget "
                    f"{self.budget_bytes}",
                    replica=self.replica_name,
                    retry_after_s=self.retry_after_s,
                    budget_bytes=self.budget_bytes,
                    resident_bytes=resident,
                    program_peak_bytes=peak)
            self._reserved[req.id] = need

    def release(self, request_id: str) -> None:
        with self._lock:
            self._reserved.pop(request_id, None)

    def snapshot(self) -> dict:
        """The /stats + state-RPC block: the exact arithmetic admission
        runs, so a rejected client can see why."""
        with self._lock:
            resident = sum(self._reserved.values())
            rejects = self._rejects
        return {
            "budget_bytes": self.budget_bytes,
            "resident_bytes": resident,
            "headroom_bytes": (self.budget_bytes - resident
                               if self.budget_bytes > 0 else None),
            "program_peaks": dict(self.program_peaks),
            "rejects": rejects,
            "enabled": self.budget_bytes > 0,
        }


class Worker:
    """Socket server exposing one replica over the framed protocol.

    One accept loop, one handler thread per connection (RemoteReplica
    holds two long-lived connections — control + poller — and dials
    ephemeral ones for lifecycle calls).  Handler threads do pure host
    work; device calls stay on the replica's engine thread, so ``state``
    probes answer while a multi-minute job is on the chip.
    """

    def __init__(self, replica, cfg: Config, *,
                 host: str = "127.0.0.1", port: int = 0,
                 admission: Optional[HbmAdmission] = None,
                 default_sampler_kind: Optional[str] = None):
        self.replica = replica
        self.cfg = cfg
        self.host = host
        self._requested_port = int(port)
        self.admission = admission or HbmAdmission(
            0, replica_name=replica.name)
        self._default_kind = default_sampler_kind
        self.max_frame_bytes = int(getattr(
            cfg.serving, "max_frame_bytes", DEFAULT_MAX_FRAME_BYTES))
        self._lock = threading.Lock()
        self._requests: Dict[str, ViewRequest] = {}  # guarded-by: self._lock
        self._conns: List[socket.socket] = []  # guarded-by: self._lock
        self._stopping = False  # guarded-by: self._lock
        self._sock: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        # Worker-side metrics: reuse the replica's registry when it has
        # one (Replica does) so /metrics shows engine + admission in one
        # exposition; scripted fakes get a private registry.
        metrics = getattr(replica, "metrics", None)
        if metrics is None:
            from diff3d_tpu.serving.metrics import MetricsRegistry
            metrics = MetricsRegistry()
        self.metrics = metrics
        self._rejects_ctr = metrics.counter(
            "worker_admission_rejects_hbm_total",
            "requests rejected at the door by the HBM admission gate")
        self._resident_gauge = metrics.gauge(
            "worker_hbm_resident_bytes",
            "record bytes of in-flight requests counted by admission")
        self._headroom_gauge = metrics.gauge(
            "worker_hbm_headroom_bytes",
            "bytes left under the slice HBM budget (0 when disabled)")

    # -- lifecycle -------------------------------------------------------

    def start(self, http_port: Optional[int] = None) -> "Worker":
        self.replica.start()
        self._sock = socket.create_server((self.host, self._requested_port))
        self._sock.listen(32)
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"diff3d-worker-{self.replica.name}", daemon=True)
        self._accept_thread.start()
        if http_port is not None:
            from diff3d_tpu.serving.server import make_http_server
            self._httpd = make_http_server(self, self.host, http_port)
            self._http_thread = threading.Thread(
                target=self._httpd.serve_forever,
                name=f"diff3d-worker-http-{self.replica.name}", daemon=True)
            self._http_thread.start()
        log.info("worker %s: serving on %s:%d", self.replica.name,
                 self.host, self.port)
        return self

    @property
    def port(self) -> int:
        if self._sock is None:
            return self._requested_port
        return self._sock.getsockname()[1]

    @property
    def http_port(self) -> Optional[int]:
        return self._httpd.server_address[1] if self._httpd else None

    def stop(self, timeout: float = 10.0) -> None:
        """Close the listener and every open connection, then stop the
        replica.  Clients see the close as FrameTruncated and their
        heartbeat marks this worker dead — the abrupt shape a SIGKILL
        would have, which is exactly what the chaos tests rely on."""
        with self._lock:
            if self._stopping:
                return
            self._stopping = True
            conns = list(self._conns)
        if self._sock is not None:
            # shutdown() before close(): close() alone leaves a thread
            # blocked in accept() pinned until the join timeout.
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._sock.close()
            except OSError:
                pass
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._accept_thread is not None:
            self._accept_thread.join(timeout)
        self.replica.stop(timeout=timeout)

    # -- accept / dispatch ----------------------------------------------

    def _accept_loop(self) -> None:
        while True:
            try:
                conn, addr = self._sock.accept()
            except OSError:
                return      # listener closed: shutting down
            with self._lock:
                if self._stopping:
                    conn.close()
                    return
                self._conns.append(conn)
            threading.Thread(
                target=self._serve_conn, args=(conn, addr),
                name=f"diff3d-worker-conn-{addr[1]}", daemon=True).start()

    def _serve_conn(self, conn: socket.socket, addr) -> None:
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while True:
                try:
                    frame = recv_frame(conn, self.max_frame_bytes)
                except (FrameTooLarge, FrameGarbage) as e:
                    # Protocol violation: tell the peer (typed), then
                    # drop the connection — the stream offset is lost.
                    self._reply_error(conn, e)
                    return
                except (FrameTruncated, OSError):
                    return
                if frame is None:
                    return      # clean EOF
                op = str(frame.get("op", ""))
                args = frame.get("args") or {}
                try:
                    value = self._dispatch(op, args)
                except Exception as e:   # typed errors cross the wire
                    self._reply_error(conn, e)
                    continue
                try:
                    send_frame(conn, {"ok": True, "value": value},
                               self.max_frame_bytes)
                except (TransportError, OSError):
                    return
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _reply_error(self, conn: socket.socket, exc: BaseException) -> None:
        try:
            send_frame(conn, {"ok": False, "error": encode_error(exc)},
                       self.max_frame_bytes)
        except (TransportError, OSError):
            pass

    def _dispatch(self, op: str, args: dict) -> Any:
        if op == "ping":
            return "pong"
        if op == "state":
            return self._state()
        if op == "submit":
            return self._op_submit(args)
        if op == "poll":
            return self._op_poll(args)
        if op == "depth":
            return self.replica.depth()
        if op == "supports":
            return bool(self.replica.supports(
                args.get("sampler_kind"), args.get("steps")))
        if op == "session_records":
            return self.replica.session_records()
        if op == "session_count":
            return self.replica.session_count(args.get("session_id"))
        if op == "snapshot":
            snap = dict(self.replica.snapshot())
            snap["hbm"] = self.admission.snapshot()
            return snap
        if op == "drain":
            return bool(self.replica.drain(timeout=args.get("timeout")))
        if op == "resume":
            self.replica.resume()
            return True
        if op == "kill":
            self.replica.kill(str(args.get("reason", "killed")))
            return True
        if op == "swap_params":
            return self._op_swap(args)
        raise ValueError(f"unknown op {op!r}")

    # -- op implementations ----------------------------------------------

    def _state(self) -> dict:
        """The heartbeat payload: everything the RemoteReplica caches."""
        hbm = self.admission.snapshot()
        self._resident_gauge.set(hbm["resident_bytes"])
        self._headroom_gauge.set(hbm["headroom_bytes"] or 0)
        return {
            "name": self.replica.name,
            "health": self.replica.health,
            "depth": self.replica.depth(),
            "params_version": self.replica.params_version,
            "supported_schedules": self.replica.supported_schedules(),
            "session_records": self.replica.session_records(),
            "hbm": hbm,
        }

    def _op_submit(self, args: dict) -> dict:
        req = request_from_wire(args)
        # Admission BEFORE the replica sees the request: a rejected
        # request does no device work and leaves no ledger trace.
        try:
            self.admission.admit(req, default_kind=self._default_kind)
        except ReplicaOverBudget:
            self._rejects_ctr.inc()
            raise
        try:
            self.replica.submit(req)
        except BaseException:
            self.admission.release(req.id)
            raise
        with self._lock:
            self._requests[req.id] = req
        return {"id": req.id, "accepted": True}

    def _op_poll(self, args: dict) -> dict:
        """One poll turn for a submitted request: block up to ``wait_s``
        for progress, then report status + any frames past ``from``.
        Terminal polls release the admission reservation and drop the
        request from the table (the client owns the result now)."""
        rid = str(args.get("id", ""))
        start = max(0, int(args.get("from", 0)))
        wait_s = min(5.0, max(0.0, float(args.get("wait_s", 0.2))))
        with self._lock:
            req = self._requests.get(rid)
        if req is None:
            return {"id": rid, "status": "unknown"}
        out: Dict[str, Any] = {"id": rid, "status": "pending"}
        if req.is_trajectory:
            try:
                frames = req.wait_frames(start, timeout=wait_s)
            except BaseException:
                frames = req.frames_since(start)
            if frames:
                out["frames"] = [np.asarray(f) for f in frames]
        else:
            try:
                req.result(timeout=wait_s)
            except RequestTimeout:
                if not req.done():
                    return out      # genuinely still running
            except BaseException:
                pass                # terminal failure: classified below
        if not req.done():
            return out
        self._forget(rid)
        err = req.error
        if err is not None:
            out["status"] = "failed"
            out["error"] = encode_error(err)
            return out
        out["status"] = "done"
        out["cached"] = bool(req.cached)
        out["result"] = np.asarray(req.result(timeout=0))
        return out

    def _forget(self, rid: str) -> None:
        self.admission.release(rid)
        with self._lock:
            self._requests.pop(rid, None)

    def _op_swap(self, args: dict) -> str:
        """Rebuild the params pytree from wire leaves against the
        replica's own treedef (the registry's shape guard still runs),
        then swap — the blue/green rollout step, cross-process."""
        import jax

        leaves = args.get("leaves")
        if leaves is None:
            raise ValueError("swap_params needs 'leaves'")
        current = getattr(self.replica, "registry", None)
        if current is None:
            # Scripted fakes have no registry: pass leaves through.
            return str(self.replica.swap_params(leaves,
                                                args.get("version")))
        _, params = current.current()
        treedef = jax.tree.structure(params)
        params_new = jax.tree.unflatten(
            treedef, [np.asarray(leaf) for leaf in leaves])
        return str(self.replica.swap_params(params_new,
                                            args.get("version")))

    # -- ServingService duck-type (optional HTTP front door) -------------

    def submit(self, payload: dict) -> ViewRequest:
        from diff3d_tpu.serving.server import build_request
        req = build_request(payload, self.cfg)
        return self._admit_and_submit(req)

    def submit_trajectory(self, payload: dict) -> ViewRequest:
        from diff3d_tpu.serving.server import build_trajectory_request
        req = build_trajectory_request(payload, self.cfg)
        return self._admit_and_submit(req)

    def _admit_and_submit(self, req: ViewRequest) -> ViewRequest:
        try:
            self.admission.admit(req, default_kind=self._default_kind)
        except ReplicaOverBudget:
            self._rejects_ctr.inc()
            raise
        try:
            self.replica.submit(req)
        except BaseException:
            self.admission.release(req.id)
            raise
        with self._lock:
            self._requests[req.id] = req
        return req

    def get_request(self, request_id: str) -> Optional[ViewRequest]:
        with self._lock:
            return self._requests.get(request_id)

    def result_payload(self, req: ViewRequest) -> dict:
        from diff3d_tpu.serving.server import result_payload
        return result_payload(req)

    def health(self) -> dict:
        return {
            "status": self.replica.health,
            "replica": self.replica.name,
            "queue_depth": self.replica.depth(),
            "params_version": self.replica.params_version,
            "supported_schedules": self.replica.supported_schedules(),
            "hbm": self.admission.snapshot(),
        }

    def metrics_snapshot(self, include_memory: bool = False) -> dict:
        extra = {"hbm": self.admission.snapshot(),
                 "replica": self.replica.snapshot()}
        return self.metrics.snapshot(extra=extra)


def device_slice(spec: str) -> List[int]:
    """Parse a ``--devices`` slice: ``"0-3"`` (inclusive range) or
    ``"0,1,2"`` (explicit list) into device indices."""
    spec = spec.strip()
    if "-" in spec and "," not in spec:
        lo, hi = spec.split("-", 1)
        idx = list(range(int(lo), int(hi) + 1))
    else:
        idx = [int(p) for p in spec.split(",") if p.strip()]
    if not idx:
        raise ValueError(f"--devices {spec!r}: empty device slice")
    if len(set(idx)) != len(idx):
        raise ValueError(f"--devices {spec!r}: duplicate device index")
    return idx


def boot_worker(cfg: Config, *, name: str, devices: List[int],
                sampler_kind: str = "ancestral", steps: Optional[int] = None,
                extra_schedules: Optional[List[Tuple[str, int]]] = None,
                params=None, params_version: str = "v0",
                host: str = "127.0.0.1", port: int = 0,
                hbm_budget_bytes: int = 0,
                memcheck_dir: str = membudgets.DEFAULT_MANIFEST_DIR,
                scan_chunks: int = 1) -> Worker:
    """Build a worker: mesh over the device slice, model + samplers,
    replica, admission gate, socket server.  ``params=None`` draws
    random init params (the test/dev path)."""
    import jax

    from diff3d_tpu.models import build_xunet
    from diff3d_tpu.parallel.mesh import make_mesh
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.serving.fleet import Replica
    from diff3d_tpu.train.trainer import init_params

    all_devices = jax.devices()
    bad = [i for i in devices if i >= len(all_devices)]
    if bad:
        raise ValueError(
            f"device indices {bad} out of range: backend has "
            f"{len(all_devices)} devices")
    slice_devices = [all_devices[i] for i in devices]
    mesh_env = make_mesh(cfg.mesh, devices=slice_devices)

    model = build_xunet(cfg, "serving.worker")
    if params is None:
        params = init_params(model, cfg, jax.random.PRNGKey(0))
    default_steps = steps if steps is not None else cfg.diffusion.timesteps
    sampler = Sampler(model, params, cfg, scan_chunks=scan_chunks,
                      mesh=mesh_env, sampler_kind=sampler_kind,
                      steps=default_steps)
    extra = {}
    for kind, n_steps in (extra_schedules or []):
        if (kind, n_steps) == (sampler_kind, default_steps):
            continue
        extra[(kind, n_steps)] = Sampler(
            model, params, cfg, scan_chunks=scan_chunks, mesh=mesh_env,
            sampler_kind=kind, steps=n_steps)

    replica = Replica(name, sampler, cfg, extra_samplers=extra or None,
                      params_version=params_version)
    admission = HbmAdmission(
        hbm_budget_bytes, manifest_dir=memcheck_dir, replica_name=name,
        retry_after_s=cfg.serving.retry_after_s)
    return Worker(replica, cfg, host=host, port=port, admission=admission,
                  default_sampler_kind=sampler_kind)
