"""Orbax-backed checkpoint/resume.

Semantics parity with the reference (``/root/reference/train.py:244-251,
287-298``): periodic saves of ``{model, optim, step}`` (here: the whole
:class:`TrainState` pytree including the EMA the reference lacked), restore
resumes model + optimizer + step exactly, writes gated on the primary
process.  TPU-native upgrades: async array writes, step-indexed directories
with retention, sharded-array-aware restore (each host reads only its
shards back).

Save modes:
  * ``"full"`` (default) — the whole TrainState; exact resume.
  * ``"ema_bf16"`` — ``{step, ema_params}`` with params cast to bfloat16:
    ~1/16 the bytes of the full state (no Adam moments, no raw params,
    half-width floats): a full-width srn64 TrainState is ~2.2 GB, its
    bf16 EMA ~270 MB.  Restoring gives eval-grade weights and a *warm restart* (optimizer moments are
    re-zeroed), not an exact resume.
  * ``"full_sliced"`` — the whole TrainState streamed leaf-by-leaf as N
    sequential small device->host fetches + ``.npy`` writes with
    per-leaf retry, committed atomically (write to ``<step>.tmp``,
    rename).  Same EXACT-resume semantics as ``full`` (params, EMA,
    Adam moments, step): a transient fault costs one leaf's retry, not
    the whole save, and no single fetch ever moves more than the
    largest parameter (a few MB).  Single-host writer (each
    leaf is fully fetched); pods should keep Orbax ``full``.

The directory carries a ``ckpt_format.json`` marker so readers
(``eval_cli``, ``Trainer(transfer=True)``) auto-detect the mode; an
unmarked directory is ``"full"`` (all checkpoints written before the
marker existed were full).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import queue
import shutil
import threading
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from diff3d_tpu.parallel.multihost import is_primary
from diff3d_tpu.runtime.retry import RetryPolicy, is_transient_io_error
from diff3d_tpu.train.state import TrainState

log = logging.getLogger(__name__)

_MARKER = "ckpt_format.json"
_SLICED_MANIFEST = "sliced_manifest.json"
MODES = ("full", "ema_bf16", "full_sliced")


class CheckpointMismatchError(ValueError):
    """A checkpoint/target disagreement caught by restore preflight.

    Raised *before* any ``device_put`` when the on-disk manifest and the
    target abstract state disagree on tree structure, a leaf's shape or
    a leaf's dtype — naming the offending leaf, expected vs found, and
    the checkpoint step, instead of letting the mismatch surface as a
    raw XLA error deep inside resharding.  Subclasses ``ValueError`` so
    callers that caught the old untyped errors keep working.

    Note: a *topology* (mesh) difference is NOT an error — resharding a
    checkpoint into a different mesh is the elasticity loop's normal
    resume path (see :attr:`CheckpointManager.last_restore_reshard`).
    Only value-changing mismatches (shape/dtype/structure) are refused.
    """

    def __init__(self, msg: str, *, leaf: str | None = None,
                 expected=None, found=None, step: int | None = None):
        super().__init__(msg)
        self.leaf = leaf
        self.expected = expected
        self.found = found
        self.step = step

#: Per-leaf device->host fetch retry for sliced saves.  Any exception is
#: retried (matching the historical behavior: a transient link fault
#: costs one leaf's retry, not the whole save); the delays mirror the
#: old hand-rolled 5s/10s schedule.
_DEFAULT_FETCH_RETRY = RetryPolicy(
    max_attempts=3, base_delay_s=5.0, max_delay_s=10.0, growth=2.0,
    jitter=0.0, classify=lambda exc: True)

#: Commit retry for the async writer: exponential backoff + jitter over
#: filesystem faults.  The commit rebuilds its tmp dir from the host
#: snapshot on every attempt, so a half-written tmp tree from a failed
#: attempt is simply clobbered.
_DEFAULT_WRITE_RETRY = RetryPolicy(
    max_attempts=4, base_delay_s=0.5, max_delay_s=8.0, growth=2.0,
    jitter=0.25, classify=is_transient_io_error)


@dataclasses.dataclass
class _SlicedSnapshot:
    """A fully host-resident copy of one TrainState, ready to write.

    Built on the *training* thread (device->host fetches must not race
    the train step's donated buffers); consumed by the writer thread,
    which touches only these numpy arrays and the filesystem.
    """

    step: int
    arrays: List[np.ndarray]     # bf16 already re-viewed as uint16
    manifest: dict


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3,
                 save_interval_steps: int | None = None,
                 mode: str | None = None,
                 async_writes: bool = False,
                 max_inflight_saves: int = 2,
                 write_retry: RetryPolicy | None = None,
                 fetch_retry: RetryPolicy | None = None,
                 fault_hook: Callable[[str], None] | None = None):
        """``mode=None`` (readers, resume-without-flag) follows the
        directory's ``ckpt_format.json`` marker, defaulting to "full" on
        an unmarked directory.  An explicit mode must AGREE with an
        existing marker — silently overriding in either direction would
        either mislabel full checkpoints or quietly discard the user's
        exact-resume request.

        ``async_writes`` applies to ``full_sliced`` only (the Orbax
        modes are already async): :meth:`save` snapshots device->host on
        the calling thread, then a background writer commits the files
        with retry/backoff.  At most ``max_inflight_saves`` snapshots are
        queued — beyond that :meth:`save` blocks (backpressure, bounding
        host RAM at ``max_inflight_saves`` extra TrainState copies).  A
        write failure that survives ``write_retry`` surfaces at the next
        :meth:`save` or at the :meth:`wait_until_finished` durability
        barrier, never silently.  The written directory layout is
        byte-identical to a sync save — restore is shared and the sync
        path (``async_writes=False``) stays available as the parity
        oracle.

        ``fault_hook`` is a testing seam (see
        :mod:`diff3d_tpu.testing.faults`): called with a site name
        (``"snapshot"``, ``"write"``, ``"commit"``) at each sliced-save
        IO point so chaos tests can inject failures deterministically.
        """
        if mode is not None and mode not in MODES:
            raise ValueError(f"mode={mode!r} not in {MODES}")
        self._dir = os.path.abspath(directory)
        marker = os.path.join(self._dir, _MARKER)
        if os.path.exists(marker):
            with open(marker) as f:
                marked = json.load(f)["mode"]
            if marked not in MODES:
                raise ValueError(
                    f"{marker} declares unknown mode {marked!r}")
            if mode is not None and mode != marked:
                raise ValueError(
                    f"{self._dir} is marked mode={marked!r} but "
                    f"mode={mode!r} was requested — use a fresh "
                    "checkpoint directory to change modes")
            self.mode = marked
        else:
            self.mode = mode or "full"
        self._keep = keep
        #: Optional ``MeshEnv.topology_summary()`` dict; when set, sliced
        #: manifests record the mesh the state was sharded over at save
        #: time, and restore logs a first-class reshard when the target
        #: topology differs (writer-thread-free: set once at bring-up).
        self.mesh_info: dict | None = None
        #: After a restore whose save-time mesh differs from the current
        #: one: ``{"step", "from", "to"}`` (None otherwise).  The
        #: elasticity supervisor reads this to log/metric the reshard.
        self.last_restore_reshard: dict | None = None
        self._fire = fault_hook or (lambda site: None)
        self._fetch_retry = fetch_retry or _DEFAULT_FETCH_RETRY
        self._write_retry = write_retry or _DEFAULT_WRITE_RETRY
        self._async = bool(async_writes) and self.mode == "full_sliced"
        self._async_lock = threading.Lock()
        self._async_error: BaseException | None = None  # guarded-by: self._async_lock
        self._pending_steps: set[int] = set()  # guarded-by: self._async_lock
        self._queue: queue.Queue = queue.Queue()
        self._inflight_sem = threading.Semaphore(max(1, max_inflight_saves))
        self._writer: threading.Thread | None = None
        if self.mode == "full_sliced":
            # No Orbax involvement: saves are plain per-leaf .npy files
            # under <dir>/<step>/ with an atomic-rename commit.  The
            # writer fully fetches every leaf, which needs all shards
            # addressable and exactly one writer — single-host only
            # (pods keep Orbax 'full', whose per-host shard IO is the
            # point).
            if jax.process_count() > 1:
                raise ValueError(
                    "ckpt mode 'full_sliced' is single-host only "
                    f"(process_count={jax.process_count()}); use 'full'")
            self._mgr = None
            # Orbax handles interval gating for the managed modes; the
            # sliced writer applies the same semantics itself in save().
            self._save_interval = save_interval_steps or 1
            if is_primary():
                os.makedirs(self._dir, exist_ok=True)
        else:
            options = ocp.CheckpointManagerOptions(
                max_to_keep=keep,
                save_interval_steps=save_interval_steps or 1,
                create=True,
                enable_async_checkpointing=True,
            )
            self._mgr = ocp.CheckpointManager(self._dir, options=options)
        if not os.path.exists(marker) and self.mode != "full":
            # Never mislabel existing data: an unmarked directory that
            # already holds checkpoints holds FULL TrainStates (every
            # writer of non-full data writes the marker first), and
            # stamping it ema_bf16/full_sliced would wedge restores of
            # those steps.
            existing = (self._sliced_steps() if self._mgr is None
                        else ([self._mgr.latest_step()]
                              if self._mgr.latest_step() is not None
                              else []))
            has_orbax_dirs = any(
                d.isdigit() and not os.path.exists(
                    os.path.join(self._dir, d, _SLICED_MANIFEST))
                for d in (os.listdir(self._dir)
                          if os.path.isdir(self._dir) else []))
            if existing or (self._mgr is None and has_orbax_dirs):
                raise ValueError(
                    f"{self._dir} already contains full checkpoints; "
                    f"refusing to relabel the directory mode={self.mode!r} "
                    "— use a fresh checkpoint directory")
            if is_primary():
                os.makedirs(self._dir, exist_ok=True)
                with open(marker, "w") as f:
                    json.dump({"mode": self.mode}, f)

    # ---- full_sliced internals -------------------------------------

    def _sliced_steps(self):
        if not os.path.isdir(self._dir):
            return []
        return sorted(
            int(d) for d in os.listdir(self._dir)
            if d.isdigit() and os.path.exists(
                os.path.join(self._dir, d, _SLICED_MANIFEST)))

    def _snapshot_sliced(self, state: TrainState) -> _SlicedSnapshot:
        """Device->host copy of every leaf, on the calling thread.

        Must run on the training thread: the train step donates its
        input state, so fetching from a background thread would race
        buffer donation.  Holds one full host copy of the state (the
        price of decoupling the writer from the training loop).
        """
        self._fire("snapshot")
        step = int(jax.device_get(state.step))
        flat, _ = jax.tree_util.tree_flatten_with_path(state)
        leaves = [leaf for _, leaf in flat]
        arrays: List[np.ndarray] = []
        manifest = {
            "step": step,
            "leaves": [],
            # Leaf paths make preflight mismatches nameable ("params.
            # conv1.kernel expects ..."), and the save-time mesh makes a
            # cross-topology restore a recognised reshard, not a guess.
            "paths": [jax.tree_util.keystr(p) for p, _ in flat],
        }
        if self.mesh_info is not None:
            manifest["mesh"] = self.mesh_info
        for i, leaf in enumerate(leaves):
            def _fetch(leaf=leaf):
                # MUST be an owned copy: device_get may return a
                # zero-copy VIEW of the live device buffer (CPU
                # backend), and the training loop DONATES the state to
                # the next step — an async writer serializing that view
                # would read freed/reused memory.
                return np.array(jax.device_get(leaf), copy=True)
            arr = self._fetch_retry.call(
                _fetch, describe=f"sliced save: leaf {i} fetch")
            dtype = str(arr.dtype)       # ml_dtypes name, e.g. 'bfloat16'
            if dtype == "bfloat16":      # np.save can't round-trip bf16
                arr = arr.view(np.uint16)
            arrays.append(arr)
            manifest["leaves"].append(
                {"dtype": dtype, "shape": list(arr.shape)})
        return _SlicedSnapshot(step=step, arrays=arrays, manifest=manifest)

    def _commit_sliced(self, snap: _SlicedSnapshot) -> None:
        """Write one snapshot to disk and atomically publish it.

        Pure filesystem work over host arrays — safe on any thread, and
        safe to retry: each attempt rebuilds the tmp dir from scratch,
        so a half-written tree from a failed attempt is clobbered and
        readers only ever see the atomic ``os.replace`` result.
        """
        final = os.path.join(self._dir, str(snap.step))
        if os.path.exists(final):
            return
        tmp = final + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        for i, arr in enumerate(snap.arrays):
            self._fire("write")
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), arr)
        with open(os.path.join(tmp, _SLICED_MANIFEST), "w") as f:
            json.dump(snap.manifest, f)
        self._fire("commit")
        os.replace(tmp, final)           # commit: readers never see partial
        if self._keep and self._keep > 0:   # keep<=0 means keep-all
            for old in self._sliced_steps()[: -self._keep]:
                shutil.rmtree(os.path.join(self._dir, str(old)),
                              ignore_errors=True)

    def _writer_loop(self) -> None:
        while True:
            snap = self._queue.get()
            if snap is None:
                self._queue.task_done()
                return
            try:
                self._write_retry.call(
                    lambda: self._commit_sliced(snap),
                    describe=f"async ckpt commit (step {snap.step})")
            except BaseException as e:
                # Surfaced at the next save() or wait_until_finished():
                # a durability failure must reach the training loop, not
                # die with this thread.
                log.exception(
                    "async checkpoint commit failed permanently (step %d)",
                    snap.step)
                with self._async_lock:
                    self._async_error = e
            finally:
                with self._async_lock:
                    self._pending_steps.discard(snap.step)
                self._inflight_sem.release()
                self._queue.task_done()

    def _raise_deferred_error(self) -> None:
        with self._async_lock:
            err, self._async_error = self._async_error, None
        if err is not None:
            raise err

    def _save_sliced(self, state: TrainState, force: bool = False) -> bool:
        # A previously failed async save surfaces here, before new work:
        # durable checkpointing being broken must halt the run, not pass
        # silently while checkpoints quietly stop landing.
        self._raise_deferred_error()
        step = int(jax.device_get(state.step))
        if not force and step % self._save_interval:
            return False       # same gating Orbax applies in managed modes
        with self._async_lock:
            pending = step in self._pending_steps
        if pending or os.path.exists(os.path.join(self._dir, str(step))):
            return False
        snap = self._snapshot_sliced(state)
        if not self._async:
            self._commit_sliced(snap)    # sync parity oracle
            return True
        if self._writer is None:
            self._writer = threading.Thread(
                target=self._writer_loop, name="diff3d-ckpt-writer",
                daemon=True)
            self._writer.start()
        with self._async_lock:
            self._pending_steps.add(step)
        self._inflight_sem.acquire()     # backpressure: bounded in-flight
        self._queue.put(snap)
        return True

    def _restore_sliced(self, abstract_state: TrainState,
                        step: int | None) -> Optional[TrainState]:
        steps = self._sliced_steps()
        if step is not None and step not in steps:
            # An explicitly requested step that isn't there (never saved,
            # or pruned by retention) is a caller error worth naming —
            # not a raw FileNotFoundError from the manifest open below.
            raise ValueError(
                f"sliced checkpoint step {step} not found in {self._dir}; "
                f"available steps: {steps or 'none'}")
        step = step if step is not None else (steps[-1] if steps else None)
        if step is None:
            return None
        d = os.path.join(self._dir, str(step))
        with open(os.path.join(d, _SLICED_MANIFEST)) as f:
            manifest = json.load(f)
        abs_flat, treedef = jax.tree_util.tree_flatten_with_path(
            abstract_state)
        abs_leaves = [leaf for _, leaf in abs_flat]
        abs_paths = [jax.tree_util.keystr(p) for p, _ in abs_flat]
        # Older manifests (pre-elasticity) carry no paths: name leaves by
        # the target's paths, which are positionally correct whenever the
        # leaf count matches at all.
        paths = manifest.get("paths") or abs_paths
        if len(abs_leaves) != len(manifest["leaves"]):
            raise CheckpointMismatchError(
                f"sliced checkpoint at {d} (step {step}) has "
                f"{len(manifest['leaves'])} leaves; the target state has "
                f"{len(abs_leaves)} — model/optimizer config mismatch",
                expected=len(abs_leaves), found=len(manifest["leaves"]),
                step=step)
        # Preflight the WHOLE manifest before touching any device: a
        # mismatch at leaf 400 must not surface after 399 device_puts.
        for i, (sds, meta) in enumerate(zip(abs_leaves,
                                            manifest["leaves"])):
            name = paths[i] if i < len(paths) else f"leaf {i}"
            if tuple(meta["shape"]) != tuple(sds.shape):
                raise CheckpointMismatchError(
                    f"sliced checkpoint at {d} (step {step}): leaf "
                    f"{name!r} has shape {tuple(meta['shape'])}, target "
                    f"expects {tuple(sds.shape)} — model/optimizer "
                    "config mismatch",
                    leaf=name, expected=tuple(sds.shape),
                    found=tuple(meta["shape"]), step=step)
            if meta["dtype"] != str(sds.dtype):
                # A dtype mismatch is a config mismatch (e.g. restoring a
                # float32 run into a bf16-param config): silently casting
                # would hand back numerically different weights.
                raise CheckpointMismatchError(
                    f"sliced checkpoint at {d} (step {step}): leaf "
                    f"{name!r} was saved as {meta['dtype']}, target "
                    f"expects {sds.dtype} — model/optimizer config "
                    "mismatch",
                    leaf=name, expected=str(sds.dtype),
                    found=meta["dtype"], step=step)
        saved_mesh = manifest.get("mesh")
        self.last_restore_reshard = None
        if saved_mesh is not None and self.mesh_info is not None \
                and saved_mesh != self.mesh_info:
            # First-class reshard: the slices below are device_put into
            # the TARGET topology's shardings — restoring an 8-device
            # checkpoint onto 4 devices (or vice versa) is the elasticity
            # loop's normal resume, not an error.
            self.last_restore_reshard = {
                "step": step, "from": saved_mesh, "to": self.mesh_info}
            log.info("resharding checkpoint step %d: saved on %s -> "
                     "restoring into %s", step, saved_mesh, self.mesh_info)
        out = []
        for i, (sds, meta) in enumerate(zip(abs_leaves,
                                            manifest["leaves"])):
            arr = np.load(os.path.join(d, f"leaf_{i:05d}.npy"))
            if meta["dtype"] == "bfloat16":
                arr = jnp.asarray(arr.view(np.uint16)).view(jnp.bfloat16)
            else:
                arr = jnp.asarray(arr)
            # jnp.asarray may zero-copy ALIAS the freshly-loaded numpy
            # buffer (CPU backend, alignment permitting).  Restored
            # leaves feed a donating jit, and donation frees through the
            # XLA allocator — freeing an aliased numpy buffer corrupts
            # the heap.  jnp.copy lands the leaf in an XLA-owned buffer.
            arr = jnp.copy(arr)
            sharding = getattr(sds, "sharding", None)
            out.append(jax.device_put(arr, sharding)
                       if sharding is not None else arr)
        return jax.tree_util.tree_unflatten(treedef, out)

    # ---- public API ------------------------------------------------

    def save(self, state: TrainState, *, force: bool = False) -> bool:
        if self.mode == "full_sliced":
            return self._save_sliced(state, force=force)
        step = int(jax.device_get(state.step))
        if self.mode == "ema_bf16":
            payload = {
                "step": state.step,
                "ema_params": jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16), state.ema_params),
            }
        else:
            payload = state
        return self._mgr.save(step, args=ocp.args.StandardSave(payload),
                              force=force)

    def latest_step(self) -> Optional[int]:
        if self.mode == "full_sliced":
            steps = self._sliced_steps()
            return steps[-1] if steps else None
        return self._mgr.latest_step()

    def restore(self, abstract_state: TrainState,
                step: int | None = None) -> Optional[TrainState]:
        """Restore into the shardings/dtypes of ``abstract_state`` (build it
        with ``jax.eval_shape`` + the mesh's sharding rules).  Returns None
        when no checkpoint exists (fresh run, like the reference's
        ``--transfer`` being absent).

        Only valid for exact-resume directories (``full`` /
        ``full_sliced``) — an ``ema_bf16`` directory has no optimizer
        state to restore; use :meth:`restore_ema` (raises ValueError
        otherwise, rather than silently handing back a half-initialized
        state).
        """
        if self.mode == "full_sliced":
            return self._restore_sliced(abstract_state, step)
        if self.mode != "full":
            raise ValueError(
                f"restore() on a mode={self.mode!r} checkpoint dir; use "
                "restore_ema() and rebuild the optimizer state")
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        return self._mgr.restore(
            step, args=ocp.args.StandardRestore(abstract_state))

    def restore_ema(self, abstract_params,
                    step: int | None = None) -> Optional[Tuple[int, object]]:
        """Restore ``(step, ema_params)`` from an ``ema_bf16`` directory.

        ``abstract_params`` is the params pytree of ShapeDtypeStructs (its
        dtypes are the *target* dtypes — bf16-stored arrays are upcast on
        the way in).  Raises ValueError on a ``full`` directory: restoring
        only the EMA leaf there would need the whole abstract TrainState
        anyway, so callers branch on :attr:`mode` (see
        ``cli/_common.py:load_eval_params`` for the mode-agnostic wrapper).
        """
        if self.mode in ("full", "full_sliced"):
            raise ValueError(
                "restore_ema() from a full checkpoint needs the whole "
                "abstract TrainState; call restore() and read .ema_params")
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        abstract_bf16 = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                           sharding=s.sharding),
            abstract_params)
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(
                {"step": jax.ShapeDtypeStruct((), jnp.int32),
                 "ema_params": abstract_bf16}))
        ema = jax.tree.map(
            lambda x, s: x.astype(s.dtype), restored["ema_params"],
            abstract_params)
        return int(restored["step"]), ema

    def wait_until_finished(self) -> None:
        """Durability barrier: returns only once every accepted save is
        committed on disk, raising any deferred write failure.

        The preemption path depends on this contract — "saved then
        exited" must mean the checkpoint actually landed, for async
        saves exactly as for sync ones.
        """
        if self._mgr is not None:
            self._mgr.wait_until_finished()
            return
        if self._writer is not None:
            self._queue.join()
        self._raise_deferred_error()

    def wait(self) -> None:
        self.wait_until_finished()

    def close(self) -> None:
        if self._mgr is not None:
            self._mgr.close()
            return
        if self._writer is not None:
            self._queue.put(None)        # sentinel: drain then exit
            self._writer.join(timeout=60.0)
            if self._writer.is_alive():  # pragma: no cover - stuck disk
                log.error("checkpoint writer did not exit within 60s")
            self._writer = None
