"""Trainer: wires data, mesh, compiled step, checkpoints, and metrics.

Capability parity with both reference trainers (raw-DDP ``train.py:200-303``
and Lightning ``lightning/train.py`` + ``lightning/diff3d.py:77-127``),
minus their defects (SURVEY.md §2.7): the data path is correctly sharded
per host, gradients actually all-reduce (compiled from shardings), warmup
follows the documented 10M-example intent, checkpoints never reference
undefined state, and there are no per-step host barriers.

Observability the reference lacks: JSONL metrics (loss / lr / grad-norm /
steps-per-sec / examples-per-sec), optional ``jax.profiler`` traces.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import os
import random
import signal
import threading
import time
from typing import Callable, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.config import Config
from diff3d_tpu.diffusion import p_losses
from diff3d_tpu.models import build_model
from diff3d_tpu.parallel import MeshEnv, make_mesh
from diff3d_tpu.parallel.multihost import is_primary
from diff3d_tpu.runtime.retry import (RetryBudget, RetryPolicy,
                                      is_transient_backend_error)
from diff3d_tpu.train.checkpoint import CheckpointManager
from diff3d_tpu.train.state import TrainState, create_train_state
from diff3d_tpu.train.step import make_train_step
from diff3d_tpu.utils.profiling import RECORDER, profile_window

log = logging.getLogger(__name__)

#: Retry around each compiled-step dispatch.  Only errors the shared
#: classifier calls transient (UNAVAILABLE, connection resets, ...) are
#: retried — those surface at dispatch, before the donated input buffers
#: are consumed.  A real execution failure is non-retryable and
#: propagates to the emergency-checkpoint path.
_STEP_RETRY = RetryPolicy(max_attempts=3, base_delay_s=5.0,
                          max_delay_s=30.0,
                          classify=is_transient_backend_error)


def _input_wait() -> tuple:
    """``(seconds waited for batches, times the prefetch queue was found
    empty)`` so far, from ``prefetch_to_device``'s span and counter."""
    return (RECORDER.totals().get("prefetch.wait", (0, 0.0))[1],
            int(RECORDER.counters().get("prefetch.starved", 0)))


def init_params(model, cfg: Config, rng: jax.Array):
    """Initialise params with a dummy batch (shapes only).  Compiled —
    eager flax init dispatches thousands of tiny device ops; one compiled
    program does not."""
    H, W = cfg.model.H, cfg.model.W
    batch = {
        "x": jnp.zeros((1, H, W, 3)),
        "z": jnp.zeros((1, H, W, 3)),
        "logsnr": jnp.zeros((1, 2)),
        "R": jnp.broadcast_to(jnp.eye(3), (1, 2, 3, 3)),
        "t": jnp.zeros((1, 2, 3)),
        "K": jnp.broadcast_to(jnp.eye(3), (1, 3, 3)),
    }
    return jax.jit(
        lambda r: model.init({"params": r}, batch,
                             cond_mask=jnp.ones((1,), bool))
    )(rng)["params"]


class Trainer:
    def __init__(self, cfg: Config, loader: Optional[Iterator] = None,
                 env: Optional[MeshEnv] = None,
                 workdir: str = ".", transfer: bool = False):
        """``loader`` may be attached after construction (``self.loader``) —
        a resuming caller needs the restored step (``int(self.state.step)``)
        to build a loader that seeks the data stream to the right batch."""
        cfg.validate()
        self.cfg = cfg
        self.loader = loader
        self.env = env or make_mesh(cfg.mesh)
        self.workdir = workdir
        self.model = build_model(cfg)
        self.rng = jax.random.PRNGKey(cfg.train.seed)

        params = init_params(self.model, cfg, self.rng)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        log.info("%s: %.1fM params", type(self.model).__name__,
                 n_params / 1e6)
        state = create_train_state(params, cfg.train)

        # Place the fresh state according to the mesh policy before any
        # compile, so fsdp never materialises a replicated copy.
        self.state = jax.device_put(state, self._state_shardings(state))

        self.ckpt = CheckpointManager(
            os.path.join(workdir, cfg.train.checkpoint_dir),
            keep=cfg.train.keep_checkpoints,
            mode=cfg.train.ckpt_mode,
            async_writes=cfg.train.ckpt_async)
        # Stamp the mesh topology BEFORE any restore: sliced manifests
        # record the save-time mesh, and a restore into a different
        # topology is then recognised (and logged) as a first-class
        # reshard — the elasticity re-mesh contract (DESIGN.md §16).
        self.ckpt.mesh_info = self.env.topology_summary()
        if transfer:
            if self.ckpt.mode == "ema_bf16":
                # Warm restart: EMA-only checkpoints carry no optimizer
                # moments, so params and EMA both start from the restored
                # EMA and Adam re-accumulates; the lr schedule is advanced
                # to the restored step so warmup does not re-run.
                abstract = self._abstract_state()
                got = self.ckpt.restore_ema(abstract.params)
                if got is not None:
                    step, ema = got
                    from diff3d_tpu.train.state import advance_schedule
                    ema = jax.device_put(
                        ema, self._state_shardings(self.state).params)
                    self.state = self.state.replace(
                        step=jnp.asarray(step, jnp.int32),
                        params=ema,
                        # DISTINCT buffers: the train step donates the
                        # state, and donating the same buffer via two
                        # leaves fails at execute time.
                        ema_params=jax.tree.map(jnp.copy, ema),
                        opt_state=advance_schedule(self.state.opt_state,
                                                   step))
                    log.info("warm-restarted (ema_bf16) at step %d", step)
            else:
                restored = self.ckpt.restore(self._abstract_state())
                if restored is not None:
                    # Re-place on the mesh policy: restore() hands back
                    # single-device arrays (full_sliced leaves may even
                    # alias the loader's host buffers), and the donating
                    # sharded step must only ever see jax-owned buffers
                    # laid out like the fresh-state path above.
                    self.state = jax.device_put(
                        restored, self._state_shardings(restored))
                    log.info("resumed at step %d", int(self.state.step))

        self.step_fn = make_train_step(self.model, cfg, self.env)
        self._metrics_path = os.path.join(workdir, "metrics.jsonl")
        self._preempted = threading.Event()
        self.preempt_observed_step: Optional[int] = None
        self._preempt_uninstall = None   # cached by install_preemption_handler
        self._in_handler = False         # re-entrancy guard (main thread only)
        self._eval_fn = None
        self.val_loader: Optional[Iterator] = None

    def install_preemption_handler(
            self, signals=(signal.SIGTERM, signal.SIGINT)):
        """Catch preemption signals and finish gracefully: the training
        loop checkpoints the current state, waits on the checkpoint
        durability barrier, and returns instead of dying mid-step.
        SIGTERM is what TPU maintenance / spot reclamation sends;
        SIGINT makes a Ctrl-C'd interactive run exit just as cleanly.
        Restart with ``transfer=True`` to resume.  (The reference's only
        recovery story is rerunning with ``--transfer`` from the last
        50-step save — ``train.py:238-251``.)

        Returns an ``uninstall()`` callable that restores the previous
        handlers — installation is no longer forever, so tests and
        embedding processes (e.g. a notebook driving several trainers)
        can scope the handler to one training run.

        Idempotent and re-entrant (the elasticity loop installs and
        uninstalls every re-mesh cycle): a second ``install`` returns
        the existing uninstaller instead of chaining the handler onto
        itself, a second ``uninstall()`` is a no-op, and a signal
        arriving while the handler is already running only sets the stop
        flag — it does not recursively re-chain the previous handler.
        """
        if self._preempt_uninstall is not None:
            # Already installed: handing out a fresh chain here would
            # make the handler its own `prev` and recurse on delivery.
            return self._preempt_uninstall

        prev = {}

        def handler(signum, frame):
            log.warning("signal %d: checkpointing and stopping", signum)
            self._preempted.set()
            if self._in_handler:
                # Signal-during-signal (repeated SIGTERM from an
                # impatient scheduler): the flag is set, the chained
                # notifier already ran — re-chaining would recurse.
                return
            self._in_handler = True
            try:
                # Chain whatever handler was installed before us — on
                # pods, jax.distributed.initialize registers the
                # preemption-sync notifier on SIGTERM, and clobbering it
                # would leave reached_preemption_sync_point permanently
                # False.  The default SIGINT handler is deliberately NOT
                # chained: it raises KeyboardInterrupt, which would turn
                # this graceful stop into the emergency-checkpoint crash
                # path.
                p = prev.get(signum)
                if callable(p) and p is not signal.default_int_handler:
                    p(signum, frame)
            finally:
                self._in_handler = False

        for s in signals:
            prev[s] = signal.getsignal(s)
            signal.signal(s, handler)

        def uninstall():
            if self._preempt_uninstall is not uninstall:
                return                   # already uninstalled: no-op
            self._preempt_uninstall = None
            for s, p in prev.items():
                # Only restore what we still own — if someone installed
                # their own handler after us, clobbering it here would
                # repeat the exact bug this handle exists to fix.
                if signal.getsignal(s) is handler:
                    signal.signal(s, p if p is not None else signal.SIG_DFL)

        self._preempt_uninstall = uninstall
        return uninstall

    def _stop_requested(self, step: int) -> bool:
        """Multi-host-safe preemption check.  A process-local flag alone
        would deadlock a pod: hosts observing SIGTERM at different step
        boundaries would split between a collective checkpoint save and a
        collective train step.  On multi-process runs the decision goes
        through the coordination service's preemption-sync protocol (any
        host's notice propagates to all — our signal handler chains JAX's
        notifier — and all hosts agree on the same stop step); the local
        flag feeds single-process runs and tests."""
        if jax.process_count() > 1:
            try:
                from jax.experimental import multihost_utils

                return multihost_utils.reached_preemption_sync_point(step)
            except Exception:
                # No preemption-sync manager in this runtime: the local
                # flag is the only signal left.  Hosts may observe it at
                # different steps — a hang risk, but strictly better than
                # ignoring the preemption and losing the state entirely.
                return self._preempted.is_set()
        return self._preempted.is_set()

    def _eval_step(self, state: TrainState, batch, rng):
        """Validation loss (EMA params, no dropout, no CFG randomness
        beyond the rng given) — compiled on first use with the same
        global shardings as the train step, so multi-host runs evaluate
        ONE globally-assembled val batch (each host contributes its
        shard) rather than racing host-local batches through a shared
        computation."""
        from diff3d_tpu.parallel.multihost import shard_host_local
        batch = shard_host_local(batch, self.env.batch())
        if self._eval_fn is None:
            dcfg = self.cfg.diffusion

            def eval_fn(params, batch, rng):
                def denoise(model_batch, cond_mask):
                    return self.model.apply({"params": params}, model_batch,
                                            cond_mask=cond_mask)
                from diff3d_tpu.data.images import dequantize
                return p_losses(
                    denoise, dequantize(batch["imgs"]), batch["R"],
                    batch["T"], batch["K"], rng, cond_prob=dcfg.cond_prob,
                    loss_type=dcfg.loss_type, logsnr_min=dcfg.logsnr_min,
                    logsnr_max=dcfg.logsnr_max)

            self._eval_fn = jax.jit(
                eval_fn,
                in_shardings=(self.env.params(state.ema_params),
                              jax.tree.map(lambda _: self.env.batch(),
                                           batch),
                              self.env.replicated()),
                out_shardings=self.env.replicated())
        return self._eval_fn(state.ema_params, batch, rng)

    def _state_shardings(self, state: TrainState) -> TrainState:
        return self.env.state_shardings(state)

    def _abstract_state(self) -> TrainState:
        abstract = jax.eval_shape(
            lambda s: s, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state))
        sh = self._state_shardings(abstract)
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, sh)

    def _log(self, record: dict) -> None:
        if not is_primary():
            return
        with open(self._metrics_path, "a") as f:
            f.write(json.dumps(record) + "\n")

    def train(self, max_steps: Optional[int] = None,
              profile_steps: Optional[tuple] = None) -> TrainState:
        """Run the training loop.

        ``profile_steps=(start, stop)`` captures a ``jax.profiler`` device
        trace of those steps into ``<workdir>/profile`` through
        :func:`~diff3d_tpu.utils.profiling.profile_window`, which leaves
        ``by_scope.json`` (device seconds by block class, idle by host
        span) beside it (start after the first step so the compile isn't
        traced).

        Each ``metrics.jsonl`` record carries ``input_wait_s`` (seconds of
        its log window the loop waited for a batch) and ``starved`` (how
        often the prefetch queue was empty when asked), read from the
        loader's own spans and counter.

        Failure handling the reference lacks (SURVEY.md §5.3): a non-finite
        loss halts with a checkpoint-preserving ``FloatingPointError``
        instead of silently training on garbage, and any exception inside
        the loop triggers a best-effort emergency checkpoint so ``transfer=
        True`` (the reference's ``--transfer``) resumes at the last step.
        """
        if self.loader is None:
            raise ValueError("attach a loader before train()")
        cfg = self.cfg.train
        max_steps = max_steps if max_steps is not None else cfg.max_steps
        t0 = time.monotonic()
        # Host-side step mirror: avoids a device sync per iteration (the
        # jitted step runs async; we only block at log boundaries).
        step = int(self.state.step)
        window_start, window_t = step, t0
        window_wait, window_starved = _input_wait()
        profile = contextlib.ExitStack()     # holds the open profile window

        try:
            while step < max_steps:
                if profile_steps and step == profile_steps[0]:
                    profile.enter_context(profile_window(
                        os.path.join(self.workdir, "profile")))

                batch = next(self.loader)
                batch = {"imgs": batch["imgs"], "R": batch["R"],
                         "T": batch["T"], "K": batch["K"]}
                # Transient backend faults at dispatch (UNAVAILABLE,
                # reset connections) get the shared retry policy; real
                # step failures are non-retryable and fall through to
                # the emergency checkpoint below.
                self.state, metrics = _STEP_RETRY.call(
                    lambda: self.step_fn(self.state, batch, self.rng),
                    describe=f"train step {step + 1}")
                step += 1

                if profile_steps and step == profile_steps[1]:
                    jax.block_until_ready(metrics["loss"])
                    profile.close()

                if ((cfg.log_every > 0 and step % cfg.log_every == 0)
                        or step >= max_steps):
                    jax.block_until_ready(metrics["loss"])
                    now = time.monotonic()
                    dt = max(now - window_t, 1e-9)
                    sps = (step - window_start) / dt
                    window_start, window_t = step, now
                    wait, starved = _input_wait()
                    loss = float(metrics["loss"])
                    rec = {
                        "step": step,
                        "loss": loss,
                        "lr": float(metrics["lr"]),
                        "grad_norm": float(metrics["grad_norm"]),
                        "steps_per_sec": sps,
                        "examples_per_sec": sps * cfg.global_batch,
                        "wall_s": now - t0,
                        "input_wait_s": wait - window_wait,
                        "starved": starved - window_starved,
                    }
                    window_wait, window_starved = wait, starved
                    self._log(rec)
                    log.info("step %d loss %.4f (%.2f steps/s)",
                             step, rec["loss"], sps)
                    if not np.isfinite(loss):
                        raise FloatingPointError(
                            f"non-finite loss {loss} at step {step}; "
                            "last finite checkpoint preserved")

                saved_this_step = False
                # ckpt_every <= 0 disables periodic saves (the final-step
                # and preemption saves still run) instead of crashing on
                # a modulo-by-zero
                if ((cfg.ckpt_every > 0 and step % cfg.ckpt_every == 0)
                        or step >= max_steps):
                    # Never persist a poisoned state: ckpt cadence need not
                    # align with log cadence, so check this step's health
                    # here too.  grad_norm covers the finite-loss /
                    # non-finite-gradient case (the loss is computed from
                    # pre-update params, so it can look fine while the
                    # just-updated params are already NaN).
                    loss = float(metrics["loss"])
                    gnorm = float(metrics["grad_norm"])
                    if not (np.isfinite(loss) and np.isfinite(gnorm)):
                        raise FloatingPointError(
                            f"non-finite loss {loss} / grad_norm {gnorm} "
                            f"at step {step}; last finite checkpoint "
                            "preserved")
                    saved_this_step = self.ckpt.save(self.state)

                if (self.val_loader is not None and cfg.eval_every
                        and (step % cfg.eval_every == 0
                             or step >= max_steps)):
                    vb = next(self.val_loader)
                    # Distinct stream tag: the train step already consumes
                    # fold_in(rng, step) (step.py), so fold an eval-only
                    # constant on top to decorrelate val noise draws from
                    # that step's train draws.
                    eval_rng = jax.random.fold_in(
                        jax.random.fold_in(self.rng, step), 0xE7A1)
                    vloss = float(self._eval_step(
                        self.state,
                        {"imgs": vb["imgs"], "R": vb["R"], "T": vb["T"],
                         "K": vb["K"]},
                        eval_rng))
                    self._log({"step": step, "val_loss": vloss})
                    log.info("step %d val_loss %.4f", step, vloss)

                if self._stop_requested(step):
                    # Graceful preemption: persist the exact step and stop.
                    # Skip the save if the ckpt_every branch above already
                    # wrote this step — force=True would delete and rewrite
                    # the finished checkpoint, reopening the loss window a
                    # mid-rewrite SIGKILL was supposed to be protected from.
                    self.preempt_observed_step = step
                    log.warning("preemption flag observed at step %d",
                                step)
                    if not saved_this_step:
                        # The periodic branches carry the NaN guard; with
                        # log/ckpt cadences disabled nothing has checked
                        # this step, and the preemption save must uphold
                        # "never persist a poisoned state" on its own.
                        loss = float(metrics["loss"])
                        gnorm = float(metrics["grad_norm"])
                        if not (np.isfinite(loss) and np.isfinite(gnorm)):
                            raise FloatingPointError(
                                f"non-finite loss {loss} / grad_norm "
                                f"{gnorm} at preemption (step {step}); "
                                "last finite checkpoint preserved")
                        self.ckpt.save(self.state, force=True)
                    # Durability barrier: "saved then stopped" must mean
                    # the bytes are committed before the process exits —
                    # async saves make this wait load-bearing.
                    self.ckpt.wait_until_finished()
                    log.warning("preempted at step %d; state saved", step)
                    break
        except FloatingPointError:
            raise
        except BaseException:
            # Preemption / OOM / data error: keep the last good state so a
            # restart with transfer=True loses at most ckpt_every steps.
            try:
                self.ckpt.save(self.state, force=True)
                self.ckpt.wait_until_finished()
            except Exception:  # pragma: no cover - best effort
                log.exception("emergency checkpoint failed")
            raise
        finally:
            profile.close()      # a window still open on a mid-window exit

        self.ckpt.wait()
        return self.state


# ---- elasticity -----------------------------------------------------

#: Typed elasticity states (DESIGN.md §16).  They flow into the train
#: log and ``metrics.jsonl`` as ``{"elastic": <state>, ...}`` records so
#: a long elastic run is auditable after the fact: every disruption, the
#: topology it re-meshed to, and the step it resumed from.
ELASTIC_RUNNING = "RUNNING"
ELASTIC_REMESHING = "REMESHING"
ELASTIC_RESUMED = "RESUMED"
ELASTIC_GAVE_UP = "GAVE_UP"


@dataclasses.dataclass(frozen=True)
class ElasticEvent:
    """One elasticity state transition."""

    state: str          # one of the ELASTIC_* constants
    cycle: int          # 1-based re-mesh cycle this event belongs to
    step: int           # trainer step at the transition
    n_devices: int      # device count of the cycle's mesh (0 = unknown)
    reason: str = ""    # disruption cause / reshard description
    wall_s: float = 0.0

    def record(self) -> dict:
        return {"elastic": self.state, "cycle": self.cycle,
                "step": self.step, "n_devices": self.n_devices,
                "reason": self.reason, "wall_s": round(self.wall_s, 3)}


class ElasticityGaveUp(RuntimeError):
    """The supervisor exhausted its no-progress failure budget.

    Carries the full event history so the operator (or the chaos
    harness) sees every cycle's disposition, not just the last error.
    """

    def __init__(self, msg: str, events: List[ElasticEvent]):
        super().__init__(msg)
        self.events = list(events)


class ElasticSupervisor:
    """Re-mesh-and-resume loop around :meth:`Trainer.train`.

    The dynamic half of fault tolerance (ROADMAP item 5; PR 3 landed the
    static half): on a preemption (SIGTERM observed by the trainer's
    handler) or a transient backend fault (failed collective, reset
    transport), the supervisor tears the live cycle down, re-initialises
    the distributed runtime for the surviving host set, rebuilds the
    mesh/shardings for the new topology, restores the latest durable
    checkpoint — resharded into the new mesh by the ``full_sliced``
    restore path — and resumes the input pipeline deterministically
    (``make_loader(step, env)`` re-derives each host's shard of the
    global stream from the restored step; see the loader's elasticity
    determinism rule).

    Give-up policy: ``retry.max_attempts`` consecutive cycles *without
    forward progress* (the durable step never advanced) exhaust the
    :class:`~diff3d_tpu.runtime.retry.RetryBudget` and raise
    :class:`ElasticityGaveUp`; any cycle that advanced the step refills
    the budget — a run preempted hourly for a week should never die.

    Seams (all injectable, so chaos tests script real topology changes
    on a single host):

    * ``make_loader(step, env)`` — build the cycle's input iterator,
      seeked to ``step`` and partitioned for ``env``'s topology;
    * ``topology_fn()`` — devices for the next mesh (None = all);
    * ``reinit_fn()`` — distributed-runtime re-dial (default re-dials
      only on real multi-process jobs via
      :func:`~diff3d_tpu.parallel.multihost.reinitialize_distributed`);
    * ``fault_hook(site)`` — fired at ``"elastic.cycle"`` each bring-up
      (a :class:`~diff3d_tpu.testing.faults.FaultInjector` seam).
    """

    def __init__(self, cfg: Config,
                 make_loader: Callable[[int, MeshEnv], Iterator],
                 workdir: str = ".",
                 topology_fn: Optional[Callable[[], list]] = None,
                 reinit_fn: Optional[Callable[[], object]] = None,
                 retry: Optional[RetryPolicy] = None,
                 fault_hook: Optional[Callable[[str], None]] = None):
        self.cfg = cfg
        self.make_loader = make_loader
        self.workdir = workdir
        self.topology_fn = topology_fn
        self.reinit_fn = reinit_fn
        self.retry = retry or RetryPolicy(
            max_attempts=8, base_delay_s=2.0, max_delay_s=60.0,
            classify=is_transient_backend_error)
        self._budget = RetryBudget(self.retry.max_attempts)
        self._fire = fault_hook or (lambda site: None)
        self._metrics_path = os.path.join(workdir, "metrics.jsonl")
        self._lock = threading.Lock()
        self._events: List[ElasticEvent] = []  # guarded-by: self._lock
        self.trainer: Optional[Trainer] = None

    @property
    def events(self) -> List[ElasticEvent]:
        with self._lock:
            return list(self._events)

    def _emit(self, ev: ElasticEvent) -> None:
        with self._lock:
            self._events.append(ev)
        # File IO strictly after the lock is released (LC303): the event
        # list is shared with readers, the metrics file is not.
        log.warning("elastic %s: cycle %d step %d on %d devices%s",
                    ev.state, ev.cycle, ev.step, ev.n_devices,
                    f" ({ev.reason})" if ev.reason else "")
        if is_primary():
            with open(self._metrics_path, "a") as f:
                f.write(json.dumps(ev.record()) + "\n")

    def _give_up(self, cycle: int, step: int, n_dev: int, reason: str,
                 t0: float) -> None:
        self._emit(ElasticEvent(ELASTIC_GAVE_UP, cycle, step, n_dev,
                                reason, time.monotonic() - t0))
        raise ElasticityGaveUp(
            f"elasticity budget exhausted: {self._budget.spent} "
            f"consecutive no-progress cycles (last: {reason})", self.events)

    def run(self, max_steps: Optional[int] = None) -> TrainState:
        """Train to ``max_steps``, surviving preemptions and transient
        backend faults by re-meshing; returns the final state."""
        max_steps = (max_steps if max_steps is not None
                     else self.cfg.train.max_steps)
        t0 = time.monotonic()
        rng = random.Random(self.retry.seed)
        cycle = 0
        while True:
            cycle += 1
            trainer = None
            loader = None
            uninstall = None
            step0 = -1
            n_dev = 0
            try:
                self._fire("elastic.cycle")
                if self.reinit_fn is not None:
                    self.reinit_fn()
                elif jax.process_count() > 1:  # pragma: no cover - pods
                    from diff3d_tpu.parallel.multihost import \
                        reinitialize_distributed
                    reinitialize_distributed()
                devices = (self.topology_fn()
                           if self.topology_fn is not None else None)
                env = make_mesh(self.cfg.mesh, devices=devices)
                n_dev = int(env.mesh.size)
                trainer = Trainer(self.cfg, env=env, workdir=self.workdir,
                                  transfer=True)
                self.trainer = trainer
                step0 = int(trainer.state.step)
                reshard = trainer.ckpt.last_restore_reshard
                reason = ""
                if reshard is not None:
                    reason = (f"resharded step {reshard['step']}: "
                              f"{reshard['from']['n_devices']} -> "
                              f"{reshard['to']['n_devices']} devices")
                loader = self.make_loader(step0, env)
                trainer.loader = loader
                self._emit(ElasticEvent(
                    ELASTIC_RESUMED if cycle > 1 else ELASTIC_RUNNING,
                    cycle, step0, n_dev, reason, time.monotonic() - t0))
                uninstall = trainer.install_preemption_handler()
                state = trainer.train(max_steps)
                step = int(state.step)
                if step >= max_steps:
                    return state
                # train() returned early: graceful preemption.  Progress
                # refills the budget; a sigterm storm pinning us to the
                # same step eventually exhausts it.
                if step > step0:
                    self._budget.reset()
                elif not self._budget.spend():
                    self._give_up(cycle, step, n_dev,
                                  "preempted without progress", t0)
                self._emit(ElasticEvent(
                    ELASTIC_REMESHING, cycle, step, n_dev, "preemption",
                    time.monotonic() - t0))
            except (FloatingPointError, ElasticityGaveUp):
                raise   # poisoned state / exhausted budget: not elastic
            except Exception as exc:
                if not is_transient_backend_error(exc):
                    raise
                fail_step = step0
                if trainer is not None:
                    try:
                        fail_step = int(trainer.state.step)
                    except Exception:  # pragma: no cover - dead backend
                        pass
                if trainer is not None and fail_step > step0 >= 0:
                    self._budget.reset()
                elif not self._budget.spend():
                    self._give_up(cycle, max(fail_step, 0), n_dev,
                                  f"{type(exc).__name__}: {exc}", t0)
                self._emit(ElasticEvent(
                    ELASTIC_REMESHING, cycle, max(fail_step, 0), n_dev,
                    f"{type(exc).__name__}: {exc}", time.monotonic() - t0))
                self.retry.sleep(self.retry.delay_for(
                    max(1, self._budget.spent), rng))
            finally:
                if uninstall is not None:
                    uninstall()
                if loader is not None and hasattr(loader, "close"):
                    try:
                        loader.close()
                    except Exception:  # pragma: no cover - best effort
                        log.exception("loader close failed during re-mesh")
                if trainer is not None:
                    try:
                        trainer.ckpt.close()
                    except Exception:  # pragma: no cover - best effort
                        log.exception("ckpt close failed during re-mesh")
