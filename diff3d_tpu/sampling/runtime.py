"""Autoregressive novel-view synthesis with stochastic conditioning.

Capability parity with the reference sampler (``/root/reference/
sampling.py:129-184``): seed the record with the ground-truth first view,
then for every remaining pose run 256 reverse-diffusion steps, drawing a
fresh conditioning view from the record at *each* step, with the
guidance-weight sweep ``w = [0..7]`` as the batch axis; generated views are
appended to the record (later views condition on earlier generations) and
written as ``sampling/{step}/{gt,0..7}.png``.

TPU-native architecture (vs the reference's per-step host round-trips,
``sampling.py:97-103``):
  * the whole 256-step denoise loop is ONE compiled ``lax.scan``
    (:func:`diff3d_tpu.diffusion.sample_loop`) — the record is a fixed-size
    device array indexed by pre-sampled stochastic-conditioning choices,
    and the CFG cond/uncond double forward is folded into one 2B-batch
    model call;
  * the record buffer is DEVICE-RESIDENT across the autoregressive loop
    (:func:`diff3d_tpu.diffusion.sample_view`): each view step takes the
    record as a donated jit argument, writes its output in place via
    ``lax.dynamic_update_slice``, and returns the updated carry.  The
    Python view loop just threads device handles — zero per-view
    host->device re-upload (the pre-resident loop re-staged the whole
    ``[capacity, B, H, W, 3]`` buffer every view: O(views^2) transfer
    bytes and a host round-trip bubble per view), and ONE device->host
    fetch at the end of the object;
  * with an optional :class:`~diff3d_tpu.parallel.MeshEnv`, every
    object-batched entry point compiles with ``NamedSharding`` in/out
    specs — the object axis rides the mesh's ``data`` axis, params are
    placed per the ``replicated``/``fsdp`` policy — so
    ``synthesize_many``, ``eval_cli``, and the serving engine fan one
    batched scan over every attached chip.

The device-resident record contract (shared by offline and serving paths;
see DESIGN.md): ``record_R``/``record_T`` are pre-filled with ALL target
poses up front — the stochastic-conditioning draw only reads entries
``< record_len``, so entry ``record_len`` doubles as the pose of the view
being synthesised — and the per-object ``rng`` is carried on device and
split inside the compiled step, preserving the legacy host loop's exact
key stream (the serving bit-parity tests pin this).

The per-view unit of work is public API: :meth:`Sampler.step` (one object)
and :meth:`Sampler.step_many` (N objects, per-object view steps) run one
view's full reverse diffusion and return the updated record carry;
``synthesize``/``synthesize_many`` are thin host loops over them.  The
serving layer (``diff3d_tpu/serving``) drives ``step_many`` directly so
live requests at *different* autoregressive depths share one compiled scan
(continuous batching at view granularity).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.config import Config
from diff3d_tpu.diffusion import (SAMPLER_KINDS, sample_loop_prepare,
                                  sample_loop_scan, sample_view,
                                  sample_view_commit, schedule_start_index)
from diff3d_tpu.utils.profiling import scope, span


def to_uint8(img: np.ndarray) -> np.ndarray:
    """[-1, 1] float -> [0, 255] uint8."""
    return np.clip((np.asarray(img) + 1.0) * 127.5, 0, 255).astype(np.uint8)


def save_image(path: str, img: np.ndarray) -> None:
    """Save one ``[H, W, 3]`` image in [-1, 1]; parent dirs created."""
    from PIL import Image

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    Image.fromarray(to_uint8(img)).save(path)


def record_capacity(n_views: int) -> int:
    """Record-buffer capacity for an object synthesised to ``n_views``
    total views.

    Rounds up to a power of two: the compiled scan's shape depends on the
    record capacity, so objects with different view counts share a
    logarithmic number of compilations instead of one each.  The
    stochastic-conditioning draw only sees the first ``record_len``
    entries, so padding never leaks into sampling.  The serving layer's
    shape buckets use the same function, so a served request compiles (and
    caches) the exact program the offline path uses.
    """
    if n_views < 2:
        raise ValueError(f"n_views={n_views}: need at least 2 views "
                         "(one conditioning + one target)")
    return 1 << (n_views - 1).bit_length()


class Sampler:
    """Runs the full autoregressive view loop for one object.

    Args:
      model: a denoiser of the forward contract (docs/DESIGN.md §1),
        as :func:`diff3d_tpu.models.build_model` gives it: a Flax module
        whose ``apply({"params": p}, batch, cond_mask=..., constrain=...)``
        takes ``x`` / ``z`` at ``B`` examples and the conditioning inputs
        at ``G`` rows, ``G`` dividing ``B``.
      params: trained parameters (typically the EMA pytree).  Held as the
        *default* — every compiled entry point takes params as a jit
        argument, so callers (checkpoint hot-swap in serving) may pass a
        different same-shaped pytree per call without recompiling.
      cfg: full config (diffusion.timesteps, guidance_weights, ...).
      scan_chunks: split each view's reverse-diffusion scan into this many
        consecutive device executions (bit-identical result — the RNG
        stream is carried; `test_sampling` pins it).  1 is one device
        execution per view; k makes each view k shorter executions.
      mesh: optional :class:`~diff3d_tpu.parallel.MeshEnv`.  When given,
        the object-batched entry points compile with ``NamedSharding``
        in/out specs (object axis over the mesh's data axis, params per
        the config's ``replicated``/``fsdp``/``tp`` policy) and
        :attr:`lane_multiple` becomes the data-axis size — callers of
        :meth:`step_many` must pass an object count divisible by it
        (``synthesize_many`` pads internally; the serving engine rounds
        its lane counts).  With ``cfg.mesh.context_parallel`` on, the
        single-object path additionally threads
        ``MeshEnv.activation_constraint()`` through the model.
      sampler_kind: reverse-process update — ``"ancestral"`` (the paper's
        stochastic sampler) or ``"ddim"`` (deterministic eta=0).
      steps: number of reverse steps per view; must divide
        ``cfg.diffusion.timesteps`` (the k-step grid is an exact subset
        of the dense grid — see
        :func:`~diff3d_tpu.diffusion.sample_schedule_ts`).  ``None``
        (default) runs the full grid, bit-identical to the historical
        sampler.
      start_t: truncated-schedule (cascade refine) entry point — must be
        a grid point of the ``steps``-step schedule.  When set, every
        view step takes an extra ``[B, H, W, 3]`` ``draft`` operand: the
        draft is renoised to ``start_t`` via the forward process and only
        the remaining reverse steps run.  ``start_t=1.0`` ignores the
        draft (the VP prior at t=1 is exactly N(0,1)) and reproduces the
        untruncated sampler bit-for-bit.  Requires ``scan_chunks == 1``;
        the offline ``synthesize*`` loops have no draft source and
        refuse a truncated sampler.
    """

    def __init__(self, model, params, cfg: Config,
                 scan_chunks: int = 1, mesh=None,
                 sampler_kind: str = "ancestral",
                 steps: Optional[int] = None,
                 start_t: Optional[float] = None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self._calls = 0     # synthesize* calls so far: the spans' id
        self.w = jnp.asarray(cfg.diffusion.guidance_weights, jnp.float32)

        d = cfg.diffusion
        if sampler_kind not in SAMPLER_KINDS:
            raise ValueError(
                f"sampler_kind={sampler_kind!r} not in {SAMPLER_KINDS}")
        self.sampler_kind = sampler_kind
        steps = d.timesteps if steps is None else int(steps)
        if steps < 1 or d.timesteps % steps:
            raise ValueError(
                f"steps={steps} must be a positive divisor of "
                f"timesteps={d.timesteps}")
        self.steps = steps
        if scan_chunks < 1 or steps % scan_chunks:
            raise ValueError(
                f"scan_chunks={scan_chunks} must divide the effective "
                f"step count steps={steps}")
        self.scan_chunks = scan_chunks
        self.start_t = None if start_t is None else float(start_t)
        self.start_index = 0
        if self.start_t is not None:
            # Raises ScheduleError for an off-grid start_t.
            self.start_index = schedule_start_index(
                steps, self.start_t, timesteps=d.timesteps)
            if scan_chunks != 1:
                raise ValueError(
                    f"start_t={self.start_t} (truncated refinement) "
                    f"requires scan_chunks=1, got {scan_chunks} — the "
                    "chunk split assumes the full step count")

        # Sharding vocabulary.  lane_multiple is the divisibility quantum
        # of the object axis: NamedSharding rejects a leading dim not
        # divisible by the data-axis size, so batched callers round up to
        # a multiple (padding lanes carry live data and are discarded).
        constrain = None
        if mesh is not None:
            self.lane_multiple = mesh.data_size
            self._obj = mesh.batch()             # object axis over 'data'
            self._rep = mesh.replicated()
            self._param_shardings = mesh.params(params)
            params = jax.device_put(params, self._param_shardings)
            if cfg.mesh.context_parallel:
                constrain = mesh.activation_constraint()
        else:
            self.lane_multiple = 1
            self._obj = self._rep = self._param_shardings = None
        self.params = params

        # params is a jit ARGUMENT, not a closure constant: closing over
        # it would bake the full weight set into the compiled program
        # (hundreds of MB at srn64 scale) and force a recompile for every
        # checkpoint swap.
        def denoise_with(params, constrain=None):
            def denoise(batch, cond_mask):
                return model.apply({"params": params}, batch,
                                   cond_mask=cond_mask, constrain=constrain)
            return denoise

        # The device-resident view step: (params, record carry) ->
        # (out, record carry').  record_imgs is DONATED — the
        # dynamic_update_slice writes in place on device.
        def run_view(params, record_imgs, record_R, record_T, record_len,
                     K, rng, draft=None, constrain=None):
            return sample_view(
                denoise_with(params, constrain), record_imgs=record_imgs,
                record_R=record_R, record_T=record_T,
                record_len=record_len, K=K, w=self.w, rng=rng,
                timesteps=d.timesteps, logsnr_min=d.logsnr_min,
                logsnr_max=d.logsnr_max, clip_x0=d.clip_x0,
                steps=self.steps, sampler_kind=self.sampler_kind,
                start_t=self.start_t, draft=draft)

        def _specs(data_sharding, n_data_args, n_outs):
            """jit sharding kwargs (empty off-mesh)."""
            if mesh is None:
                return {}
            return {
                "in_shardings": ((self._param_shardings,)
                                 + (data_sharding,) * n_data_args),
                "out_shardings": ((data_sharding,) * n_outs
                                  if n_outs > 1 else data_sharding),
            }

        if scan_chunks == 1 and self.start_t is not None:
            # Truncated refinement: the draft rides as a trailing data
            # operand so the program stays params-first (shardcheck's
            # params_argnum contract).
            self._run_view = jax.jit(
                lambda p, ri, rR, rT, rl, K, rng, dr: run_view(
                    p, ri, rR, rT, rl, K, rng, draft=dr,
                    constrain=constrain),
                donate_argnums=(1,), **_specs(self._rep, 7, 4))
        elif scan_chunks == 1:
            self._run_view = jax.jit(
                lambda p, ri, rR, rT, rl, K, rng: run_view(
                    p, ri, rR, rT, rl, K, rng, constrain=constrain),
                donate_argnums=(1,), **_specs(self._rep, 6, 4))
        else:
            # Chunked pieces: `prepare` + chunks + `commit` compose to
            # exactly `run_view` (scan over xs == fold of scans over xs
            # slices; the rng split and the record write bracket them),
            # but each chunk is its own device execution.  All pieces
            # take/return device carries, so the chunked path is equally
            # host-transfer-free between views.
            def prepare_view(record_len, rng, record_imgs):
                rng, k = jax.random.split(rng)
                state, xs = sample_loop_prepare(
                    record_len=record_len, rng=k, timesteps=d.timesteps,
                    shape=(self.w.shape[0],) + record_imgs.shape[-3:],
                    logsnr_min=d.logsnr_min, logsnr_max=d.logsnr_max,
                    steps=self.steps)
                return state, xs, rng

            def chunk_view(params, state, xs, record_imgs, record_R,
                           record_T, record_len, K, constrain=None):
                return sample_loop_scan(
                    denoise_with(params, constrain), state, xs,
                    record_imgs=record_imgs, record_R=record_R,
                    record_T=record_T, target_R=record_R[record_len],
                    target_T=record_T[record_len], K=K, w=self.w,
                    logsnr_max=d.logsnr_max, clip_x0=d.clip_x0,
                    deterministic=(self.sampler_kind == "ddim"))

            n_per = self.steps // scan_chunks
            sh = {} if mesh is None else {"out_shardings": self._rep}
            jit_prepare = jax.jit(
                prepare_view,
                **({} if mesh is None
                   else {"in_shardings": (self._rep,) * 3, **sh}))
            jit_chunk = jax.jit(
                lambda p, s, xs, ri, rR, rT, rl, K: chunk_view(
                    p, s, xs, ri, rR, rT, rl, K, constrain=constrain),
                **({} if mesh is None
                   else {"in_shardings": (self._param_shardings,)
                         + (self._rep,) * 7, **sh}))
            jit_commit = jax.jit(
                sample_view_commit, donate_argnums=(0,),
                **({} if mesh is None
                   else {"in_shardings": (self._rep,) * 3,
                         "out_shardings": (self._rep,) * 3}))

            def run_view_chunked(params, record_imgs, record_R, record_T,
                                 record_len, K, rng):
                state, xs, rng = jit_prepare(record_len, rng, record_imgs)
                for c in range(scan_chunks):
                    sl = jax.tree.map(
                        lambda x: x[c * n_per:(c + 1) * n_per], xs)
                    state = jit_chunk(params, state, sl, record_imgs,
                                      record_R, record_T, record_len, K)
                out, record_imgs, record_len = jit_commit(
                    record_imgs, record_len, state.img)
                return out, record_imgs, record_len, rng

            self._run_view = run_view_chunked

        # Object-batched variant: vmap folds an extra leading object axis
        # into every model call (N*2B examples instead of 2B), so N
        # independent objects' guidance sweeps share one compiled scan —
        # at 64^2 the per-object batch of 8 underfills the chip and the
        # per-object loop was the eval cost center.  record_len is batched
        # per object (in_axes 0): the offline path passes the same step
        # for every object, while the serving engine mixes requests at
        # different autoregressive depths in one device batch.  On a mesh
        # the object axis is sharded over 'data', so one launch spans all
        # chips.  (The context-parallel constrain hook is single-object
        # only: under vmap its [B, F, H, W, C] spec would land on the
        # wrong axes.)
        if scan_chunks == 1 and self.start_t is not None:
            def run_view_draft(p, ri, rR, rT, rl, K, rng, dr):
                return run_view(p, ri, rR, rT, rl, K, rng, draft=dr)
            self._run_view_many = jax.jit(
                jax.vmap(run_view_draft,
                         in_axes=(None, 0, 0, 0, 0, 0, 0, 0)),
                donate_argnums=(1,), **_specs(self._obj, 7, 4))
        elif scan_chunks == 1:
            self._run_view_many = jax.jit(
                jax.vmap(run_view, in_axes=(None, 0, 0, 0, 0, 0, 0)),
                donate_argnums=(1,), **_specs(self._obj, 6, 4))
        else:
            jit_prepare_many = jax.jit(
                jax.vmap(prepare_view, in_axes=(0, 0, 0)),
                **({} if mesh is None
                   else {"in_shardings": (self._obj,) * 3,
                         "out_shardings": self._obj}))
            jit_chunk_many = jax.jit(
                jax.vmap(chunk_view, in_axes=(None, 0, 0, 0, 0, 0, 0, 0)),
                **({} if mesh is None
                   else {"in_shardings": (self._param_shardings,)
                         + (self._obj,) * 7,
                         "out_shardings": self._obj}))
            jit_commit_many = jax.jit(
                jax.vmap(sample_view_commit, in_axes=(0, 0, 0)),
                donate_argnums=(0,),
                **({} if mesh is None
                   else {"in_shardings": (self._obj,) * 3,
                         "out_shardings": (self._obj,) * 3}))
            n_per_many = self.steps // scan_chunks

            def run_view_many_chunked(params, record_imgs, record_R,
                                      record_T, record_len, K, rngs):
                state, xs, rngs = jit_prepare_many(record_len, rngs,
                                                   record_imgs)
                for c in range(scan_chunks):
                    sl = jax.tree.map(
                        lambda x: x[:, c * n_per_many:(c + 1) * n_per_many],
                        xs)
                    state = jit_chunk_many(params, state, sl, record_imgs,
                                           record_R, record_T, record_len,
                                           K)
                out, record_imgs, record_len = jit_commit_many(
                    record_imgs, record_len, state.img)
                return out, record_imgs, record_len, rngs

            self._run_view_many = run_view_many_chunked

    @property
    def model_calls_per_view(self) -> int:
        """Denoiser invocations per synthesised view (each reverse step is
        one 2B-batched CFG call) — the latency dial the step schedule
        turns.  A truncated (``start_t``) sampler runs only the grid tail,
        so the truncated steps are subtracted."""
        return self.steps - self.start_index

    # ------------------------------------------------------------------
    # Per-view step API (public): one view's full reverse diffusion.
    # ------------------------------------------------------------------

    def _check_draft(self, draft, batched: bool):
        """The draft operand is exactly as optional as ``start_t``: a
        truncated sampler cannot run without one, an untruncated sampler
        has no operand slot for one."""
        if self.start_t is not None and draft is None:
            raise ValueError(
                f"this sampler was built with start_t={self.start_t}: "
                "every view step needs the "
                + ("[N, B, H, W, 3] drafts" if batched
                   else "[B, H, W, 3] draft")
                + " operand to renoise from")
        if self.start_t is None and draft is not None:
            raise ValueError(
                "draft passed to an untruncated sampler — build the "
                "Sampler with start_t to enable cascade refinement")

    def step(self, record_imgs, record_R, record_T, step, K, rng, *,
             draft=None, params=None):
        """One view's reverse diffusion for ONE object, device-resident.

        Args:
          record_imgs / record_R / record_T: ``[capacity, B, H, W, 3]`` /
            ``[capacity, 3, 3]`` / ``[capacity, 3]`` record buffers
            (see :func:`record_capacity`).  The pose buffers must be
            pre-filled with every view's pose — entry ``step`` is the
            target pose of the view being synthesised.
          step: number of valid record entries (== the view index being
            synthesised).
          K: ``[3, 3]`` intrinsics.
          rng: the per-object PRNG carry (NOT a per-view key — the
            per-view key is split off inside the compiled step, exactly
            like the legacy host loop did).
          params: optional parameter pytree overriding the constructor
            default (same treedef/shapes — no recompile).
        Returns:
          ``(out, record_imgs, step + 1, rng)`` — ``out`` is the
          ``[B, H, W, 3]`` generated view (device array; callers block),
          and the rest is the updated record carry for the next view.
          ``record_imgs`` is DONATED: a passed-in device buffer is
          invalidated and the returned one must be used instead (numpy
          inputs are first copied into an XLA-owned buffer — see
          :meth:`_owned` — so the caller's array is unaffected).
        """
        self._check_draft(draft, batched=False)
        p = self.params if params is None else params
        args = (p, self._owned(record_imgs), jnp.asarray(record_R),
                jnp.asarray(record_T), jnp.asarray(step, jnp.int32),
                jnp.asarray(K), jnp.asarray(rng))
        if self.start_t is not None:
            args += (jnp.asarray(draft, jnp.float32),)
        return self._run_view(*args)

    def step_many(self, record_imgs, record_R, record_T, steps, K, rngs,
                  *, drafts=None, params=None):
        """One view step for N objects in ONE batched program.

        Everything gains a leading object axis; ``steps`` is ``[N]`` —
        per-object record lengths, so co-batched objects may sit at
        different autoregressive depths (the serving engine's continuous
        batching relies on this).  ``rngs`` is ``[N]`` stacked per-object
        PRNG carries (split per view inside, like :meth:`step`).  On a
        mesh, N must be a multiple of :attr:`lane_multiple` (the sharded
        program cannot split a non-divisible object axis).  Returns
        ``(out [N, B, H, W, 3], record_imgs, steps + 1, rngs)`` with the
        same donation contract as :meth:`step`.
        """
        n = int(np.shape(record_imgs)[0])
        if n % self.lane_multiple:
            raise ValueError(
                f"step_many: {n} objects is not a multiple of the mesh's "
                f"data-axis size {self.lane_multiple} — pad the batch "
                "(repeat a live lane; padded outputs are discarded) or "
                "use synthesize_many, which pads internally")
        self._check_draft(drafts, batched=True)
        p = self.params if params is None else params
        args = (p, self._owned(record_imgs), jnp.asarray(record_R),
                jnp.asarray(record_T), jnp.asarray(steps, jnp.int32),
                jnp.asarray(K), jnp.asarray(rngs))
        if self.start_t is not None:
            args += (jnp.asarray(drafts, jnp.float32),)
        return self._run_view_many(*args)

    def lower_step_many(self, lanes: int, capacity: int, *,
                        H: Optional[int] = None, W: Optional[int] = None):
        """Lower the :meth:`step_many` program on ABSTRACT args (no
        buffers staged) — the analysis hook shardcheck and bench use to
        audit the compiled scan's collectives/dtypes per shape bucket.

        ``lanes`` is the object count N (must satisfy the same
        :attr:`lane_multiple` divisibility as a real call), ``capacity``
        the record capacity (:func:`record_capacity`).  Returns a
        ``jax.stages.Lowered``.  Only the single-execution path
        (``scan_chunks == 1``) is one program; the chunked path is a
        Python composition and has no single lowering.
        """
        if self.scan_chunks != 1:
            raise ValueError(
                "lower_step_many: scan_chunks="
                f"{self.scan_chunks} composes multiple programs in "
                "Python; lower a scan_chunks=1 sampler instead")
        if lanes % self.lane_multiple:
            raise ValueError(
                f"lower_step_many: lanes={lanes} is not a multiple of "
                f"the mesh's data-axis size {self.lane_multiple}")
        B = int(self.w.shape[0])
        H = self.cfg.model.H if H is None else int(H)
        W = self.cfg.model.W if W is None else int(W)
        f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
        sds = jax.ShapeDtypeStruct
        abstract_params = jax.tree.map(
            lambda x: sds(jnp.shape(x), x.dtype), self.params)
        abstract_args = [
            abstract_params,
            sds((lanes, capacity, B, H, W, 3), f32),
            sds((lanes, capacity, 3, 3), f32),
            sds((lanes, capacity, 3), f32),
            sds((lanes,), i32),
            sds((lanes, 3, 3), f32),
            sds((lanes, 2), u32)]
        if self.start_t is not None:
            abstract_args.append(sds((lanes, B, H, W, 3), f32))
        return self._run_view_many.lower(*abstract_args)

    # ------------------------------------------------------------------
    # Offline loops: thin host loops threading the device-resident carry.
    # ------------------------------------------------------------------

    def _record_init(self, imgs0, R, T, n_views):
        """Host-side record build: view 0 seeded, ALL poses pre-filled
        (the device-resident contract — see the module docstring)."""
        B = int(self.w.shape[0])
        H, W = imgs0.shape[-3:-1]
        capacity = record_capacity(n_views) if n_views > 1 else 1
        record_imgs = np.zeros((capacity, B, H, W, 3), np.float32)
        record_R = np.zeros((capacity, 3, 3), np.float32)
        record_T = np.zeros((capacity, 3), np.float32)
        record_imgs[0] = imgs0[None]
        record_R[:n_views] = R[:n_views]
        record_T[:n_views] = T[:n_views]
        return record_imgs, record_R, record_T

    def _owned(self, x, sharding=None):
        """XLA-owned device upload of a potentially-donated operand.

        ``jnp.asarray``/``device_put`` may zero-copy ALIAS an aligned
        numpy buffer (CPU backend); the view-step programs DONATE the
        record carry, and donating such an alias frees memory the XLA
        allocator does not own — heap corruption that surfaces far from
        here.  Host inputs are therefore copied into an XLA-allocated
        buffer; device arrays pass through untouched, so the
        steady-state loop still threads donated handles copy-free.
        """
        if isinstance(x, jax.Array):
            return x
        arr = (jax.device_put(x, sharding)
               if self.mesh is not None and sharding is not None
               else jnp.asarray(x))
        return jnp.copy(arr)

    def _put(self, x, sharding):
        return self._owned(x, sharding)

    def _fetch(self, rec_i, index, call: int) -> np.ndarray:
        """The offline loops' one device->host fetch: wait for the last
        view step (``sampler.wait``: the device's time, not the host's),
        then slice the generated views on device and copy them
        (``sampler.fetch``)."""
        with span("sampler.wait", id=call):
            rec_i = jax.block_until_ready(rec_i)
        with span("sampler.fetch", id=call):
            with scope("record"):
                out = rec_i[index]
            return np.asarray(out)

    def _check_no_truncation(self, entry: str) -> None:
        if self.start_t is not None:
            raise ValueError(
                f"{entry}: this sampler was built with start_t="
                f"{self.start_t} (truncated refinement) and needs a draft "
                "per view; the offline loops have no draft source — use "
                "CascadeSampler (diff3d_tpu.cascade) or the step API")

    def synthesize(self, views: Dict[str, np.ndarray], rng: jax.Array,
                   out_dir: Optional[str] = None,
                   max_views: Optional[int] = None) -> np.ndarray:
        """Autoregressively synthesise every view of ``views`` (the dict
        produced by ``SRNDataset.all_views``) from view 0.

        The record carry stays on device for the whole loop; the only
        device->host traffic is ONE fetch of the generated views at the
        end (PNGs, when requested, are written from that fetch).

        Returns ``[n_views-1, B, H, W, 3]`` generated images (B = number
        of guidance weights).  When ``out_dir`` is given, saves
        ``{out_dir}/{step}/gt.png`` and ``{out_dir}/{step}/{i}.png`` per
        view — the reference's output layout (``sampling.py:179-182``).
        """
        self._check_no_truncation("synthesize")
        imgs = np.asarray(views["imgs"], np.float32)
        R = np.asarray(views["R"], np.float32)
        T = np.asarray(views["T"], np.float32)
        K = np.asarray(views["K"], np.float32)
        n_views = imgs.shape[0] if max_views is None else min(
            imgs.shape[0], max_views)
        B = int(self.w.shape[0])
        H, W = imgs.shape[1:3]
        if n_views < 2:
            return np.zeros((0, B, H, W, 3), np.float32)

        self._calls += 1
        call = self._calls
        with span("sampler.stage", id=call):
            record_imgs, record_R, record_T = self._record_init(
                imgs[0], R, T, n_views)

            # One-time upload of the carry; after this the loop only
            # threads returned device handles (rec_i is donated each step
            # and written in place).
            rec_i = self._put(record_imgs, self._rep)
            rec_R = self._put(record_R, self._rep)
            rec_T = self._put(record_T, self._rep)
            K_d = self._put(K, self._rep)
            step_d = self._put(np.asarray(1, np.int32), self._rep)
            rng_d = self._put(np.asarray(rng), self._rep)
        for _ in range(1, n_views):
            # the host's cost of one view's enqueue, timed as one on purpose
            with span("sampler.dispatch", id=call):
                _, rec_i, step_d, rng_d = self._run_view(
                    self.params, rec_i, rec_R, rec_T, step_d, K_d, rng_d)
        # Single fetch: slice the generated views on device, pull once.
        outs = self._fetch(rec_i, (slice(1, n_views),), call)

        if out_dir is not None:
            for step in range(1, n_views):
                save_image(os.path.join(out_dir, str(step), "gt.png"),
                           imgs[step])
                for i in range(B):
                    save_image(os.path.join(out_dir, str(step), f"{i}.png"),
                               outs[step - 1, i])
        return outs

    def synthesize_many(self, views_list: Sequence[Dict[str, np.ndarray]],
                        rngs: Sequence[jax.Array],
                        max_views: Optional[int] = None) -> np.ndarray:
        """Autoregressively synthesise N objects' views in ONE batched
        program (objects are independent — the reference scores them
        strictly sequentially, ``sampling.py:169-184``; here the object
        axis becomes an extra batch dim on every model call, sharded over
        the mesh's data axis when a mesh is attached).

        ``rngs`` holds one key per object.  Given the same per-object key,
        the per-object rng stream is identical to a sequential
        ``synthesize(views, key)`` call, so results match the sequential
        path to float tolerance (XLA may tile the larger batch
        differently, so bitwise equality is not guaranteed).

        On a mesh, N is padded internally to a multiple of
        :attr:`lane_multiple` by repeating object 0 (live data — zero
        lanes would run denormal-slow); padded outputs are discarded.

        Every object contributes ``n_views = min(min_i views_i,
        max_views)`` views — batch objects with equal view counts to avoid
        truncation.  Returns ``[N, n_views-1, B, H, W, 3]``.
        """
        self._check_no_truncation("synthesize_many")
        N = len(views_list)
        assert N == len(rngs)
        n_views = min(v["imgs"].shape[0] for v in views_list)
        if max_views is not None:
            n_views = min(n_views, max_views)
        B = int(self.w.shape[0])
        H, W = views_list[0]["imgs"].shape[1:3]
        if n_views < 2:
            return np.zeros((N, 0, B, H, W, 3), np.float32)

        self._calls += 1
        call = self._calls
        with span("sampler.stage", id=call):
            mult = self.lane_multiple
            pad_idx = list(range(N)) + [0] * (-N % mult)
            recs = [self._record_init(
                        np.asarray(views_list[i]["imgs"][0], np.float32),
                        np.asarray(views_list[i]["R"], np.float32),
                        np.asarray(views_list[i]["T"], np.float32), n_views)
                    for i in pad_idx]
            record_imgs = np.stack([r[0] for r in recs])
            record_R = np.stack([r[1] for r in recs])
            record_T = np.stack([r[2] for r in recs])
            Ks = np.stack([np.asarray(views_list[i]["K"], np.float32)
                           for i in pad_idx])
            keys = np.stack([np.asarray(rngs[i]) for i in pad_idx])
            steps = np.full((len(pad_idx),), 1, np.int32)

            rec_i = self._put(record_imgs, self._obj)
            rec_R = self._put(record_R, self._obj)
            rec_T = self._put(record_T, self._obj)
            Ks_d = self._put(Ks, self._obj)
            steps_d = self._put(steps, self._obj)
            keys_d = self._put(keys, self._obj)
        for _ in range(1, n_views):
            # the host's cost of one view's enqueue, timed as one on purpose
            with span("sampler.dispatch", id=call):
                _, rec_i, steps_d, keys_d = self._run_view_many(
                    self.params, rec_i, rec_R, rec_T, steps_d, Ks_d, keys_d)
        # Single fetch: drop padding lanes + the seeded view 0 on device.
        return self._fetch(rec_i, (slice(None, N), slice(1, n_views)), call)
