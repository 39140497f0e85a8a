"""Continuous-time logSNR-parameterised variance-preserving DDPM.

This is the single home of the diffusion math that the reference duplicates
in three near-identical copies (``/root/reference/train.py:30-177``,
``lightning/diff3d.py:131-238``, ``sampling.py:59-127``).  Everything is a
pure function over explicit ``jax.random`` keys, jit/scan/pjit-friendly.

Layout note: images are channels-last ``[B, H, W, 3]`` (TPU-native NHWC);
the reference uses NCHW.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.utils.profiling import scope

# A denoiser: (batch dict, cond_mask [G] bool) -> eps_hat [B, H, W, 3], with
# the batch's conditioning inputs at G rows too (G divides B, group-major:
# the model's forward contract, models/xunet.py).
# Dropout/other rngs are expected to be bound by the caller (closure over
# model.apply with its `rngs=`).
DenoiseFn = Callable[[dict, jnp.ndarray], jnp.ndarray]

# Reverse-process update rules understood by `sample_loop` / `Sampler`:
# "ancestral" is the paper's stochastic DDPM step, "ddim" the deterministic
# eta=0 update (Song et al., DDIM) over the same x0-prediction.
SAMPLER_KINDS = ("ancestral", "ddim")


def logsnr_schedule_cosine(t: jnp.ndarray, *, logsnr_min: float = -20.0,
                           logsnr_max: float = 20.0) -> jnp.ndarray:
    """Cosine schedule in SNR space: ``logsnr(t) = -2 log(tan(a t + b))``.

    Parity: reference ``train.py:30-34``.  ``t`` in [0, 1] maps to logsnr in
    [logsnr_max, logsnr_min] (monotonically decreasing).
    """
    b = np.arctan(np.exp(-0.5 * logsnr_max))
    a = np.arctan(np.exp(-0.5 * logsnr_min)) - b
    return -2.0 * jnp.log(jnp.tan(a * t + b))


def alpha_sigma(logsnr: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """VP coefficients ``alpha = sqrt(sigmoid(logsnr))``,
    ``sigma = sqrt(sigmoid(-logsnr))`` (reference ``train.py:54-55``)."""
    return (jnp.sqrt(jax.nn.sigmoid(logsnr)),
            jnp.sqrt(jax.nn.sigmoid(-logsnr)))


def q_sample(z: jnp.ndarray, logsnr: jnp.ndarray,
             noise: jnp.ndarray) -> jnp.ndarray:
    """Forward process ``z_t = alpha z + sigma eps`` (reference
    ``train.py:50-60``).  ``logsnr`` is ``[B]``, images ``[B, H, W, C]``."""
    alpha, sigma = alpha_sigma(logsnr)
    return alpha[:, None, None, None] * z + sigma[:, None, None, None] * noise


def make_model_batch(x: jnp.ndarray, z: jnp.ndarray, logsnr: jnp.ndarray,
                     R: jnp.ndarray, t: jnp.ndarray, K: jnp.ndarray,
                     *, logsnr_max: float = 20.0) -> dict:
    """Pack the model input dict (parity with ``xt2batch``,
    ``train.py:36-46``): the conditioning frame gets the schedule's max
    logSNR (= clean, ``logsnr_schedule_cosine(0)``) stacked with the target
    frame's logsnr into ``[B, 2]``."""
    cond_logsnr = jnp.full_like(logsnr, logsnr_max)
    return {
        "x": x,
        "z": z,
        "logsnr": jnp.stack([cond_logsnr, logsnr], axis=1),
        "R": R,
        "t": t,
        "K": K,
    }


def p_losses(denoise_fn: DenoiseFn, imgs: jnp.ndarray, R: jnp.ndarray,
             T: jnp.ndarray, K: jnp.ndarray, rng: jax.Array, *,
             cond_prob: float = 0.1, loss_type: str = "l2",
             logsnr_min: float = -20.0, logsnr_max: float = 20.0
             ) -> jnp.ndarray:
    """epsilon-prediction loss with classifier-free-guidance dropout.

    Parity: reference ``train.py:80-114`` (and its per-step logsnr draw at
    ``train.py:272``).  ``imgs`` is ``[B, 2, H, W, 3]`` — frame 0 is the
    source view ``x``, frame 1 the target view ``z``.  With probability
    ``cond_prob`` a batch element is trained unconditionally: its
    conditioning frame is replaced by pure N(0,1) noise and ``cond_mask`` is
    False (the "max noise level" CFG variant, ``lightning/diff3d.py:13-16``).
    """
    B = imgs.shape[0]
    with scope("loss"):
        x, z = imgs[:, 0], imgs[:, 1]

        k_t, k_noise, k_mask, k_xnoise = jax.random.split(rng, 4)
        logsnr = logsnr_schedule_cosine(
            jax.random.uniform(k_t, (B,)), logsnr_min=logsnr_min,
            logsnr_max=logsnr_max)
        noise = jax.random.normal(k_noise, z.shape, z.dtype)
        z_noisy = q_sample(z, logsnr, noise)

        cond_mask = jax.random.uniform(k_mask, (B,)) > cond_prob
        x_cond = jnp.where(cond_mask[:, None, None, None], x,
                           jax.random.normal(k_xnoise, x.shape, x.dtype))
        batch = make_model_batch(x_cond, z_noisy, logsnr, R, T, K,
                                 logsnr_max=logsnr_max)
    eps_hat = denoise_fn(batch, cond_mask)

    with scope("loss"):
        if loss_type == "l1":
            return jnp.mean(jnp.abs(noise - eps_hat))
        if loss_type == "l2":
            return jnp.mean(jnp.square(noise - eps_hat))
        if loss_type == "huber":
            # torch smooth_l1 with beta=1 (reference train.py:109).
            d = jnp.abs(noise - eps_hat)
            return jnp.mean(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5))
    raise NotImplementedError(loss_type)


def p_mean_variance(eps_cond: jnp.ndarray, eps_uncond: jnp.ndarray,
                    z: jnp.ndarray, logsnr: jnp.ndarray,
                    logsnr_next: jnp.ndarray, w: jnp.ndarray, *,
                    clip_x0: bool = True
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One ancestral step in logSNR form (reference ``train.py:131-166``).

    ``c = -expm1(logsnr - logsnr_next)``; CFG combine
    ``eps = (1+w) eps_cond - w eps_uncond``; ``z0 = (z - sigma eps)/alpha``
    clamped to [-1, 1]; posterior mean
    ``alpha_next (z (1-c)/alpha + c z0)``, variance
    ``sigmoid(-logsnr_next) * c``.
    ``w`` is ``[B]`` (the guidance sweep IS the batch axis, sampling.py:158).
    """
    c = -jnp.expm1(logsnr - logsnr_next)
    alpha, sigma = alpha_sigma(logsnr)
    alpha_next, _ = alpha_sigma(logsnr_next)
    sq_sigma_next = jax.nn.sigmoid(-logsnr_next)

    w = w[:, None, None, None]
    eps = (1.0 + w) * eps_cond - w * eps_uncond
    z_start = (z - sigma * eps) / alpha
    if clip_x0:
        z_start = jnp.clip(z_start, -1.0, 1.0)
    mean = alpha_next * (z * (1.0 - c) / alpha + c * z_start)
    return mean, sq_sigma_next * c


def ddim_step(eps_cond: jnp.ndarray, eps_uncond: jnp.ndarray,
              z: jnp.ndarray, logsnr: jnp.ndarray,
              logsnr_next: jnp.ndarray, w: jnp.ndarray, *,
              clip_x0: bool = True) -> jnp.ndarray:
    """One deterministic DDIM step (eta = 0) in logSNR form.

    Shares the CFG combine and clipped x0-prediction with
    :func:`p_mean_variance`; after clipping, eps is RE-derived from the
    clipped x0 (``eps = (z - alpha x0)/sigma``) so the update stays on the
    manifold implied by the clamp, then
    ``z_next = alpha_next x0 + sigma_next eps``.  At logsnr_next ==
    logsnr_max (t = 0) sigma_next ~ 0 and this returns x0 — no special
    final-step guard is needed.
    """
    alpha, sigma = alpha_sigma(logsnr)
    alpha_next, sigma_next = alpha_sigma(logsnr_next)

    w = w[:, None, None, None]
    eps = (1.0 + w) * eps_cond - w * eps_uncond
    z_start = (z - sigma * eps) / alpha
    if clip_x0:
        z_start = jnp.clip(z_start, -1.0, 1.0)
        eps = (z - alpha * z_start) / sigma
    return alpha_next * z_start + sigma_next * eps


class ScheduleError(ValueError):
    """A sampling-schedule parameter is off the valid grid — ``steps``
    not a divisor of the dense schedule, or ``start_t`` not one of the
    grid's time points."""


def schedule_start_index(steps: int, start_t: float, *,
                         timesteps: int) -> int:
    """Index of ``start_t`` in the ``[steps + 1]`` grid of
    :func:`sample_schedule_ts` (grid points ``t_i = 1 - i/steps``).

    Truncated (draft-seeded) refinement must START on a grid point:
    entering between points would evaluate logsnrs no full run ever
    visits and silently break the exact-subset property the parity
    oracle depends on.  Raises :class:`ScheduleError` for off-grid
    ``start_t``, or one leaving no reverse steps (``start_t <= 0``).
    """
    start_t = float(start_t)
    idx = round((1.0 - start_t) * steps)
    if (not 0 <= idx < steps
            or abs((1.0 - idx / steps) - start_t) > 1e-6):
        pts = [round(1.0 - i / steps, 6) for i in range(steps)]
        raise ScheduleError(
            f"start_t={start_t} is not a grid point of the {steps}-step "
            f"schedule (timesteps={timesteps}): valid start points are "
            f"{pts} (start_t=1.0 runs the whole grid; 0.0 would leave "
            "no reverse steps)")
    return idx


def sample_schedule_ts(steps: int | None, *, timesteps: int,
                       start_t: float | None = None) -> jnp.ndarray:
    """The time grid for a ``k``-step sampling run (``[k + 1]`` entries,
    or the tail of them when ``start_t`` truncates the schedule).

    ``steps`` must divide ``timesteps`` (the dense grid size, 256 in the
    paper configs): the result is the stride-``timesteps // steps`` subset
    of ``linspace(1, 0, timesteps + 1)``, so every k-step logsnr grid is an
    EXACT index subset of the dense grid and ``steps == timesteps`` (stride
    1) reproduces the dense grid bit-for-bit — the ancestral parity oracle
    relies on that.  ``steps=None`` means the full grid.

    ``start_t`` (cascade refinement) truncates the grid to ``[start_t, 0]``:
    the caller renoises an upsampled draft to ``start_t`` via the forward
    process and runs only the remaining reverse steps.  It must be one of
    the grid's own time points (:func:`schedule_start_index`);
    ``start_t=1.0`` is the untruncated grid, so the truncated path degrades
    exactly to the full schedule.
    """
    if steps is None:
        steps = timesteps
    steps = int(steps)
    if steps < 1 or timesteps % steps:
        divisors = [d for d in range(1, timesteps + 1) if timesteps % d == 0]
        raise ScheduleError(
            f"steps={steps} must be a positive divisor of the dense "
            f"schedule (timesteps={timesteps}); valid step counts are "
            f"{divisors}")
    ts = jnp.linspace(1.0, 0.0, timesteps + 1)[::timesteps // steps]
    if start_t is not None:
        ts = ts[schedule_start_index(steps, start_t, timesteps=timesteps):]
    return ts


class SampleState(NamedTuple):
    img: jnp.ndarray   # current z_t, [B, H, W, 3]
    rng: jax.Array


def sample_loop(denoise_fn: DenoiseFn, *, record_imgs: jnp.ndarray,
                record_R: jnp.ndarray, record_T: jnp.ndarray,
                record_len: jnp.ndarray, target_R: jnp.ndarray,
                target_T: jnp.ndarray, K: jnp.ndarray, w: jnp.ndarray,
                rng: jax.Array, timesteps: int = 256,
                logsnr_min: float = -20.0, logsnr_max: float = 20.0,
                clip_x0: bool = True, steps: int | None = None,
                sampler_kind: str = "ancestral",
                start_t: float | None = None,
                draft: jnp.ndarray | None = None,
                hoist_cond: bool = True) -> jnp.ndarray:
    """Full reverse-diffusion for one novel view, as a single ``lax.scan``.

    Stochastic conditioning (reference ``sampling.py:129-155``): at every
    step a conditioning view is drawn uniformly from the first
    ``record_len`` entries of a fixed-size record buffer.  The reference's
    cond+uncond double forward (``sampling.py:97-99``) is folded into ONE
    batched model call of size 2B so the scan body stays static.

    Args:
      record_imgs: ``[N, B, H, W, 3]`` record buffer (autoregressive
        history; entry b is the image generated with guidance ``w[b]``).
      record_R / record_T: ``[N, 3, 3]`` / ``[N, 3]`` poses of the record.
      record_len: scalar int — number of valid entries.
      target_R / target_T: pose of the view being synthesised.
      K: ``[3, 3]`` shared intrinsics.
      w: ``[B]`` guidance weights (one image per weight).
      steps: schedule subset size (see :func:`sample_schedule_ts`);
        ``None`` runs the full ``timesteps`` grid.
      sampler_kind: one of :data:`SAMPLER_KINDS`.
      start_t / draft: truncated refinement — renoise the ``[B, H, W, 3]``
        draft to grid point ``start_t`` and run only the remaining steps
        (see :func:`sample_loop_prepare`).
    Returns:
      ``[B, H, W, 3]`` generated view.
    """
    if sampler_kind not in SAMPLER_KINDS:
        raise ValueError(
            f"sampler_kind={sampler_kind!r} not in {SAMPLER_KINDS}")
    state, xs = sample_loop_prepare(
        record_len=record_len, rng=rng, timesteps=timesteps,
        shape=(w.shape[0],) + record_imgs.shape[-3:],
        logsnr_min=logsnr_min, logsnr_max=logsnr_max, steps=steps,
        start_t=start_t, draft=draft)
    state = sample_loop_scan(
        denoise_fn, state, xs, record_imgs=record_imgs, record_R=record_R,
        record_T=record_T, target_R=target_R, target_T=target_T, K=K,
        w=w, logsnr_max=logsnr_max, clip_x0=clip_x0,
        deterministic=(sampler_kind == "ddim"), hoist_cond=hoist_cond)
    return state.img


def sample_view(denoise_fn: DenoiseFn, *, record_imgs: jnp.ndarray,
                record_R: jnp.ndarray, record_T: jnp.ndarray,
                record_len: jnp.ndarray, K: jnp.ndarray, w: jnp.ndarray,
                rng: jax.Array, timesteps: int = 256,
                logsnr_min: float = -20.0, logsnr_max: float = 20.0,
                clip_x0: bool = True, steps: int | None = None,
                sampler_kind: str = "ancestral",
                start_t: float | None = None,
                draft: jnp.ndarray | None = None):
    """One autoregressive view step over a DEVICE-RESIDENT record.

    The record-carry contract (the sampler's host loop never touches the
    buffers between views):

      * ``record_R`` / ``record_T`` are pre-filled with the poses of ALL
        views up front — safe because the stochastic-conditioning draw
        (:func:`sample_loop_prepare`) only reads indices ``<
        record_len``, so entry ``record_len`` doubles as the target pose
        of the view being synthesised.
      * the generated view is written back at index ``record_len`` via
        ``lax.dynamic_update_slice`` (donate ``record_imgs`` when
        jitting: the update is then in place on device).
      * ``rng`` is the per-object carry; it is split here exactly like
        the legacy host loop's ``rng, k = jax.random.split(rng)``, so
        the per-view key stream is bit-identical to the pre-resident
        sampler (the serving parity tests pin this).

    Returns ``(out, record_imgs, record_len + 1, rng)`` with ``out``
    ``[B, H, W, 3]`` — a pure carry update; the host feeds the returned
    buffers straight into the next call.
    """
    with scope("sampler"):
        rng, k = jax.random.split(rng)
    with scope("record"):
        target_R, target_T = record_R[record_len], record_T[record_len]
    out = sample_loop(
        denoise_fn, record_imgs=record_imgs, record_R=record_R,
        record_T=record_T, record_len=record_len,
        target_R=target_R, target_T=target_T,
        K=K, w=w, rng=k, timesteps=timesteps, logsnr_min=logsnr_min,
        logsnr_max=logsnr_max, clip_x0=clip_x0, steps=steps,
        sampler_kind=sampler_kind, start_t=start_t, draft=draft)
    out2, record_imgs, record_len = sample_view_commit(
        record_imgs, record_len, out)
    return out2, record_imgs, record_len, rng


def sample_view_commit(record_imgs: jnp.ndarray, record_len: jnp.ndarray,
                       img: jnp.ndarray):
    """Append ``img`` to the record at index ``record_len`` (the
    device-resident tail of :func:`sample_view`, split out so chunked
    callers can commit after their last :func:`sample_loop_scan` chunk).
    Returns ``(img, record_imgs, record_len + 1)``."""
    with scope("record"):
        start = (record_len,) + (0,) * (record_imgs.ndim - 1)
        record_imgs = jax.lax.dynamic_update_slice(
            record_imgs, img[None].astype(record_imgs.dtype), start)
        return img, record_imgs, record_len + 1


def sample_loop_prepare(*, record_len: jnp.ndarray, rng: jax.Array,
                        timesteps: int, shape, logsnr_min: float,
                        logsnr_max: float, steps: int | None = None,
                        start_t: float | None = None,
                        draft: jnp.ndarray | None = None):
    """Initial carry + per-step scan inputs for :func:`sample_loop_scan`.

    Splitting preparation from the scan lets a caller CHUNK the reverse
    diffusion across several device executions (``Sampler(scan_chunks=k)``)
    with a bit-identical RNG stream: ``scan(step, s0, xs)`` equals folding
    ``sample_loop_scan`` over consecutive slices of ``xs`` because every
    per-step key derives from the carried rng.  (Each chunk is its own,
    shorter device execution; ``chunks=1`` is one execution per view.)
    ``shape`` is ``(B, H, W, 3)``.

    ``steps`` (default: ``timesteps``) subsets the dense grid via
    :func:`sample_schedule_ts`.  All random draws — init image and the
    stochastic-conditioning indices — stay on the SAME carried key stream
    regardless of ``steps``; at ``steps == timesteps`` every array here is
    bit-identical to the historical full-grid path, which is what keeps
    the 256-step ancestral sampler usable as a parity oracle.

    ``start_t`` + ``draft`` (cascade refinement): the grid is truncated to
    ``[start_t, 0]`` and the init image becomes the ``[B, H, W, 3]`` draft
    renoised to ``start_t`` via the forward process (:func:`q_sample`)
    using the SAME ``k_init`` draw the untruncated path spends on pure
    noise — the key stream is schedule-independent either way.  At
    ``start_t = 1.0`` the VP prior is exactly ``N(0, 1)``, so the draft is
    ignored and the init is the untruncated path's noise bit-for-bit: a
    stride-1-from-t=max cascade run equals the ancestral dense oracle.
    """
    with scope("sampler"):
        ts = sample_schedule_ts(steps, timesteps=timesteps, start_t=start_t)
        n_steps = ts.shape[0] - 1
        logsnrs = logsnr_schedule_cosine(ts[:-1], logsnr_min=logsnr_min,
                                         logsnr_max=logsnr_max)
        logsnr_nexts = logsnr_schedule_cosine(ts[1:], logsnr_min=logsnr_min,
                                              logsnr_max=logsnr_max)
        rng, k_init, k_idx = jax.random.split(rng, 3)
        noise = jax.random.normal(k_init, shape)
        if draft is None or start_t is None or float(start_t) >= 1.0:
            init_img = noise
        else:
            logsnr_start = logsnr_schedule_cosine(
                jnp.asarray(start_t), logsnr_min=logsnr_min,
                logsnr_max=logsnr_max)
            init_img = q_sample(draft.astype(noise.dtype),
                                jnp.full((shape[0],), logsnr_start), noise)
        # Pre-sampled stochastic-conditioning indices (reference
        # `random.choice(record)`, sampling.py:138) — computed up front so
        # the scan body is trace-static.
        cond_idx = jax.random.randint(k_idx, (n_steps,), 0, record_len)
        return SampleState(init_img, rng), (logsnrs, logsnr_nexts, cond_idx)


def sample_loop_scan(denoise_fn: DenoiseFn, state: SampleState, xs, *,
                     record_imgs: jnp.ndarray, record_R: jnp.ndarray,
                     record_T: jnp.ndarray, target_R: jnp.ndarray,
                     target_T: jnp.ndarray, K: jnp.ndarray, w: jnp.ndarray,
                     logsnr_max: float, clip_x0: bool,
                     deterministic: bool = False,
                     hoist_cond: bool = True) -> SampleState:
    """``lax.scan`` the reverse steps in ``xs`` from ``state`` (a full
    run, or one chunk of it — see :func:`sample_loop_prepare`).

    ``deterministic`` selects the DDIM (eta=0) update instead of the
    ancestral one.  Both branches split the carried rng identically
    (``rng, k_x, k_noise``) so the uncond-frame draws and the downstream
    key stream are shared between samplers at matched seeds — the DDIM
    path simply never consumes ``k_noise``.

    ``hoist_cond`` precomputes the intrinsics-only conditioning stage
    (``pinhole_rays_cam``: the K_inv @ pixel-grid contraction, constant
    across the trajectory's steps) once before the scan and feeds it to
    the model as ``batch['cam_dirs']`` — certified loop-invariant by
    ``equiv.verify_hoist`` and bit-exact vs the unhoisted body (the
    rngcheck stream manifests are byte-identical either way).  False
    keeps the in-loop computation (the equivalence oracle).
    """
    B = w.shape[0]

    # The B guidance weights of a view share one pose and one logSNR, so
    # the model gets its conditioning at 2 rows — the conditional one and
    # the unconditional one, in the order of the `[cond x B, uncond x B]`
    # examples — and computes that branch twice, not 2B times (the
    # group-major rule of the model's forward contract).
    with scope("sampler"):
        K2 = jnp.broadcast_to(K[None], (2, 3, 3))
        w_mask_2 = jnp.array([True, False])

    cam_dirs = None
    if hoist_cond:
        from diff3d_tpu.geometry import pinhole_rays_cam

        H, W = record_imgs.shape[-3:-1]
        with scope("conditioning"):
            cam_dirs = pinhole_rays_cam(
                K2[:, None].astype(jnp.float32), H, W)     # [2, 1, H, W, 3]

    def step(state: SampleState, xs):
        logsnr, logsnr_next, idx, = xs
        with scope("sampler"):
            rng, k_x, k_noise = jax.random.split(state.rng, 3)
        with scope("record"):
            cond_img = record_imgs[idx]                     # [B, H, W, 3]
            R = jnp.stack([record_R[idx], target_R])        # [2, 3, 3]
            T = jnp.stack([record_T[idx], target_T])        # [2, 3]

        with scope("sampler"):
            # Fold CFG cond + uncond passes into one 2B model call.
            x_uncond = jax.random.normal(k_x, cond_img.shape,
                                         cond_img.dtype)
            batch = make_model_batch(
                jnp.concatenate([cond_img, x_uncond]),
                jnp.concatenate([state.img, state.img]),
                jnp.full((2,), logsnr),
                jnp.broadcast_to(R[None], (2, 2, 3, 3)),
                jnp.broadcast_to(T[None], (2, 2, 3)),
                K2,
                logsnr_max=logsnr_max)
            if cam_dirs is not None:
                batch = dict(batch, cam_dirs=cam_dirs)     # scan constant
        eps = denoise_fn(batch, w_mask_2)

        with scope("sampler"):
            eps_cond, eps_uncond = eps[:B], eps[B:]
            if deterministic:
                img = ddim_step(
                    eps_cond, eps_uncond, state.img, logsnr, logsnr_next,
                    w.astype(state.img.dtype), clip_x0=clip_x0)
            else:
                mean, var = p_mean_variance(
                    eps_cond, eps_uncond, state.img, logsnr, logsnr_next,
                    w.astype(state.img.dtype), clip_x0=clip_x0)
                noise = jax.random.normal(
                    k_noise, state.img.shape, state.img.dtype)
                # Reference guard `if logsnr_next == 0: return mean`
                # (train.py:125-126) — kept for parity even though the
                # schedule's min logsnr is -20, so it never fires there.
                img = jnp.where(logsnr_next == 0.0, mean,
                                mean + jnp.sqrt(var) * noise)
        return SampleState(img, rng), None

    # the loop itself (the `while` op and its counter) is the sampler's
    with scope("sampler"):
        state, _ = jax.lax.scan(step, state, xs)
    return state
