"""TPU Pallas fused GroupNorm epilogues (forward + backward).

The X-UNet's per-step cost is dominated by memory-bound elementwise
chains around its ~40 ``FrameGroupNorm`` sites: every ``ResnetBlock``
runs GN -> SiLU at its entry and GN -> FiLM(scale/shift) before its
second conv, each as a string of separate XLA ops — statistics,
normalize, affine, modulate, activation — and each op is a full
``[B, F, H, W, C]`` HBM round trip.  This module fuses each chain into
one VMEM-resident kernel:

  * **forward** — a two-phase tile program over ``[N, L, C]`` (frames
    folded into N, pixels into L).  Phase 0 streams the row tiles once,
    accumulating per-channel sum / sum-of-squares in f32 VMEM scratch
    (the same mean/E[x^2] formulation Flax's GroupNorm uses).  Phase 1
    reduces channels to group statistics with a 0/1 group-membership
    mask matmul (static counts — padded rows and channels are excluded
    exactly), then re-streams each tile, normalizing, applying
    gamma/beta, the optional per-pixel FiLM ``(1+scale)*y + shift``,
    and the optional SiLU, writing the only ``[N, L, C]``-sized HBM
    traffic of the whole chain.  Under differentiation the per-channel
    mean/rstd are written out as an ``[N, 8, C_pad]`` residual
    (sublane-replicated — TPU output blocks need (8, 128)-aligned
    trailing dims); the inference path skips them.
  * **backward** — the standard GN gradient in the same two-phase
    shape: phase 0 re-derives x_hat and the upstream gradient through
    SiLU/FiLM per tile, accumulating the two per-channel reductions
    ``sum(dxhat)`` / ``sum(dxhat * xhat)`` plus per-N dgamma/dbeta
    partials in scratch; phase 1 turns them into group means via the
    same mask matmul and emits ``dx = rstd * (dxhat - mean_g(dxhat)
    - xhat * mean_g(dxhat * xhat))`` and the per-pixel dscale/dshift
    tiles.  dgamma/dbeta partials are summed over N outside the kernel.

Channels are zero-padded to the 128-lane tile and rows to the f32
sublane multiple; padded channels carry zero gamma and land in
out-of-range mask groups, so they never pollute real statistics.  All
accumulation is float32 regardless of input dtype (bf16 inputs use the
MXU mask matmuls with f32 ``preferred_element_type``).

On a CPU process the kernels run in Pallas interpret mode (tests); on a
TPU process they are compiled or the call raises
(:func:`diff3d_tpu.ops.dispatch.interpret_default`).
:mod:`diff3d_tpu.ops.dispatch` only routes here when asked ('pallas')
or on TPU ('auto').
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from diff3d_tpu.ops import dispatch

LANE = 128          # TPU lane width: channels padded to a multiple
MAX_C = 4096        # padded-channel cap (srn128 up-path concat is 2048)
MIN_SUBLANE = 8     # f32 sublane granularity: row tiles padded to this
EPS = 1e-5          # torch/Flax GroupNorm epsilon (models/layers.py)
#: Row-tile VMEM budget: block_rows * C_pad * 4B stays under this, so
#: the streamed x/scale/shift/out tiles plus double-buffering fit VMEM
#: comfortably even at C_pad=2048.
_TILE_BYTES = 512 * 1024


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _row_block(L: int, C_pad: int) -> int:
    """Rows per tile: 128 at model widths, halved while the f32 tile
    exceeds the VMEM budget, shrunk to the sublane-padded L for tiny
    test images."""
    br = 128
    while br > MIN_SUBLANE and br * C_pad * 4 > _TILE_BYTES:
        br //= 2
    if L < br:
        br = max(MIN_SUBLANE, _round_up(L, MIN_SUBLANE))
    return br


def _g_pad(C_pad: int, group_size: int) -> int:
    """Mask-group count padded to full lanes.  Covers every padded
    channel's ``c // group_size`` id: pad channels (c >= C) map to ids
    >= num_groups, i.e. into all-pad groups that real channels never
    read back."""
    return _round_up((C_pad + group_size - 1) // group_size, LANE)


def _out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes set so
    the kernels work inside ``shard_map`` (same contract as
    pallas_attention)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def supports(x: jnp.ndarray, *args, num_groups: int = 32,
             **kwargs) -> bool:
    """Shapes/dtypes the fused kernel handles: ``[N, L, C]`` with C
    divisible by ``num_groups`` and padded channels within MAX_C."""
    if getattr(x, "ndim", 0) != 3:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    C = x.shape[-1]
    if C < 1 or C % num_groups:
        return False
    return _round_up(C, LANE) <= MAX_C


def _auto(x: jnp.ndarray, *args, **kwargs) -> bool:
    """'auto' policy: the fusion pays off once the chain is actually
    memory-bound — any real feature map qualifies; only degenerate
    few-pixel shapes stay on XLA."""
    return x.shape[1] >= 64


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _group_masks(C: int, C_pad: int, G_pad: int, group_size: int):
    """The 0/1 group-membership matrix ``M [C_pad, G_pad]`` (channel c
    belongs to group c // group_size; padded channels excluded), built
    from 2D iotas in-kernel."""
    cid = jax.lax.broadcasted_iota(jnp.int32, (C_pad, G_pad), 0)
    gid = jax.lax.broadcasted_iota(jnp.int32, (C_pad, G_pad), 1)
    member = (cid // group_size == gid) & (cid < C)
    return member.astype(jnp.float32)


def _channel_stats(chan_sum, chan_sq, M, count: float):
    """Per-channel mean / rstd ``[1, C_pad]`` from per-channel sums via
    the group mask: reduce channels -> groups, normalize by the static
    real-element count, broadcast groups -> channels.  Padded channels
    (all-zero mask rows) come back with mean = rstd = 0."""
    gsum = jnp.dot(chan_sum, M, preferred_element_type=jnp.float32)
    gsq = jnp.dot(chan_sq, M, preferred_element_type=jnp.float32)
    gmean = gsum / count
    gvar = jnp.maximum(gsq / count - gmean * gmean, 0.0)
    grstd = jax.lax.rsqrt(gvar + EPS)
    mean_c = jnp.dot(gmean, M.T, preferred_element_type=jnp.float32)
    rstd_c = jnp.dot(grstd, M.T, preferred_element_type=jnp.float32)
    return mean_c, rstd_c


def _silu_grad(y: jnp.ndarray) -> jnp.ndarray:
    sig = jax.nn.sigmoid(y)
    return sig * (1.0 + y * (1.0 - sig))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _fwd_kernel(*refs, L: int, C: int, C_pad: int, G_pad: int,
                group_size: int, block_rows: int, film: bool, silu: bool,
                save_stats: bool):
    if film:
        x_ref, gamma_ref, beta_ref, scale_ref, shift_ref = refs[:5]
        rest = refs[5:]
    else:
        x_ref, gamma_ref, beta_ref = refs[:3]
        scale_ref = shift_ref = None
        rest = refs[3:]
    if save_stats:
        o_ref, mean_ref, rstd_ref, sum_scr, sq_scr = rest
    else:
        o_ref, sum_scr, sq_scr = rest
        mean_ref = rstd_ref = None
    p = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when((p == 0) & (t == 0))
    def _init():
        sum_scr[...] = jnp.zeros_like(sum_scr)
        sq_scr[...] = jnp.zeros_like(sq_scr)

    @pl.when(p == 0)
    def _accumulate():
        x = x_ref[0].astype(jnp.float32)               # [br, C_pad]
        rows = t * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)
        x = jnp.where(rows < L, x, 0.0)                # mask pad rows
        sum_scr[...] += jnp.sum(x, axis=0, keepdims=True)
        sq_scr[...] += jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(p == 1)
    def _emit():
        M = _group_masks(C, C_pad, G_pad, group_size)
        mean_c, rstd_c = _channel_stats(
            sum_scr[0:1, :], sq_scr[0:1, :], M,
            float(L * group_size))
        x = x_ref[0].astype(jnp.float32)
        y = (x - mean_c) * rstd_c
        y = y * gamma_ref[0:1, :] + beta_ref[0:1, :]
        if film:
            y = y * (1.0 + scale_ref[0].astype(jnp.float32)) \
                + shift_ref[0].astype(jnp.float32)
        if silu:
            y = y * jax.nn.sigmoid(y)
        o_ref[0] = y.astype(o_ref.dtype)
        if save_stats:
            @pl.when(t == 0)
            def _stats():
                mean_ref[0] = jnp.broadcast_to(mean_c, mean_ref.shape[1:])
                rstd_ref[0] = jnp.broadcast_to(rstd_c, rstd_ref.shape[1:])


def _pad_rows_chans(x, L_pad: int, C_pad: int):
    N, L, C = x.shape
    return jnp.pad(x, ((0, 0), (0, L_pad - L), (0, C_pad - C)))


def _affine_tile(p, C_pad: int):
    """[C] f32 param -> sublane-replicated [8, C_pad] kernel operand."""
    p = jnp.pad(p.astype(jnp.float32), (0, C_pad - p.shape[0]))
    return jnp.broadcast_to(p[None], (MIN_SUBLANE, C_pad))


def _fwd_call(x, gamma, beta, scale, shift, *, num_groups: int,
              film: bool, silu: bool, interpret: bool, save_stats: bool):
    N, L, C = x.shape
    C_pad = _round_up(C, LANE)
    br = _row_block(L, C_pad)
    L_pad = _round_up(L, br)
    gs = C // num_groups
    G_pad = _g_pad(C_pad, gs)
    grid = (N, 2, L_pad // br)

    xp = _pad_rows_chans(x, L_pad, C_pad)
    gp, bp = _affine_tile(gamma, C_pad), _affine_tile(beta, C_pad)
    x_spec = pl.BlockSpec((1, br, C_pad), lambda n, p, t: (n, t, 0))
    ab_spec = pl.BlockSpec((MIN_SUBLANE, C_pad), lambda n, p, t: (0, 0))
    # Each out block is visited through all of phase 0 at row 0 (no
    # write, no flush — the index only changes on phase 1's walk), then
    # written exactly once with real data.
    o_spec = pl.BlockSpec((1, br, C_pad), lambda n, p, t: (n, p * t, 0))
    st_spec = pl.BlockSpec((1, MIN_SUBLANE, C_pad),
                           lambda n, p, t: (n, 0, 0))

    operands = [xp, gp, bp]
    in_specs = [x_spec, ab_spec, ab_spec]
    if film:
        operands += [_pad_rows_chans(scale, L_pad, C_pad),
                     _pad_rows_chans(shift, L_pad, C_pad)]
        in_specs += [x_spec, x_spec]
    out_specs = [o_spec]
    out_shape = [_out_struct((N, L_pad, C_pad), x.dtype, x)]
    if save_stats:
        out_specs += [st_spec, st_spec]
        out_shape += [
            _out_struct((N, MIN_SUBLANE, C_pad), jnp.float32, x),
            _out_struct((N, MIN_SUBLANE, C_pad), jnp.float32, x)]

    kernel = functools.partial(
        _fwd_kernel, L=L, C=C, C_pad=C_pad, G_pad=G_pad, group_size=gs,
        block_rows=br, film=film, silu=silu, save_stats=save_stats)
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_vmem((MIN_SUBLANE, C_pad)),
                        _vmem((MIN_SUBLANE, C_pad))],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*operands)
    out = outs[0][:, :L, :C]
    if save_stats:
        return out, outs[1], outs[2]
    return out, None, None


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_kernel(*refs, L: int, C: int, C_pad: int, G_pad: int,
                group_size: int, block_rows: int, film: bool, silu: bool):
    if film:
        (x_ref, g_ref, gamma_ref, beta_ref, scale_ref, shift_ref,
         mean_ref, rstd_ref, dx_ref, dscale_ref, dshift_ref,
         dgamma_ref, dbeta_ref, s1_scr, s2_scr, dg_scr, db_scr) = refs
    else:
        (x_ref, g_ref, gamma_ref, beta_ref, mean_ref, rstd_ref,
         dx_ref, dgamma_ref, dbeta_ref, s1_scr, s2_scr, dg_scr,
         db_scr) = refs
        scale_ref = shift_ref = dscale_ref = dshift_ref = None
    p = pl.program_id(1)
    t = pl.program_id(2)

    mean_c = mean_ref[0][0:1, :]                       # [1, C_pad]
    rstd_c = rstd_ref[0][0:1, :]
    gamma = gamma_ref[0:1, :]

    def _tile_grads():
        """(xhat, y_gn, dy_f, dy_gn, dxhat) for the current tile.
        All padding is benign: the upstream gradient is zero-padded, so
        every padded row/channel contributes exact zeros."""
        x = x_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        xhat = (x - mean_c) * rstd_c
        y_gn = xhat * gamma + beta_ref[0:1, :]
        if film:
            scale = scale_ref[0].astype(jnp.float32)
            y = y_gn * (1.0 + scale) + shift_ref[0].astype(jnp.float32)
        else:
            scale = None
            y = y_gn
        dy_f = g * _silu_grad(y) if silu else g
        dy_gn = dy_f * (1.0 + scale) if film else dy_f
        dxhat = dy_gn * gamma
        return xhat, y_gn, dy_f, dy_gn, dxhat

    @pl.when((p == 0) & (t == 0))
    def _init():
        s1_scr[...] = jnp.zeros_like(s1_scr)
        s2_scr[...] = jnp.zeros_like(s2_scr)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    @pl.when(p == 0)
    def _accumulate():
        xhat, _y_gn, _dy_f, dy_gn, dxhat = _tile_grads()
        s1_scr[...] += jnp.sum(dxhat, axis=0, keepdims=True)
        s2_scr[...] += jnp.sum(dxhat * xhat, axis=0, keepdims=True)
        dg_scr[...] += jnp.sum(dy_gn * xhat, axis=0, keepdims=True)
        db_scr[...] += jnp.sum(dy_gn, axis=0, keepdims=True)

    @pl.when(p == 1)
    def _emit():
        M = _group_masks(C, C_pad, G_pad, group_size)
        count = float(L * group_size)
        gS1 = jnp.dot(s1_scr[0:1, :], M,
                      preferred_element_type=jnp.float32) / count
        gS2 = jnp.dot(s2_scr[0:1, :], M,
                      preferred_element_type=jnp.float32) / count
        m1_c = jnp.dot(gS1, M.T, preferred_element_type=jnp.float32)
        m2_c = jnp.dot(gS2, M.T, preferred_element_type=jnp.float32)
        xhat, y_gn, dy_f, _dy_gn, dxhat = _tile_grads()
        dx = rstd_c * (dxhat - m1_c - xhat * m2_c)
        dx_ref[0] = dx.astype(dx_ref.dtype)
        if film:
            dscale_ref[0] = (dy_f * y_gn).astype(dscale_ref.dtype)
            dshift_ref[0] = dy_f.astype(dshift_ref.dtype)

        @pl.when(t == 0)
        def _partials():
            dgamma_ref[0] = dg_scr[...]
            dbeta_ref[0] = db_scr[...]


def _bwd_call(x, g, gamma, beta, scale, shift, mean, rstd, *,
              num_groups: int, film: bool, silu: bool, interpret: bool):
    N, L, C = x.shape
    C_pad = _round_up(C, LANE)
    br = _row_block(L, C_pad)
    L_pad = _round_up(L, br)
    gs = C // num_groups
    G_pad = _g_pad(C_pad, gs)
    grid = (N, 2, L_pad // br)

    xp = _pad_rows_chans(x, L_pad, C_pad)
    gup = _pad_rows_chans(g, L_pad, C_pad)
    gp, bp = _affine_tile(gamma, C_pad), _affine_tile(beta, C_pad)
    x_spec = pl.BlockSpec((1, br, C_pad), lambda n, p, t: (n, t, 0))
    ab_spec = pl.BlockSpec((MIN_SUBLANE, C_pad), lambda n, p, t: (0, 0))
    o_spec = pl.BlockSpec((1, br, C_pad), lambda n, p, t: (n, p * t, 0))
    st_spec = pl.BlockSpec((1, MIN_SUBLANE, C_pad),
                           lambda n, p, t: (n, 0, 0))

    operands = [xp, gup, gp, bp]
    in_specs = [x_spec, x_spec, ab_spec, ab_spec]
    if film:
        operands += [_pad_rows_chans(scale, L_pad, C_pad),
                     _pad_rows_chans(shift, L_pad, C_pad)]
        in_specs += [x_spec, x_spec]
    operands += [mean, rstd]
    in_specs += [st_spec, st_spec]

    out_specs = [o_spec]
    out_shape = [_out_struct((N, L_pad, C_pad), x.dtype, x)]
    if film:
        out_specs += [o_spec, o_spec]
        out_shape += [
            _out_struct((N, L_pad, C_pad), scale.dtype, x),
            _out_struct((N, L_pad, C_pad), shift.dtype, x)]
    out_specs += [st_spec, st_spec]
    out_shape += [
        _out_struct((N, MIN_SUBLANE, C_pad), jnp.float32, x),
        _out_struct((N, MIN_SUBLANE, C_pad), jnp.float32, x)]

    kernel = functools.partial(
        _bwd_kernel, L=L, C=C, C_pad=C_pad, G_pad=G_pad, group_size=gs,
        block_rows=br, film=film, silu=silu)
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_vmem((MIN_SUBLANE, C_pad))] * 4,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*operands)
    dx = outs[0][:, :L, :C]
    nxt = 1
    if film:
        dscale = outs[nxt][:, :L, :C]
        dshift = outs[nxt + 1][:, :L, :C]
        nxt += 2
    else:
        dscale = dshift = None
    # Per-N partials: row 0 of the sublane-replicated block, real
    # channels only, summed over N in XLA (a [N, C] reduce — tiny).
    dgamma = jnp.sum(outs[nxt][:, 0, :C], axis=0)
    dbeta = jnp.sum(outs[nxt + 1][:, 0, :C], axis=0)
    return dx, dscale, dshift, dgamma, dbeta


# --------------------------------------------------------------------------
# public entry: custom-vjp fused GroupNorm epilogue over [N, L, C]
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _fused(x, gamma, beta, scale, shift, num_groups: int, film: bool,
           silu: bool, interpret: bool):
    # Primal (inference) path: no stats residuals materialised.
    out, _, _ = _fwd_call(x, gamma, beta, scale, shift,
                          num_groups=num_groups, film=film, silu=silu,
                          interpret=interpret, save_stats=False)
    return out


def _fused_fwd(x, gamma, beta, scale, shift, num_groups: int, film: bool,
               silu: bool, interpret: bool):
    out, mean, rstd = _fwd_call(x, gamma, beta, scale, shift,
                                num_groups=num_groups, film=film,
                                silu=silu, interpret=interpret,
                                save_stats=True)
    return out, (x, gamma, beta, scale, shift, mean, rstd)


def _fused_bwd(num_groups: int, film: bool, silu: bool, interpret: bool,
               res, g):
    x, gamma, beta, scale, shift, mean, rstd = res
    dx, dscale, dshift, dgamma, dbeta = _bwd_call(
        x, g, gamma, beta, scale, shift, mean, rstd,
        num_groups=num_groups, film=film, silu=silu, interpret=interpret)
    if not film:
        dscale = jnp.zeros_like(scale)
        dshift = jnp.zeros_like(shift)
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dscale, dshift)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_groupnorm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                    *, num_groups: int, scale: Optional[jnp.ndarray] = None,
                    shift: Optional[jnp.ndarray] = None, silu: bool = False,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused GroupNorm -> (FiLM) -> (SiLU) over ``[N, L, C]``.

    ``gamma`` / ``beta`` are the ``[C]`` GroupNorm affine params
    (float32, like Flax keeps them); ``scale`` / ``shift`` — both or
    neither — are per-pixel FiLM tensors shaped like ``x`` and the
    epilogue becomes ``y * (1 + scale) + shift``.  ``silu`` appends the
    activation.  ``interpret`` defaults to True on a CPU process only
    (the CPU tests exercise exactly the tile program the TPU compiles).  Epsilon is the torch-parity 1e-5.
    """
    assert supports(x, num_groups=num_groups), \
        (x.shape, x.dtype, num_groups)
    film = scale is not None
    assert film == (shift is not None), "scale and shift come together"
    if film:
        assert scale.shape == x.shape and shift.shape == x.shape, \
            (x.shape, scale.shape, shift.shape)
    else:
        scale = jnp.zeros((), x.dtype)
        shift = jnp.zeros((), x.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _fused(x, gamma, beta, scale, shift, int(num_groups), film,
                  bool(silu), bool(interpret))


def xla_groupnorm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                  *, num_groups: int, scale: Optional[jnp.ndarray] = None,
                  shift: Optional[jnp.ndarray] = None, silu: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """The unfused XLA composition of the same chain — the dispatch
    fallback and the parity reference the kernel tests compare against.
    Statistics in f32 with Flax GroupNorm's mean/E[x^2] formulation and
    the same 1e-5 epsilon."""
    del interpret
    N, L, C = x.shape
    xf = x.astype(jnp.float32).reshape(N, L, num_groups, C // num_groups)
    mean = jnp.mean(xf, axis=(1, 3), keepdims=True)
    mean2 = jnp.mean(xf * xf, axis=(1, 3), keepdims=True)
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    y = (xf - mean) * jax.lax.rsqrt(var + EPS)
    y = y.reshape(N, L, C)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    y = y.astype(x.dtype)
    if scale is not None:
        y = y * (1.0 + scale) + shift
    if silu:
        y = jax.nn.silu(y)
    return y


dispatch.register("groupnorm", "pallas", fused_groupnorm,
                  supports=supports, auto=_auto)
dispatch.register("groupnorm", "xla", xla_groupnorm)
