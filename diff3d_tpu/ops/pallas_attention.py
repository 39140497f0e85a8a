"""TPU Pallas attention kernels: flash attention (forward + backward),
attention under a selection (forward) and plain grouped-query attention
(forward).

Three kernels share this file and its helpers.  :func:`flash_attention`,
described first, is the X-UNet's, asked for by hand: no mask, one
key-value head per query head, float32 dots, 128 x 128 tiles, its own
backward kernels.  :func:`selected_attention` is the token denoiser's: a
selection ``keep [B, Lq, Lk]``, grouped queries, operands to the MXU in
the dtype given, blocks sized from the shape, the XLA expression's
gradient.  :func:`plain_attention` (the last section) is that algorithm
without a selection and made to run at head dim 64: what ``sdpa``
resolves to on a TPU process from 2048 keys on.  The last two share
their online-softmax step and block rule.

Replaces the reference's ``torch.nn.MultiheadAttention`` sdpa core
(``/root/reference/xunet.py:154-177``, which delegates to cuDNN) with a
hand-tiled TPU kernel:

  * **forward** — online-softmax flash attention: the KV sequence is
    streamed through VMEM in ``block_k`` tiles while running max / sum /
    output accumulators live in VMEM scratch; one QK^T and one PV matmul
    per tile hit the MXU, nothing of size ``[Lq, Lk]`` ever touches HBM.
    Under differentiation the per-row log-sum-exp is written out as the
    backward residual (lane-replicated to a ``[.., 128]`` tile — TPU
    output blocks need the last two dims (8, 128)-aligned); the inference
    path skips the residual entirely.
  * **backward** — the standard two-kernel flash backward: one kernel
    accumulating dK/dV over query tiles and one accumulating dQ over key
    tiles, each recomputing the probabilities from (Q, K, lse).  The
    ``delta = rowsum(dO * O)`` term is computed in-kernel from the dO/O
    blocks (each block holds the full padded head dim, so the row sum is
    block-local).

Head dim is zero-padded to a multiple of the 128 lane width (D <= 512;
one lane tile at the srn64 deep levels' D=128, two at srn128's D=256 —
the q/k/v blocks and the output accumulator are ``D_pad`` lanes wide,
while the running max / sum and the lse residual stay one lane tile) and
sequence lengths to the tile size; padded key columns are masked to
-1e30 before the softmax so both passes ignore them.  Zero-padded head
columns contribute nothing to QK^T and stay zero through PV.  All
accumulation is float32 regardless of input dtype (bf16 inputs still use
the MXU with f32 accumulation via ``preferred_element_type``).

On a CPU process the kernels run in Pallas interpret mode (tests); on a
TPU process they are compiled or the call raises
(:func:`diff3d_tpu.ops.dispatch.interpret_default`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from diff3d_tpu.ops import dispatch

LANE = 128          # TPU lane width: head dim is padded to a multiple
MAX_D = 512         # supported head-dim cap (4 lane tiles in VMEM)
MIN_SUBLANE = 8     # f32 sublane granularity: seq tiles padded to this
NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _d_pad(D: int) -> int:
    """Head dim padded to full lane tiles (128 -> 128, 256 -> 256,
    96 -> 128, 160 -> 256)."""
    return _round_up(D, LANE)


def _out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes set, so the
    kernels work inside ``shard_map`` with its default ``check_vma=True``
    (the ring-attention engine path)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def supports(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> bool:
    """Shapes/dtypes this kernel handles: ``[B, L, H, D]`` with
    D <= MAX_D (512; covers srn128's deep-level D=256) and one
    key-value head per query head."""
    if q.ndim != 4 or k.ndim != 4 or k.shape != v.shape:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    D = q.shape[-1]
    return D <= MAX_D and k.shape[-1] == D and k.shape[2] == q.shape[2]


def _block_sizes(Lq: int, Lk: int) -> tuple[int, int, int, int]:
    """Pick (block_q, block_k, Lq_pad, Lk_pad)."""
    bq = 128 if Lq >= 128 else _round_up(Lq, MIN_SUBLANE)
    bk = 128 if Lk >= 128 else _round_up(Lk, MIN_SUBLANE)
    return bq, bk, _round_up(Lq, bq), _round_up(Lk, bk)


def _key_mask(ki: jax.Array, block_k: int, Lk: int) -> jnp.ndarray:
    """[1, block_k] bool — True for real (non-pad) key columns."""
    col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return col < Lk


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse_then_scratch,
                scale: float, Lk: int, block_k: int, save_lse: bool):
    if save_lse:
        lse_ref, m_scr, l_scr, acc_scr = maybe_lse_then_scratch
    else:
        m_scr, l_scr, acc_scr = maybe_lse_then_scratch
        lse_ref = None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [bq, D_pad]
    k = k_ref[0].astype(jnp.float32)                       # [bk, D_pad]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_key_mask(ki, block_k, Lk), s, NEG_INF)  # [bq, bk]

    m_prev = m_scr[:, :1]                                  # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)             # [bq, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                        # rescale old acc
    p = jnp.exp(s - m_new)                                 # [bq, bk]

    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                       # [bk, D_pad]
    pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv

    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        if save_lse:
            lse = m_scr[:, :1] + jnp.log(l_safe)           # [bq, 1]
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_call(q, k, v, *, scale: float, Lq: int, Lk: int, interpret: bool,
              save_lse: bool):
    """q/k/v: ``[N, L_pad, D_pad]``.  Returns ``o`` (and ``lse
    [N, Lq_pad, LANE]`` lane-replicated when ``save_lse``)."""
    N, Lq_pad, D_pad = q.shape
    Lk_pad = k.shape[1]
    bq, bk, _, _ = _block_sizes(Lq_pad, Lk_pad)
    grid = (N, Lq_pad // bq, Lk_pad // bk)

    qo_spec = pl.BlockSpec((1, bq, D_pad), lambda n, qi, ki: (n, qi, 0))
    kv_spec = pl.BlockSpec((1, bk, D_pad), lambda n, qi, ki: (n, ki, 0))
    lse_spec = pl.BlockSpec((1, bq, LANE), lambda n, qi, ki: (n, qi, 0))
    out_specs = [qo_spec]
    out_shape = [_out_struct((N, Lq_pad, D_pad), q.dtype, q)]
    if save_lse:
        out_specs.append(lse_spec)
        out_shape.append(
            _out_struct((N, Lq_pad, LANE), jnp.float32, q))

    kernel = functools.partial(_fwd_kernel, scale=scale, Lk=Lk, block_k=bk,
                               save_lse=save_lse)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((bq, LANE)), _vmem((bq, LANE)), _vmem((bq, D_pad)),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)
    return (outs[0], outs[1]) if save_lse else (outs[0], None)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, glse_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                     Lk: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)                       # [bq, D_pad]
    k = k_ref[0].astype(jnp.float32)                       # [bk, D_pad]
    v = v_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)                       # [bq, D_pad]
    do = do_ref[0].astype(jnp.float32)                     # [bq, D_pad]
    lse = lse_ref[0][:, :1]                                # [bq, 1]
    # delta = rowsum(dO * O): block-local (the D_pad-wide block covers the
    # whole padded head dim; padded columns are zero and contribute 0)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)        # [bq, 1]
    glse = glse_ref[0][:, :1]                              # [bq, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_key_mask(ki, block_k, Lk), s, NEG_INF)
    p = jnp.exp(s - lse)                                   # [bq, bk]

    # dV += P^T dO ; dP = dO V^T ; dS = P*(dP - delta + glse) ; dK += dS^T Q
    # (glse is the lse-output cotangent: d lse_i / d s_ij = p_ij)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta + glse) * scale
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, glse_ref,
                   dq_ref, dq_scr, *, scale: float, Lk: int, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    glse = glse_ref[0][:, :1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_key_mask(ki, block_k, Lk), s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta + glse) * scale                   # [bq, bk]
    dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, glse, *, scale: float, Lq: int, Lk: int,
              interpret: bool):
    N, Lq_pad, D_pad = q.shape
    Lk_pad = k.shape[1]
    bq, bk, _, _ = _block_sizes(Lq_pad, Lk_pad)

    q_spec = pl.BlockSpec((1, bq, D_pad), lambda n, a, b: (n, b, 0))
    k_spec = pl.BlockSpec((1, bk, D_pad), lambda n, ki, qi: (n, ki, 0))
    lse_spec = pl.BlockSpec((1, bq, LANE), lambda n, a, b: (n, b, 0))
    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, Lk=Lk, block_k=bk),
        grid=(N, Lk_pad // bk, Lq_pad // bq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, lse_spec,
                  lse_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            _out_struct((N, Lk_pad, D_pad), q.dtype, q),
            _out_struct((N, Lk_pad, D_pad), q.dtype, q),
        ],
        scratch_shapes=[_vmem((bk, D_pad)), _vmem((bk, D_pad))],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )
    dk, dv = dkdv(q, k, v, o, do, lse, glse)

    q2_spec = pl.BlockSpec((1, bq, D_pad), lambda n, qi, ki: (n, qi, 0))
    k2_spec = pl.BlockSpec((1, bk, D_pad), lambda n, qi, ki: (n, ki, 0))
    lse2_spec = pl.BlockSpec((1, bq, LANE), lambda n, qi, ki: (n, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, Lk=Lk, block_k=bk),
        grid=(N, Lq_pad // bq, Lk_pad // bk),
        in_specs=[q2_spec, k2_spec, k2_spec, q2_spec, q2_spec, lse2_spec,
                  lse2_spec],
        out_specs=q2_spec,
        out_shape=_out_struct((N, Lq_pad, D_pad), q.dtype, q),
        scratch_shapes=[_vmem((bq, D_pad))],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, o, do, lse, glse)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public entry: custom-vjp flash attention over [B, L, H, D]
# --------------------------------------------------------------------------

def _pad_qkv(x: jnp.ndarray, L_pad: int) -> jnp.ndarray:
    """[B, L, H, D] -> [B*H, L_pad, D_pad] (D_pad = full lane tiles)."""
    B, L, H, D = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * H, L, D)
    return jnp.pad(x, ((0, 0), (0, L_pad - L), (0, _d_pad(D) - D)))


def _unpad(x: jnp.ndarray, B: int, H: int, L: int, D: int) -> jnp.ndarray:
    """[B*H, L_pad, D_pad] -> [B, L, H, D]."""
    x = x[:, :L, :D].reshape(B, H, L, D)
    return jnp.moveaxis(x, 1, 2)


def _run_fwd(q, k, v, scale: float, interpret: bool, save_lse: bool):
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    bq, bk, Lq_pad, Lk_pad = _block_sizes(Lq, Lk)
    qp, kp, vp = (_pad_qkv(q, Lq_pad), _pad_qkv(k, Lk_pad),
                  _pad_qkv(v, Lk_pad))
    o, lse = _fwd_call(qp, kp, vp, scale=scale, Lq=Lq, Lk=Lk,
                       interpret=interpret, save_lse=save_lse)
    return _unpad(o, B, H, Lq, D), (qp, kp, vp, o, lse)


def _unpad_lse(lse, B, H, L):
    """Lane-replicated ``[B*H, L_pad, LANE]`` -> ``[B, L, H]``."""
    return jnp.moveaxis(lse[:, :L, 0].reshape(B, H, L), 1, 2)


def _pad_lse(g, B, H, L, L_pad):
    """``[B, L, H]`` -> lane-replicated ``[B*H, L_pad, LANE]``."""
    g = jnp.moveaxis(g, 2, 1).reshape(B * H, L)
    g = jnp.pad(g, ((0, 0), (0, L_pad - L)))
    return jnp.broadcast_to(g[..., None], (B * H, L_pad, LANE))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, scale: float, interpret: bool):
    # Primal (inference) path: no residuals materialised.
    return _run_fwd(q, k, v, scale, interpret, save_lse=False)[0]


def _flash_fwd(q, k, v, scale: float, interpret: bool):
    out, (qp, kp, vp, o, lse) = _run_fwd(q, k, v, scale, interpret,
                                         save_lse=True)
    B, Lq, H, D = q.shape
    return out, (qp, kp, vp, o, lse, (B, H, Lq, k.shape[1], D))


def _flash_bwd(scale, interpret, res, g):
    qp, kp, vp, o, lse, (B, H, Lq, Lk, D) = res
    Lq_pad = qp.shape[1]
    dop = _pad_qkv(g, Lq_pad)
    dq, dk, dv = _bwd_call(qp, kp, vp, o, lse, dop, jnp.zeros_like(lse),
                           scale=scale, Lq=Lq, Lk=Lk, interpret=interpret)
    return (_unpad(dq, B, H, Lq, D), _unpad(dk, B, H, Lk, D),
            _unpad(dv, B, H, Lk, D))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_lse(q, k, v, scale: float, interpret: bool):
    out, (_, _, _, _, lse) = _run_fwd(q, k, v, scale, interpret,
                                      save_lse=True)
    B, Lq, H, _ = q.shape
    return out, _unpad_lse(lse, B, H, Lq)


def _flash_lse_fwd(q, k, v, scale: float, interpret: bool):
    out, (qp, kp, vp, o, lse) = _run_fwd(q, k, v, scale, interpret,
                                         save_lse=True)
    B, Lq, H, D = q.shape
    return ((out, _unpad_lse(lse, B, H, Lq)),
            (qp, kp, vp, o, lse, (B, H, Lq, k.shape[1], D)))


def _flash_lse_bwd(scale, interpret, res, gs):
    g_o, g_lse = gs
    qp, kp, vp, o, lse, (B, H, Lq, Lk, D) = res
    Lq_pad = qp.shape[1]
    dop = _pad_qkv(g_o, Lq_pad)
    glse = _pad_lse(g_lse.astype(jnp.float32), B, H, Lq, Lq_pad)
    dq, dk, dv = _bwd_call(qp, kp, vp, o, lse, dop, glse, scale=scale,
                           Lq=Lq, Lk=Lk, interpret=interpret)
    return (_unpad(dq, B, H, Lq, D), _unpad(dk, B, H, Lk, D),
            _unpad(dv, B, H, Lk, D))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention over ``[B, L, H, D]`` (jax.nn layout).

    ``scale`` defaults to ``1/sqrt(D)`` (matching
    ``jax.nn.dot_product_attention``).  ``interpret`` defaults to True on
    a CPU process only (tests exercise the exact tile program the TPU
    compiles).
    """
    assert supports(q, k, v), (q.shape, k.shape, v.shape, q.dtype)
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _flash(q, k, v, scale, bool(interpret))


def flash_attention_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp, ``(o [B, L, H, D], lse [B, L, H] float32)``.

    This is the building block for blockwise/ring attention
    (:func:`diff3d_tpu.parallel.ring_attention.ring_sdpa`): partial
    attention outputs over KV shards combine exactly via
    ``lse = logaddexp(lse1, lse2); o = o1*exp(lse1-lse) + o2*exp(lse2-lse)``.
    Differentiable in both outputs (the lse cotangent folds into the
    backward kernels' ``dS`` term).
    """
    assert supports(q, k, v), (q.shape, k.shape, v.shape, q.dtype)
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _flash_lse(q, k, v, scale, bool(interpret))


# --------------------------------------------------------------------------
# attention under a selection: forward kernel, grouped queries
# --------------------------------------------------------------------------

SELECT_BLOCK_Q = (512, 256, 128, 64, 32)   # int8 ``keep`` tiles are (32, 128)
SELECT_BLOCK_K = (2048, 1024, 512, 256, 128)
SELECT_TILE = 1 << 19       # score elements of one head's block
SELECT_VMEM_BYTES = 64 << 20   # of the v5e's 128 MiB; the default is 16


def _selected_blocks(Lq: int, Lk: int, tile: int = SELECT_TILE
                     ) -> Optional[tuple[int, int]]:
    """(query block, key block) for ``Lq`` queries on ``Lk`` keys, or
    None when a length is no whole number of blocks.  Wide key blocks
    first: the row max and row sum cross the lanes once per row and key
    block, whatever the block's width (at 512 queries x 8192 keys on the
    v5e, 256 x 2048 ran at 8.3 ms a layer-example, 512 x 512 at 16.6:
    PERF.md section 6, PR 27); then as many queries as keep one head's
    float32 score block at ``tile`` elements (``SELECT_TILE``: 2 MB)."""
    bk = next((c for c in SELECT_BLOCK_K if Lk % c == 0), None)
    if bk is None:
        return None
    bq = next((c for c in SELECT_BLOCK_Q
               if Lq % c == 0 and c * bk <= tile), None)
    return None if bq is None else (bq, bk)


def _grouped_operands(q, k, v) -> bool:
    """``q [B, Lq, Hq, D]``, ``k, v [B, Lk, Hkv, D]`` of one dtype (bf16
    or float32), ``Hkv`` dividing ``Hq``."""
    if q.ndim != 4 or k.ndim != 4 or k.shape != v.shape:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16) \
            or k.dtype != q.dtype or v.dtype != q.dtype:
        return False
    return (k.shape[0] == q.shape[0] and k.shape[3] == q.shape[3]
            and q.shape[2] % k.shape[2] == 0)


def selected_supports(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      keep: jnp.ndarray) -> bool:
    """Shapes/dtypes :func:`selected_attention` handles: grouped
    operands (:func:`_grouped_operands`), ``keep [B, Lq, Lk]``; ``D``
    whole lane tiles (no head-dim padding on this path), ``Lq`` / ``Lk``
    whole query / key blocks."""
    if not _grouped_operands(q, k, v) or keep.ndim != 3:
        return False
    B, Lq, _, D = q.shape
    Lk = k.shape[1]
    return (D % LANE == 0 and D <= MAX_D and keep.shape == (B, Lq, Lk)
            and _selected_blocks(Lq, Lk) is not None)


def _init_running(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax(s, v, m_scr, l_scr, acc_scr, h: int):
    """Fold the float32 score block ``s [bq, bk]`` of head ``h`` and its
    values ``v [bk, W]`` into the head's running max, sum and output."""
    m_prev = m_scr[h, :, :1]                               # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[h, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.dot(p.astype(v.dtype), v,
                 preferred_element_type=jnp.float32)       # [bq, W]
    acc_scr[h] = acc_scr[h] * alpha + pv
    m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
    l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])


def _scores(q, k):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _selected_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_scr, l_scr,
                     acc_scr):
    """One (example, key-value head, query block, key block) step: the
    ``group`` query heads that share this key-value head take the same
    ``k`` / ``v`` block and the same ``keep`` block in turn.  ``q`` and
    ``o`` blocks are ``[bq, group * D]``: head ``g`` is the lane slice
    ``[g * D, (g + 1) * D)``, whole lane tiles, so nothing is transposed
    in HBM or on chip."""
    D = k_ref.shape[-1]
    group = q_ref.shape[-1] // D
    scale = float(1.0 / np.sqrt(D))
    ki = pl.program_id(3)

    pl.when(ki == 0)(lambda: _init_running(m_scr, l_scr, acc_scr))

    # 0 on a kept key, NEG_INF on the others: float32 absorbs any score
    # into NEG_INF, so a dropped key's probability is exp(NEG_INF - m) = 0
    # once its row has met a kept key, and a row without any (no caller
    # makes one) averages all keys, as the XLA expression does.
    bias = (keep_ref[0].astype(jnp.float32) - 1.0) * (-NEG_INF)
    k = k_ref[0]                                           # [bk, D]
    v = v_ref[0]
    for g in range(group):
        q = q_ref[0, :, g * D:(g + 1) * D]                 # [bq, D]
        s = _scores(q, k) * scale + bias                   # [bq, bk] f32
        _online_softmax(s, v, m_scr, l_scr, acc_scr, g)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        for g in range(group):
            o_ref[0, :, g * D:(g + 1) * D] = (
                acc_scr[g] / l_scr[g, :, :1]).astype(o_ref.dtype)


def _selected_fwd(q, k, v, keep, interpret: bool):
    B, Lq, Hq, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq, bk = _selected_blocks(Lq, Lk)
    qo_spec = pl.BlockSpec((1, bq, group * D),
                           lambda b, h, qi, ki: (b, qi, h))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, h, qi, ki: (b, ki, h))
    keep_spec = pl.BlockSpec((1, bq, bk), lambda b, h, qi, ki: (b, qi, ki))
    out = pl.pallas_call(
        _selected_kernel,
        grid=(B, Hkv, Lq // bq, Lk // bk),
        in_specs=[qo_spec, kv_spec, kv_spec, keep_spec],
        out_specs=qo_spec,
        out_shape=_out_struct((B, Lq, Hq * D), q.dtype, q),
        scratch_shapes=[_vmem((group, bq, LANE)), _vmem((group, bq, LANE)),
                        _vmem((group, bq, D))],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=SELECT_VMEM_BYTES),
        interpret=interpret,
    )(q.reshape(B, Lq, Hq * D), k.reshape(B, Lk, Hkv * D),
      v.reshape(B, Lk, Hkv * D), keep.astype(jnp.int8))
    return out.reshape(B, Lq, Hq, D)


def selected_reference(q, k, v, keep):
    """The XLA expression the kernel stands for, and the one it is
    differentiated through: a dense score tile, masked."""
    return jax.nn.dot_product_attention(q, k, v, mask=keep[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _selected(q, k, v, keep, interpret: bool):
    return _selected_fwd(q, k, v, keep, interpret)


def _selected_vjp_fwd(q, k, v, keep, interpret: bool):
    return _selected_fwd(q, k, v, keep, interpret), (q, k, v, keep)


def _selected_vjp_bwd(interpret, res, g):
    q, k, v, keep = res
    _, vjp = jax.vjp(lambda q, k, v: selected_reference(q, k, v, keep),
                     q, k, v)
    return (*vjp(g), np.zeros(keep.shape, jax.dtypes.float0))


_selected.defvjp(_selected_vjp_fwd, _selected_vjp_bwd)


def selected_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       keep: jnp.ndarray,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attention of ``q [B, Lq, Hq, D]`` over the keys ``keep [B, Lq, Lk]``
    (bool, shared by all heads) selects among ``k, v [B, Lk, Hkv, D]``:
    softmax over the kept keys alone, scores over ``sqrt(D)``.

    One forward kernel: key / value blocks stream through VMEM against a
    running max, sum and output accumulator in float32, the selection is
    applied to each score block on chip and only the output is written,
    so nothing of size ``[Hq, Lq, Lk]`` touches HBM.  The ``Hq / Hkv``
    query heads of a group share each ``k`` / ``v`` / ``keep`` block
    (:func:`_selected_kernel`).  Operands go to the MXU in the dtype
    given; the probabilities are cast to it for the ``PV`` product, as
    ``jax.nn.dot_product_attention`` casts them.  The gradient is the
    XLA expression's (:func:`selected_reference`) on the saved operands:
    there is no backward kernel.
    """
    assert selected_supports(q, k, v, keep), (q.shape, k.shape, v.shape,
                                              keep.shape, q.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _selected(q, k, v, keep, bool(interpret))


# --------------------------------------------------------------------------
# plain grouped-query attention: forward kernel, head dim 64 or lane tiles
# --------------------------------------------------------------------------

PLAIN_TILE = 1 << 20        # no ``keep`` block in VMEM: twice the queries


def plain_supports(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> bool:
    """Shapes/dtypes :func:`plain_attention` handles: grouped operands
    (:func:`_grouped_operands`); ``D`` whole lane tiles, or 64 with an
    even ``Hkv`` (two key-value heads to a lane tile); ``Lq`` / ``Lk``
    whole query / key blocks."""
    if not _grouped_operands(q, k, v):
        return False
    D, Hkv = k.shape[3], k.shape[2]
    lanes = (D % LANE == 0 and D <= MAX_D) or \
        (2 * D == LANE and Hkv % 2 == 0)
    return lanes and _selected_blocks(q.shape[1], k.shape[1],
                                      PLAIN_TILE) is not None


def _plain_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  D: int, group: int):
    """One (example, key-value block of lanes, query block, key block)
    step.  At ``D`` of whole lane tiles the ``k`` / ``v`` block is one
    key-value head and the ``group`` query heads are lane slices of the
    ``q`` block, as in :func:`_selected_kernel`.

    At ``D = 64`` the block ``[bk, 128]`` is two key-value heads, one a
    lane half, as ``[L, Hkv * 64]`` lies in memory, and the ``q`` block
    their ``2 * group`` query heads: nothing is transposed or padded in
    HBM.  Query head ``h`` is the half ``e = h % 2`` of its tile and
    attends the half ``f = h // group`` of ``k`` / ``v``.  Its tile with
    the other half zeroed contracts over all 128 lanes to the head's own
    scores (a 64-deep pass costs the MXU what a 128-deep one does); ``p
    @ v`` is ``[bq, 128]`` with the head's output in half ``f`` (the other
    half is the neighbour's values under this head's probabilities,
    dropped).  Where ``e != f`` the halves of ``q``, and of the output
    when it is written, are swapped by a lane rotation in float32 (Mosaic
    rotates 32-bit lanes alone), on ``[bq, 128]`` tiles: next to nothing
    beside the ``[bq, bk]`` softmax."""
    W = k_ref.shape[-1]
    packed = W != D
    heads = q_ref.shape[-1] // D
    bq = q_ref.shape[1]
    scale = float(1.0 / np.sqrt(D))
    # a power of two (D = 64, 256) scales q exactly, in any float dtype:
    # [bq, D] multiplications in place of [bq, bk]; the scores' bits stay
    exact = math.frexp(scale)[0] == 0.5
    ki = pl.program_id(3)
    pl.when(ki == 0)(lambda: _init_running(m_scr, l_scr, acc_scr))
    if packed:
        half = jax.lax.broadcasted_iota(jnp.int32, (bq, LANE), 1) // D

    def swap(x):
        return pltpu.roll(x.astype(jnp.float32), D, 1)

    k, v = k_ref[0], v_ref[0]                              # [bk, W]
    for h in range(heads):
        if packed:
            tile = q_ref[0, :, h // 2 * LANE:(h // 2 + 1) * LANE]
            q = jnp.where(half == h % 2, tile, jnp.zeros_like(tile))
            if h % 2 != h // group:
                q = swap(q).astype(tile.dtype)
        else:
            q = q_ref[0, :, h * D:(h + 1) * D]
        if exact:
            s = _scores(q * jnp.asarray(scale, q.dtype), k)
        else:
            s = _scores(q, k) * scale                      # [bq, bk] f32
        _online_softmax(s, v, m_scr, l_scr, acc_scr, h)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        def out(h):
            o = acc_scr[h] / l_scr[h, :, :1]
            return swap(o) if packed and h % 2 != h // group else o
        for t in range(heads * D // W):
            o_ref[0, :, t * W:(t + 1) * W] = (
                jnp.where(half == 0, out(2 * t), out(2 * t + 1))
                if packed else out(t)).astype(o_ref.dtype)


def _plain_fwd(q, k, v, interpret: bool):
    B, Lq, Hq, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    W = max(D, LANE)                  # lanes of a k / v block
    heads = W // D * group            # query heads that attend them
    bq, bk = _selected_blocks(Lq, Lk, PLAIN_TILE)
    qo_spec = pl.BlockSpec((1, bq, heads * D),
                           lambda b, h, qi, ki: (b, qi, h))
    kv_spec = pl.BlockSpec((1, bk, W), lambda b, h, qi, ki: (b, ki, h))
    out = pl.pallas_call(
        functools.partial(_plain_kernel, D=D, group=group),
        grid=(B, Hkv * D // W, Lq // bq, Lk // bk),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        out_shape=_out_struct((B, Lq, Hq * D), q.dtype, q),
        scratch_shapes=[_vmem((heads, bq, LANE)), _vmem((heads, bq, LANE)),
                        _vmem((heads, bq, W))],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=SELECT_VMEM_BYTES),
        interpret=interpret,
    )(q.reshape(B, Lq, Hq * D), k.reshape(B, Lk, Hkv * D),
      v.reshape(B, Lk, Hkv * D))
    return out.reshape(B, Lq, Hq, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _plain(q, k, v, interpret: bool):
    return _plain_fwd(q, k, v, interpret)


def _plain_vjp_fwd(q, k, v, interpret: bool):
    return _plain_fwd(q, k, v, interpret), (q, k, v)


def _plain_vjp_bwd(interpret, res, g):
    return jax.vjp(jax.nn.dot_product_attention, *res)[1](g)


_plain.defvjp(_plain_vjp_fwd, _plain_vjp_bwd)


def plain_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attention of ``q [B, Lq, Hq, D]`` over all of ``k, v [B, Lk, Hkv,
    D]``, scores over ``sqrt(D)``: ``jax.nn.dot_product_attention(q, k,
    v)`` with no ``[Hq, Lq, Lk]`` array in HBM.

    :func:`selected_attention`'s algorithm and block rule without a
    selection (512 x 2048 where it has 256 x 2048: no ``keep`` block),
    and made to run at head dim 64 (:func:`_plain_kernel`): operands to
    the MXU in the dtype given, float32 accumulation and softmax, the
    probabilities cast to the values' dtype for ``PV``.  The gradient is
    the XLA expression's on the saved operands: there is no backward
    kernel (:func:`flash_attention` has two, and float32 dots, 128 x 128
    tiles and one key-value head per query head).
    """
    assert plain_supports(q, k, v), (q.shape, k.shape, v.shape, q.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _plain(q, k, v, bool(interpret))
