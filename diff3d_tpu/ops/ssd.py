"""The selective state-space recurrence of a Mamba-2 mixer as a chunked
scan (the "state-space dual" form), a pure function of one example.

Per head ``h`` with state ``S [P, N]`` (``S = 0`` before the first
token), over the tokens ``t`` of the example in order::

    S_t = exp(dt[t, h] * A[h]) * S_{t-1} + dt[t, h] * x[t, h] B[t]^T
    y[t, h] = S_t C[t] + D[h] * x[t, h]

``B`` and ``C`` are shared by all heads (one group).  The sequence is cut
into chunks of ``chunk`` tokens (zero-padded at the end where ``chunk``
does not divide ``L``: a padded token has ``dt = 0``, so it neither
decays nor feeds the state).  With ``a = dt * A`` and ``cum`` its
inclusive sum inside a chunk:

  1. inside each chunk, token ``i`` reads token ``j <= i`` with weight
     ``exp(cum_i - cum_j) dt_j (C_i . B_j)``: the decay-masked ``(C B^T)
     x`` product, one ``[H, chunk, chunk]`` tile a chunk;
  2. each chunk's own contribution to the state at its end,
     ``sum_j exp(cum_end - cum_j) dt_j x_j B_j^T``;
  3. a recurrence over the chunks' states (``L / chunk`` steps of an
     elementwise update: 32 at ``L`` 8192, chunk 256);
  4. the state a chunk starts from, read by its tokens:
     ``exp(cum_i) C_i . S``.

Decays, ``dt``, cumulative sums and states are float32; the operands of
the four contractions are in ``x``'s dtype (the compute dtype), their
results float32.  Every decay is ``exp`` of a non-positive number: no
factor is ever divided out, so strong decays (``a`` of -6 a token) and
weak ones (-1e-4) are both exact to rounding.

Op ``'ssm_scan'`` of the kernel registry (:mod:`diff3d_tpu.ops.dispatch`)
has two cores; each traced :func:`ssd` site adds 1 to the recorder's
``ssm_scan.<core>``.  Both are differentiable and indifferent to ``vmap``.

  * :func:`ssd_chunked` (``'xla'``) — the four steps chunk-batched: the
    ``[n, H, chunk, chunk]`` decay tile and ``M``, each chunk's own state
    ``[n, H, P, N]`` and the states the chunks start from go through HBM
    (at ``L`` 8192, 128 heads: 1.07 GB of float32 decays an example).
  * :func:`ssd_pallas` (``'pallas'``, a TPU process where
    :func:`pallas_supports` holds, from ``MIN_TOKENS`` tokens an example
    on) — one Pallas kernel an example, grid
    (chunk, head block): a block's decay tiles and ``M`` live in VMEM
    only, the running states of all heads stay in VMEM from chunk to
    chunk, ``x``, ``B``, ``C``, ``dt`` and the chunks' cumulative decays
    are read once and ``y`` written once (:func:`_scan_kernel`).  Same
    arithmetic at the same rounding points; its gradient is the XLA
    core's on the saved operands (no backward kernel).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from diff3d_tpu.ops import dispatch
from diff3d_tpu.utils.profiling import count


def _padded(x, dt, B, C, chunk: int):
    """The operands zero-padded to whole chunks, the chunk length and the
    number of chunks."""
    L = x.shape[0]
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                       for a in (x, dt, B, C))
    return x, dt, B, C, Q, (L + pad) // Q


def _decays(dt, A, n: int, Q: int):
    """``dt`` float32 by chunk, ``[n, Q, H]``, and ``cum``, the inclusive
    sum of ``dt * A`` inside each chunk."""
    dtc = dt.astype(jnp.float32).reshape(n, Q, -1)
    return dtc, jnp.cumsum(dtc * A.astype(jnp.float32), axis=1)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray,
                chunk: int) -> jnp.ndarray:
    """``x [L, H, P]``, ``dt [L, H]`` float32 (positive), ``A [H]``
    float32 (negative), ``B, C [L, N]``, ``D [H]`` -> ``y [L, H, P]`` in
    ``x``'s dtype."""
    L, H, P = x.shape
    dtype, f32 = x.dtype, jnp.float32
    x, dt, B, C, Q, n = _padded(x, dt, B, C, chunk)
    xc = x.reshape(n, Q, H, P)
    Bc, Cc = B.reshape(n, Q, -1), C.reshape(n, Q, -1)
    dtc, cum = _decays(dt, A, n, Q)                          # [n, Q, H]

    # 1. inside the chunks
    G = jnp.einsum("cin,cjn->cij", Cc, Bc, preferred_element_type=f32)
    cum_h = jnp.swapaxes(cum, 1, 2)                          # [n, H, Q]
    seg = cum_h[:, :, :, None] - cum_h[:, :, None, :]        # i - j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))        # 0 where j > i
    M = G[:, None] * decay * jnp.swapaxes(dtc, 1, 2)[:, :, None, :]
    y = jnp.einsum("chij,cjhp->cihp", M.astype(dtype), xc,
                   preferred_element_type=f32)

    # 2. each chunk's contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc             # [n, Q, H]
    xw = (xc.astype(f32) * to_end[..., None]).astype(dtype)
    own = jnp.einsum("cjhp,cjn->chpn", xw, Bc, preferred_element_type=f32)

    # 3. the state each chunk starts from
    def carry(S, inp):
        keep, add = inp
        return keep[:, None, None] * S + add, S
    _, before = jax.lax.scan(carry, jnp.zeros(own.shape[1:], f32),
                             (jnp.exp(cum[:, -1, :]), own))

    # 4. what that state gives the chunk's tokens
    y = y + (jnp.einsum("cin,chpn->cihp", Cc, before.astype(dtype),
                        preferred_element_type=f32)
             * jnp.exp(cum)[..., None])
    y = y + D.astype(f32)[:, None] * xc.astype(f32)
    return y.reshape(n * Q, H, P)[:L].astype(dtype)


# --------------------------------------------------------------------------
# the Pallas core: one kernel an example, decay tiles and states in VMEM
# --------------------------------------------------------------------------

LANE = 128
# Lanes of ``x`` / ``y`` a grid step takes: ``LANES // P`` heads share one
# ``C B^T`` tile and one fetch of ``C`` and ``B`` (PERF.md section 5).
LANES = 1024
# Tokens from which the kernel beats the XLA core on one v5e (bf16, head
# dim 64, state 128, chunk 256; one example, ms, XLA -> kernel; PERF.md
# section 5).  128 heads: L 1024 0.222 -> 0.243, 2048 0.461 ->
# 0.491, 4096 1.184 -> 0.962, 8192 3.105 -> 2.690; 64 heads: 0.213 ->
# 0.210, 0.225 -> 0.229, 0.456 -> 0.425, 1.192 -> 0.844.
MIN_TOKENS = 4096
VMEM_BYTES = 64 << 20           # the limit handed to the compiler
VMEM_BUDGET = 48 << 20          # what ``pallas_supports`` lets a shape need


def _head_block(H: int, P: int) -> int:
    """Lanes a grid step takes: the most, up to ``LANES``, that divide
    ``H * P`` in whole lane tiles."""
    W = LANES
    while (H * P) % W:
        W //= 2
    return W


def _vmem_need(Q: int, N: int, H: int, P: int, itemsize: int) -> int:
    W = _head_block(H, P)
    blocks = 2 * (2 * Q * W + 2 * Q * N) * itemsize   # x, y, C, B^T; twice
    states = 4 * N * H * P                            # every head's S
    tiles = 4 * Q * Q * 8                             # G and a head's temps
    return blocks + states + tiles + 4 * 4 * Q * W    # float32 y, xw


def pallas_supports(x, dt, A, B, C, D, chunk: int) -> bool:
    """Operands :func:`ssd_pallas` handles: ``x`` bfloat16 or float32 and
    ``B`` / ``C`` alike; a head's ``P`` lanes inside one lane tile and
    ``H * P`` whole tiles; ``N`` whole lane tiles; the chunk (``min(chunk,
    L)``) whole lane tiles (it is the lane axis of ``B^T`` and of the
    chunks' decays); every head's state and a step's blocks inside
    ``VMEM_BUDGET``."""
    if x.ndim != 3 or dt.shape != x.shape[:2]:
        return False
    L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    return (x.dtype in (jnp.bfloat16, jnp.float32)
            and B.dtype == C.dtype == x.dtype
            and LANE % P == 0 and (H * P) % LANE == 0
            and N % LANE == 0 and Q % LANE == 0
            and _vmem_need(Q, N, H, P, x.dtype.itemsize) <= VMEM_BUDGET)


def _on_lanes(vals, P: int):
    """One lane tile from the values of its ``128 // P`` heads (each
    ``[R, 128]`` or a column ``[R, 1]``): head ``e``'s on its ``P``
    lanes."""
    out = vals[-1]
    if len(vals) > 1:
        head = jax.lax.broadcasted_iota(
            jnp.int32, (vals[-1].shape[0], LANE), 1) // P
        for e in range(len(vals) - 1):
            out = jnp.where(head == e, vals[e], out)
    return jnp.broadcast_to(out, (out.shape[0], LANE))


def _scan_kernel(x_ref, c_ref, bt_ref, cum_ref, dt_ref, end_ref, at_ref,
                 keep_ref, d_ref, y_ref, g_scr, s_scr, *, P: int):
    """One (chunk, head block) step.  ``x_ref`` / ``y_ref`` ``[Q, W]``:
    the block's ``W // P`` heads side by side, as ``[L, H * P]`` lies in
    memory; ``c_ref [Q, N]``, ``bt_ref [N, Q]``; ``[1, W // P, Q]``, a
    head a row: ``cum_ref``, ``dt_ref``, ``end_ref`` (``dt_j exp(cum_end -
    cum_j)``, token ``j``'s weight in the chunk's own state) and ``at_ref``
    (``exp(cum_i)``); on each head's lanes: ``keep_ref [1, 1, W]``
    (``exp(cum_end)``, the state's decay over the chunk) and ``d_ref [1,
    W]``.  ``g_scr [Q, Q]`` holds the chunk's ``C B^T`` (made at its first
    head block), ``s_scr [n_blocks, N, W]`` every head's state
    transposed, ``S^T``, as the chunk starts (zero at chunk 0).

    The steps of the module docstring: ``C @ S^T`` and ``B^T @ xw`` take
    all heads of the block in one product each.  Inside the chunk, per
    lane tile: at ``P`` 64 a tile holds two heads, and ``M_h [Q, Q] @ x``
    takes the whole tile (a 64-lane product costs the MXU what a 128-lane
    one does); the head's own lanes are kept by a select, so nothing is
    shifted between lanes."""
    ci, gi = pl.program_id(0), pl.program_id(1)
    Q, W = x_ref.shape
    dtype, f32 = x_ref.dtype, jnp.float32

    @pl.when(gi == 0)
    def _():
        g_scr[...] = jnp.dot(c_ref[...], bt_ref[...],
                             preferred_element_type=f32)

    @pl.when(ci == 0)
    def _():
        s_scr[gi] = jnp.zeros(s_scr.shape[1:], f32)

    cum, dt = cum_ref[0], dt_ref[0]                  # [hb, Q], a head a row
    col = cum.T                                       # [Q, hb], a column
    to_end, at_i = end_ref[0].T, at_ref[0].T
    k = LANE // P
    tiles = [slice(t * LANE, (t + 1) * LANE) for t in range(W // LANE)]

    def heads(t, cols):                 # the columns of tile t's heads
        return [cols[:, h:h + 1] for h in range(t * k, (t + 1) * k)]
    x = x_ref[...]
    S = s_scr[gi]                                     # [N, W] float32
    # 4. the state the chunk starts from, read by its tokens
    y_state = jnp.dot(c_ref[...], S.astype(dtype), preferred_element_type=f32)
    # 2. and 3.: the state at the chunk's end
    xw = jnp.concatenate([x[:, lanes].astype(f32)
                          * _on_lanes(heads(t, to_end), P)
                          for t, lanes in enumerate(tiles)], axis=1)
    s_scr[gi] = keep_ref[0] * S + jnp.dot(
        bt_ref[...], xw.astype(dtype), preferred_element_type=f32)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    g = g_scr[...]
    for t, lanes in enumerate(tiles):
        xt = x[:, lanes]
        # 1. inside the chunk, head by head
        y_heads = []
        for h in range(t * k, (t + 1) * k):
            seg = col[:, h:h + 1] - cum[h:h + 1, :]              # i - j
            decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
            M = g * decay * dt[h:h + 1, :]
            y_heads.append(jnp.dot(M.astype(dtype), xt,
                                   preferred_element_type=f32))
        # each head's own lanes of its [Q, 128] product
        y = _on_lanes(y_heads, P)
        y = y + y_state[:, lanes] * _on_lanes(heads(t, at_i), P)
        y = y + d_ref[:, lanes] * xt.astype(f32)
        y_ref[:, lanes] = y.astype(dtype)


# Jitted on its own so that a program's scan sites of one shape share one
# trace and one Mosaic lowering (the nine of a hybrid view program: their
# trace + lower took twice the XLA core's without it, PERF.md section 6).
@functools.partial(jax.jit, static_argnums=(6, 7))
def _scan_call(x, dt, A, B, C, D, chunk: int, interpret: bool):
    L, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    x, dt, B, C, Q, n = _padded(x, dt, B, C, chunk)
    dtc, cum = _decays(dt, A, n, Q)
    # the XLA core's own expressions for the per-head factors
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc
    keep = jnp.repeat(jnp.exp(cum[:, -1:, :]), P, axis=-1)     # [n, 1, H P]
    W = _head_block(H, P)
    hb, nb = W // P, H * P // W

    def by_head(a):                                   # [n, Q, H] -> rows
        return a.reshape(n * Q, nb, hb).transpose(1, 2, 0)
    step = pl.BlockSpec((Q, W), lambda c, g: (c, g))
    head_rows = pl.BlockSpec((1, hb, Q), lambda c, g: (g, 0, c))
    y = pl.pallas_call(
        functools.partial(_scan_kernel, P=P),
        grid=(n, nb),
        in_specs=[step,
                  pl.BlockSpec((Q, N), lambda c, g: (c, 0)),
                  pl.BlockSpec((N, Q), lambda c, g: (0, c)),
                  head_rows, head_rows, head_rows, head_rows,
                  pl.BlockSpec((1, 1, W), lambda c, g: (c, 0, g)),
                  pl.BlockSpec((1, W), lambda c, g: (0, g))],
        out_specs=step,
        out_shape=jax.ShapeDtypeStruct((n * Q, H * P), x.dtype),
        scratch_shapes=[pltpu.VMEM((Q, Q), f32),
                        pltpu.VMEM((nb, N, W), f32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(x.reshape(n * Q, H * P), C, B.T, by_head(cum), by_head(dtc),
      by_head(to_end), by_head(jnp.exp(cum)), keep,
      jnp.repeat(D.astype(f32), P)[None])
    return y.reshape(n * Q, H, P)[:L]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, A, B, C, D, chunk: int, interpret: bool):
    return _scan_call(x, dt, A, B, C, D, chunk, interpret)


def _scan_vjp_fwd(x, dt, A, B, C, D, chunk: int, interpret: bool):
    return (_scan_call(x, dt, A, B, C, D, chunk, interpret),
            (x, dt, A, B, C, D))


def _scan_vjp_bwd(chunk, interpret, res, g):
    return jax.vjp(functools.partial(ssd_chunked, chunk=chunk), *res)[1](g)


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def ssd_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
               B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray, chunk: int,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """:func:`ssd_chunked` as one Pallas kernel (module docstring; shapes
    as there), on operands :func:`pallas_supports` accepts."""
    assert pallas_supports(x, dt, A, B, C, D, chunk), (
        x.shape, B.shape, x.dtype, chunk)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _scan(x, dt, A, B, C, D, chunk, bool(interpret))


def _pallas_auto(x, *_) -> bool:
    return x.shape[0] >= MIN_TOKENS


dispatch.register("ssm_scan", "xla", ssd_chunked)
dispatch.register("ssm_scan", "pallas", ssd_pallas, supports=pallas_supports,
                  auto=_pallas_auto)


def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, D: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """The recurrence of the module docstring over one example, by the
    core the registry resolves (shapes as :func:`ssd_chunked`)."""
    core = dispatch.resolve("ssm_scan", "auto", x, dt, A, B, C, D, chunk)
    count(f"ssm_scan.{core.name}")
    return core.fn(x, dt, A, B, C, D, chunk)
