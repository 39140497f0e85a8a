"""The routed experts' work on the padded block layout, three ops of
:mod:`diff3d_tpu.ops.dispatch` with an XLA core and a TPU Pallas forward
kernel each: ``'expert_ffn'`` (the blocks' matmuls), ``'expert_rows'``
(rows into the layout) and ``'expert_combine'`` (rows back out as the
gated sum).

All take what :func:`diff3d_tpu.models.moe.expert_outputs` lays out:
``rows [n_blocks, m, D]`` (block ``b`` holds rows of one expert only,
``e_blk[b]``; a run's padding is zero rows), ``ends [E]`` (where each
expert's padded run ends, in rows; the last entry is the number of rows
in use, a multiple of ``m``) and the held experts' stacked matrices
``w_gate / w_up [E, D, F]``, ``w_down [E, F, D]`` in the compute dtype;
``expert_ffn`` returns ``[n_blocks, m, D]``, block ``b`` = ``w_down_e
(silu(x w_gate_e) * (x w_up_e))`` with float32 accumulation and ``h`` cast
to the compute dtype before the down matmul.

  * :func:`expert_ffn_reference` — ``lax.scan`` over the blocks: every
    block of the static bound is computed, each step reads its expert's
    three matrices by a dynamic index and runs three matmuls with ``h``
    through HBM.  On one v5e at ``keye_vl2_tok128`` (384 blocks of 256
    rows, 128 experts of 2048 x 768, bf16): 30.8 us a block, 11.8 ms a
    chunk, against 12.3 us of arithmetic (PERF.md section 6, PR 29).
  * :func:`expert_ffn` — one kernel, one grid step a block.  ``e_blk``
    and the number of blocks in use are scalar-prefetch operands: the
    weights' block index follows the table, so a block whose expert is
    the previous block's fetches nothing and the next expert's matrices
    arrive behind this block's matmuls; gate, up, silu, product and down
    run in one body on one expert's matrices resident in VMEM, ``h``
    never in HBM; a block past the last run costs a grid step (no fetch,
    no matmul, nothing written: those rows of the result are undefined
    and the caller never reads them).  There: 14.4 us a block in use,
    about 320 of the 384 on the reference's routing, 4.6 ms a chunk,
    bit-equal to the scan.  The bound stays static, so the call's shape
    does not depend on the routing.  The gradient is the scan's (there
    is no backward kernel).

How rows move (PR 33).  The XLA cores are one gather each over the static
bound — every row of the ``n_blocks m`` on the way in, every one of the
``T k`` assignments on the way back, which at ``D`` 4096 also writes the
picked rows out in float32 — whatever share of them lands on an expert
held here.  The Pallas cores move a row by one DMA, and only rows in use:

  * :func:`expert_rows` — grid over blocks, ``used`` and each block's
    count of valid rows (a prefix of the block) prefetched, the block's
    token ids in SMEM by their own block spec; the valid rows' copies
    ``x[token] -> VMEM`` are all started, then waited on, the padding
    zeroed, the block written; a block past the last run costs a grid
    step and nothing else.
  * :func:`expert_combine` — grid over tiles of 64 tokens, the tile's
    ``at`` and gates in SMEM by their own block specs; one copy ``ys[at]
    -> VMEM`` for each slot whose assignment is held here, all started
    before the first is waited on, landing one after the other; then per
    token the gated sum of its rows in float32 in slot order, cast once.
    A slot held elsewhere costs a scalar compare.  No zero row, no ``[T,
    k, D]`` tile in any dtype, and nothing read from the blocks
    ``expert_ffn`` left unwritten.

Mosaic lets a DMA slice the tiled second-minor dimension of an array by
eight rows, not one, so a row is addressed through ``[rows, D / 128,
128]`` (:func:`_as_rows`: a row is one contiguous run of whole tiles,
its index an untiled dimension; in bf16 not every ``D / 128`` can be
sliced so, and there the XLA cores move the rows): ``x`` goes there by an XLA relayout of
the chunk's ``T`` rows, the rows that landed in VMEM are re-tiled in the
kernel, and ``ys`` by a third small kernel that re-tiles the blocks in
use alone.  Each kernel has the sampler's object axis as a leading grid
axis, reached through :func:`jax.custom_batching.custom_vmap`: jax's own
batching of a ``pallas_call`` with prefetched scalars is a loop that
slices every operand out and the result in.  Their gradients are the XLA
expressions'.  ``auto`` takes them where the layer holds at most half of
the experts it routes over (:func:`held_share_auto`; PERF.md section 5
has the readings at both cells' shapes).

On a CPU process the kernels run in Pallas interpret mode (tests); on a
TPU process they are compiled or the call raises
(:func:`diff3d_tpu.ops.dispatch.interpret_default`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from diff3d_tpu.ops import dispatch
from diff3d_tpu.ops.pallas_attention import LANE, _out_struct

# One expert's three matrices stay whole in VMEM, double-buffered, beside
# the row and result blocks and the float32 gate / up tiles: 27 MB at
# the cell's shapes in bf16, 49 in float32 (walking the width F in
# chunks, or a limit of 48 MiB, ran no faster: PERF.md section 6, PR 29).
# Of the v5e's 128 MiB; the compiler's default limit is 16.
VMEM_BYTES = 100 << 20          # the limit handed to the compiler
VMEM_BUDGET = 80 << 20          # what ``supports`` lets a shape need


def expert_ffn_reference(rows: jnp.ndarray, e_blk: jnp.ndarray,
                         ends: jnp.ndarray, w_gate: jnp.ndarray,
                         w_up: jnp.ndarray,
                         w_down: jnp.ndarray) -> jnp.ndarray:
    """The XLA core, and the one the kernel is differentiated through."""
    del ends

    def one_block(_, inp):
        xb, e = inp
        f32 = jnp.float32
        h = (jax.nn.silu(jnp.dot(xb, w_gate[e], preferred_element_type=f32))
             * jnp.dot(xb, w_up[e], preferred_element_type=f32))
        return None, jnp.dot(h.astype(xb.dtype), w_down[e])

    # every block of the static bound is computed: one past the last run
    # holds zero rows and gives zeros (no bias), and under the sampler's
    # vmap a ``lax.cond`` that skipped it would run both branches anyway
    _, ys = jax.lax.scan(one_block, None, (rows, e_blk))
    return ys


def _vmem_need(m: int, D: int, F: int, itemsize: int) -> int:
    """Bytes the kernel holds on chip: the three matrices and the row and
    result blocks twice (the pipeline's two buffers), gate, up and the
    result in float32 once."""
    return (2 * (3 * D * F + 2 * m * D) * itemsize
            + (2 * m * F + m * D) * 4)


def expert_ffn_supports(rows, e_blk, ends, w_gate, w_up, w_down) -> bool:
    """Shapes/dtypes :func:`expert_ffn` handles: ``D`` and ``F`` whole
    lane tiles, the block a whole sublane tile of the dtype (16 rows in
    bf16, 8 in float32), one compute dtype (bf16 or float32) on rows and
    matrices, one expert's matrices within the VMEM budget."""
    if rows.ndim != 3 or w_gate.ndim != 3 or e_blk.ndim != 1 \
            or ends.ndim != 1:
        return False
    dtype = rows.dtype
    if dtype not in (jnp.float32, jnp.bfloat16) or any(
            w.dtype != dtype for w in (w_gate, w_up, w_down)):
        return False
    n_blocks, m, D = rows.shape
    E, _, F = w_gate.shape
    sublane = 32 // jnp.dtype(dtype).itemsize
    return (w_gate.shape == (E, D, F) and w_up.shape == (E, D, F)
            and w_down.shape == (E, F, D) and e_blk.shape == (n_blocks,)
            and ends.shape == (E,)
            and D % LANE == 0 and F % LANE == 0 and m % sublane == 0
            and _vmem_need(m, D, F, jnp.dtype(dtype).itemsize)
            <= VMEM_BUDGET)


def _last_in_use(b, used):
    """A block past the last run stands at the last block in use: its
    block indices do not change, so nothing of it moves."""
    return jnp.minimum(b, jnp.maximum(used - 1, 0))


def _no_grad(a):
    return np.zeros(a.shape, jax.dtypes.float0)


def _ffn_kernel(e_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    """One block of rows through its expert.  The expert's matrices are
    this step's ``w*_ref`` blocks by the prefetched table; past the
    ``used`` blocks nothing runs."""
    del e_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _block():
        f32 = jnp.float32
        x = x_ref[0]                                         # [m, D]
        h = (jax.nn.silu(jnp.dot(x, wg_ref[0], preferred_element_type=f32))
             * jnp.dot(x, wu_ref[0], preferred_element_type=f32))
        o_ref[0] = jnp.dot(h.astype(x.dtype), wd_ref[0],
                           preferred_element_type=f32).astype(o_ref.dtype)


def _ffn_fwd(rows, e_blk, ends, w_gate, w_up, w_down, interpret: bool):
    n_blocks, m, D = rows.shape
    F = w_gate.shape[-1]
    used = (ends[-1:] // m).astype(jnp.int32)                # [1]

    # past the last run the row, expert and result block indices do not
    # change, so nothing moves
    def at(b, used_ref):
        return _last_in_use(b, used_ref[0])

    def block(b, e_ref, used_ref):
        return (at(b, used_ref), 0, 0)

    def expert(b, e_ref, used_ref):
        return (e_ref[at(b, used_ref)], 0, 0)

    return pl.pallas_call(
        _ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((1, m, D), block),
                      pl.BlockSpec((1, D, F), expert),
                      pl.BlockSpec((1, D, F), expert),
                      pl.BlockSpec((1, F, D), expert)],
            out_specs=pl.BlockSpec((1, m, D), block)),
        out_shape=_out_struct((n_blocks, m, D), rows.dtype, rows),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(e_blk.astype(jnp.int32), used, rows, w_gate, w_up, w_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ffn(rows, e_blk, ends, w_gate, w_up, w_down, interpret: bool):
    return _ffn_fwd(rows, e_blk, ends, w_gate, w_up, w_down, interpret)


def _ffn_vjp_fwd(rows, e_blk, ends, w_gate, w_up, w_down, interpret: bool):
    return (_ffn_fwd(rows, e_blk, ends, w_gate, w_up, w_down, interpret),
            (rows, e_blk, ends, w_gate, w_up, w_down))


def _ffn_vjp_bwd(interpret, res, g):
    rows, e_blk, ends, w_gate, w_up, w_down = res
    _, vjp = jax.vjp(
        lambda r, a, b, c: expert_ffn_reference(r, e_blk, ends, a, b, c),
        rows, w_gate, w_up, w_down)
    d_rows, d_gate, d_up, d_down = vjp(g)
    return d_rows, _no_grad(e_blk), _no_grad(ends), d_gate, d_up, d_down


_ffn.defvjp(_ffn_vjp_fwd, _ffn_vjp_bwd)


def expert_ffn(rows: jnp.ndarray, e_blk: jnp.ndarray, ends: jnp.ndarray,
               w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """The Pallas core (module docstring).  Blocks ``b`` with ``b * m >=
    ends[-1]`` of the result are not written."""
    assert expert_ffn_supports(rows, e_blk, ends, w_gate, w_up, w_down), (
        rows.shape, e_blk.shape, ends.shape, w_gate.shape, w_down.shape,
        rows.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _ffn(rows, e_blk, ends, w_gate, w_up, w_down, bool(interpret))



# --------------------------------------------------------------------------
# how rows move: into the padded layout, and back out as the gated sum
# --------------------------------------------------------------------------

# Tokens a grid step of the way back: the row copies of a tile land in a
# ``K x COMBINE_TILE x D`` buffer (5 MiB at K 10, D 4096 in bf16).
COMBINE_TILE = 64
MOVE_VMEM_BYTES = 64 << 20       # the limit handed to the compiler
MOVE_VMEM_BUDGET = 48 << 20      # what ``supports`` lets a shape need


def expert_rows_reference(x: jnp.ndarray, token: jnp.ndarray,
                          ends: jnp.ndarray, block: int) -> jnp.ndarray:
    """The XLA core of op ``'expert_rows'``: ``x [T, D]``, ``token
    [n_blocks block]`` (``T``: a padding row) ``-> [n_blocks, block, D]``
    by one gather over every row of the static bound, the padding reading
    an appended zero row."""
    del ends
    D = x.shape[1]
    x0 = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
    return x0[token].reshape(token.shape[0] // block, block, D)


def expert_combine_reference(ys: jnp.ndarray, at: jnp.ndarray,
                             gates: jnp.ndarray,
                             ends: jnp.ndarray) -> jnp.ndarray:
    """The XLA core of op ``'expert_combine'``: ``ys [n_blocks, m, D]``,
    ``at [T K]`` (``n_blocks m``: held elsewhere), ``gates [T, K]``
    float32 ``-> [T, D]`` by one gather over all ``T K`` assignments
    (those held elsewhere read an appended zero row), the picked rows in
    float32, the gated sum over the slots."""
    n_blocks, m, D = ys.shape
    T, K = gates.shape
    y0 = jnp.concatenate([ys.reshape(n_blocks * m, D),
                          jnp.zeros((1, D), ys.dtype)])
    picked = y0[at].reshape(T, K, D).astype(jnp.float32)
    return (picked * gates[..., None]).sum(axis=1).astype(ys.dtype)


def _movable(dtype, D: int, m: int) -> bool:
    """D whole lane tiles, a block whole sublane tiles, and a row ``[D /
    128, 128]`` of :func:`_as_rows` that a DMA can slice: in bf16, where
    rows are packed in pairs, ``D / 128`` a multiple of 8 or 2 or 4 (the
    v5e's compiler, asked at 1 to 64 lane tiles, refuses 1, 3, 5, 6, 9,
    12, 20, 36 and 60, a row of 7680; float32 takes every count)."""
    sublane = 32 // jnp.dtype(dtype).itemsize
    n = D // LANE
    return (dtype in (jnp.float32, jnp.bfloat16) and D % LANE == 0
            and m % sublane == 0
            and (dtype == jnp.float32 or n % 8 == 0 or n in (2, 4)))


def expert_rows_supports(x, token, ends, block, **_) -> bool:
    """``D`` whole lane tiles, bf16 / float32, the block a whole sublane
    tile; a block of rows three times within the VMEM budget (where the
    copies land, and the result block twice)."""
    if x.ndim != 2 or token.ndim != 1 or ends.ndim != 1:
        return False
    D = x.shape[1]
    return (token.shape[0] % block == 0 and _movable(x.dtype, D, block)
            and 3 * block * D * jnp.dtype(x.dtype).itemsize
            <= MOVE_VMEM_BUDGET)


def _combine_tile(T: int) -> int:
    tm = min(COMBINE_TILE, T)
    while T % tm:
        tm //= 2
    return tm


def expert_combine_supports(ys, at, gates, ends, **_) -> bool:
    """As :func:`expert_rows_supports`; within the budget a block of
    ``ys`` in and out twice each (the re-tiling), and the buffer the
    copies of a tile of tokens land in beside the result tile twice."""
    if ys.ndim != 3 or at.ndim != 1 or gates.ndim != 2 or ends.ndim != 1:
        return False
    n_blocks, m, D = ys.shape
    T, K = gates.shape
    size = jnp.dtype(ys.dtype).itemsize
    return (at.shape[0] == T * K and gates.dtype == jnp.float32
            and _movable(ys.dtype, D, m)
            and 4 * m * D * size <= MOVE_VMEM_BUDGET
            and _combine_tile(T) * D * size * (K + 2) <= MOVE_VMEM_BUDGET)


def held_share_auto(*_, held: Optional[int] = None,
                    of: Optional[int] = None, **__) -> bool:
    """Where the per-row copies win (PERF.md section 5): the layer holds
    at most half of the experts it routes over, so the static bound is at
    least twice the rows that land here."""
    return held is not None and of is not None and 2 * held <= of


def _as_rows(a: jnp.ndarray) -> jnp.ndarray:
    """``[..., D] -> [..., D / 128, 128]``: with a row's lane tiles as
    the array's second-minor dimension a row is one contiguous run of
    whole tiles in HBM and the row index an untiled dimension, which a
    DMA may slice one at a time (of ``[rows, D]`` it may take eight)."""
    return a.reshape(*a.shape[:-1], a.shape[-1] // LANE, LANE)


def _move_params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=MOVE_VMEM_BYTES)


def _rows_kernel(used_ref, n_ref, tok_ref, x_ref, o_ref, landing, sem):
    """One block of the padded layout: its valid rows (a prefix of the
    block) copied from ``x`` in HBM, all started before the first is
    waited on, the padding zeroed; past the ``used`` blocks nothing
    runs."""
    o, b = pl.program_id(0), pl.program_id(1)
    m, D = o_ref.shape[2:]

    @pl.when(b < used_ref[o])
    def _block():
        n = n_ref[o, b]

        def copy(r, t):
            return pltpu.make_async_copy(x_ref.at[o, pl.ds(t, 1)],
                                         landing.at[pl.ds(r, 1)], sem)

        def start(r, c):
            copy(r, tok_ref[0, 0, 0, r]).start()
            return c

        def wait(r, c):
            copy(0, 0).wait()
            return c

        jax.lax.fori_loop(0, n, start, 0)
        jax.lax.fori_loop(0, n, wait, 0)
        rows = landing[...].reshape(m, D)
        row = jax.lax.broadcasted_iota(jnp.int32, (m, D), 0)
        o_ref[0, 0] = jnp.where(row < n, rows, jnp.zeros_like(rows))


def _rows_call(x, token, n_valid, used, interpret: bool):
    """``x [B, T, D / 128, 128]``, ``token [B, n_blocks, m]``, ``n_valid
    [B, n_blocks]``, ``used [B]`` -> ``[B, n_blocks, m, D]``."""
    B, T, S, _ = x.shape
    _, n_blocks, m = token.shape
    D = S * LANE

    def block(o, b, used_ref, n_ref):
        return (o, _last_in_use(b, used_ref[o]), 0, 0)

    return pl.pallas_call(
        _rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, 1, 1, m), block,
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, m, D), block),
            scratch_shapes=[pltpu.VMEM((m, S, LANE), x.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=_out_struct((B, n_blocks, m, D), x.dtype, x),
        compiler_params=_move_params(interpret),
        interpret=interpret,
    )(used, n_valid, token.reshape(B, n_blocks, 1, m), x)


def _retile_kernel(used_ref, y_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[pl.program_id(0)])
    def _block():
        o_ref[0, 0] = _as_rows(y_ref[0, 0])


def _retile_call(ys, used, interpret: bool):
    """``ys [B, n_blocks, m, D] -> [B, n_blocks, m, D / 128, 128]``, the
    blocks in use alone (the others are not written)."""
    B, n_blocks, m, D = ys.shape

    def block(o, b, used_ref):
        return (o, _last_in_use(b, used_ref[o]), 0, 0)

    return pl.pallas_call(
        _retile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, 1, m, D), block)],
            out_specs=pl.BlockSpec(
                (1, 1, m, D // LANE, LANE),
                lambda o, b, u: (*block(o, b, u), 0))),
        out_shape=_out_struct((B, n_blocks, m, D // LANE, LANE), ys.dtype,
                              ys),
        compiler_params=_move_params(interpret),
        interpret=interpret,
    )(used, ys)


def _combine_kernel(at_ref, g_ref, y_ref, o_ref, landing, gate, end, sem):
    """One tile of tokens: a row copy from ``ys`` in HBM for each slot
    whose assignment is held here, all started before the first is waited
    on, landing one after the other with their gates beside them; then,
    token by token, the gated sum of that token's rows in float32, in
    slot order, cast once.  A slot held elsewhere costs a scalar compare
    and nothing else."""
    o = pl.program_id(0)
    tm = o_ref.shape[1]
    K = landing.shape[0] // tm
    rows = y_ref.shape[1]

    def copy(a, n):
        return pltpu.make_async_copy(y_ref.at[o, pl.ds(a, 1)],
                                     landing.at[pl.ds(n, 1)], sem)

    def start(i, n):
        for k in range(K):
            a = at_ref[0, 0, 0, i * K + k]

            @pl.when(a < rows)
            def _held():
                copy(a, n).start()
                gate[n] = g_ref[0, 0, 0, i * K + k]
            n = n + (a < rows).astype(jnp.int32)
        end[i] = n
        return n

    def wait(j, c):
        copy(0, 0).wait()
        return c

    def token(i, lo):
        hi = end[i]
        acc = jax.lax.fori_loop(
            lo, hi, lambda j, acc: acc + (landing[j].astype(jnp.float32)
                                          * gate[j]),
            jnp.zeros(landing.shape[1:], jnp.float32))
        o_ref[0, i] = acc.astype(o_ref.dtype)
        return hi

    n = jax.lax.fori_loop(0, tm, start, jnp.int32(0))
    jax.lax.fori_loop(0, n, wait, 0)
    jax.lax.fori_loop(0, tm, token, jnp.int32(0))


def _combine_call(ys, at, gates, interpret: bool):
    """``ys [B, rows, D / 128, 128]``, ``at [B, T, K]`` (``>= rows``: held
    elsewhere), ``gates [B, T, K]`` float32 -> ``[B, T, D / 128, 128]``."""
    B, rows, S, _ = ys.shape
    _, T, K = gates.shape
    tm = _combine_tile(T)
    tile = pl.BlockSpec((1, 1, 1, tm * K), lambda o, t: (o, t, 0, 0),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _combine_kernel,
        grid=(B, T // tm),
        in_specs=[tile, tile, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tm, S, LANE), lambda o, t: (o, t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tm * K, S, LANE), ys.dtype),
                        pltpu.SMEM((tm * K,), jnp.float32),
                        pltpu.SMEM((tm,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        out_shape=_out_struct((B, T, S, LANE), ys.dtype, ys),
        compiler_params=_move_params(interpret),
        interpret=interpret,
    )(at.reshape(B, T // tm, 1, tm * K),
      gates.reshape(B, T // tm, 1, tm * K), ys)


@functools.lru_cache(maxsize=None)
def _per_object(call, interpret: bool):
    """``call`` (operands and results with a leading object axis, a grid
    axis of the kernel) as a function of one object's operands whose
    ``vmap`` is ``call`` on the stacked operands: the sampler maps the
    view program over objects, and jax's own batching of a
    ``pallas_call`` with prefetched scalars is a loop that slices every
    operand out and the result in."""
    @jax.custom_batching.custom_vmap
    def one(*args):
        return call(*(a[None] for a in args), interpret)[0]

    @one.def_vmap
    def _stacked(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args, in_batched)]
        return call(*args, interpret), True

    return one


def _used(ends, m: int):
    return (ends[-1] // m).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rows(x, token, ends, block: int, interpret: bool):
    T = x.shape[0]
    token = token.reshape(-1, block)
    n_valid = (token < T).sum(axis=1).astype(jnp.int32)
    return _per_object(_rows_call, interpret)(
        _as_rows(x), token.astype(jnp.int32), n_valid, _used(ends, block))


def _rows_vjp_fwd(x, token, ends, block, interpret):
    rows = _rows(x, token, ends, block, interpret)
    # what is saved for a backward holds nothing undefined: the blocks the
    # kernel did not write are zero rows, as the gather gives them
    live = jnp.arange(rows.shape[0]) < _used(ends, block)
    return (jnp.where(live[:, None, None], rows, jnp.zeros_like(rows)),
            (x, token, ends))


def _rows_vjp_bwd(block, interpret, res, g):
    x, token, ends = res
    _, vjp = jax.vjp(lambda x: expert_rows_reference(x, token, ends, block),
                     x)
    return (*vjp(g), _no_grad(token), _no_grad(ends))


_rows.defvjp(_rows_vjp_fwd, _rows_vjp_bwd)


def expert_rows(x: jnp.ndarray, token: jnp.ndarray, ends: jnp.ndarray,
                block: int, interpret: Optional[bool] = None) -> jnp.ndarray:
    """The Pallas core of op ``'expert_rows'`` (module docstring).  Blocks
    ``b`` with ``b * block >= ends[-1]`` of the result are not written."""
    assert expert_rows_supports(x, token, ends, block), (
        x.shape, token.shape, ends.shape, block, x.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _rows(x, token, ends, block, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(ys, at, gates, ends, interpret: bool):
    n_blocks, m, D = ys.shape
    T, K = gates.shape
    rows = _per_object(_retile_call, interpret)(ys, _used(ends, m))
    out = _per_object(_combine_call, interpret)(
        rows.reshape(n_blocks * m, D // LANE, LANE),
        at.reshape(T, K).astype(jnp.int32), gates)
    return out.reshape(T, D)


def _combine_vjp_fwd(ys, at, gates, ends, interpret):
    return _combine(ys, at, gates, ends, interpret), (ys, at, gates, ends)


def _combine_vjp_bwd(interpret, res, g):
    ys, at, gates, ends = res
    _, vjp = jax.vjp(lambda y, w: expert_combine_reference(y, at, w, ends),
                     ys, gates)
    d_ys, d_gates = vjp(g)
    return d_ys, _no_grad(at), d_gates, _no_grad(ends)


_combine.defvjp(_combine_vjp_fwd, _combine_vjp_bwd)


def expert_combine(ys: jnp.ndarray, at: jnp.ndarray, gates: jnp.ndarray,
                   ends: jnp.ndarray,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """The Pallas core of op ``'expert_combine'`` (module docstring).
    Reads no row of ``ys`` that ``at`` does not point at."""
    assert expert_combine_supports(ys, at, gates, ends), (
        ys.shape, at.shape, gates.shape, ys.dtype, gates.dtype)
    if interpret is None:
        interpret = dispatch.interpret_default()
    return _combine(ys, at, gates, ends, bool(interpret))

dispatch.register("expert_ffn", "xla", expert_ffn_reference)
# The kernel wins wherever it runs (PERF.md section 6, PR 29): no 'auto'
# policy beyond 'supports'.
dispatch.register("expert_ffn", "pallas", expert_ffn,
                  supports=expert_ffn_supports)
# How rows move is chosen by the held share (``held_share_auto``).
dispatch.register("expert_rows", "xla", expert_rows_reference)
dispatch.register("expert_rows", "pallas", expert_rows,
                  supports=expert_rows_supports, auto=held_share_auto)
dispatch.register("expert_combine", "xla", expert_combine_reference)
dispatch.register("expert_combine", "pallas", expert_combine,
                  supports=expert_combine_supports, auto=held_share_auto)
