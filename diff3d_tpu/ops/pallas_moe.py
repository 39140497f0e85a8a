"""The routed experts' matmuls over the padded block layout: the XLA scan
and a grouped-matmul TPU Pallas forward kernel, the two cores of op
``'expert_ffn'`` of :mod:`diff3d_tpu.ops.dispatch`.

Both take what :func:`diff3d_tpu.models.moe.expert_outputs` lays out:
``rows [n_blocks, m, D]`` (block ``b`` holds rows of one expert only,
``e_blk[b]``; a run's padding and the blocks past the last run are zero
rows), ``ends [E]`` (where each expert's padded run ends, in rows; the
last entry is the number of rows in use, a multiple of ``m``) and the
held experts' stacked matrices ``w_gate / w_up [E, D, F]``, ``w_down [E,
F, D]`` in the compute dtype; both return ``[n_blocks, m, D]``, block
``b`` = ``w_down_e (silu(x w_gate_e) * (x w_up_e))`` with float32
accumulation and ``h`` cast to the compute dtype before the down matmul.

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

On a CPU process the kernel runs in Pallas interpret mode (tests); on a
TPU process it is compiled or the call raises
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

    # a block past the last run stands at the last block in use: its row,
    # expert and result block indices do not change, so nothing moves
    def at(b, used_ref):
        return jnp.minimum(b, jnp.maximum(used_ref[0] - 1, 0))

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
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return d_rows, zero(e_blk), zero(ends), d_gate, d_up, d_down


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


dispatch.register("expert_ffn", "xla", expert_ffn_reference)
# The kernel wins wherever it runs (PERF.md section 6, PR 29): no 'auto'
# policy beyond 'supports'.
dispatch.register("expert_ffn", "pallas", expert_ffn,
                  supports=expert_ffn_supports)
