"""Routed experts: a router over all experts, top-k renormalised gates, and
the part of the result that the experts held here give.

The layer is told which experts it holds (``held = (first, count)``).  It
routes every token over all ``num_experts``, and computes, for the tokens
routed to an expert it holds, that expert's gated output; what the absent
experts would add is left out and nothing stands in for it.  With all
experts held that is the whole layer; the shares of a set of chips that
together hold every expert add up to it (tests/test_token_denoiser.py).
No token is dropped: there is no capacity.

A block whose feed-forward has a second branch on the same normed tokens
(a shared expert: ``h += r (routed(u) + shared(u))``, one norm, one
residual add) hands that branch to the layer as ``beside``, a function
of a chunk's normed tokens: it runs inside the chunk map, between the
norm and the add, and is counted whole on every chip where the routed
part is a share (tests/test_hybrid_moe_denoiser.py adds the shares up).

How the experts' matmuls are laid out (:func:`expert_outputs`): the
``T x k`` assignments of a chunk of ``T`` tokens are sorted by expert and
each expert's run is padded to a multiple of ``block`` rows, so that
every block of rows belongs to one expert; each block is multiplied by
that expert's three matrices (the number of blocks is a static bound,
``T k / block + experts``, which covers any routing: no capacity, and a
call's shape does not depend on how the tokens were routed).  The ``[T x
k, D]`` dispatch buffer exists for one chunk at a time
(:class:`RoutedExperts` maps over chunks of ``token_chunk`` tokens).

Three ops of the kernel registry (:mod:`diff3d_tpu.ops.pallas_moe`) do
the work on that layout, each with an XLA core and a Pallas core chosen
from what the process, the shapes and the layer are, by no option:

  * ``'expert_rows'`` - the rows into the layout.  XLA: one gather over
    every row of the static bound.  Pallas: one row copy (a DMA) for each
    valid row of a block in use; a block past the last run costs a grid
    step and nothing else.
  * ``'expert_ffn'`` - the blocks' matmuls.  On a TPU process, where the
    widths are whole lane tiles, one grouped Pallas kernel that fetches
    an expert's matrices once per run of blocks, fuses gate, up and down
    per block and skips the blocks of the bound past the last run;
    everywhere else a ``lax.scan`` over all the blocks, which is also the
    kernel's gradient.
  * ``'expert_combine'`` - the rows back out as the gated sum over the
    ``k`` slots.  XLA: one gather over all ``T k`` assignments (those
    held elsewhere read a zero row), the picked rows in float32, the sum.
    Pallas: one row copy for each assignment held here, the sum in
    float32 in slot order, no ``[T, k, D]`` tile.

The two ways rows move cost XLA by the static bound and the kernels by
the rows that land here, so the kernels are taken **where the layer holds
at most half of the experts it routes over** (``of=``: a share of eight
chips' experts fills an eighth of the bound) on a TPU process; with
every expert held the bound is tight and XLA's bulk gathers are as fast
or faster (PERF.md section 5 has both cells' readings).  CPU processes
(tests, the analysis passes, the tiny presets) take the XLA cores of all
three.  The sort and the layout's index arithmetic are plain XLA on
both.  Either way the layer is differentiable (each kernel's gradient is
its XLA expression's) and indifferent to ``vmap`` (the sampler maps its
view program over objects).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from diff3d_tpu.ops import dispatch
from diff3d_tpu.ops import pallas_moe  # noqa: F401 - registers the ops
from diff3d_tpu.utils.profiling import count, scope


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def add_residual(h: jnp.ndarray, y: jnp.ndarray, r: float = 1.0
                 ) -> jnp.ndarray:
    """``h + r y`` in ``h``'s dtype, ``r`` a model's residual multiplier
    (applied in float32); at 1 the plain add, so that a model without
    one lowers as it did."""
    if r != 1.0:
        y = r * y.astype(jnp.float32)
    return h + y.astype(h.dtype)


def residual_half(h: jnp.ndarray, norm_scale: jnp.ndarray, eps: float,
                  r: float, fn: Callable[[jnp.ndarray], jnp.ndarray]
                  ) -> jnp.ndarray:
    """``h [B, L, D] -> h + r fn(norm(h))``, ``fn`` on one example's
    normed tokens ``[L, D]`` at a time."""
    def one_example(hb):
        with scope("residual"):
            u = rms_norm(hb, norm_scale, eps)
        y = fn(u)
        with scope("residual"):
            return add_residual(hb, y, r)

    return jax.lax.map(one_example, h)


def route(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``[T, E]`` float32 router logits -> (``[T, k]`` expert ids,
    ``[T, k]`` float32 gates): softmax over all experts, the ``k``
    largest, renormalised to sum to one."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    return ids, gates / gates.sum(axis=-1, keepdims=True)


def expert_outputs(x: jnp.ndarray, ids: jnp.ndarray, gates: jnp.ndarray,
                   w_gate: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray, *, first: int, block: int,
                   of: Optional[int] = None,
                   impl: str = "auto") -> jnp.ndarray:
    """Gated sum of the held experts' outputs for one chunk of tokens.

    ``x [T, D]`` (compute dtype), ``ids / gates [T, k]`` as :func:`route`
    gives them (ids over all experts), ``w_gate / w_up [E, D, F]``,
    ``w_down [E, F, D]`` the held experts ``first .. first + E - 1``, in
    the compute dtype.
    Expert ``e``: ``w_down_e (silu(w_gate_e x) * w_up_e x)``.
    ``of`` is the number of experts ``ids`` range over (``None``: the
    ``E`` held are all), which the rows' two ops read beside ``E``.
    ``impl`` ('auto' | 'pallas' | 'xla') is the request to the registry
    for all three ops; the layer leaves it at 'auto'.  Each traced site
    adds 1 to the recorder's ``experts.<core>`` (the blocks' matmuls),
    ``experts.rows.<core>`` and ``experts.combine.<core>`` by the core
    each resolved to.
    """
    T = x.shape[0]
    K = ids.shape[1]
    E = w_gate.shape[0]
    A, m = T * K, block
    local = ids.reshape(A) - first
    # assignments to experts held elsewhere sort last, into bucket E
    e_of = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(e_of, stable=True)           # sorted -> assignment
    cnt = (e_of[:, None] == jnp.arange(E)[None, :]).sum(axis=0)
    before = jnp.arange(E)[:, None] < jnp.arange(E)[None, :]
    starts = lambda n: (n[:, None] * before).sum(axis=0)  # noqa: E731
    off = starts(cnt)                                # run starts, sorted
    padded = -(-cnt // m) * m
    poff = starts(padded)                            # run starts, padded

    # the padded layout: block b holds rows of one expert only
    n_blocks = -(-A // m) + E                        # a bound, static
    blk_start = jnp.arange(n_blocks)[:, None] * m
    ends = poff + padded                             # run ends, padded
    e_blk = (blk_start >= ends[None, :]).sum(axis=1)
    # a block past the last run counts as the last expert's: its rows lie
    # beyond that run's padding, so none of them is valid
    e_blk = jnp.minimum(e_blk, E - 1)
    # per block, then spread over the block's m rows: lookups in the
    # experts' small tables per row are slow gathers on the TPU
    spread = lambda a: jnp.repeat(a, m)  # noqa: E731
    r = jnp.arange(n_blocks * m) - spread(poff[e_blk])
    valid = r < spread(cnt[e_blk])
    src = order[jnp.clip(spread(off[e_blk]) + r, 0, A - 1)]
    token = jnp.where(valid, src // K, T)            # T: a padding row
    share = dict(held=E, of=E if of is None else of)
    core = dispatch.resolve("expert_rows", impl, x, token, ends, m, **share)
    count(f"experts.rows.{core.name}")
    rows = core.fn(x, token, ends, m)

    core = dispatch.resolve("expert_ffn", impl, rows, e_blk, ends,
                            w_gate, w_up, w_down)
    count(f"experts.{core.name}")
    ys = core.fn(rows, e_blk, ends, w_gate, w_up, w_down)
    # (the kernel leaves the blocks past the last run unwritten, where the
    # scan gives zeros; ``at`` below points into runs only)
    # back: assignment a sits at sorted position pos[a], and in the padded
    # layout a run is shifted as a whole, so the shift is looked up with
    # a one-hot product, not a gather per assignment
    pos = jnp.argsort(order)
    shift = jnp.concatenate([poff - off, jnp.zeros((1,), poff.dtype)])
    onehot = (e_of[:, None] == jnp.arange(E + 1)[None, :])
    at = jnp.where(e_of < E,
                   pos + (onehot * shift[None, :]).sum(axis=1),
                   n_blocks * m)
    core = dispatch.resolve("expert_combine", impl, ys, at, gates, ends,
                            **share)
    count(f"experts.combine.{core.name}")
    return core.fn(ys, at, gates, ends)


class RoutedExperts(nn.Module):
    """``h [..., D] -> h + r (experts(u) + beside(u))``, ``u = norm(h)``:
    the layer's second half, the held experts' part of it and, where the
    block has one, a second branch ``beside`` (``[T, D]`` normed tokens in
    the compute dtype ``-> [T, D]``) on the same ``u`` under the same
    residual add.  Norm, routing, experts, ``beside`` and the residual add
    run on one chunk of ``token_chunk`` tokens at a time, so only the
    layer's input and output exist at the size of the whole call.  Each
    traced site with a ``beside`` adds 1 to the recorder's
    ``experts.shared``."""

    num_experts: int
    top_k: int
    width: int
    held: Tuple[int, int]
    token_chunk: int
    block: int
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 beside: Optional[Callable[[jnp.ndarray], jnp.ndarray]]
                 = None) -> jnp.ndarray:
        D = h.shape[-1]
        first, held = self.held
        x = h.reshape(-1, D)
        T = x.shape[0]
        chunk = min(self.token_chunk, T)
        if T % chunk:
            raise ValueError(
                f"RoutedExperts: token_chunk={chunk} must divide the "
                f"{T} tokens of a call")

        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (D, self.num_experts))
        w_gate = self.param("w_gate", init, (held, D, self.width))
        w_up = self.param("w_up", init, (held, D, self.width))
        w_down = self.param("w_down", init, (held, self.width, D))

        # The experts' matrices in the compute dtype, made when the layer
        # runs and dropped after it.  ``tie`` is zero, but it is computed
        # from this call's tokens: a cast that depends on nothing but the
        # parameters is lifted by XLA out of the sampler's loop, and a
        # bf16 copy of every layer's experts (4.8 GB at four layers of
        # 128) then lives beside the float32 parameters.  Neither
        # ``optimization_barrier`` nor a cast of the one expert inside
        # the scan stops that (compiled for the v5e, PR 26).  The token is
        # made finite first, so that a NaN or inf in it stays that
        # token's own and does not reach every expert's weights.
        with scope("experts"):
            x00 = x[0, 0].astype(jnp.float32)
            tie = jnp.where(jnp.isfinite(x00), x00, 0.0) * 0.0
            w_gate, w_up, w_down = ((w + tie).astype(self.dtype)
                                    for w in (w_gate, w_up, w_down))

        def one_chunk(hc):
            with scope("residual"):
                xc = rms_norm(hc, norm_scale, self.eps).astype(self.dtype)
            with scope("moe_router"):
                logits = jnp.dot(xc, router.astype(self.dtype),
                                 preferred_element_type=jnp.float32)
                ids, gates = route(logits, self.top_k)
            with scope("experts"):
                y = expert_outputs(xc, ids, gates, w_gate, w_up, w_down,
                                   first=first, block=self.block,
                                   of=self.num_experts)
            if beside is not None:
                y = y.astype(jnp.float32) + beside(xc)
            with scope("residual"):
                return add_residual(hc, y, self.residual)

        if beside is not None:
            count("experts.shared")
        with scope("experts"):
            y = jax.lax.map(one_chunk, x.reshape(T // chunk, chunk, D))
            return y.reshape(h.shape)
