"""Attention core with backend dispatch.

The reference runs ``torch.nn.MultiheadAttention`` over ``H*W`` tokens
(``/root/reference/xunet.py:154-177``) — 4096 tokens at 64^2, 16384 at
128^2.  Here the softmax(QK^T)V core is a swappable backend registered
with :mod:`diff3d_tpu.ops.dispatch` (shared with the fused GroupNorm
epilogues):

  * ``'xla'``    — ``jax.nn.dot_product_attention``: the score tile is
    written to HBM and read back (XLA emits no flash kernel on the v5e:
    at ``[32, 512, 8192]`` it makes three passes over a 512 MB float32
    tile, with a selection or without, PERF.md section 6, PRs 27 and 31).
  * ``'pallas'`` — hand-written TPU Pallas kernels
    (:mod:`diff3d_tpu.ops.pallas_attention`).  Under a selection,
    ``selected_attention``.  Without one, two kernels behind the one
    name, chosen by shape (:func:`_pallas_sdpa`): ``plain_attention``
    (forward kernel, grouped queries, head dim 64 or whole lane tiles,
    the XLA expression's gradient) from ``PLAIN_MIN_KEYS`` keys on where
    it supports the operands, and for grouped heads at any length;
    ``flash_attention`` (any lengths, head dims up to 512, its own
    backward kernels, one key-value head per query head) everywhere
    else — so the X-UNet's sites (``L <= 1024``) asked for ``'pallas'``
    by hand get the kernel they always got.
  * ``'auto'``   — on a TPU process ``plain_attention`` where the rule
    above takes it, else xla: ``flash_attention`` is never chosen
    (:func:`_pallas_auto` has the measurements).

Two ops are registered: ``'sdpa'`` (plain) and ``'sdpa_selected'`` (with
``keep``); each has its own ``supports`` and ``auto`` policy.

All shapes here are ``[B, L, n_heads, head_dim]`` (jax.nn convention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from diff3d_tpu.ops import dispatch
from diff3d_tpu.utils.profiling import count

# Keys from which ``plain_attention`` beats XLA's score tile on one v5e
# (bf16, 32 query heads, all queries against L keys, ms; my chip run, PR
# 31).  D 64, 8 kv heads: L 2048 2.35 -> 0.59, 4096 9.08 -> 2.09, 8192
# 36.3 -> 7.6; D 128, 4 kv heads: 2.40 -> 0.60, 8.93 -> 2.09, 36.4 ->
# 7.8.  At 1024 one example is a tie (0.26 / 0.23, 0.28 / 0.28) and at
# 512 XLA wins (0.25 / 0.28): the X-UNet's sites stay with XLA.
PLAIN_MIN_KEYS = 2048


def _xla_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.dot_product_attention(q, k, v)


def _pallas_auto(q, k, v) -> bool:
    """Where a kernel beats XLA without a selection: ``plain_attention``
    on operands it supports from ``PLAIN_MIN_KEYS`` keys on.

    ``flash_attention`` is not in the rule.  The one it had, ``D > 64
    and L >= 4096``, came from the retired set-up; on this chip (PERF.md
    section 5, PRs 30 and 31) it loses to XLA at head dim 64 (float32
    dots, 128 x 128 tiles, the head dim padded to the lanes: 76.3 ms
    against 36.3 at 32 heads x 8192 x 8192, where ``plain_attention``
    reads 7.6), so ``D > 64`` was right; and at ``D`` 128 and ``L >=
    4096``, the half that never had a caller, it reads 18.2 ms at L 4096
    (XLA 15.8) and 74.5 at 8192 where ``plain_attention``, which takes
    every whole-block shape there, reads 2.5 and 8.9.  What is left to
    ``flash_attention`` is ``impl='pallas'`` by hand off that rule
    (ragged lengths, head dims 32 / 96 / 160, a backward kernel) and
    ``ring_sdpa(impl='pallas')``."""
    from diff3d_tpu.ops.pallas_attention import plain_supports

    return k.shape[1] >= PLAIN_MIN_KEYS and plain_supports(q, k, v)


def _pallas_sdpa(q: jnp.ndarray, k: jnp.ndarray,
                 v: jnp.ndarray) -> jnp.ndarray:
    """``plain_attention`` where it wins, and where ``flash_attention``
    cannot run (grouped heads); ``flash_attention`` elsewhere."""
    from diff3d_tpu.ops.pallas_attention import (flash_attention,
                                                 plain_attention, supports)

    if _pallas_auto(q, k, v) or not supports(q, k, v):
        return plain_attention(q, k, v)
    return flash_attention(q, k, v)


def _pallas_supports(q, k, v) -> bool:
    from diff3d_tpu.ops.pallas_attention import plain_supports, supports

    return plain_supports(q, k, v) or supports(q, k, v)


def _xla_selected(q, k, v, keep) -> jnp.ndarray:
    from diff3d_tpu.ops.pallas_attention import selected_reference

    return selected_reference(q, k, v, keep)


def _pallas_selected(q, k, v, keep) -> jnp.ndarray:
    from diff3d_tpu.ops.pallas_attention import selected_attention

    return selected_attention(q, k, v, keep)


def _pallas_selected_supports(q, k, v, keep) -> bool:
    from diff3d_tpu.ops.pallas_attention import selected_supports

    return selected_supports(q, k, v, keep)


dispatch.register("sdpa", "xla", _xla_sdpa)
dispatch.register("sdpa", "pallas", _pallas_sdpa,
                  supports=_pallas_supports, auto=_pallas_auto)
# Under a selection the kernel wins wherever it runs (one v5e chip,
# [32 / 4 heads, 512, 8192] x 128: 8.3 ms a layer-example against XLA's
# 36.6, PERF.md section 6, PR 27): no 'auto' policy beyond 'supports'.
dispatch.register("sdpa_selected", "xla", _xla_selected)
dispatch.register("sdpa_selected", "pallas", _pallas_selected,
                  supports=_pallas_selected_supports)


def _resolve_auto(q: jnp.ndarray) -> str:
    """Backend an ``impl='auto'`` sdpa call resolves to for ``q``.

    'auto' resolves from the PROCESS-DEFAULT backend, not from where the
    computation is actually placed: a TPU-backed process tracing a
    CPU-mesh program must pass ``impl='xla'`` explicitly (tests/conftest
    and the dryrun pin the whole process to CPU instead, which also
    resolves correctly)."""
    return dispatch.resolve("sdpa", "auto", q, q, q).name


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         impl: str = "auto", keep: jnp.ndarray | None = None) -> jnp.ndarray:
    """Scaled dot-product attention over ``[B, L, H, D]`` tensors.

    ``keep`` (optional ``[B, Lq, Lk]`` bool, shared by all heads) is a
    selection: the softmax runs over the kept keys alone, as the sparse
    attention of the token denoiser needs (models/sparse_attention.py).
    ``k`` / ``v`` may then have fewer heads than ``q`` (grouped queries:
    their head count divides ``q``'s).  A selection has two cores, op
    ``'sdpa_selected'`` of the registry: the Pallas forward kernel
    ``selected_attention``, which keeps the score tile on chip, and the
    XLA expression (a dense score tile, masked), which is also the
    kernel's gradient.  ``'auto'`` takes the kernel on a TPU process
    when its ``supports`` holds (head dim whole lane tiles, whole query
    and key blocks) and the XLA expression otherwise, so CPU processes
    lower as they did; ``'pallas'`` is honoured or raises; the
    sequence-parallel cores have no mask operand.  Each traced site adds
    1 to the recorder's ``sdpa.selected.pallas`` or ``sdpa.selected.xla``.

    ``impl`` may also name a sequence-parallel core — ``'ring:<axis>'`` or
    ``'ulysses:<axis>'`` — in which case q/k/v are local token shards of a
    global sequence sharded over mesh axis ``<axis>`` and the call must be
    inside ``shard_map`` with that axis in scope.  This is how the X-UNet's
    attention layers scale past one device's tokens: set
    ``ModelConfig.attn_impl='ring:model'`` and run the step in a
    ``shard_map`` whose specs shard the spatial axis.  Everything else
    ('auto' | 'pallas' | 'xla') goes through the shared kernel registry;
    each traced site adds 1 to the recorder's ``sdpa.plain.pallas`` or
    ``sdpa.plain.xla``, by the core it resolved to.
    """
    if keep is not None:
        core = dispatch.resolve("sdpa_selected", impl, q, k, v, keep)
        count(f"sdpa.selected.{core.name}")
        return core.fn(q, k, v, keep)
    if ":" in impl:
        from diff3d_tpu.parallel import ring_sdpa, ulysses_sdpa
        kind, _, axis = impl.partition(":")
        fn = {"ring": ring_sdpa, "ulysses": ulysses_sdpa}[kind]
        return fn(q, k, v, axis_name=axis)
    core = dispatch.resolve("sdpa", impl, q, k, v)
    count(f"sdpa.plain.{core.name}")
    return core.fn(q, k, v)


def multi_head_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         num_heads: int, impl: str = "auto") -> jnp.ndarray:
    """Splits pre-projected ``[B, L, C]`` q/k/v into heads, runs sdpa,
    merges heads back to ``[B, Lq, C]``.  Projections live in the Flax
    layer (:class:`diff3d_tpu.models.layers.AttnLayer`)."""
    B, Lq, C = q.shape
    Lk = k.shape[1]
    D = C // num_heads
    out = sdpa(q.reshape(B, Lq, num_heads, D),
               k.reshape(B, Lk, num_heads, D),
               v.reshape(B, Lk, num_heads, D), impl=impl)
    return out.reshape(B, Lq, C)
