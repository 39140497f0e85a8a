"""Attention core with backend dispatch.

The reference runs ``torch.nn.MultiheadAttention`` over ``H*W`` tokens
(``/root/reference/xunet.py:154-177``) — 4096 tokens at 64^2, 16384 at
128^2.  Here the softmax(QK^T)V core is a swappable backend registered
with :mod:`diff3d_tpu.ops.dispatch` (shared with the fused GroupNorm
epilogues):

  * ``'xla'``    — ``jax.nn.dot_product_attention``: the score tile is
    written to HBM and read back (XLA emits no flash kernel on the v5e:
    under a selection at ``[32, 512, 8192]`` it makes three passes over
    a 512 MB float32 tile, PERF.md section 6, PR 27).
  * ``'pallas'`` — hand-written TPU Pallas kernels
    (:mod:`diff3d_tpu.ops.pallas_attention`): ``flash_attention`` for
    the plain core, ``selected_attention`` for the core under a
    selection.
  * ``'auto'``   — pallas on a TPU process when the operands qualify,
    else xla.

Two ops are registered: ``'sdpa'`` (plain) and ``'sdpa_selected'`` (with
``keep``); each has its own ``supports`` and ``auto`` policy.

All shapes here are ``[B, L, n_heads, head_dim]`` (jax.nn convention).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from diff3d_tpu.ops import dispatch
from diff3d_tpu.utils.profiling import count


def _xla_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.dot_product_attention(q, k, v)


def _pallas_sdpa(q: jnp.ndarray, k: jnp.ndarray,
                 v: jnp.ndarray) -> jnp.ndarray:
    from diff3d_tpu.ops.pallas_attention import flash_attention

    return flash_attention(q, k, v)


def _pallas_supports(q, k, v) -> bool:
    from diff3d_tpu.ops.pallas_attention import supports

    return supports(q, k, v)


def _pallas_auto(q, *args) -> bool:
    """A rule carried over from the retired set-up's measurement, never
    re-measured on this chip (ROADMAP.md design item 3): the Pallas
    flash kernel zero-pads the head dim to the 128-lane MXU
    tile, so at D=32/64 it wastes 4x/2x of every QK^T and PV matmul and
    XLA's fused attention wins; only lane-filling heads (D > 64) with
    sequences long enough that the materialised [L, L] logits' HBM traffic
    dominates are worth the flash kernel."""
    D, L = q.shape[-1], q.shape[1]
    return D > 64 and L >= 4096


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
    ('auto' | 'pallas' | 'xla') goes through the shared kernel registry.
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
    return dispatch.dispatch("sdpa", impl, q, k, v)


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
