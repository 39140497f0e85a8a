"""Sequence/context parallelism: ring attention + all-to-all (Ulysses).

The reference never shards its attention sequence (``/root/reference/
xunet.py:199-208`` runs full ``H*W``-token attention per device; SURVEY.md
§5.7) — long-context scaling is a capability the TPU framework adds.  Two
standard schemes, both pure-JAX collectives so XLA schedules them on ICI:

* :func:`ring_sdpa` — blockwise (flash-style) attention with the KV shard
  rotating around the mesh axis via ``lax.ppermute``; each of the
  ``n_shards`` steps computes a local ``(o, lse)`` partial attention and
  folds it into the running result via the exact log-sum-exp combine.
  Memory per device is O(L/n), compute overlaps with the ring transfer.
  The local block engine is the Pallas flash kernel
  (:func:`diff3d_tpu.ops.pallas_attention.flash_attention_lse`) when the
  shapes support it on TPU — nothing of size ``[L/n, L/n]`` touches HBM —
  with an einsum fallback elsewhere.  This is the kernel's designed role:
  the single-chip X-UNet shapes are XLA-fused-sdpa territory (measured —
  see ops/attention._resolve_auto), long-context ring shards are where a
  hand kernel pays.
* :func:`ulysses_sdpa` — ``all_to_all`` reshards tokens->heads so each
  device holds ALL tokens for H/n heads, runs an ordinary (flash) sdpa,
  and reshards back.  Cheaper for moderate L when heads divide evenly.

Both are drop-in sdpa cores over local shards ``[B, L/n, H, D]`` of a
global ``[B, L, H, D]`` array inside ``shard_map``; exactness vs unsharded
attention (values AND grads, both engines) is covered by tests on the
8-device CPU mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _block_olse_einsum(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       scale: float):
    """One KV-block attention: returns ``(o [B, Lq, H, D] float32,
    lse [B, Lq, H] float32)``."""
    s = jnp.einsum("blhd,bmhd->blhm", q, k,
                   preferred_element_type=jnp.float32) * scale
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("blhm,bmhd->blhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32) / l[..., None]
    return o, m[..., 0] + jnp.log(l)


def _block_olse_pallas(q, k, v, scale: float):
    from diff3d_tpu.ops.pallas_attention import flash_attention_lse

    o, lse = flash_attention_lse(q, k, v, scale=scale)
    return o.astype(jnp.float32), lse


def _pick_engine(q, k, v, impl: str):
    if impl == "einsum":
        return _block_olse_einsum
    from diff3d_tpu.ops.pallas_attention import supports

    if impl == "pallas":
        assert supports(q, k, v), (q.shape, q.dtype)
        return _block_olse_pallas
    # 'auto': flash kernel on a TPU process where shapes qualify
    from diff3d_tpu.ops import dispatch

    return (_block_olse_pallas
            if dispatch.default_backend() == "tpu" and supports(q, k, v)
            else _block_olse_einsum)


def ring_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              axis_name: str, scale: Optional[float] = None,
              impl: str = "auto") -> jnp.ndarray:
    """Ring attention over a sharded token axis.

    Args:
      q, k, v: local shards ``[B, L/n, H, D]`` (token axis sharded over
        ``axis_name``); every query attends to every global key.
      axis_name: the mesh axis the sequence is sharded over.
      impl: local block engine — 'auto' | 'pallas' | 'einsum'.

    Returns the local output shard ``[B, L/n, H, D]``.
    """
    n = jax.lax.psum(1, axis_name)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]
    block = _pick_engine(q, k, v, impl)

    o0, lse0 = block(q, k, v, scale)

    def step(carry, _):
        o, lse, k, v = carry
        # rotate KV to the next device while (logically) computing; XLA
        # overlaps the ppermute with the block attention where profitable.
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        bo, blse = block(q, k, v, scale)
        lse_new = jnp.logaddexp(lse, blse)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + bo * jnp.exp(blse - lse_new)[..., None])
        return (o, lse_new, k, v), None

    (o, _, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), None,
                                   length=n - 1)
    return o.astype(q.dtype)


def ulysses_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 axis_name: str,
                 scale: Optional[float] = None) -> jnp.ndarray:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Reshards ``[B, L/n, H, D]`` -> ``[B, L, H/n, D]``, runs full-sequence
    attention on the local head subset, reshards back.  Requires
    ``H % n == 0``.
    """
    n = jax.lax.psum(1, axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(f"heads {H} not divisible by axis size {n}")
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5

    def scatter_heads(x):  # [B, L/n, H, D] -> [B, L, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def gather_heads(x):   # [B, L, H/n, D] -> [B, L/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = jax.nn.dot_product_attention(qg, kg, vg, scale=scale)
    return gather_heads(out)
