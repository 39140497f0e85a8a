"""The token denoiser's plain halves: grouped-query attention over all
keys with no position, and a dense gated MLP.

Both are ``h [B, L, D] -> h + r f(norm(h))`` (``r`` the model's residual
multiplier), one example at a time with the norm and the residual add
inside the map (:func:`~diff3d_tpu.models.moe.residual_half`), as the selected-key attention and
the routed experts do it: only the half's input and output exist at the
size of the whole call.

  attention  ``q = W_q u`` (``Hq`` heads), ``k = W_k u``, ``v = W_v u``
             (``Hkv`` heads), no bias, no norm on ``q`` / ``k``, no
             rotary or other position; scores times ``scale`` (a
             model's ``attention_multiplier``; ``None`` is
             ``head_dim^-1/2``), softmax over all ``L`` keys (a denoiser
             is not causal), ``W_o``.  ``q`` carries ``scale *
             head_dim^1/2`` into :func:`~diff3d_tpu.ops.attention.sdpa`,
             which divides by ``head_dim^1/2``.  One tile of ``q_chunk``
             queries at a time: where the registry resolves to XLA (a
             CPU process; fewer than 2048 keys) a tile's float32 scores
             are ``[Hq, q_chunk, L]``, not ``[Hq, L, L]``; on a TPU
             process the tile is one call of the Pallas kernel
             ``plain_attention``, which writes no scores at all.
             The ``full_attention`` layer of a ``qwen3_next`` block is
             the same layer with three more parts (``gated``): ``[q_h |
             gate_h] = (W_q u)_h``, each ``head_dim`` wide; per-head
             zero-centred RMSNorms on ``q`` and ``k`` (``1 + w``); the
             rotary turn on the first ``rotary_dim`` dims of every
             ``q`` and ``k`` head by the token's index in the sequence
             (pair ``i`` is ``(x_i, x_{i + rotary_dim / 2})``), the rest
             passed through; the heads' outputs times ``sigmoid(gate)``
             before ``W_o``.  Each traced site with the gate adds 1 to
             the recorder's ``attention.gated``.
  MLP        ``[a | b] = W_1 u``, ``W_2 (silu(a) * b)``, no bias; as a
             ``qwen3_next`` shared expert (``gated``), times
             ``sigmoid(w_g^T u)`` per token.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from diff3d_tpu.models.moe import residual_half, rms_norm
from diff3d_tpu.models.sparse_attention import (Kernel, Scale, mrope_tables,
                                                rotate)
from diff3d_tpu.ops.attention import sdpa
from diff3d_tpu.utils.profiling import count, scope


def dense(u: jnp.ndarray, kernel: jnp.ndarray, dtype,
          out=None) -> jnp.ndarray:
    """``u @ kernel`` with both operands in the compute dtype; the result
    in it too, or in ``out``."""
    return jnp.dot(u.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=out)


class FullAttention(nn.Module):
    hidden: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    q_chunk: int
    scale: Optional[float] = None
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # a qwen3_next block's full_attention layer
    gated: bool = False
    rotary_dim: int = 0
    rope_theta: float = 1e7

    def setup(self):
        pd, d = self.param_dtype, self.head_dim
        self.q_proj = Kernel(self.num_heads * d * (2 if self.gated else 1),
                             pd)
        self.k_proj = Kernel(self.num_kv_heads * d, pd)
        self.v_proj = Kernel(self.num_kv_heads * d, pd)
        if self.gated:
            self.q_norm = Scale(pd, centred=True)
            self.k_norm = Scale(pd, centred=True)
        self.o_proj = Kernel(self.hidden, pd)

    def _gated_parts(self, q, k, q_norm, k_norm):
        """``(W_q u)`` split into ``q`` and the gate, both ``[L, Hq, d]``,
        and ``q``, ``k`` normed and turned."""
        L, d, r = k.shape[0], self.head_dim, self.rotary_dim
        q, gate = jnp.split(q.reshape(L, self.num_heads, 2 * d), 2, axis=-1)
        q = rms_norm(q, q_norm, self.eps)
        k = rms_norm(k, k_norm, self.eps)
        with scope("rope"):
            # one axis: token t turned by t alone
            cos, sin = mrope_tables(L, 1, 1, r, self.rope_theta,
                                    (r // 2, 0, 0))
            q, k = (jnp.concatenate([rotate(x[..., :r], cos, sin),
                                     x[..., r:]], axis=-1) for x in (q, k))
        return q, gate, k

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 post_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        L, D = h.shape[1:]
        d, C = self.head_dim, min(self.q_chunk, L)
        if L % C:
            raise ValueError(
                f"q_chunk={self.q_chunk} must divide the {L} tokens")
        Wq, Wk, Wv = self.q_proj(D), self.k_proj(D), self.v_proj(D)
        Wo = self.o_proj(self.num_heads * d)
        if self.gated:
            count("attention.gated")
            norms = self.q_norm(d), self.k_norm(d)

        def attend(u):
            with scope("attention"):
                q = dense(u, Wq, self.dtype)
                if not self.gated:
                    q = q.reshape(L, self.num_heads, d)
                k = dense(u, Wk, self.dtype).reshape(L, self.num_kv_heads, d)
                v = dense(u, Wv, self.dtype).reshape(L, self.num_kv_heads, d)
                if self.gated:
                    q, gate, k = self._gated_parts(q, k, *norms)
                if self.scale is not None:
                    q = q * jnp.asarray(self.scale * d ** 0.5, q.dtype)

                def one_tile(c):
                    qc = jax.lax.dynamic_slice_in_dim(q, c * C, C)
                    return sdpa(qc[None], k[None], v[None])[0]

                out = jax.lax.map(one_tile, jnp.arange(L // C))
                if self.gated:
                    out = (out.reshape(L, self.num_heads, d).astype(
                        jnp.float32) * jax.nn.sigmoid(
                            gate.astype(jnp.float32))).astype(self.dtype)
                return dense(out.reshape(L, self.num_heads * d), Wo,
                             self.dtype, jnp.float32)

        with scope("attention"):
            return residual_half(h, norm_scale, self.eps, self.residual,
                                 attend, post_scale)


class GatedMLP(nn.Module):
    hidden: int
    width: int
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    gated: bool = False

    def setup(self):
        self.w1 = Kernel(2 * self.width, self.param_dtype)
        self.w2 = Kernel(self.hidden, self.param_dtype)
        if self.gated:
            self.gate = Kernel(1, self.param_dtype)

    def branch(self):
        """``u [T, D] -> W_2 (silu(a) * b)`` float32, a plain function of
        the layer's kernels: what a map's body may call (no module can
        be), here over examples and, where the MLP is a shared expert
        beside routed ones, in their chunk map
        (:class:`~diff3d_tpu.models.moe.RoutedExperts` ``beside``).
        ``gated``: times ``sigmoid(w_g^T u)``, one number a token; each
        traced branch with the gate adds 1 to the recorder's
        ``experts.shared_gate``."""
        W1, W2 = self.w1(self.hidden), self.w2(self.width)
        Wg = self.gate(self.hidden) if self.gated else None
        if self.gated:
            count("experts.shared_gate")

        def mlp(u):
            with scope("mlp"):
                a, b = jnp.split(dense(u, W1, self.dtype), 2, axis=-1)
                g = nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
                y = dense(g, W2, self.dtype, jnp.float32)
                if Wg is not None:
                    y = y * jax.nn.sigmoid(dense(u, Wg, self.dtype,
                                                 jnp.float32))
                return y

        return mlp

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 post_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        mlp = self.branch()
        with scope("mlp"):
            return residual_half(h, norm_scale, self.eps, self.residual,
                                 mlp, post_scale)
