"""A Gated DeltaNet mixer, the ``linear_attention`` layer of a
``qwen3_next`` block: the token denoiser's second mixer with a carried
state, whose update first takes out what the state already predicts for
the token's key (the delta rule) before it writes the token's value.

``h [B, L, D] -> h + r post(mixer(norm(h)))``, one example at a time
(:func:`~diff3d_tpu.models.moe.residual_half`).  With ``Hk`` key heads of
``dk``, ``Hv`` value heads of ``dv`` (value head ``j`` reads key head ``j
// (Hv / Hk)``) and no bias anywhere, on the normed tokens ``u [L, D]`` of
an example:

  * ``[q | k | v | z] = W_qkvz u``, widths ``Hk dk | Hk dk | Hv dv | Hv
    dv``; ``[b | a] = W_ba u``, widths ``Hv | Hv``;
  * ``[q | k | v] <- silu(conv([q | k | v]))``: depthwise and causal over
    ``conv`` taps, no bias (:func:`~diff3d_tpu.models.mamba.causal_conv`);
  * ``q <- l2norm(q) / dk^1/2``, ``k <- l2norm(k)`` per key head (eps
    1e-6, float32); ``beta = sigmoid(b)``, ``g = -exp(A_log)
    softplus(a + dt_bias)`` per value head;
  * the gated delta rule over the example's tokens in their order
    (conditioning frame, then target frame) from a zero state,
    :func:`diff3d_tpu.ops.delta.delta_scan`;
  * ``o <- rmsnorm(o) w * silu(z)`` per value head over its ``dv``
    (norm first, then the gate; ``w`` a plain weight made 1), ``W_out o``.

The published ``in_proj_qkvz`` / ``in_proj_ba`` group their columns by
key head; here each of the four (two) parts is one run of columns.  That
is a fixed permutation of the matrices' columns: with seeded weights the
two are the same model.  Scope tags: ``delta_proj`` (``W_qkvz``, ``W_ba``,
``W_out``), ``delta_conv``, ``delta_scan`` (the l2 norms, ``beta``, ``g``,
the op), ``delta_gate`` (the gated norm).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from diff3d_tpu.models.mamba import ConvTaps, _dt_bias_init, causal_conv
from diff3d_tpu.models.moe import residual_half, rms_norm
from diff3d_tpu.models.sparse_attention import Kernel, Scale
from diff3d_tpu.models.token_layers import dense
from diff3d_tpu.ops.delta import delta_scan
from diff3d_tpu.utils.profiling import scope


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """``x / (|x|^2 + eps)^1/2`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                     keepdims=True) + eps)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)``, the published initialisation (with
    :func:`~diff3d_tpu.models.mamba._dt_bias_init`'s ``dt_bias``, the
    Gated DeltaNet paper's code's: docs/DESIGN.md section 28)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0)
                   ).astype(dtype)


class GatedDeltaNet(nn.Module):
    hidden: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int
    chunk: int
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        pd, Hv = self.param_dtype, self.value_heads
        kd, vd = self.key_heads * self.key_dim, Hv * self.value_dim
        self.in_proj_qkvz = Kernel(2 * kd + 2 * vd, pd)
        self.in_proj_ba = Kernel(2 * Hv, pd)
        self.conv1d = ConvTaps(bias=False, param_dtype=pd)
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (Hv,), pd)
        self.A_log = self.param("A_log", _a_log_init, (Hv,), pd)
        self.norm = Scale(pd)
        self.out_proj = Kernel(self.hidden, pd)

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 post_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        L, D = h.shape[1:]
        Hk, Hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        kd, vd = Hk * dk, Hv * dv
        W_qkvz, W_ba = self.in_proj_qkvz(D), self.in_proj_ba(D)
        W_out = self.out_proj(vd)
        taps, _ = self.conv1d(self.conv, 2 * kd + vd)
        gate_scale = self.norm(dv)
        f32, dt, eps = jnp.float32, self.dtype, self.eps

        def mix(u):
            with scope("delta_proj"):
                qkv, z = jnp.split(dense(u, W_qkvz, dt), [2 * kd + vd],
                                   axis=-1)
                b, a = jnp.split(dense(u, W_ba, dt, f32), 2, axis=-1)
            with scope("delta_conv"):
                qkv = nn.silu(causal_conv(qkv, taps))
                q, k, v = jnp.split(qkv, [kd, 2 * kd], axis=-1)
            with scope("delta_scan"):
                q = l2norm(q.reshape(L, Hk, dk)) * dk ** -0.5
                k = l2norm(k.reshape(L, Hk, dk))
                beta = jax.nn.sigmoid(b)
                g = -jnp.exp(self.A_log.astype(f32)) * jax.nn.softplus(
                    a + self.dt_bias.astype(f32))
                o = delta_scan(q.astype(dt), k.astype(dt),
                               v.reshape(L, Hv, dv).astype(dt), g, beta,
                               self.chunk)
            with scope("delta_gate"):
                o = rms_norm(o.astype(f32), gate_scale, eps) * nn.silu(
                    z.reshape(L, Hv, dv).astype(f32))
            with scope("delta_proj"):
                return dense(o.reshape(L, vd), W_out, dt, f32)

        with scope("delta_scan"):
            return residual_half(h, norm_scale, eps, self.residual, mix,
                                 post_scale)
