"""A Mamba-2 state-space mixer: the token denoiser's first layer whose
result at token ``t`` depends on a carried state, and its first that is
causal.

``h [B, L, D] -> h + r mixer(norm(h))``, one example at a time.  With
``d_inner = n_heads * d_head``, ``N = d_state`` and one group (``B`` and
``C`` shared by all heads), on the normed tokens ``u [L, D]`` of an
example:

  * ``[z | xBC | dt] = W_in u``, widths ``d_inner | d_inner + 2 N |
    n_heads``, no bias;
  * ``xBC <- silu(conv(xBC) + b)``: depthwise and causal over ``d_conv``
    taps, ``xBC'_t = sum_j w_j * xBC_{t - (d_conv - 1) + j}``, zeros
    before the sequence; then split into ``x`` (``n_heads x d_head``),
    ``B`` and ``C`` (``N`` each);
  * ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` per head, and
    the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
    S_t C_t + D x_t`` from ``S = 0``, over the example's tokens in their
    order (conditioning frame, then target frame): a target token reads
    every conditioning token and the target tokens before it
    (:func:`diff3d_tpu.ops.ssd.ssd`, a chunked scan with float32 decays
    and states);
  * ``y <- rmsnorm(y * silu(z))`` over all ``d_inner`` (one group) with
    weight ``g``; ``W_out y`` back to ``D``, no bias.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.models.moe import residual_half, rms_norm
from diff3d_tpu.models.sparse_attention import Kernel, Scale
from diff3d_tpu.models.token_layers import dense
from diff3d_tpu.ops.ssd import ssd
from diff3d_tpu.utils.profiling import scope


def causal_conv(x: jnp.ndarray, taps: jnp.ndarray, bias: jnp.ndarray
                ) -> jnp.ndarray:
    """Depthwise causal convolution of ``x [L, C]`` by ``taps [K, C]``
    (the last tap on the token itself) plus ``bias [C]``, float32."""
    L, K = x.shape[0], taps.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((K - 1, 0), (0, 0)))
    return bias + sum(taps[j] * xp[j:j + L] for j in range(K))


def _a_log_init(key, shape):
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))


def _dt_bias_init(key, shape):
    """The inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape) * np.log(100.0)
                 + np.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


class ConvTaps(nn.Module):
    @nn.compact
    def __call__(self, taps: int, channels: int):
        return (self.param("kernel", nn.initializers.normal(taps ** -0.5),
                           (taps, channels)),
                self.param("bias", nn.initializers.zeros, (channels,)))


class Mamba2Mixer(nn.Module):
    hidden: int
    n_heads: int
    d_head: int
    d_state: int
    d_conv: int
    chunk: int
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        d_inner = self.n_heads * self.d_head
        self.in_proj = Kernel(2 * d_inner + 2 * self.d_state + self.n_heads)
        self.conv = ConvTaps()
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.n_heads,))
        self.A_log = self.param("A_log", _a_log_init, (self.n_heads,))
        self.D = self.param("D", nn.initializers.ones, (self.n_heads,))
        self.norm = Scale()
        self.out_proj = Kernel(self.hidden)

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray
                 ) -> jnp.ndarray:
        L = h.shape[1]
        H, P, N = self.n_heads, self.d_head, self.d_state
        d_inner = H * P
        W_in, W_out = self.in_proj(self.hidden), self.out_proj(d_inner)
        taps, conv_bias = self.conv(self.d_conv, d_inner + 2 * N)
        gate_scale = self.norm(d_inner)
        f32 = jnp.float32

        def mix(u):
            with scope("ssm_proj"):
                z, xBC, dt = jnp.split(
                    dense(u, W_in, self.dtype),
                    [d_inner, 2 * d_inner + 2 * N], axis=-1)
            with scope("ssm_conv"):
                xBC = nn.silu(causal_conv(xBC, taps, conv_bias)).astype(
                    self.dtype)
                x, B, C = jnp.split(xBC, [d_inner, d_inner + N], axis=-1)
            with scope("ssm_scan"):
                dt = jax.nn.softplus(dt.astype(f32) + self.dt_bias)
                y = ssd(x.reshape(L, H, P), dt, -jnp.exp(self.A_log), B, C,
                        self.D, self.chunk)
            with scope("ssm_gate"):
                y = y.reshape(L, d_inner).astype(f32) * nn.silu(
                    z.astype(f32))
                y = rms_norm(y, gate_scale, self.eps)
            with scope("ssm_proj"):
                return dense(y, W_out, self.dtype, f32)

        with scope("ssm_scan"):
            return residual_half(h, norm_scale, self.eps, self.residual,
                                 mix)
