"""Grouped-query attention over the keys a lightning indexer selects.

Per layer, on the normed tokens ``u [B, L, D]`` of ``B`` examples:

  * attention heads: ``q = W_q u`` (``Hq`` heads), ``k = W_k u``, ``v =
    W_v u`` (``Hkv`` heads, ``Hkv`` divides ``Hq``), no bias; RMSNorm per
    head on ``q`` and ``k``; multi-axis rotary embedding over (frame,
    patch row, patch column) (:func:`mrope_tables`, :func:`rotate`);
  * lightning indexer: ``qI = W_qI u`` (``Hi`` heads of ``Di``), ``kI =
    W_kI u`` (one head), ``w = W_w u`` (``Hi``), the same rotary embedding
    at half the section sizes; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])`` with ``w`` scaled by ``Hi^-1/2 Di^-1/2``, in float32;
  * token ``t`` attends to the ``topk`` keys with the largest ``I[t, .]``
    among all ``L`` (a denoiser is not causal): softmax over those keys
    alone, scores over ``sqrt(head_dim)``; ``W_o`` back to ``D``.

With ``topk >= L`` every key is selected and the layer is dense
grouped-query attention.  One tile of ``q_chunk`` queries against all
keys at a time, one example at a time
(:func:`diff3d_tpu.ops.attention.sdpa` with ``keep``), so no ``[B, Hq, L,
L]`` array exists and ``q`` exists for one example only
(:func:`attend_example`: plain functions of the layer's arrays, mapped
over the examples).  The core under the selection has two forms, and
``sdpa`` picks by what the process and the shapes are: on a TPU process,
at a head dim of whole lane tiles and whole query / key blocks, one
Pallas kernel that streams the keys through VMEM and writes only the
tile's output (``ops/pallas_attention.selected_attention``); elsewhere
(CPU processes, toy widths) the tile's scores computed dense and masked
by XLA, which is also the kernel's gradient.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.models.moe import residual_half, rms_norm
from diff3d_tpu.ops.attention import sdpa
from diff3d_tpu.utils.profiling import scope

def mrope_tables(frames: int, rows: int, cols: int, head_dim: int,
                 theta: float, section: Sequence[int]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``cos, sin [L, head_dim / 2]`` of the multi-axis rotary embedding:
    token ``(f, r, c)`` sits at ``f * rows * cols + r * cols + c``;
    frequency pair ``i`` has ``theta^(-2 i / head_dim)`` and is turned by
    the frame index for the first ``section[0]`` pairs, by the row for
    the next ``section[1]``, by the column for the last ``section[2]``."""
    half = head_dim // 2
    assert sum(section) == half, (section, head_dim)
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    f, r, c = np.meshgrid(np.arange(frames), np.arange(rows),
                          np.arange(cols), indexing="ij")
    pos = np.stack([f.ravel(), r.ravel(), c.ravel()], axis=1)   # [L, 3]
    axis = np.repeat(np.arange(3), section)                     # [half]
    ang = pos[:, axis] * inv[None, :]
    return (np.cos(ang).astype(np.float32),
            np.sin(ang).astype(np.float32))


def rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
           ) -> jnp.ndarray:
    """Rotary embedding of ``x [..., L, heads, d]`` by ``cos, sin [L,
    d / 2]``: pair ``i`` is ``(x[i], x[i + d / 2])``."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def index_scores(qi: jnp.ndarray, ki: jnp.ndarray, wi: jnp.ndarray
                 ) -> jnp.ndarray:
    """``qi [C, Hi, Di]``, ``ki [L, Di]``, ``wi [C, Hi]`` (float32, scale
    folded in) -> ``I [C, L]`` float32."""
    dots = jnp.einsum("chd,sd->chs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("chs,ch->cs", jax.nn.relu(dots), wi)


def kth_largest(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """The ``k``-th largest value of each row of float32 ``scores [C,
    L]``, exactly, by bisection on the bits: floats map monotonically to
    unsigned integers, and 32 passes of compare-and-count fix the
    integer's bits from the top.  On the v5e that is 3.5 times faster
    than ``lax.top_k``, which sorts each row (PERF.md section 6, PR 26)."""
    u = jax.lax.bitcast_convert_type(jax.lax.stop_gradient(scores),
                                     jnp.uint32)
    top = jnp.uint32(1 << 31)
    key = jnp.where(u >= top, ~u, u | top)           # order-preserving

    def fix_bit(i, prefix):
        cand = prefix | (top >> i.astype(jnp.uint32))
        enough = (key >= cand[:, None]).sum(axis=-1) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, fix_bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(kth >= top, kth & ~top, ~kth), jnp.float32)


def select_top(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """``[C, L]`` float32 -> bool ``[C, L]``: the keys whose score is at
    least the ``k``-th largest of their row (all keys when ``k >= L``;
    keys tied with the ``k``-th are all kept)."""
    L = scores.shape[-1]
    if k >= L:
        return jnp.ones(scores.shape, bool)
    return scores >= kth_largest(scores, k)[..., None]


class Kernel(nn.Module):
    """The kernel of a bias-free dense layer, under the name ``nn.Dense``
    would give it: the layer's arithmetic is in :func:`attend_example`,
    inside a map over examples where no module can be called."""

    features: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, fan_in: int) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (fan_in, self.features), self.param_dtype)


class Scale(nn.Module):
    """A norm's weight ``w``, made 1.  ``centred``: the zero-centred form
    of a ``qwen3_next`` block, ``w`` made 0 and the norm scaling by ``1 +
    w`` (float32), which is what this returns."""

    param_dtype: jnp.dtype = jnp.float32
    centred: bool = False

    @nn.compact
    def __call__(self, n: int) -> jnp.ndarray:
        if self.centred:
            w = self.param("scale", nn.initializers.zeros, (n,),
                           self.param_dtype)
            return 1.0 + w.astype(jnp.float32)
        return self.param("scale", nn.initializers.ones, (n,),
                          self.param_dtype)


class SparseAttention(nn.Module):
    """``h [B, L, D] -> h + attention(norm(h))``: the layer's first half,
    one example at a time, norm and residual add included, so that only
    the layer's input and output exist at the size of the whole call."""

    hidden: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    indexer_heads: int
    indexer_dim: int
    topk: int
    q_chunk: int
    grid: Tuple[int, int, int]          # frames, patch rows, patch columns
    rope_theta: float
    mrope_section: Tuple[int, int, int]
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.q_proj = Kernel(self.num_heads * self.head_dim)
        self.k_proj = Kernel(self.num_kv_heads * self.head_dim)
        self.v_proj = Kernel(self.num_kv_heads * self.head_dim)
        self.q_norm = Scale()
        self.k_norm = Scale()
        self.indexer_q = Kernel(self.indexer_heads * self.indexer_dim)
        self.indexer_k = Kernel(self.indexer_dim)
        self.indexer_w = Kernel(self.indexer_heads)
        self.o_proj = Kernel(self.hidden)

    def _arrays(self) -> dict:
        D = self.hidden
        return {"q": self.q_proj(D), "k": self.k_proj(D),
                "v": self.v_proj(D), "q_norm": self.q_norm(self.head_dim),
                "k_norm": self.k_norm(self.head_dim),
                "qi": self.indexer_q(D), "ki": self.indexer_k(D),
                "wi": self.indexer_w(D),
                "o": self.o_proj(self.num_heads * self.head_dim)}

    def _dense(self, u, kernel):
        return jnp.dot(u.astype(self.dtype), kernel.astype(self.dtype))

    def _indexer(self, u, W):
        """One example ``u [L, D]`` -> ``qI [L, Hi, Di]``, ``kI [L, Di]``
        rotated, ``w [L, Hi]`` float32 with the scale folded in."""
        L = u.shape[0]
        cos, sin = mrope_tables(*self.grid, self.indexer_dim,
                                self.rope_theta,
                                [s // 2 for s in self.mrope_section])
        qi = self._dense(u, W["qi"]).reshape(L, self.indexer_heads,
                                             self.indexer_dim)
        ki = self._dense(u, W["ki"]).reshape(L, 1, self.indexer_dim)
        with scope("rope"):
            qi = rotate(qi, cos, sin)
            ki = rotate(ki, cos, sin)[:, 0]
        wi = self._dense(u, W["wi"]).astype(jnp.float32) * (
            self.indexer_heads ** -0.5 * self.indexer_dim ** -0.5)
        return qi, ki, wi

    def attend_example(self, u: jnp.ndarray, W: dict) -> jnp.ndarray:
        """``u [L, D]`` of one example -> ``[L, D]``."""
        L = u.shape[0]
        C = min(self.q_chunk, L)
        with scope("attention"):
            q = self._dense(u, W["q"]).reshape(L, self.num_heads,
                                               self.head_dim)
            k = self._dense(u, W["k"]).reshape(L, self.num_kv_heads,
                                               self.head_dim)
            v = self._dense(u, W["v"]).reshape(L, self.num_kv_heads,
                                               self.head_dim)
            q = rms_norm(q, W["q_norm"], self.eps)
            k = rms_norm(k, W["k_norm"], self.eps)
        with scope("rope"):
            cos, sin = mrope_tables(*self.grid, self.head_dim,
                                    self.rope_theta, self.mrope_section)
            q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        with scope("indexer"):
            qi, ki, wi = self._indexer(u, W)

        def one_tile(c):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, c * C, C)  # noqa: E731
            with scope("indexer"):
                keep = select_top(index_scores(cut(qi), ki, cut(wi)),
                                  self.topk)
            with scope("sparse_attention"):
                return sdpa(cut(q)[None], k[None], v[None],
                            keep=keep[None])[0]

        with scope("sparse_attention"):
            out = jax.lax.map(one_tile, jnp.arange(L // C))
            out = out.reshape(L, self.num_heads * self.head_dim)
        with scope("attention"):
            return self._dense(out, W["o"])

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 post_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        L = h.shape[1]
        assert L == int(np.prod(self.grid)), (L, self.grid)
        if L % min(self.q_chunk, L):
            raise ValueError(
                f"q_chunk={self.q_chunk} must divide the {L} tokens")
        W = self._arrays()

        with scope("sparse_attention"):
            return residual_half(h, norm_scale, self.eps, self.residual,
                                 lambda u: self.attend_example(u, W),
                                 post_scale)
