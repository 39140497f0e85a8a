"""Multi-head latent attention, as the DeepSeek-V2 / V3 modules of a
``pangu_ultra_moe`` block compute it, in the decompressed form: every
query of a denoiser attends all keys at once, as prefill does, so the
keys and values are expanded from their latent and no latent is cached.

``h [B, L, D] -> h + r post(attn(norm(h)))`` one example at a time
(:func:`~diff3d_tpu.models.moe.residual_half`), no bias anywhere, ``n``
an RMSNorm with a weight of its own::

  queries  c_q = n_qa(W_qa u)                        q_lora_rank wide
           [q_nope | q_pe]_h = (W_qb c_q)_h          qk_nope + qk_rope
  keys,    [c_kv | k_pe] = W_kva u                   kv_lora_rank + qk_rope
  values   [k_nope | v]_h = (W_kvb n_kva(c_kv))_h    qk_nope + v_head
  rotary   every head's q_pe and the one k_pe, shared by all heads,
           turned by the token's index in the sequence (conditioning
           frame, then target frame, patches row-major): pair ``i`` is
           ``(x_i, x_{i + d/2})``, angle ``pos * theta^(-2i/d)``,
           ``d = qk_rope``
  core     q_h = [q_nope | q_pe]_h, k_h = [k_nope_h | k_pe]; softmax over
           all L keys (a denoiser is not causal) of q_h k_h^T / (qk_nope +
           qk_rope)^1/2, times v_h; W_o over the heads' v_head-wide
           outputs.

The core is :func:`~diff3d_tpu.ops.attention.sdpa_latent` on the split
operands (``q_nope``, the turned ``q_pe``, ``k_nope``, the one turned
``k_pe``, ``v``).  On a TPU process it runs the Pallas kernel
``latent_attention`` on all ``L`` queries of an example at once, which
reads the shared rotary key once a key block for all heads and keeps the
scores in VMEM; elsewhere (every CPU process) the XLA expression, one
tile of ``q_chunk`` queries at a time, whose float32 scores are
``[heads, q_chunk, L]`` a tile.  Scope tags: ``mla_q`` (``W_qa``,
``n_qa``, ``W_qb``), ``mla_kv`` (``W_kva``, ``n_kva``, ``W_kvb``),
``rope``, ``attention`` (the core and ``W_o``).  Each traced site adds 1
to the recorder's ``attention.latent``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from diff3d_tpu.models.moe import residual_half, rms_norm
from diff3d_tpu.models.sparse_attention import (Kernel, Scale,
                                                mrope_tables, rotate)
from diff3d_tpu.models.token_layers import dense
from diff3d_tpu.ops.attention import sdpa_latent
from diff3d_tpu.utils.profiling import count, scope


class LatentAttention(nn.Module):
    hidden: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_chunk: int
    rope_theta: float
    eps: float = 1e-6
    residual: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        H, pd = self.num_heads, self.param_dtype
        self.q_a_proj = Kernel(self.q_lora_rank, pd)
        self.q_a_norm = Scale(pd)
        self.q_b_proj = Kernel(
            H * (self.qk_nope_head_dim + self.qk_rope_head_dim), pd)
        self.kv_a_proj = Kernel(self.kv_lora_rank + self.qk_rope_head_dim,
                                pd)
        self.kv_a_norm = Scale(pd)
        self.kv_b_proj = Kernel(
            H * (self.qk_nope_head_dim + self.v_head_dim), pd)
        self.o_proj = Kernel(self.hidden, pd)

    def __call__(self, h: jnp.ndarray, norm_scale: jnp.ndarray,
                 post_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        L, D = h.shape[1:]
        H, C = self.num_heads, min(self.q_chunk, L)
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        if L % C:
            raise ValueError(
                f"q_chunk={self.q_chunk} must divide the {L} tokens")
        qr, kvr = self.q_lora_rank, self.kv_lora_rank
        W = {"qa": self.q_a_proj(D), "qn": self.q_a_norm(qr),
             "qb": self.q_b_proj(qr), "kva": self.kv_a_proj(D),
             "kvn": self.kv_a_norm(kvr), "kvb": self.kv_b_proj(kvr),
             "o": self.o_proj(H * dv)}
        # one axis: token t turned by t alone
        cos, sin = mrope_tables(L, 1, 1, dr, self.rope_theta,
                                (dr // 2, 0, 0))
        dt, eps = self.dtype, self.eps

        def attend(u):
            # a leading example axis of 1, as sdpa_latent takes them
            with scope("mla_q"):
                cq = rms_norm(dense(u, W["qa"], dt), W["qn"], eps)
                # W_qb's columns by part, so that each part comes out of
                # its own product whole, in the layout the core reads
                qb = W["qb"].reshape(qr, H, dn + dr)
                q_nope = dense(cq, qb[..., :dn].reshape(qr, H * dn), dt)
                q_pe = dense(cq, qb[..., dn:].reshape(qr, H * dr), dt)
            with scope("mla_kv"):
                ckv, k_pe = jnp.split(dense(u, W["kva"], dt), [kvr], axis=-1)
                kv = dense(rms_norm(ckv, W["kvn"], eps), W["kvb"], dt)
                k_nope, v = jnp.split(kv.reshape(1, L, H, dn + dv), [dn],
                                      axis=-1)
            with scope("rope"):
                q_pe = rotate(q_pe.reshape(1, L, H, dr), cos, sin)
                k_pe = rotate(k_pe[None, :, None], cos, sin)[:, :, 0]
            with scope("attention"):
                out = sdpa_latent(q_nope.reshape(1, L, H, dn), q_pe, k_nope,
                                  k_pe, v, q_chunk=C)
                return dense(out.reshape(L, H * dv), W["o"], dt,
                             jnp.float32)

        count("attention.latent")
        with scope("attention"):
            return residual_half(h, norm_scale, self.eps, self.residual,
                                 attend, post_scale)
