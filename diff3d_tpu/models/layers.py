"""X-UNet building blocks as Flax modules, NHWC with a frames axis.

All feature maps are ``[B, F, H, W, C]`` (channels-last — TPU/XLA's native
conv layout; the reference uses NCHW).  ``F`` is the number of frames
(source + target view = 2), kept general where the reference hardcodes 2
(``/root/reference/xunet.py:70``).

Parity targets (reference ``xunet.py``): ``GroupNorm`` over frames (:61-71),
``FiLM`` (:74-87), BigGAN-style ``ResnetBlock`` with zero-init second conv
and /sqrt(2) residual (:90-152), shared-weight frame self/cross attention
(:154-220), ``XUNetBlock`` (:222-256).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.ops import dispatch
from diff3d_tpu.ops import pallas_film  # noqa: F401 - registers 'groupnorm'
from diff3d_tpu.ops.attention import multi_head_attention
from diff3d_tpu.utils.profiling import scope


def nearest_neighbor_upsample(h: jnp.ndarray) -> jnp.ndarray:
    """x2 spatial nearest upsample of ``[B, F, H, W, C]``
    (reference ``xunet.py:17-20``)."""
    h = jnp.repeat(h, 2, axis=2)
    return jnp.repeat(h, 2, axis=3)


def avgpool_downsample(h: jnp.ndarray, k: int = 2) -> jnp.ndarray:
    """kxk average-pool downsample of ``[B, F, H, W, C]``
    (reference ``xunet.py:23-28``)."""
    B, F, H, W, C = h.shape
    h = h.reshape(B, F, H // k, k, W // k, k, C)
    return h.mean(axis=(3, 5))


def _num_groups(C: int, preferred: int = 32) -> int:
    """Largest group count <= preferred that divides C (the reference always
    has C a multiple of 32; this generalises for tiny test widths)."""
    g = min(preferred, C)
    while C % g:
        g -= 1
    return g


class _GroupNormParams(nn.Module):
    """Parameter-only stand-in for ``nn.GroupNorm`` on the fused-kernel
    path: same child name ("GroupNorm_0"), param names ("scale"/"bias"),
    shapes, dtypes and inits, so a checkpoint trained with either kernel
    backend restores bit-for-bit into the other."""

    features: int

    @nn.compact
    def __call__(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        gamma = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        beta = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        return gamma, beta


class FrameGroupNorm(nn.Module):
    """Group normalization applied per frame (reference ``xunet.py:61-71``:
    frames are folded into the batch axis before GN), with optional fused
    FiLM/SiLU epilogues.

    ``kernels`` routes through :mod:`diff3d_tpu.ops.dispatch`: 'xla' (the
    default) runs the plain ``nn.GroupNorm`` composition — bit-identical
    graphs to the pre-kernel-layer code; 'pallas'/'auto' may run the fused
    GroupNorm->FiLM->SiLU Pallas kernel
    (:mod:`diff3d_tpu.ops.pallas_film`), which keeps the whole chain in
    VMEM.  ``scale``/``shift`` (both or neither, shaped like ``h``) append
    the FiLM modulation ``y*(1+scale)+shift``; ``silu`` appends the
    activation.  The parameter tree is identical on every path."""

    num_groups: int = 32
    dtype: jnp.dtype = jnp.float32
    kernels: str = "xla"
    silu: bool = False

    @nn.compact
    def __call__(self, h: jnp.ndarray,
                 scale: Optional[jnp.ndarray] = None,
                 shift: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        # one tag for every backend: the fused Pallas path keeps it
        with scope("groupnorm"):
            B, F, H, W, C = h.shape
            groups = _num_groups(C, self.num_groups)
            flat = jax.ShapeDtypeStruct((B * F, H * W, C), h.dtype)
            impl = dispatch.resolve("groupnorm", self.kernels, flat,
                                    num_groups=groups)
            if impl.name == "pallas":
                gamma, beta = _GroupNormParams(C, name="GroupNorm_0")()
                kw = {}
                if scale is not None:
                    kw = dict(scale=scale.reshape(B * F, H * W, C),
                              shift=shift.reshape(B * F, H * W, C))
                out = impl.fn(h.reshape(B * F, H * W, C), gamma, beta,
                              num_groups=groups, silu=self.silu, **kw)
                return out.reshape(B, F, H, W, C)
            # epsilon matches torch.nn.GroupNorm's 1e-5 (reference
            # xunet.py:66); Flax's default 1e-6 drifts ~1e-5/application
            # across the ~40 GNs of a converted checkpoint's forward.
            out = nn.GroupNorm(num_groups=groups, epsilon=1e-5,
                               dtype=self.dtype)(h.reshape(B * F, H, W, C))
            out = out.reshape(B, F, H, W, C)
            if scale is not None:
                out = out * (1.0 + scale) + shift
            if self.silu:
                out = nn.silu(out)
            return out


def per_example(a: jnp.ndarray, B: int) -> jnp.ndarray:
    """``[G, ...]`` conditioning rows viewed at the ``B`` examples they
    serve, group-major: example ``b`` reads row ``b // (B // G)``.  At
    ``G == B`` (one row per example) this is ``a`` itself: no op is added."""
    G = a.shape[0]
    if G == B:
        return a
    a = jnp.broadcast_to(a[:, None], (G, B // G) + a.shape[1:])
    return a.reshape((B,) + a.shape[2:])


class FiLM(nn.Module):
    """Feature-wise linear modulation (reference ``xunet.py:74-87``):
    ``Dense(emb_ch -> 2*features)`` on SiLU(emb), split into scale/shift,
    ``h * (1 + scale) + shift``.  ``emb`` is ``[G, F, h, w, emb_ch]`` —
    channels-last, so no transposes are needed (the reference transposes
    twice around its Linear).

    ``G`` divides the leading dimension ``B`` of ``h`` (the model's forward
    contract, :mod:`diff3d_tpu.models.xunet`): the dense runs once per
    conditioning row, and only the modulation meets ``h`` at ``B``, with
    scale/shift broadcast over the ``B // G`` examples of a group — ``h``
    is viewed as ``[G, B // G, F, h, w, C]``, nothing of the conditioning
    branch exists at ``B`` rows.  At ``G == B`` there is no such view:
    the ops are the per-example ones.

    With ``h=None`` the module only *emits* ``(scale, shift)``, at ``G`` —
    the fused-kernel path hands them to :class:`FrameGroupNorm`'s epilogue
    instead of applying them here.  The parameter tree (``Dense_0``) is
    unchanged either way."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: Optional[jnp.ndarray], emb: jnp.ndarray
                 ) -> Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        with scope("film"):
            emb = nn.Dense(2 * self.features, dtype=self.dtype)(nn.silu(emb))
            scale, shift = jnp.split(emb, 2, axis=-1)
            if h is None:
                return scale, shift
            G, B = emb.shape[0], h.shape[0]
            if G == B:
                return h * (1.0 + scale) + shift
            hg = h.reshape((G, B // G) + h.shape[1:])
            hg = hg * (1.0 + scale[:, None]) + shift[:, None]
            # The barrier keeps the product in this 6-D view, where the
            # broadcast over a group's examples is a whole dimension and
            # fuses into the GroupNorm pass that streams `h`.  Without it
            # XLA moves the reshape above the product and fuses that into
            # the next conv, whose batch dimension cannot express a
            # broadcast over its middle: it then writes scale and shift
            # out at B rows first (PERF.md, PR 25: 3.0 ms of a 59.3 ms
            # model call at srn64).
            return jax.lax.optimization_barrier(hg).reshape(h.shape)


class ResnetBlock(nn.Module):
    """BigGAN-style residual block over frames (reference ``xunet.py:90-152``).

    GN -> SiLU -> conv3x3 -> GN -> FiLM -> dropout -> conv3x3(zero-init) ->
    (+ 1x1-projected skip if channels change) -> /sqrt(2) -> optional
    up/down resample of the summed output.
    """

    features: int
    dropout: float = 0.0
    resample: Optional[str] = None   # None | 'up' | 'down'
    dtype: jnp.dtype = jnp.float32
    kernels: str = "xla"

    @nn.compact
    def __call__(self, h_in: jnp.ndarray, emb: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        B, F, H, W, C = h_in.shape

        # One trace-time dispatch decision (on conv1's output shape)
        # covers the whole block, so the FiLM emit/apply split always
        # agrees with the second GroupNorm's backend.
        flat2 = jax.ShapeDtypeStruct((B * F, H * W, self.features),
                                     jnp.dtype(self.dtype))
        use_fused = dispatch.resolve(
            "groupnorm", self.kernels, flat2,
            num_groups=_num_groups(self.features)).name == "pallas"

        h = FrameGroupNorm(dtype=self.dtype, kernels=self.kernels,
                           silu=True)(h_in)
        with scope("conv"):
            h = nn.Conv(self.features, (3, 3), dtype=self.dtype,
                        name="conv1")(h.reshape(B * F, H, W, C))
            h = h.reshape(B, F, H, W, self.features)
        if use_fused:
            scale, shift = FiLM(self.features, dtype=self.dtype)(None, emb)
            with scope("film"):
                scale = jnp.broadcast_to(per_example(scale, B), h.shape)
                shift = jnp.broadcast_to(per_example(shift, B), h.shape)
            h = FrameGroupNorm(dtype=self.dtype, kernels=self.kernels)(
                h, scale=scale, shift=shift)
        else:
            h = FrameGroupNorm(dtype=self.dtype, kernels=self.kernels)(h)
            h = FiLM(self.features, dtype=self.dtype)(h, emb)
        with scope("dropout"):
            h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        # Zero-init final conv (reference xunet.py:131) so the block starts
        # as (scaled) identity.
        with scope("conv"):
            h = nn.Conv(self.features, (3, 3), dtype=self.dtype,
                        kernel_init=nn.initializers.zeros,
                        name="conv2")(h.reshape(B * F, H, W, self.features))
            h = h.reshape(B, F, H, W, self.features)

            if C != self.features:
                h_in = nn.Conv(self.features, (1, 1), dtype=self.dtype,
                               name="skip_proj")(
                                   h_in.reshape(B * F, H, W, C))
                h_in = h_in.reshape(B, F, H, W, self.features)

        with scope("residual"):
            out = (h + h_in) / np.sqrt(2.0)
            if self.resample == "up":
                out = nearest_neighbor_upsample(out)
            elif self.resample == "down":
                out = avgpool_downsample(out)
        return out


class AttnLayer(nn.Module):
    """Multi-head attention over token sequences (reference
    ``xunet.py:154-177`` wraps ``torch.nn.MultiheadAttention``): q/k/v/out
    projections with bias + sdpa core (backend-dispatched for TPU)."""

    num_heads: int = 4
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, q: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
        C = q.shape[-1]
        with scope("attention"):
            qp = nn.Dense(C, dtype=self.dtype, name="q_proj")(q)
            kp = nn.Dense(C, dtype=self.dtype, name="k_proj")(kv)
            vp = nn.Dense(C, dtype=self.dtype, name="v_proj")(kv)
            out = multi_head_attention(qp, kp, vp, self.num_heads,
                                       impl=self.attn_impl)
            return nn.Dense(C, dtype=self.dtype, name="out_proj")(out)


class AttnBlock(nn.Module):
    """Frame self/cross attention over ``H*W`` tokens (reference
    ``xunet.py:179-220``).  ONE ``AttnLayer`` is shared by both frames
    (reference ``xunet.py:188``); here both frames run in a single batched
    call (frames folded into the batch axis) instead of two sequential ones.
    Output: zero-init 1x1 conv, residual /sqrt(2).
    """

    attn_type: str                  # 'self' | 'cross'
    num_heads: int = 4
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32
    kernels: str = "xla"

    @nn.compact
    def __call__(self, h_in: jnp.ndarray) -> jnp.ndarray:
        B, F, H, W, C = h_in.shape
        h = FrameGroupNorm(dtype=self.dtype, kernels=self.kernels)(h_in)
        with scope("attention"):
            tokens = h.reshape(B, F, H * W, C)
            q = tokens.reshape(B * F, H * W, C)
            if self.attn_type == "self":
                kv = q
            elif self.attn_type == "cross":
                # Each frame attends to the other (reference
                # xunet.py:206-211; generalised beyond F=2 as "next frame,
                # cyclically").
                kv = jnp.roll(tokens, shift=-1, axis=1).reshape(
                    B * F, H * W, C)
            else:
                raise NotImplementedError(self.attn_type)

        h = AttnLayer(self.num_heads, self.attn_impl, self.dtype,
                      name="attn")(q, kv)
        with scope("conv"):
            h = h.reshape(B * F, H, W, C)
            h = nn.Conv(C, (1, 1), dtype=self.dtype,
                        kernel_init=nn.initializers.zeros,
                        name="out_conv")(h)
            h = h.reshape(B, F, H, W, C)
        with scope("residual"):
            return (h + h_in) / np.sqrt(2.0)


class XUNetBlock(nn.Module):
    """ResnetBlock followed by optional self- then cross-attention
    (reference ``xunet.py:222-256``)."""

    features: int
    use_attn: bool = False
    num_heads: int = 4
    dropout: float = 0.0
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32
    kernels: str = "xla"

    @nn.compact
    def __call__(self, x: jnp.ndarray, emb: jnp.ndarray,
                 deterministic: bool = True) -> jnp.ndarray:
        h = ResnetBlock(self.features, self.dropout, dtype=self.dtype,
                        kernels=self.kernels,
                        name="resnetblock")(x, emb, deterministic)
        if self.use_attn:
            h = AttnBlock("self", self.num_heads, self.attn_impl,
                          self.dtype, kernels=self.kernels,
                          name="attnblock_self")(h)
            h = AttnBlock("cross", self.num_heads, self.attn_impl,
                          self.dtype, kernels=self.kernels,
                          name="attnblock_cross")(h)
        return h
