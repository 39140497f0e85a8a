"""The X-UNet (Watson et al., 3DiM) as a Flax module.

Parity target: reference ``/root/reference/xunet.py:355-536``.  One model
definition replaces the reference's two variants (root + lightning, which
differ only in device handling).  Differences by design, not omission:

  * channels-last ``[B, F, H, W, C]`` layout (TPU-native; reference is NCHW);
  * conditioning rays computed on-device (see
    :mod:`diff3d_tpu.models.conditioning`);
  * up-path input channel arithmetic (reference ``xunet.py:432-460``) is
    implicit — Flax convs infer input width, and the skip push/pop structure
    reproduces the same concatenations (asserted empty at the end, like
    reference ``xunet.py:533``);
  * optional bf16 compute and per-block rematerialisation for the 128^2
    config that OOMs the reference's GPUs (README.md:39).

Forward contract (reference ``xunet.py:477-536``): batch dict with
``x [B,H,W,3]``, ``z [B,H,W,3]``, ``logsnr [G,2]``, ``R [G,2,3,3]``,
``t [G,2,3]``, ``K [G,3,3]`` (and the optional ``cam_dirs [G,1,H,W,3]``)
plus ``cond_mask [G] bool``; returns the predicted noise for the target
frame, ``[B, H, W, 3]``.

``G`` divides ``B``, and example ``b`` uses conditioning row
``b // (B // G)`` (group-major): the conditioning branch — pose
embedding, its level convs, every FiLM dense — is computed once per
distinct conditioning, not once per example.  The rule is read from the
shapes alone.  ``G == B`` is one row per example (training, distillation,
evaluation); the sampler's scan passes ``G = 2`` (a conditional and an
unconditional row) for its ``2 x guidance weights`` examples.  Each trace
adds ``G`` and ``B`` to the recorder's ``conditioning.groups`` /
``conditioning.examples`` counters (:mod:`diff3d_tpu.utils.profiling`).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from diff3d_tpu.config import ModelConfig
from diff3d_tpu.models.conditioning import (ConditioningProcessor,
                                            conditioning_rows)
from diff3d_tpu.models.layers import FrameGroupNorm, ResnetBlock, XUNetBlock
from diff3d_tpu.utils.profiling import count, scope


class XUNet(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, batch: dict, *, cond_mask: jnp.ndarray,
                 deterministic: bool = True,
                 constrain=None) -> jnp.ndarray:
        """``constrain`` (optional ``h -> h``): sharding-constraint hook
        applied to every block's ``[B, F, h, w, C]`` output — GSPMD context
        parallelism when it pins the spatial axis to a mesh axis
        (``MeshEnv.activation_constraint``); identity otherwise."""
        cfg = self.cfg
        cfg.validate()
        if constrain is None:
            constrain = lambda h: h  # noqa: E731
        dtype = jnp.dtype(cfg.dtype)
        B, H, W, C = batch["x"].shape
        assert (H, W) == (cfg.H, cfg.W), ((H, W), (cfg.H, cfg.W))
        G = conditioning_rows(batch, cond_mask)
        # once per trace, like the compile.* events
        count("conditioning.groups", G)
        count("conditioning.examples", B)

        num_res = cfg.num_resolutions
        dim_out = [cfg.ch * m for m in cfg.ch_mult]

        if cfg.remat:
            import jax

            policy = {
                "nothing": None,   # save-nothing: recompute the whole block
                "dots": jax.checkpoint_policies.dots_saveable,
            }[cfg.remat_policy]
            # argnums count `self` as 0, so `deterministic` is 3
            block_cls = nn.remat(XUNetBlock, static_argnums=(3,),
                                 policy=policy)
            resnet_cls = nn.remat(ResnetBlock, static_argnums=(3,),
                                  policy=policy)
        else:
            block_cls, resnet_cls = XUNetBlock, ResnetBlock

        logsnr_emb, pose_embs = ConditioningProcessor(
            emb_ch=cfg.emb_ch, H=H, W=W, num_resolutions=num_res,
            use_pos_emb=cfg.use_pos_emb,
            use_ref_pose_emb=cfg.use_ref_pose_emb,
            logsnr_clip=cfg.logsnr_clip, dtype=dtype,
            name="conditioningprocessor")(batch, cond_mask)

        def level_emb(i):
            # [G, F, 1, 1, emb_ch] + [G, F, h, w, emb_ch]
            with scope("conditioning"):
                return logsnr_emb[:, :, None, None, :] + pose_embs[i]

        # Stem: both frames through one 3x3 conv (reference xunet.py:493-495).
        with scope("conv"):
            h = jnp.stack([batch["x"], batch["z"]], axis=1).astype(dtype)
            F = h.shape[1]
            h = nn.Conv(cfg.ch, (3, 3), dtype=dtype,
                        name="stem_conv")(h.reshape(B * F, H, W, C))
            h = constrain(h.reshape(B, F, H, W, cfg.ch))

        # Down path (reference xunet.py:498-512).
        hs = [h]
        for i_level in range(num_res):
            emb = level_emb(i_level)
            use_attn = i_level in cfg.attn_levels
            for i_block in range(cfg.num_res_blocks):
                h = constrain(block_cls(
                    features=dim_out[i_level], use_attn=use_attn,
                    num_heads=cfg.attn_heads, dropout=cfg.dropout,
                    attn_impl=cfg.attn_impl, dtype=dtype,
                    kernels=cfg.kernels,
                    name=f"down_{i_level}_{i_block}")(h, emb, deterministic))
                hs.append(h)
            if i_level != num_res - 1:
                h = constrain(resnet_cls(
                    features=dim_out[i_level], dropout=cfg.dropout,
                    resample="down", dtype=dtype, kernels=cfg.kernels,
                    name=f"down_{i_level}_downsample")(h, emb, deterministic))
                hs.append(h)

        # Middle (reference xunet.py:419-424,515-517).
        h = constrain(block_cls(
            features=dim_out[-1], use_attn=num_res in cfg.attn_levels,
            num_heads=cfg.attn_heads, dropout=cfg.dropout,
            attn_impl=cfg.attn_impl, dtype=dtype,
            kernels=cfg.kernels,
            name="middle")(h, level_emb(num_res - 1), deterministic))

        # Up path (reference xunet.py:521-531): each block consumes
        # concat([h, skip]) on the channel axis.
        for i_level in reversed(range(num_res)):
            emb = level_emb(i_level)
            use_attn = i_level in cfg.attn_levels
            for i_block in range(cfg.num_res_blocks + 1):
                with scope("residual"):
                    h = jnp.concatenate([h, hs.pop()], axis=-1)
                h = constrain(block_cls(
                    features=dim_out[i_level], use_attn=use_attn,
                    num_heads=cfg.attn_heads, dropout=cfg.dropout,
                    attn_impl=cfg.attn_impl, dtype=dtype,
                    kernels=cfg.kernels,
                    name=f"up_{i_level}_{i_block}")(h, emb, deterministic))
            if i_level != 0:
                h = constrain(resnet_cls(
                    features=dim_out[i_level], dropout=cfg.dropout,
                    resample="up", dtype=dtype, kernels=cfg.kernels,
                    name=f"up_{i_level}_upsample")(h, emb, deterministic))
        assert not hs

        # Head: GN -> SiLU -> zero-init conv -> target frame's eps-hat
        # (reference xunet.py:472-474,535-536).
        h = FrameGroupNorm(dtype=dtype, kernels=cfg.kernels, silu=True,
                           name="last_gn")(h)
        with scope("conv"):
            h = nn.Conv(3, (3, 3), dtype=dtype,
                        kernel_init=nn.initializers.zeros,
                        name="last_conv")(h.reshape(B * F, H, W, dim_out[0]))
            h = h.reshape(B, F, H, W, 3)
            return h[:, 1].astype(jnp.float32)
