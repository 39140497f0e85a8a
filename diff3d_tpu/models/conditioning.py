"""Pose/noise-level conditioning (reference ``xunet.py:259-352``), fully
on-device.

The reference drops to CPU numpy + visu3d for ray generation inside the hot
forward (``xunet.py:311-314``); here rays come from
:func:`diff3d_tpu.geometry.pinhole_rays` in pure jnp, so the whole
conditioning path lives inside the jitted step.
"""

from __future__ import annotations

from typing import List, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.geometry import (pinhole_rays_cam, pinhole_rays_world,
                                 posenc_ddpm, posenc_nerf)
from diff3d_tpu.geometry.posenc import posenc_nerf_channels
from diff3d_tpu.utils.profiling import scope

# 93 (pos, degrees 0..15) + 51 (dir, degrees 0..8) = 144 channels,
# reference xunet.py:317-320.
POS_DEG = 15
DIR_DEG = 8
POSE_EMB_CH = posenc_nerf_channels(0, POS_DEG) + posenc_nerf_channels(0, DIR_DEG)


def conditioning_rows(batch: dict, cond_mask: jnp.ndarray) -> int:
    """``G`` of the forward contract (docs/DESIGN.md §1), checked: the
    conditioning inputs and ``cond_mask`` share one leading dimension,
    and it divides the ``B`` examples of ``x`` / ``z``."""
    B = batch["x"].shape[0]
    if cond_mask.ndim != 1 or B % cond_mask.shape[0]:
        raise ValueError(
            f"cond_mask {cond_mask.shape}: the conditioning rows must "
            f"divide the {B} examples of x / z")
    G = cond_mask.shape[0]
    for k in ("logsnr", "R", "t", "K", "cam_dirs"):
        if k in batch and batch[k].shape[0] != G:
            raise ValueError(
                f"batch[{k!r}] has {batch[k].shape[0]} rows, cond_mask "
                f"has {G}: conditioning inputs share one leading "
                "dimension")
    return G


class ConditioningProcessor(nn.Module):
    """Produces ``(logsnr_emb [G,F,emb_ch], pose_embs[level])`` for the UNet.

    Everything here runs at ``G``, the leading dimension of the
    conditioning inputs (``logsnr``, ``R``, ``t``, ``K``, the optional
    ``cam_dirs``) and of ``cond_mask`` — never at the leading dimension
    ``B`` of ``x`` / ``z``.  ``G`` divides ``B`` and example ``b`` uses row
    ``b // (B // G)`` (group-major; :class:`XUNet` checks it, and
    :class:`~diff3d_tpu.models.layers.FiLM` is where the rows meet the
    examples).  ``G == B`` is one row per example, as in training.

    Mechanism (parity with reference ``xunet.py:301-352``):
      1. clip logsnr to the schedule bounds; DDPM-posenc it with
         ``max_time=1.`` and MLP to ``emb_ch``.  (The reference's unused
         ``lossnr`` arctan normalisation at ``xunet.py:306`` is dead code
         and intentionally NOT reproduced.)
      2. per-pixel rays from (R, t, K); NeRF-posenc pos (deg 15) and dir
         (deg 8) -> 144 channels.
      3. zero the pose embedding of BOTH frames where ``cond_mask`` is
         False (classifier-free guidance, ``xunet.py:323-326``).
      4. add learnable per-pixel ``pos_emb`` and per-frame first/other
         embeddings (``xunet.py:281-290,333-337``).
      5. strided 3x3 convs 144 -> emb_ch, stride ``2^level`` per UNet level.
    """

    emb_ch: int
    H: int
    W: int
    num_resolutions: int
    use_pos_emb: bool = True
    use_ref_pose_emb: bool = True
    logsnr_clip: float = 20.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, batch: dict, cond_mask: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
        with scope("conditioning"):
            H, W = self.H, self.W
            D = POSE_EMB_CH

            logsnr = jnp.clip(batch["logsnr"], -self.logsnr_clip,
                              self.logsnr_clip)                      # [G, F]
            # Encodings stay float32: their sinusoid arguments reach ~2e4
            # (posenc_ddpm's x1000 scaling) and 2^14 (NeRF degree 15), far past
            # bf16's mantissa — bf16 here destroys all phase information.
            # The Dense/Conv layers below cast to the compute dtype
            # themselves.
            logsnr_emb = posenc_ddpm(logsnr, emb_ch=self.emb_ch, max_time=1.0,
                                     dtype=jnp.float32)  # [G, F, emb_ch]
            logsnr_emb = nn.Dense(self.emb_ch, dtype=self.dtype)(logsnr_emb)
            logsnr_emb = nn.Dense(self.emb_ch, dtype=self.dtype)(
                nn.silu(logsnr_emb))

            # [G, F, H, W, 3] each; K broadcast over the frame axis
            # (reference unsqueezes K at xunet.py:312).  The intrinsics-only
            # half (K_inv @ pixel grid) may arrive precomputed as
            # batch['cam_dirs'] — the sampler's scan hoists it once per
            # trajectory (diffusion/core.py) instead of recomputing it every
            # denoise step; both branches are bit-identical by construction
            # (pinhole_rays is the composition of the two stages).
            cam_dirs = batch.get("cam_dirs")
            if cam_dirs is None:
                cam_dirs = pinhole_rays_cam(
                    batch["K"][:, None].astype(jnp.float32), H, W)
            pos, dirs = pinhole_rays_world(batch["R"].astype(jnp.float32),
                                           batch["t"].astype(jnp.float32),
                                           cam_dirs)
            pose_emb = jnp.concatenate(
                [posenc_nerf(pos, 0, POS_DEG), posenc_nerf(dirs, 0, DIR_DEG)],
                axis=-1)                             # [G, F, H, W, 144]

            pose_emb = jnp.where(cond_mask[:, None, None, None, None],
                                 pose_emb, jnp.zeros_like(pose_emb))

            if self.use_pos_emb:
                pos_emb = self.param(
                    "pos_emb", nn.initializers.normal(1.0 / np.sqrt(D)),
                    (H, W, D))
                pose_emb = pose_emb + pos_emb[None, None]
            if self.use_ref_pose_emb:
                first_emb = self.param(
                    "first_emb", nn.initializers.normal(1.0 / np.sqrt(D)),
                    (1, 1, 1, 1, D))
                other_emb = self.param(
                    "other_emb", nn.initializers.normal(1.0 / np.sqrt(D)),
                    (1, 1, 1, 1, D))
                # frame 0 = reference view, frames 1.. = others
                # (reference concat at xunet.py:336 assumes F=2).
                F = pose_emb.shape[1]
                ref_emb = jnp.concatenate(
                    [first_emb] + [other_emb] * (F - 1), axis=1)
                pose_emb = pose_emb + ref_emb

            G, F = pose_emb.shape[:2]
            flat = pose_emb.reshape(G * F, H, W, D)
            pose_embs = []
            for i_level in range(self.num_resolutions):
                s = 2 ** i_level
                # Explicit (1, 1) padding = torch's padding=1 (reference
                # xunet.py:292-299).  NOT "SAME": at stride >= 2 SAME aligns
                # the sampling grid differently, which silently breaks
                # converted-checkpoint parity at every level below the first.
                lvl = nn.Conv(self.emb_ch, (3, 3), strides=(s, s),
                              padding=((1, 1), (1, 1)), dtype=self.dtype,
                              name=f"level_conv_{i_level}")(flat)
                pose_embs.append(
                    lvl.reshape(G, F, H // s, W // s, self.emb_ch))

            return logsnr_emb, pose_embs
