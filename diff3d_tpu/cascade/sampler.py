"""The cascade sampler: a 64² student draft pass feeding a truncated 128²
refinement pass.

Both phases run through the ordinary :class:`~diff3d_tpu.sampling.Sampler`
— the draft is a plain few-step sampler at the low resolution (its params
default to the refine params resolution-adapted via
``convert/progressive.py``; a distilled student checkpoint can be passed
instead), and the refine phase is a ``start_t``-truncated sampler whose
per-view ``draft`` operand is the upsampled draft view renoised inside
the compiled scan.  So every mesh/sharding/donation property of the
single-pass path carries over unchanged, and the cascade programs are
lowered and audited by the same four analysis pillars
(``step_many_cascade_draft`` / ``step_many_cascade_refine``).

RNG across phases: one parent key splits into independent draft and
refine streams (``split(rng)``), each then threaded per view exactly like
the single-pass sampler — the refine stream is the one that must match
the single-pass oracle under truncation-at-t=1.0 (the bit-parity
acceptance test), so it never depends on how many draws the draft made.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from diff3d_tpu.cascade.plan import CascadePlan
from diff3d_tpu.config import Config
from diff3d_tpu.convert.progressive import adapt_params_resolution
from diff3d_tpu.models import XUNet, build_xunet
from diff3d_tpu.sampling import Sampler


def upsample_draft(draft, dst_hw: Tuple[int, int]):
    """Bilinearly upsample ``[..., h, w, 3]`` draft images to ``dst_hw``
    — the same interpolation ``convert/progressive.py`` uses for the
    positional embedding, so the draft the refine pass renoises is
    spatially aligned with the prior the 128² model learned."""
    draft = jnp.asarray(draft)
    shape = draft.shape[:-3] + (dst_hw[0], dst_hw[1], draft.shape[-1])
    return jax.image.resize(draft, shape, method="bilinear")


def downsample_views(views: Dict[str, np.ndarray],
                     resolution: int) -> Dict[str, np.ndarray]:
    """An ``all_views``-style dict resized to ``resolution``² for the
    draft phase: images area-matched via bilinear resize, intrinsics
    rescaled (fx/fy/cx/cy rows scale with the image), poses unchanged."""
    imgs = np.asarray(views["imgs"], np.float32)
    H = imgs.shape[1]
    scale = resolution / H
    out = dict(views)
    out["imgs"] = np.asarray(jax.image.resize(
        imgs, (imgs.shape[0], resolution, resolution, imgs.shape[-1]),
        method="bilinear"))
    K = np.array(views["K"], np.float32)
    K[:2] *= scale
    out["K"] = K
    return out


class CascadeSampler:
    """Runs the two-phase cascade for one object.

    Args:
      model / params / cfg: the refine-resolution (served) model — the
        same triple a single-pass :class:`Sampler` takes; ``cfg.model``
        must match ``plan.refine.resolution``.
      plan: the :class:`CascadePlan`.
      mesh: optional MeshEnv, shared by both phases.
      draft_params: optional distilled-student params at the draft
        resolution; ``None`` resolution-adapts the refine params
        (``convert/progressive.py`` — everything but ``pos_emb`` is
        resolution-independent).
    """

    def __init__(self, model: XUNet, params, cfg: Config,
                 plan: CascadePlan, *, mesh=None, draft_params=None):
        # the draft is the same X-UNet at another resolution
        # (adapt_params_resolution): no other denoiser has that
        build_xunet(cfg, "CascadeSampler")
        if (cfg.model.H, cfg.model.W) != (plan.refine.resolution,) * 2:
            raise ValueError(
                f"cfg.model is {cfg.model.H}x{cfg.model.W} but the plan "
                f"refines at {plan.refine.resolution}² — the served "
                "model IS the refine phase")
        self.cfg = cfg
        self.plan = plan
        dr = plan.draft.resolution
        self.draft_cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, H=dr, W=dr))
        if draft_params is None:
            draft_params = adapt_params_resolution(params, (dr, dr))
        self.draft = Sampler(
            build_xunet(self.draft_cfg, "CascadeSampler"), draft_params, self.draft_cfg,
            mesh=mesh, sampler_kind=plan.draft.sampler_kind,
            steps=plan.draft.steps)
        self.refine = Sampler(
            model, params, cfg, mesh=mesh,
            sampler_kind=plan.refine.sampler_kind,
            steps=plan.refine.steps, start_t=plan.refine.start_t)

    @property
    def model_calls_per_view(self) -> int:
        """Draft + refine denoiser invocations per view (the refine
        sampler already subtracts its truncated steps)."""
        return (self.draft.model_calls_per_view
                + self.refine.model_calls_per_view)

    def upsample(self, drafts):
        """Draft views → refine resolution (see :func:`upsample_draft`)."""
        return upsample_draft(drafts, (self.cfg.model.H, self.cfg.model.W))

    def synthesize_draft(self, views: Dict[str, np.ndarray],
                         rng: jax.Array,
                         max_views: Optional[int] = None) -> np.ndarray:
        """The draft pass: downsample the conditioning views and run the
        student.  Returns ``[n_views-1, B, dr, dr, 3]``."""
        return self.draft.synthesize(
            downsample_views(views, self.plan.draft.resolution), rng,
            max_views=max_views)

    def refine_views(self, views: Dict[str, np.ndarray],
                     drafts: Sequence[np.ndarray], rng: jax.Array,
                     max_views: Optional[int] = None) -> np.ndarray:
        """The refine pass: autoregressively re-synthesise views
        ``1..n_views-1`` at full resolution, each view's reverse scan
        entered at ``start_t`` from its (upsampled) draft.

        ``drafts`` is ``[n_views-1, B, h, w, 3]`` at either resolution
        (upsampled here if needed).  The record/RNG contract is exactly
        :meth:`Sampler.synthesize`'s — same per-view key stream, the
        record conditioning on *refined* outputs — so at
        ``start_t = 1.0`` this is bit-identical to the single-pass
        sampler given the same ``rng``.
        """
        imgs = np.asarray(views["imgs"], np.float32)
        R = np.asarray(views["R"], np.float32)
        T = np.asarray(views["T"], np.float32)
        K = np.asarray(views["K"], np.float32)
        n_views = imgs.shape[0] if max_views is None else min(
            imgs.shape[0], max_views)
        B = int(self.refine.w.shape[0])
        H, W = self.cfg.model.H, self.cfg.model.W
        if n_views < 2:
            return np.zeros((0, B, H, W, 3), np.float32)
        if len(drafts) < n_views - 1:
            raise ValueError(
                f"{len(drafts)} drafts for {n_views - 1} refined views")
        drafts_up = np.asarray(self.upsample(np.asarray(drafts)),
                               np.float32)

        record_imgs, record_R, record_T = self.refine._record_init(
            imgs[0], R, T, n_views)
        rec_i, step_d, rng_d = record_imgs, 1, np.asarray(rng)
        for v in range(1, n_views):
            _, rec_i, step_d, rng_d = self.refine.step(
                rec_i, record_R, record_T, step_d, K, rng_d,
                draft=drafts_up[v - 1])
        return np.asarray(jax.block_until_ready(rec_i[1:n_views]))

    def synthesize_cascade(self, views: Dict[str, np.ndarray],
                           rng: jax.Array,
                           max_views: Optional[int] = None) -> dict:
        """The full draft → upsample → refine pipeline for one object.

        Returns ``{"draft": [V, B, dr, dr, 3],
        "refined": [V, B, H, W, 3]}`` (V = n_views - 1).  The parent key
        splits once into the two phase streams.
        """
        k_draft, k_refine = jax.random.split(jnp.asarray(rng))
        drafts = self.synthesize_draft(views, k_draft, max_views=max_views)
        refined = self.refine_views(views, drafts, k_refine,
                                    max_views=max_views)
        return {"draft": drafts, "refined": refined}
