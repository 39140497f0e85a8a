"""A token denoiser: both frames as one sequence of patches through
decoder layers, each a sequence mixer (selected-key attention, attention
over all keys, multi-head latent attention, a Mamba-2 state-space mixer,
a Gated DeltaNet mixer, or gated attention over all keys: the config's
``layer_types``, or the pattern its ``full_attention_interval`` makes)
and a feed-forward (routed experts, a dense gated MLP, or both on one
normed input under one residual add: routed experts beside a shared
expert, which may carry a sigmoid gate of its own).

The second kind of denoiser beside the X-UNet, on the same forward
contract (docs/DESIGN.md §1): batch dict with ``x [B,H,W,3]``,
``z [B,H,W,3]``, ``logsnr [G,2]``, ``R [G,2,3,3]``, ``t [G,2,3]``,
``K [G,3,3]`` (and the optional ``cam_dirs [G,1,H,W,3]``) plus
``cond_mask [G] bool``; returns the predicted noise of the target frame,
``[B, H, W, 3]`` float32.  ``G`` divides ``B`` and example ``b`` reads
conditioning row ``b // (B // G)``: the ray and logSNR embeddings are
computed at ``G`` rows and meet the examples in one broadcast add.

  tokens   ``patch x patch`` patches of the conditioning frame, then of
           the target frame, row-major: ``L = 2 (H / patch) (W / patch)``
           (8192 at 128^2, patch 2); no extra token.
  input    patch projection of the pixels + projection of the patch's
           pixels' ray encoding (``geometry/posenc.py``, as
           ``ConditioningProcessor`` computes it, zero where
           ``cond_mask`` drops it) + an MLP of the frame's logSNR
           sinusoid; the sum times ``embedding_multiplier``.
  layers   pre-norm, ``h += r mixer(norm(h)); h += r ffn(norm(h))`` with
           ``r = residual_multiplier`` (:mod:`.sparse_attention`,
           :mod:`.token_layers`, :mod:`.mamba`, :mod:`.latent_attention`,
           :mod:`.gated_deltanet`; :mod:`.moe`); with both feed-forwards,
           ``ffn(u) = routed(u) + mlp(u)``; with ``sandwich_norm``, each
           half's output normed again before its add; the first
           ``first_k_dense_replace`` layers with a dense MLP for ``ffn``;
           in a ``qwen3_next`` block, every norm but the Gated DeltaNet
           mixer's gated one (and the final norm) scaling by ``1 + w``.
  output   RMSNorm, a linear head to ``patch^2 * 3`` values per target
           token over ``logits_scaling``, un-patchified.

``deterministic`` and ``constrain`` are accepted for the contract's sake:
the model has no dropout, and no activation sharding hook yet.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from diff3d_tpu.config import TokenModelConfig
from diff3d_tpu.geometry import (pinhole_rays_cam, pinhole_rays_world,
                                 posenc_ddpm, posenc_nerf)
from diff3d_tpu.models.conditioning import (DIR_DEG, POS_DEG,
                                            conditioning_rows)
from diff3d_tpu.models.gated_deltanet import GatedDeltaNet
from diff3d_tpu.models.latent_attention import LatentAttention
from diff3d_tpu.models.mamba import Mamba2Mixer
from diff3d_tpu.models.moe import RoutedExperts, rms_norm
from diff3d_tpu.models.sparse_attention import Scale, SparseAttention
from diff3d_tpu.models.token_layers import FullAttention, GatedMLP
from diff3d_tpu.utils.profiling import count, scope


def patchify(img: jnp.ndarray, p: int) -> jnp.ndarray:
    """``[..., H, W, C] -> [..., (H/p)(W/p), p*p*C]``, patches row-major,
    a patch's values in (row, column, channel) order."""
    *lead, H, W, C = img.shape
    x = img.reshape(*lead, H // p, p, W // p, p, C)
    x = jnp.moveaxis(x, -4, -3)                  # [..., H/p, W/p, p, p, C]
    return x.reshape(*lead, (H // p) * (W // p), p * p * C)


def unpatchify(tok: jnp.ndarray, p: int, H: int, W: int) -> jnp.ndarray:
    """Inverse of :func:`patchify`."""
    *lead, _, PC = tok.shape
    C = PC // (p * p)
    x = tok.reshape(*lead, H // p, W // p, p, p, C)
    x = jnp.moveaxis(x, -3, -4)
    return x.reshape(*lead, H, W, C)


class DecoderLayer(nn.Module):
    """Pre-norm, ``h += r mixer(norm(h)); h += r ffn(norm(h))``.  The two
    norms' scales live here, each under its half's name (``attn_norm``
    beside ``attn``, ...); each half applies its norm and its residual add
    itself, example by example and chunk by chunk.  ``kind`` is the
    layer's entry of ``cfg.mixers``; the feed-forward is the routed
    experts where the config has experts, else the dense MLP.  A config
    with both widths (``num_experts`` and ``shared_intermediate_size``)
    has both under the experts' norm and residual add: the MLP is then
    no half of its own (no ``mlp_norm``) but the branch ``moe`` computes
    beside the experts, chunk by chunk, on the same normed tokens.  A
    ``dense`` layer (one of the config's ``first_k_dense_replace``) has
    a dense MLP of ``dense_intermediate_size`` in place of both.  With
    ``sandwich_norm`` each half's output is normed again before its
    residual add, by a scale of its own (``attn_post_norm``, ...)."""

    cfg: TokenModelConfig
    kind: str = "sparse_attention"
    dense: bool = False

    @property
    def halves(self):
        """The names of the layer's two halves in its parameter tree."""
        mixer = {"mamba": "mamba", "linear_attention": "linear_attn"}
        return (mixer.get(self.kind, "attn"),
                "moe" if self.cfg.num_experts and not self.dense else "mlp")

    def setup(self):
        cfg = self.cfg
        pd = jnp.dtype(cfg.param_dtype)
        for half in self.halves:
            setattr(self, half + "_norm", Scale(pd, cfg.qwen3_next))
            if cfg.sandwich_norm:
                setattr(self, half + "_post_norm", Scale(pd))
        common = dict(eps=cfg.rms_norm_eps,
                      residual=cfg.residual_multiplier,
                      dtype=jnp.dtype(cfg.dtype))
        if self.kind == "mamba":
            self.mamba = Mamba2Mixer(
                hidden=cfg.hidden_size, n_heads=cfg.mamba_n_heads,
                d_head=cfg.mamba_d_head, d_state=cfg.mamba_d_state,
                d_conv=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size,
                **common)
        elif self.kind == "linear_attention":
            self.linear_attn = GatedDeltaNet(
                hidden=cfg.hidden_size, key_heads=cfg.linear_num_key_heads,
                value_heads=cfg.linear_num_value_heads,
                key_dim=cfg.linear_key_head_dim,
                value_dim=cfg.linear_value_head_dim,
                conv=cfg.linear_conv_kernel_dim, chunk=cfg.delta_chunk,
                param_dtype=pd, **common)
        elif self.kind in ("attention", "full_attention"):
            gated = (dict(param_dtype=pd, gated=True,
                          rotary_dim=cfg.rotary_dim,
                          rope_theta=cfg.rope_theta)
                     if self.kind == "full_attention" else {})
            self.attn = FullAttention(
                hidden=cfg.hidden_size, num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim, q_chunk=cfg.q_chunk,
                scale=cfg.attention_multiplier, **gated, **common)
        elif self.kind == "latent_attention":
            self.attn = LatentAttention(
                hidden=cfg.hidden_size, num_heads=cfg.num_attention_heads,
                q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                v_head_dim=cfg.v_head_dim, q_chunk=cfg.q_chunk,
                rope_theta=cfg.rope_theta, param_dtype=pd, **common)
        else:
            self.attn = SparseAttention(
                hidden=cfg.hidden_size, num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim,
                indexer_heads=cfg.indexer_num_heads,
                indexer_dim=cfg.indexer_head_dim, topk=cfg.indexer_topk,
                q_chunk=cfg.q_chunk,
                grid=(2, cfg.H // cfg.patch, cfg.W // cfg.patch),
                rope_theta=cfg.rope_theta,
                mrope_section=tuple(cfg.mrope_section), **common)
        if self.halves[1] == "moe":
            self.moe = RoutedExperts(
                num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
                width=cfg.moe_intermediate_size,
                held=tuple(cfg.experts_held),
                token_chunk=cfg.expert_token_chunk, block=cfg.expert_block,
                scoring=cfg.scoring_func,
                routed_scale=cfg.routed_scaling_factor, param_dtype=pd,
                **common)
        width = (cfg.dense_intermediate_size if self.dense
                 else cfg.shared_intermediate_size)
        if width:
            self.mlp = GatedMLP(hidden=cfg.hidden_size, width=width,
                                param_dtype=pd, gated=(
                                    cfg.qwen3_next
                                    and self.halves[1] == "moe"),
                                **common)

    def _norms(self, half: str, D: int):
        """The half's norm scale and its post-norm's (``None`` without
        ``sandwich_norm``)."""
        post = (getattr(self, half + "_post_norm")(D)
                if self.cfg.sandwich_norm else None)
        return getattr(self, half + "_norm")(D), post

    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        mixer = self.halves[0]
        pre, post = self._norms(mixer, h.shape[-1])
        h = getattr(self, mixer)(h, pre, post_scale=post)
        return self.feed_forward(h)

    def feed_forward(self, h: jnp.ndarray) -> jnp.ndarray:
        """The layer's second half, ``h + r ffn(norm(h))``."""
        half = self.halves[1]
        pre, post = self._norms(half, h.shape[-1])
        if half == "mlp":
            return self.mlp(h, pre, post_scale=post)
        beside = (self.mlp.branch() if self.cfg.shared_intermediate_size
                  else None)
        return self.moe(h, pre, beside=beside, post_scale=post)


class TokenDenoiser(nn.Module):
    cfg: TokenModelConfig

    def setup(self):
        cfg = self.cfg
        cfg.validate()
        dtype = jnp.dtype(cfg.dtype)
        pd = jnp.dtype(cfg.param_dtype)
        D = cfg.hidden_size
        self.patch_embed = nn.Dense(D, dtype=dtype, param_dtype=pd)
        self.ray_proj = nn.Dense(D, dtype=dtype, param_dtype=pd)
        self.logsnr_mlp_0 = nn.Dense(D, dtype=dtype, param_dtype=pd)
        self.logsnr_mlp_1 = nn.Dense(D, dtype=dtype, param_dtype=pd)
        self.layers = [DecoderLayer(cfg, kind, cfg.dense_layer(i))
                       for i, kind in enumerate(cfg.mixers)]
        self.final_norm = Scale(pd, cfg.qwen3_next)
        self.head = nn.Dense(cfg.patch * cfg.patch * 3, dtype=dtype,
                             param_dtype=pd,
                             kernel_init=nn.initializers.zeros)

    def embed(self, batch: dict, cond_mask: jnp.ndarray) -> jnp.ndarray:
        """The layers' input, ``[B, L, D]``."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        p = cfg.patch
        B, H, W, _ = batch["x"].shape
        assert (H, W) == (cfg.H, cfg.W), ((H, W), (cfg.H, cfg.W))
        G = conditioning_rows(batch, cond_mask)
        # once per trace, like the compile.* events
        count("conditioning.groups", G)
        count("conditioning.examples", B)
        if cfg.num_experts:
            count("experts.held", cfg.experts_held[1])
            count("experts.of", cfg.num_experts)

        with scope("conditioning"):
            logsnr = jnp.clip(batch["logsnr"], -cfg.logsnr_clip,
                              cfg.logsnr_clip)                    # [G, 2]
            # sinusoids and rays stay float32, as in ConditioningProcessor
            le = posenc_ddpm(logsnr, emb_ch=cfg.emb_ch, max_time=1.0,
                             dtype=jnp.float32)
            le = self.logsnr_mlp_1(nn.silu(self.logsnr_mlp_0(le)))
            cam_dirs = batch.get("cam_dirs")
            if cam_dirs is None:
                cam_dirs = pinhole_rays_cam(
                    batch["K"][:, None].astype(jnp.float32), H, W)
            pos, dirs = pinhole_rays_world(batch["R"].astype(jnp.float32),
                                           batch["t"].astype(jnp.float32),
                                           cam_dirs)
            rays = jnp.concatenate(
                [posenc_nerf(pos, 0, POS_DEG),
                 posenc_nerf(dirs, 0, DIR_DEG)], axis=-1)  # [G,2,H,W,144]
            rays = jnp.where(cond_mask[:, None, None, None, None], rays,
                             jnp.zeros_like(rays))
            cond = self.ray_proj(patchify(rays, p))        # [G,2,L/2,D]
            cond = (cond + le[:, :, None, :]).reshape(G, -1, cond.shape[-1])
        with scope("patch_embed"):
            pix = jnp.stack([batch["x"], batch["z"]], axis=1).astype(dtype)
            h = self.patch_embed(patchify(pix, p))         # [B,2,L/2,D]
            h = h.reshape(G, B // G, -1, h.shape[-1]) + cond[:, None]
            if cfg.embedding_multiplier != 1.0:
                h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
            return h.reshape(B, -1, h.shape[-1])

    def __call__(self, batch: dict, *, cond_mask: jnp.ndarray,
                 deterministic: bool = True, constrain=None) -> jnp.ndarray:
        cfg = self.cfg
        h = self.embed(batch, cond_mask)
        B, L, _ = h.shape
        for layer in self.layers:
            h = layer(h)
        with scope("residual"):
            h = rms_norm(h[:, L // 2:], self.final_norm(h.shape[-1]),
                         cfg.rms_norm_eps)
        with scope("patch_embed"):
            eps = self.head(h)
            if cfg.logits_scaling != 1.0:
                eps = eps / jnp.asarray(cfg.logits_scaling, eps.dtype)
            return unpatchify(eps, cfg.patch, cfg.H, cfg.W).astype(
                jnp.float32)
