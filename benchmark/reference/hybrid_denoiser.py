"""Plain ``jax.numpy`` token denoiser on the hybrid decoder block of
granite-4.0-h-micro (config.json of the public model, ``model_type
granitemoehybrid``): Mamba-2 state-space layers and grouped-query
attention layers without any position, in the order ``layer_types``
gives, each followed by a dense gated MLP, with Granite's four scalars.
Written from the equations of ISSUE 30 / ``configs/
granite4_h_micro_tok128.json``.  Nothing of ``diff3d_tpu`` is imported:
this file is the yardstick the timed path is compared with.  It is
float32 with every contraction at ``Precision.HIGHEST``; ``prec`` rounds
the operands of every contraction as ``reference/xunet.py`` does (the
control).

With ``r = residual_multiplier`` and RMSNorm ``n`` (eps from the config),
every layer is ``h <- h + r mixer(n(h)); h <- h + r mlp(n(h))``:

  mamba      ``[z | xBC | dt] = W_in u``, widths ``d_inner | d_inner + 2 N
             | heads`` (``d_inner = heads x d_head``, ``N = d_state``, one
             group), no bias.  ``xBC <- silu(conv(xBC) + b)``: depthwise,
             causal, ``d_conv`` taps, ``xBC'_t = sum_j w_j * xBC_{t -
             (d_conv - 1) + j}``, zeros before the sequence.  Split ``x``
             (heads x d_head), ``B``, ``C`` (``N`` each, shared by the
             heads).  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
             per head.  Per head, state ``S [d_head, N]``, ``S = 0`` before
             an example's first token: ``S_t = exp(dt_t A) S_{t-1} + dt_t
             x_t B_t^T``, ``y_t = S_t C_t + D x_t`` -- **a sequential
             recurrence over the tokens** (a ``lax.scan`` over ``t``; no
             chunk anywhere), in token order: conditioning frame, then
             target frame.  ``y <- n_g(y * silu(z))`` (RMSNorm over all
             ``d_inner``, weight ``g``), ``W_out y``, no bias.
  attention  ``q = W_q u`` (Hq x d), ``k, v`` (Hkv x d), no bias, no norm
             on q or k, no rotary or other position; scores times
             ``attention_multiplier`` (not ``d^-1/2``); softmax over all
             ``L`` keys (a denoiser is not causal: the stated departure);
             ``W_o``.
  mlp        ``[a | b] = W_1 u``, ``W_2 (silu(a) * b)``, no bias.

Around the layers, as ``reference/token_denoiser.py`` (whose ``embed``,
patch layout and key stream are imported, not repeated): the embedding's
sum times ``embedding_multiplier``; final RMSNorm and a linear head on the
target frame's tokens, over ``logits_scaling``.

The recurrence runs the examples of a call side by side, ``SIDE_BY_SIDE``
at a time (one step then moves that many ``[heads, d_head, N]`` states;
one example at a time would take as many steps again for each), and every
other part example by example, so that the full size fits one chip.
``state_reset_every`` (a key no configuration file has) zeroes the state
every that many tokens: the planted fault of a chunked scan that loses its
carry.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from . import diffusion as rd
from .token_denoiser import (_Params, _dummy_batch, embed, first_batch, mm,
                             rms_norm, tokens_of, unpatchify)
from .xunet import _round, silu

_HI = jax.lax.Precision.HIGHEST
MODEL_KEYS = ("H", "W", "patch", "hidden_size", "num_hidden_layers",
              "layer_types", "num_attention_heads", "num_key_value_heads",
              "rms_norm_eps", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_d_conv", "mamba_expand",
              "mamba_n_groups", "mamba_chunk_size",
              "shared_intermediate_size", "embedding_multiplier",
              "residual_multiplier", "attention_multiplier",
              "logits_scaling", "emb_ch", "logsnr_clip")
SIDE_BY_SIDE = 4    # examples whose recurrences share a scan step


def model_dict(config: dict) -> dict:
    """The reference's view of a ``benchmark/configs`` file of this
    model: the published keys by their published names, the denoiser's
    own, and ``head_dim`` (the config has none: hidden over heads)."""
    missing = [k for k in MODEL_KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {k: config[k] for k in MODEL_KEYS}
    m["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    if len(m["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    if config.get("num_local_experts") or m["mamba_n_groups"] != 1:
        raise ValueError("this reference has no routed experts and one "
                         "group of B and C")
    return m


# ----------------------------------------------------------------- layers

def mamba_mixer(u, LP, cfg: dict, prec):
    """``u [B, L, D]`` (normed) of ``B`` examples side by side ->
    ``[B, L, D]``."""
    Bn, L, _ = u.shape
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    K, di = cfg["mamba_d_conv"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    reset = cfg.get("state_reset_every", 0)
    z, xBC, dt = jnp.split(mm(u, LP("mamba/in_proj/kernel"), prec),
                           [di, 2 * di + 2 * N], axis=-1)
    taps = LP("mamba/conv/kernel")
    xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    xBC = silu(LP("mamba/conv/bias")
               + sum(taps[j] * xp[:, j:j + L] for j in range(K)))
    x, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt + LP("mamba/dt_bias"))            # [B, L, H]
    A = -jnp.exp(LP("mamba/A_log"))
    Dskip = LP("mamba/D")

    def step(S, inp):
        t, xt, dtt, Bt, Ct = inp          # [B,H,P], [B,H], [B,N], [B,N]
        if reset:
            S = jnp.where(t % reset == 0, 0.0, S)
        fed = (dtt[..., None] * _round(xt, prec))[..., None] \
            * _round(Bt, prec)[:, None, None, :]
        S = jnp.exp(dtt * A)[..., None, None] * S + fed
        y = (_round(S, prec) * _round(Ct, prec)[:, None, None, :]).sum(-1)
        return S, y + Dskip[:, None] * xt

    by_t = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, y = jax.lax.scan(
        step, jnp.zeros((Bn, H, P, N), jnp.float32),
        (jnp.arange(L), by_t(x.reshape(Bn, L, H, P)), by_t(dt), by_t(Bm),
         by_t(Cm)))
    y = by_t(y).reshape(Bn, L, di) * silu(z)
    y = rms_norm(y, LP("mamba/norm/scale"), cfg["rms_norm_eps"])
    return mm(y, LP("mamba/out_proj/kernel"), prec)


def attention(u, LP, cfg: dict, prec):
    """``u [L, D]`` (normed) of one example -> ``[L, D]``, one head's
    ``[L, L]`` scores at a time."""
    L = u.shape[0]
    Hq, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(u, LP("attn/q_proj/kernel"), prec).reshape(L, Hq, d)
    k = mm(u, LP("attn/k_proj/kernel"), prec).reshape(L, Hkv, d)
    v = mm(u, LP("attn/v_proj/kernel"), prec).reshape(L, Hkv, d)
    group = Hq // Hkv

    def head(h):
        g = h // group
        s = jnp.einsum("td,sd->ts", _round(q[:, h], prec),
                       _round(k[:, g], prec), precision=_HI,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(s * cfg["attention_multiplier"], axis=-1)
        return jnp.einsum("ts,sd->td", _round(p, prec),
                          _round(v[:, g], prec), precision=_HI,
                          preferred_element_type=jnp.float32)

    out = jnp.swapaxes(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
    return mm(out.reshape(L, Hq * d), LP("attn/o_proj/kernel"), prec)


def mlp(u, LP, prec):
    a, b = jnp.split(mm(u, LP("mlp/w1/kernel"), prec), 2, axis=-1)
    return mm(silu(a) * b, LP("mlp/w2/kernel"), prec)


# ---------------------------------------------------------------- forward

def _layer_params(P, i: int, kind: str, cfg: dict):
    """Declares layer ``i``'s leaves (so that spec mode sees them) and
    returns the lookup by the name inside the layer."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, N = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    di, F = H * cfg["mamba_d_head"], cfg["shared_intermediate_size"]
    if kind == "mamba":
        spec = {
            "mamba_norm/scale": ((D,), "one"),
            "mamba/in_proj/kernel": ((D, 2 * di + 2 * N + H), "dense"),
            "mamba/conv/kernel": ((cfg["mamba_d_conv"], di + 2 * N),
                                  "dense"),
            "mamba/conv/bias": ((di + 2 * N,), "bias"),
            "mamba/dt_bias": ((H,), "dt_bias"),
            "mamba/A_log": ((H,), "a_log"),
            "mamba/D": ((H,), "one"),
            "mamba/norm/scale": ((di,), "one"),
            "mamba/out_proj/kernel": ((di, D), "dense"),
        }
    elif kind == "attention":
        spec = {
            "attn_norm/scale": ((D,), "one"),
            "attn/q_proj/kernel": ((D, Hq * d), "dense"),
            "attn/k_proj/kernel": ((D, Hkv * d), "dense"),
            "attn/v_proj/kernel": ((D, Hkv * d), "dense"),
            "attn/o_proj/kernel": ((Hq * d, D), "dense"),
        }
    else:
        raise ValueError(f"layer {i}: no layer of type {kind!r}")
    spec.update({"mlp_norm/scale": ((D,), "one"),
                 "mlp/w1/kernel": ((D, 2 * F), "dense"),
                 "mlp/w2/kernel": ((F, D), "dense")})
    got = {name: P(f"layers_{i}/{name}", shape, k)
           for name, (shape, k) in spec.items()}
    return got.__getitem__


def forward(params, batch: dict, cond_mask, cfg: dict, *, prec="float32",
            _p=None):
    """Predicted noise of the target frame ``[B, H, W, 3]``; ``batch``
    and ``cond_mask`` as ``reference/token_denoiser.py`` documents
    them."""
    P = _p or _Params(params)
    H, W, p, D = cfg["H"], cfg["W"], cfg["patch"], cfg["hidden_size"]
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = embed(P, batch, cond_mask, cfg, prec) * cfg["embedding_multiplier"]
    B, L, _ = h.shape
    side = math.gcd(B, SIDE_BY_SIDE)
    for i, kind in enumerate(cfg["layer_types"]):
        LP = _layer_params(P, i, kind, cfg)
        if kind == "mamba":
            def some(hs, LP=LP):
                u = rms_norm(hs, LP("mamba_norm/scale"), eps)
                return hs + r * mamba_mixer(u, LP, cfg, prec)
            h = jax.lax.map(some, h.reshape(B // side, side, L, D)
                            ).reshape(B, L, D)
        else:
            def one(hb, LP=LP):
                u = rms_norm(hb, LP("attn_norm/scale"), eps)
                return hb + r * attention(u, LP, cfg, prec)
            h = jax.lax.map(one, h)

        def ffn(hb, LP=LP):
            return hb + r * mlp(rms_norm(hb, LP("mlp_norm/scale"), eps),
                                LP, prec)
        h = jax.lax.map(ffn, h)
    h = rms_norm(h[:, L // 2:], P("final_norm/scale", (D,), "one"), eps)
    out = mm(h, P("head/kernel", (D, p * p * 3), "dense_zero"),
             prec) + P("head/bias", (p * p * 3,), "bias")
    return unpatchify(out / cfg["logits_scaling"], p, H, W)


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """``{path: (shape, kind)}`` of every parameter, in forward order."""
    rec = _Params(None)
    jax.eval_shape(lambda: forward(None, _dummy_batch(cfg),
                                   jnp.ones((1,), bool), cfg, _p=rec))
    return dict(rec.shapes)


def _place(key, shape, kind: str, zero_gain: float):
    """One leaf from its key.  Matrices N(0, 1/fan_in) (the conv's taps
    have fan-in ``d_conv``: N(0, 1/4)); the head, which the program
    initialises to zero, at ``zero_gain`` of that; biases N(0, 0.1^2);
    norm weights and ``D`` 1; ``A_log = log(1 .. heads)`` (the published
    model's initialiser); ``dt_bias`` the inverse softplus of a
    log-uniform draw in [1e-3, 1e-1].  Per-token decays ``exp(dt A)``
    then run from about 0.9999 to about 0.002, so state crosses chunk and
    frame boundaries and also dies inside a chunk."""
    if kind in ("dense", "dense_zero"):
        gain = zero_gain if kind == "dense_zero" else 1.0
        return jax.random.normal(key, shape, jnp.float32) * (
            gain / math.sqrt(shape[-2]))
    if kind == "bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape) * math.log(100.0)
                     + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def _outer_gain(name: str, cfg: dict) -> float:
    """What the two outer scalars are there to undo.  The published
    model's embedding is small and ``embedding_multiplier`` brings it to
    the layers' scale: N(0, 1/fan_in) projections times 12 would make
    the residual stream 95% embedding and leave the layers, at 0.22 of
    their output each, next to nothing of the answer.  So the embedding's
    last projections stand at ``1 / embedding_multiplier`` of
    :func:`_place`'s draw, and the head at ``logits_scaling`` times it,
    which leaves the predicted noise the size it has in
    ``reference/token_denoiser.py`` (0.3 of unit variance)."""
    top = name.split("/")[0]
    if top in ("patch_embed", "ray_proj", "logsnr_mlp_1"):
        return 1.0 / cfg["embedding_multiplier"]
    return float(cfg["logits_scaling"]) if top == "head" else 1.0


def make_params(cfg: dict, key, *, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """() -> the seeded float32 parameters (:func:`_place`,
    :func:`_outer_gain`), made on the default device: one compiled
    generator per distinct layer, one draw per leaf."""
    shapes = param_shapes(cfg)
    groups: Dict[str, list] = {}
    for name in shapes:
        head = name.split("/")[0]
        groups.setdefault(head if head.startswith("layers_") else "",
                          []).append(name)
    jitted: Dict[tuple, Callable] = {}

    def all_params():
        out = {}
        for g, (head, members) in enumerate(groups.items()):
            local = tuple((n[len(head) + 1:] if head else n, *shapes[n])
                          for n in members)
            if local not in jitted:
                jitted[local] = jax.jit(lambda k, local=local: {
                    n: _place(jax.random.fold_in(k, j), s, kind, zero_gain)
                    for j, (n, s, kind) in enumerate(local)})
            for n, v in jitted[local](jax.random.fold_in(key, g)).items():
                out[f"{head}/{n}" if head else n] = (
                    v if head else v * _outer_gain(n, cfg))
        return out

    return all_params


# --------------------------------------------------------------- sampling

def synthesize_view(params, record_imgs, record_R, record_T, record_len,
                    K, key, mcfg: dict, dcfg: dict, *, steps: int,
                    kind: str = "ddim", prec="float32"):
    """One novel view of one object for every guidance weight, by this
    model, as ``reference/token_denoiser.py synthesize_view`` does it:
    ``reference/diffusion.py``'s key stream, schedule, guidance and
    reverse step, the model call at two conditioning rows.  Returns the
    ``[B, H, W, 3]`` view and the object's next key."""
    w = jnp.asarray(dcfg["guidance_weights"], jnp.float32)
    B = w.shape[0]
    H, W = mcfg["H"], mcfg["W"]
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    T = dcfg["timesteps"]
    ts = jnp.linspace(1.0, 0.0, T + 1)[::T // steps]
    logsnrs = rd.logsnr_cosine(ts[:-1], lo, hi)
    logsnr_nexts = rd.logsnr_cosine(ts[1:], lo, hi)

    next_key, k = jax.random.split(key)
    carry_key, k_init, k_idx = jax.random.split(k, 3)
    z0 = jax.random.normal(k_init, (B, H, W, 3))
    idx = jax.random.randint(k_idx, (steps,), 0, record_len)
    tgt_R, tgt_T = record_R[record_len], record_T[record_len]
    mask = jnp.array([True, False])

    def step(carry, xs):
        z, ck = carry
        logsnr, logsnr_next, i = xs
        ck, k_x, k_noise = jax.random.split(ck, 3)
        batch = first_batch(record_imgs[i], z, logsnr, hi,
                            jnp.stack([record_R[i], tgt_R]),
                            jnp.stack([record_T[i], tgt_T]), K, k_x)
        eps = forward(params, batch, mask, mcfg, prec=prec)
        eps = rd.guided_eps(eps[:B], eps[B:], w)
        noise = jax.random.normal(k_noise, z.shape, jnp.float32)
        z = rd.reverse_step(eps, z, logsnr, logsnr_next, noise, kind,
                            dcfg["clip_x0"])
        return (z, ck), None

    (z, _), _ = jax.lax.scan(step, (z0, carry_key),
                             (logsnrs, logsnr_nexts, idx))
    return z, next_key


__all__ = ["model_dict", "tokens_of", "forward", "param_shapes",
           "make_params", "synthesize_view", "mamba_mixer", "attention",
           "mlp"]
