"""Plain ``jax.numpy`` token denoiser on the decoder block of
openPangu-Ultra-MoE-718B (config.json of the public model, ``model_type
pangu_ultra_moe``): multi-head latent attention under sandwich norms, a
leading dense layer, then layers of sigmoid-routed experts beside a shared
expert.  Written from the equations in
``configs/pangu_ultra_moe_tok128.json``.  Nothing of ``diff3d_tpu`` is
imported: this file is the yardstick the timed path is compared with.
Float32, every contraction at ``Precision.HIGHEST``; ``prec`` rounds the
operands of every contraction as ``reference/xunet.py`` does (the
control).  The parameters may be stored in bfloat16: every leaf is read
in float32 (exact) where a layer uses it.

With RMSNorm ``n`` (a weight each, eps from the config) and no bias in
the layers, every layer is::

    h <- h + n_post_attn(mla(n_attn(h)))
    h <- h + n_post_ffn(ffn(n_ffn(h)))

(without ``sandwich_norm``, the two post-norms are left out)::

    mla      c_q = n_qa(W_qa u); [q_nope | q_pe]_h = (W_qb c_q)_h
             [c_kv | k_pe] = W_kva u; [k_nope | v]_h = (W_kvb n_kva(c_kv))_h
             q_pe of every head and the one k_pe turned by the token's
             index t in the sequence: pair i = (x_i, x_{i+d/2}) by
             t * theta^(-2i/d), d = qk_rope_head_dim
             per head: s = (q_nope_h . k_nope_h + q_pe_h . k_pe) /
             (qk_nope + qk_rope)^1/2 over all L keys, softmax, times v_h
             W_o [o_1 ... o_H]
    ffn      the first ``first_k_dense_replace`` layers:
             W_2 (silu(a) * b), [a | b] = W_1 u, width intermediate_size
             the others: logits W_r u over all experts; s = sigmoid;
             the k largest s; g = routed_scaling_factor * s_top / sum s_top;
             sum over the held experts among the token's k of
             g_e W_down_e (silu(W_gate_e u) * W_up_e u), plus the shared
             expert (width n_shared_experts * moe_intermediate_size) once

Departures from the published model: a **share of the experts**
(``experts_held = [first, count]``: the router scores all experts, the
sum runs over the held ones alone, what the absent ones would add is
left out and nothing stands in for it, before the post-norm); the first
pipeline stage's layers alone; no multi-token-prediction module;
bidirectional attention; the embedding and head of a denoiser
(``reference/token_denoiser.py embed``; no embedding multiplier).

The routed sum is written twice, as ``reference/hybrid_moe_denoiser.py``
writes it: literally (``literal=True``, every held expert on every token)
and by windows of the tokens routed to each held expert.
``tests/test_mla_moe_denoiser.py`` holds the two to each other.

A view is synthesised in blocks (:func:`make_view_fn`): the reverse steps
a Python loop, ``BLOCK`` examples at a time, and each block through one
compiled program per piece (the embedding, a dense layer, an expert
layer, the head), so that a layer's matrices exist in float32 only while
that layer runs.

Two keys no configuration file has plant a fault: ``post_norms_dropped``
builds the layer without its sandwich norms (pre-norm only), and
``experts_dropped`` zeroes the held experts' sum.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import diffusion as rd
from . import hybrid_denoiser as rh
from .token_denoiser import (_Params, _dummy_batch, embed, first_batch, mm,
                             rms_norm, tokens_of, unpatchify)
from .xunet import _round, silu

_HI = jax.lax.Precision.HIGHEST
MODEL_KEYS = ("H", "W", "patch", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rope_theta", "rms_norm_eps", "first_k_dense_replace",
              "moe_intermediate_size", "num_experts_per_tok",
              "routed_scaling_factor", "sandwich_norm", "scoring_func",
              "emb_ch", "logsnr_clip")
BLOCK = 2           # examples of one compiled piece of a view (full size)
WINDOW = 1024       # rows of one expert computed at a time (full size)


def model_dict(config: dict) -> dict:
    """The reference's view of a ``benchmark/configs`` file of this
    model.  The file keeps the published names: ``n_routed_experts`` is
    the count **held here** (the guide's rule for a share) and
    ``published.n_routed_experts`` the router's width;
    ``intermediate_size`` is the dense layers' width and the shared
    expert is ``n_shared_experts * moe_intermediate_size`` wide."""
    missing = [k for k in MODEL_KEYS + ("intermediate_size",
                                        "n_routed_experts",
                                        "n_shared_experts",
                                        "norm_topk_prob", "experts_held")
               if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {k: config[k] for k in MODEL_KEYS}
    m["num_experts"] = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    m["experts_held"] = list(config["experts_held"])
    m["dense_intermediate_size"] = config["intermediate_size"]
    m["shared_intermediate_size"] = (config["n_shared_experts"]
                                     * config["moe_intermediate_size"])
    m["param_dtype"] = config.get("param_dtype", "float32")
    first, held = m["experts_held"]
    if (held != config["n_routed_experts"] or first < 0 or held < 1
            or first + held > m["num_experts"]):
        raise ValueError(
            f"experts_held={m['experts_held']} must be a range of the "
            f"{m['num_experts']} experts, n_routed_experts="
            f"{config['n_routed_experts']} of them")
    if not (config["norm_topk_prob"] and m["scoring_func"] == "sigmoid"
            and config["n_shared_experts"] >= 1
            and 0 <= m["first_k_dense_replace"] < m["num_hidden_layers"]):
        raise ValueError("this reference has sigmoid gates normalised over "
                         "the top-k, a shared expert and expert layers")
    return m


# ----------------------------------------------------------------- layers

def rope(x, cfg: dict):
    """``x [L, heads, d]`` turned by the token's index as the module
    docstring says (angles in float64)."""
    L, _, d = x.shape
    half = d // 2
    freq = float(cfg["rope_theta"]) ** (-2.0 * np.arange(half) / d)
    ang = np.arange(L)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, LP, cfg: dict, prec):
    """``u [L, D]`` (normed) of one example -> ``[L, D]``, one head's
    ``[L, L]`` scores at a time."""
    L = u.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    cq = rms_norm(mm(u, LP("attn/q_a_proj/kernel"), prec),
                  LP("attn/q_a_norm/scale"), eps)
    q = mm(cq, LP("attn/q_b_proj/kernel"), prec).reshape(L, H, dn + dr)
    ckv = mm(u, LP("attn/kv_a_proj/kernel"), prec)
    kv = mm(rms_norm(ckv[:, :kvr], LP("attn/kv_a_norm/scale"), eps),
            LP("attn/kv_b_proj/kernel"), prec).reshape(L, H, dn + dv)
    q_pe = rope(q[..., dn:], cfg)
    k_pe = rope(ckv[:, None, kvr:], cfg)[:, 0]           # all heads share

    def dot(a, b):
        return jnp.einsum("td,sd->ts", _round(a, prec), _round(b, prec),
                          precision=_HI, preferred_element_type=jnp.float32)

    def head(h):
        s = (dot(q[:, h, :dn], kv[:, h, :dn]) + dot(q_pe[:, h], k_pe)) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ts,sd->td", _round(p, prec),
                          _round(kv[:, h, dn:], prec), precision=_HI,
                          preferred_element_type=jnp.float32)

    out = jnp.swapaxes(jax.lax.map(head, jnp.arange(H)), 0, 1)
    return mm(out.reshape(L, H * dv), LP("attn/o_proj/kernel"), prec)


def routing(u, LP, cfg: dict, prec):
    """(ids ``[T, k]`` over all experts, gates ``[T, k]``): the ``k``
    largest sigmoid scores, normalised over those ``k``, times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(mm(u, LP("moe/router"), prec))
    top, ids = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    return ids, (cfg["routed_scaling_factor"] * top
                 / top.sum(axis=-1, keepdims=True))


def routed_share(u, LP, cfg: dict, prec, literal=False):
    """``u [T, D]`` (normed) -> (the held experts' gated sum ``[T, D]``,
    rows routed to each held expert ``[held]``)."""
    T, D = u.shape
    first, held = cfg["experts_held"]
    ids, gates = routing(u, LP, cfg, prec)
    load = (ids[..., None] == first + jnp.arange(held)).sum(axis=(0, 1))
    wg, wu, wd = LP("moe/w_gate"), LP("moe/w_up"), LP("moe/w_down")

    def ffn(x, e):
        return mm(silu(mm(x, wg[e], prec)) * mm(x, wu[e], prec), wd[e],
                  prec)

    if literal:
        out = jnp.zeros((T, D), jnp.float32)
        for e in range(held):
            gate = jnp.where(ids == first + e, gates, 0.0).sum(axis=-1)
            out = out + gate[:, None] * ffn(u, e)
        return out, load

    # every (token, slot) assignment sorted by expert: a held expert's
    # tokens are one run, computed WINDOW rows at a time
    K = ids.shape[1]
    flat = ids.reshape(T * K)
    order = jnp.argsort(flat, stable=True)
    start = jnp.searchsorted(flat[order], first + jnp.arange(held + 1))

    def one_expert(e, out):
        n = start[e + 1] - start[e]

        def one_window(state):
            j, out = state
            at = start[e] + j * WINDOW + jnp.arange(WINDOW)
            live = at < start[e + 1]
            a = order[jnp.minimum(at, T * K - 1)]
            g = jnp.where(live, gates.reshape(T * K)[a], 0.0)
            return j + 1, out.at[a // K].add(ffn(u[a // K], e) * g[:, None])

        return jax.lax.while_loop(lambda s: s[0] * WINDOW < n, one_window,
                                  (jnp.int32(0), out))[1]

    out = jax.lax.fori_loop(0, held, one_expert,
                            jnp.zeros((T, D), jnp.float32))
    return out, load


def _post(y, LP, half: str, cfg: dict):
    if not cfg["sandwich_norm"] or cfg.get("post_norms_dropped"):
        return y
    return rms_norm(y, LP(f"{half}_post_norm/scale"), cfg["rms_norm_eps"])


def attention_half(hb, LP, cfg: dict, prec):
    """``hb [L, D]`` of one example -> ``hb + post(mla(n(hb)))``."""
    u = rms_norm(hb, LP("attn_norm/scale"), cfg["rms_norm_eps"])
    return hb + _post(attention(u, LP, cfg, prec), LP, "attn", cfg)


def ffn_half(hb, LP, cfg: dict, prec, dense: bool, literal=False):
    """``hb [L, D]`` of one example -> (``hb + post(ffn(n(hb)))``, rows
    routed to each held expert ``[held]``, zeros in a dense layer)."""
    held = cfg["experts_held"][1]
    if dense:
        u = rms_norm(hb, LP("mlp_norm/scale"), cfg["rms_norm_eps"])
        y = _post(rh.mlp(u, LP, prec), LP, "mlp", cfg)
        return hb + y, jnp.zeros((held,), jnp.int32)
    u = rms_norm(hb, LP("moe_norm/scale"), cfg["rms_norm_eps"])
    y, load = routed_share(u, LP, cfg, prec, literal)
    if cfg.get("experts_dropped"):
        y = jnp.zeros_like(y)
    y = y + rh.mlp(u, LP, prec)
    return hb + _post(y, LP, "moe", cfg), load


def layer(h, LP, cfg: dict, prec, dense: bool, literal=False):
    """``h [B, L, D]`` -> (the layer's output, rows routed to each held
    expert over the ``B`` examples), one example at a time."""
    def one(hb):
        return ffn_half(attention_half(hb, LP, cfg, prec), LP, cfg, prec,
                        dense, literal)

    h, load = jax.lax.map(one, h)
    return h, load.sum(axis=0)


# ---------------------------------------------------------------- forward

def _layer_params(P, i: int, cfg: dict):
    """Declares layer ``i``'s leaves and returns the lookup by the name
    inside the layer."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    spec = {
        "attn_norm/scale": ((D,), "one"),
        "attn/q_a_proj/kernel": ((D, qr), "dense"),
        "attn/q_a_norm/scale": ((qr,), "one"),
        "attn/q_b_proj/kernel": ((qr, H * (dn + dr)), "dense"),
        "attn/kv_a_proj/kernel": ((D, kvr + dr), "dense"),
        "attn/kv_a_norm/scale": ((kvr,), "one"),
        "attn/kv_b_proj/kernel": ((kvr, H * (dn + dv)), "dense"),
        "attn/o_proj/kernel": ((H * dv, D), "dense"),
    }
    if i < cfg["first_k_dense_replace"]:
        halves, F = ("attn", "mlp"), cfg["dense_intermediate_size"]
    else:
        halves, F = ("attn", "moe"), cfg["shared_intermediate_size"]
        E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        held = cfg["experts_held"][1]
        spec.update({"moe/router": ((D, E), "dense"),
                     "moe/w_gate": ((held, D, Fe), "dense"),
                     "moe/w_up": ((held, D, Fe), "dense"),
                     "moe/w_down": ((held, Fe, D), "expert_down")})
    spec.update({f"{halves[1]}_norm/scale": ((D,), "one"),
                 "mlp/w1/kernel": ((D, 2 * F), "dense"),
                 "mlp/w2/kernel": ((F, D), "dense")})
    if cfg["sandwich_norm"]:
        spec.update({f"{h}_post_norm/scale": ((D,), "one") for h in halves})
    got = {name: P(f"layers_{i}/{name}", shape, k)
           for name, (shape, k) in spec.items()}
    return got.__getitem__


def _head(P, h, cfg: dict, prec):
    """Final RMSNorm and the linear head on the target frame's tokens."""
    D, p = cfg["hidden_size"], cfg["patch"]
    L = h.shape[1]
    h = rms_norm(h[:, L // 2:], P("final_norm/scale", (D,), "one"),
                 cfg["rms_norm_eps"])
    out = mm(h, P("head/kernel", (D, p * p * 3), "dense_zero"),
             prec) + P("head/bias", (p * p * 3,), "bias")
    return unpatchify(out, p, cfg["H"], cfg["W"])


class _Float32(_Params):
    """``_Params`` that reads every leaf in float32."""

    def __call__(self, path: str, shape, kind: str):
        return super().__call__(path, shape, kind).astype(jnp.float32)


def forward(params, batch: dict, cond_mask, cfg: dict, *, prec="float32",
            literal=False, _p=None):
    """Predicted noise of the target frame ``[B, H, W, 3]`` and, per
    layer, the rows routed to each held expert ``[layers, held]`` (all
    examples of the call; zeros in a dense layer); ``batch`` and
    ``cond_mask`` as ``reference/token_denoiser.py`` documents them."""
    P = _p or _Float32(params)
    h = embed(P, batch, cond_mask, cfg, prec)
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        h, load = layer(h, _layer_params(P, i, cfg), cfg, prec,
                        i < cfg["first_k_dense_replace"], literal)
        loads.append(load)
    return _head(P, h, cfg, prec), jnp.stack(loads)


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """``{path: (shape, kind)}`` of every parameter, in forward order."""
    rec = _Params(None)
    jax.eval_shape(lambda: forward(None, _dummy_batch(cfg),
                                   jnp.ones((1,), bool), cfg, literal=True,
                                   _p=rec))
    return dict(rec.shapes)


def _expert_gain(cfg: dict) -> float:
    """The gain of the held experts' ``w_down``: ``(num_experts /
    held)^1/2`` (5.66 at 8 of 256), as ``reference/hybrid_moe_denoiser.py
    _expert_gain`` sets it and for its reason: a token's ``k`` gates sum
    to ``routed_scaling_factor`` and only ``held / num_experts`` of its
    assignments land here, so at N(0, 1/fan_in) the held share is a small
    part of the layer's output and leaving it out (the planted fault
    ``experts_dropped``) moves the answer less than the control does.  At
    this gain the share's variance is what all ``k`` experts of a token
    would add.  Nothing but the weights' scale: the program and the
    reference compute the same held share."""
    return math.sqrt(cfg["num_experts"] / cfg["experts_held"][1])


def make_params(cfg: dict, key, *, dtype=jnp.float32, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """() -> the seeded parameters in ``dtype``, made on the default
    device: matrices N(0, 1/fan_in) (an expert stack ``[E, in, out]`` has
    fan-in ``in``), the head at ``zero_gain`` of that, biases N(0, 0.1^2),
    norm weights 1 (``reference/hybrid_denoiser.py _place``), the held
    experts' ``w_down`` times :func:`_expert_gain`; each leaf drawn in
    float32 and rounded to ``dtype``: one compiled generator per distinct
    layer, one draw per leaf."""
    shapes = param_shapes(cfg)
    gain = _expert_gain(cfg)

    def leaf(k, shape, kind):
        if kind == "expert_down":
            w = gain * rh._place(k, shape, "dense", zero_gain)
        else:
            w = rh._place(k, shape, kind, zero_gain)
        return w.astype(dtype)

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
                    n: leaf(jax.random.fold_in(k, j), s, kind)
                    for j, (n, s, kind) in enumerate(local)})
            for n, v in jitted[local](jax.random.fold_in(key, g)).items():
                out[f"{head}/{n}" if head else n] = v
        return out

    return all_params


# --------------------------------------------------------------- sampling

def make_view_fn(mcfg: dict, dcfg: dict, *, steps: int, kind: str = "ddim",
                 literal=False) -> Callable:
    """``view(params, record_imgs, record_R, record_T, record_len, K, key,
    prec) -> (image [B, H, W, 3], the object's next key, load)``: one novel
    view of one object for every guidance weight, as
    ``reference/hybrid_moe_denoiser.py make_view_fn`` computes it (the key
    stream, schedule, guidance and reverse step of
    ``reference/diffusion.py``; the model call at two conditioning rows),
    **in blocks**: the reverse steps a Python loop, a step's ``2 B``
    examples ``BLOCK`` at a time (those of one conditioning row together),
    and a block through one compiled program per piece -- the embedding,
    a dense layer, an expert layer (one program for all of them), the
    head -- each given only its own leaves, so that a layer's leaves are
    read in float32 only while it runs (one program over all layers would
    hold every layer's at once beside the stored ones).  ``prec`` is a
    name (``"float32"``, or ``"fp8"`` for the control), static: a
    mantissa width traced through one program would make every layer's
    rounded matrices beside its float32 ones, 5 GB at the dense layer.
    ``record_len`` is a Python int.  ``load``: the held experts' load over the steps' model
    calls (most rows any held expert got in one layer of one call, mean
    rows a held expert got a layer and call, share of all ``T k``
    assignments of the expert layers that landed on a held expert)."""
    w = jnp.asarray(dcfg["guidance_weights"], jnp.float32)
    B = w.shape[0]
    H, W = mcfg["H"], mcfg["W"]
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    T = dcfg["timesteps"]
    n_dense = mcfg["first_k_dense_replace"]
    if B % BLOCK:
        raise ValueError(f"{B} guidance weights are not blocks of {BLOCK}")

    @jax.jit
    def start(key, record_len):
        next_key, k = jax.random.split(key)
        carry_key, k_init, k_idx = jax.random.split(k, 3)
        return (next_key, carry_key, jax.random.normal(k_init, (B, H, W, 3)),
                jax.random.randint(k_idx, (steps,), 0, record_len))

    @jax.jit
    def model_call(ck, cond, z, logsnr, R, t, K):
        ck, k_x, k_noise = jax.random.split(ck, 3)
        return ck, k_noise, first_batch(cond, z, logsnr, hi, R, t, K, k_x)

    static = functools.partial(jax.jit, static_argnames="prec")

    @static
    def embed_block(outer, batch, cond_mask, prec):
        return embed(_Float32(outer), batch, cond_mask, mcfg, prec)

    def layer_block(dense):
        @static
        def run(mine, h, prec):
            LP = _layer_params(_Float32(mine), 0, dict(
                mcfg, first_k_dense_replace=int(dense)))
            return layer(h, LP, mcfg, prec, dense, literal)
        return run

    blocks = {True: layer_block(True), False: layer_block(False)}

    @static
    def head_block(outer, h, prec):
        return _head(_Float32(outer), h, mcfg, prec)

    @jax.jit
    def finish(eps, z, logsnr, logsnr_next, k_noise):
        eps = rd.guided_eps(eps[:B], eps[B:], w)
        noise = jax.random.normal(k_noise, z.shape, jnp.float32)
        return rd.reverse_step(eps, z, logsnr, logsnr_next, noise, kind,
                               dcfg["clip_x0"])

    def view(params, record_imgs, record_R, record_T, record_len: int, K,
             key, prec="float32"):
        outer = {n: v for n, v in params.items()
                 if not n.startswith("layers_")}
        mine = [{n[len(f"layers_{i}/"):]: v for n, v in params.items()
                 if n.startswith(f"layers_{i}/")}
                for i in range(mcfg["num_hidden_layers"])]
        mine = [{f"layers_0/{n}": v for n, v in m.items()} for m in mine]
        ts = jnp.linspace(1.0, 0.0, T + 1)[::T // steps]
        logsnrs = rd.logsnr_cosine(ts[:-1], lo, hi)
        logsnr_nexts = rd.logsnr_cosine(ts[1:], lo, hi)
        next_key, ck, z, idx = start(key, record_len)
        mask = jnp.array([True, False])
        loads = []
        for s in range(steps):
            i = int(idx[s])
            ck, k_noise, batch = model_call(
                ck, record_imgs[i], z, logsnrs[s],
                jnp.stack([record_R[i], record_R[record_len]]),
                jnp.stack([record_T[i], record_T[record_len]]), K)
            eps, load = [], []
            for g in range(2):                   # a conditioning row
                row = {n: batch[n][g:g + 1] for n in ("logsnr", "R", "t",
                                                      "K")}
                for b in range(g * B, (g + 1) * B, BLOCK):
                    part = dict(row, x=batch["x"][b:b + BLOCK],
                                z=batch["z"][b:b + BLOCK])
                    h = embed_block(outer, part, mask[g:g + 1], prec)
                    for j, m in enumerate(mine):
                        h, rows = blocks[j < n_dense](m, h, prec)
                        if j >= n_dense:
                            load.append(rows)
                    eps.append(head_block(outer, h, prec))
            loads.append(jnp.stack(load).reshape(
                -1, len(mine) - n_dense, load[0].shape[0]).sum(0))
            z = finish(jnp.concatenate(eps), z, logsnrs[s], logsnr_nexts[s],
                       k_noise)
        loads = jnp.stack(loads)             # [steps, expert layers, held]
        assigned = 2 * B * tokens_of(mcfg) * mcfg["num_experts_per_tok"]
        return z, next_key, jnp.stack([
            loads.max(), loads.mean(),
            loads.sum(axis=-1).mean() / assigned])

    return view


def synthesize_view(params, record_imgs, record_R, record_T, record_len,
                    K, key, mcfg: dict, dcfg: dict, *, steps: int,
                    kind: str = "ddim", prec="float32", literal=False):
    """:func:`make_view_fn`'s function, made and called once."""
    return make_view_fn(mcfg, dcfg, steps=steps, kind=kind,
                        literal=literal)(
        params, record_imgs, record_R, record_T, int(record_len), K, key,
        prec)


__all__ = ["model_dict", "tokens_of", "forward", "param_shapes",
           "make_params", "make_view_fn", "synthesize_view", "routing",
           "routed_share", "attention", "rope", "layer"]
