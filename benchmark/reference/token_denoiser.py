"""Plain ``jax.numpy`` token denoiser: two frames as one sequence of
patches through the decoder block of Keye-VL-2.0-30B-A3B (config.json of
the public model: grouped-query attention 32/4 x 128 with per-head
RMSNorm and multi-axis rotary embedding, a lightning indexer that selects
the keys each token attends to, 128 routed experts top-8), written from
the equations of ISSUE 26 / ``configs/keye_vl2_tok128.json``.  Nothing of
``diff3d_tpu`` is imported: this file is the yardstick the timed path is
compared with.  It is float32 with every contraction at
``Precision.HIGHEST``; ``prec`` rounds the operands of every contraction
as ``reference/xunet.py`` does (the control).

Per layer, pre-norm (RMSNorm, eps from the config), ``h <- h + f(norm h)``
twice:

  attention  q = W_q u (Hq x d), k = W_k u, v = W_v u (Hkv x d), no bias;
             RMSNorm per head on q and k; rotary embedding: frequency
             pair i (components i and i + d/2) of a head turns by
             ``pos * theta^(-2i/d)`` where pos is the token's frame index
             for the first ``mrope_section[0]`` pairs, its patch row for
             the next ``[1]``, its patch column for the last ``[2]``;
             scores / sqrt(d); softmax over the selected keys; W_o.
  indexer    qI = W_qI u (Hi x di), kI = W_kI u (di), w = W_w u (Hi); the
             same rotary embedding with half the section sizes;
             ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(Hi
             di)``; S_t = the keys whose I[t, .] is among the ``topk``
             largest over all L positions (a denoiser is not causal;
             keys tied with the topk-th are all kept).
  experts    p = softmax(W_r u) over all experts, the top-k, renormalised;
             expert e: ``W_down_e (silu(W_gate_e u) * W_up_e u)``; only the
             experts ``experts_held = [first, first + count)`` add to the
             result.

Two things are written twice: once literally, for small sizes and for the
gradient (``literal`` names them: ``"attention"``, selection by
``argsort`` and a gather of the selected keys, exactly ``topk`` of them;
``"experts"``, a plain loop in which every held expert computes every
token; ``True`` is both), and once so that the full size runs on one chip
in minutes (selection as a mask at the topk-th value of a sort, because
gathers crawl on the TPU; each expert computing only the tokens routed
to it, window by window).  ``tests/test_token_denoiser.py`` holds the two
to each other.

Around the layers, as ``diff3d_tpu/models/token_denoiser.py`` documents
it: tokens are ``patch x patch`` patches of the conditioning frame, then
the target frame, row-major; a token's input is the projection of its
pixels + the projection of its pixels' ray encoding (zero where
``cond_mask`` drops it) + an MLP of its frame's logSNR sinusoid;
conditioning inputs have ``G`` rows, ``G`` divides ``B``, example ``b``
reads row ``b // (B // G)``; final RMSNorm and a linear head on the
target frame's tokens.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import diffusion as rd
from .xunet import (DIR_DEG, POS_DEG, POSE_CH, _round, camera_rays,
                    posenc_ddpm, posenc_nerf, silu)

_HI = jax.lax.Precision.HIGHEST
MODEL_KEYS = ("H", "W", "patch", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "rms_norm_eps", "rope_theta", "mrope_section", "num_experts",
              "num_experts_per_tok", "moe_intermediate_size",
              "experts_held", "indexer_num_heads", "indexer_head_dim",
              "indexer_topk", "emb_ch", "logsnr_clip")
WINDOW = 1024       # rows of one expert computed at a time (full size)


def model_dict(config: dict) -> dict:
    """The reference's view of a ``benchmark/configs`` file of this
    model: the published keys by their published names (``sa_config``
    flattened) and the denoiser's own."""
    sa = config["sa_config"]
    m = {k: config[k] for k in MODEL_KEYS if k in config}
    m.update(mrope_section=list(config["rope_scaling"]["mrope_section"]),
             indexer_num_heads=sa["indexer_num_heads"],
             indexer_head_dim=sa["indexer_head_dim"],
             indexer_topk=sa["topk"])
    missing = [k for k in MODEL_KEYS if k not in m]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    return m


def tokens_of(cfg: dict) -> int:
    return 2 * (cfg["H"] // cfg["patch"]) * (cfg["W"] // cfg["patch"])


# ----------------------------------------------------------------- layers

def mm(x, w, prec):
    return jnp.einsum("...i,io->...o", _round(x, prec), _round(w, prec),
                      precision=_HI, preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * scale


def patchify(img, p: int):
    """``[..., H, W, C] -> [..., (H/p)(W/p), p*p*C]``."""
    *lead, H, W, C = img.shape
    out = []
    for i in range(p):
        for j in range(p):
            out.append(img[..., i::p, j::p, :])     # [..., H/p, W/p, C]
    x = jnp.concatenate(out, axis=-1)               # (i, j, c) order
    return x.reshape(*lead, (H // p) * (W // p), p * p * C)


def unpatchify(tok, p: int, H: int, W: int):
    *lead, _, PC = tok.shape
    C = PC // (p * p)
    x = tok.reshape(*lead, H // p, W // p, p, p, C)
    x = jnp.swapaxes(x, -3, -4)                     # H/p, p, W/p, p, C
    return x.reshape(*lead, H, W, C)


def rope(x, cfg: dict, section):
    """``x [L, heads, d]`` turned as the module docstring says."""
    L, _, d = x.shape
    half = d // 2
    rows, cols = cfg["H"] // cfg["patch"], cfg["W"] // cfg["patch"]
    n = np.arange(L)
    pos = np.stack([n // (rows * cols), (n // cols) % rows, n % cols])
    which = np.concatenate([np.full(s, a) for a, s in enumerate(section)])
    assert which.size == half, (section, d)
    freq = float(cfg["rope_theta"]) ** (-2.0 * np.arange(half) / d)
    ang = jnp.asarray(pos[which].T * freq[None, :], jnp.float32)  # [L, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_matrix(u, P, cfg: dict, prec):
    """``I [L, L]`` float32 of one example."""
    Hi, di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    L = u.shape[0]
    half_sec = [s // 2 for s in cfg["mrope_section"]]
    qi = rope(mm(u, P("attn/indexer_q/kernel"), prec).reshape(L, Hi, di),
              cfg, half_sec)
    ki = rope(mm(u, P("attn/indexer_k/kernel"), prec).reshape(L, 1, di),
              cfg, half_sec)[:, 0]
    w = mm(u, P("attn/indexer_w/kernel"), prec) / math.sqrt(Hi * di)

    def head(acc, j):
        dots = jnp.einsum("td,sd->ts", _round(qi[:, j], prec),
                          _round(ki, prec), precision=_HI,
                          preferred_element_type=jnp.float32)
        return acc + w[:, j, None] * jnp.maximum(dots, 0.0), None

    out, _ = jax.lax.scan(head, jnp.zeros((L, L), jnp.float32),
                          jnp.arange(Hi))
    return out


def selection(I, k: int):
    """bool ``[L, L]``: key s is selected by token t when ``I[t, s]`` is
    at least the k-th largest of ``I[t, .]``."""
    L = I.shape[-1]
    if k >= L:
        return jnp.ones(I.shape, bool)
    kth = jnp.sort(I, axis=-1)[:, L - k]
    return I >= kth[:, None]


def _is(literal, what: str) -> bool:
    return literal is True or (literal and what in literal)


def attention(u, P, cfg: dict, prec, literal=False):
    """``u [L, D]`` (normed) of one example -> ``[L, D]``."""
    L = u.shape[0]
    Hq, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = mm(u, P("attn/q_proj/kernel"), prec).reshape(L, Hq, d)
    k = mm(u, P("attn/k_proj/kernel"), prec).reshape(L, Hkv, d)
    v = mm(u, P("attn/v_proj/kernel"), prec).reshape(L, Hkv, d)
    q = rope(rms_norm(q, P("attn/q_norm/scale"), eps), cfg,
             cfg["mrope_section"])
    k = rope(rms_norm(k, P("attn/k_norm/scale"), eps), cfg,
             cfg["mrope_section"])
    I = index_matrix(u, P, cfg, prec)
    topk = min(cfg["indexer_topk"], L)
    group = Hq // Hkv

    if _is(literal, "attention"):
        idx = jnp.argsort(-I, axis=-1)[:, :topk]              # [L, topk]
        ks, vs = k[idx], v[idx]                               # [L,topk,Hkv,d]
        outs = []
        for h in range(Hq):
            s = jnp.einsum("td,tnd->tn", _round(q[:, h], prec),
                           _round(ks[:, :, h // group], prec), precision=_HI,
                           preferred_element_type=jnp.float32) / math.sqrt(d)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum(
                "tn,tnd->td", _round(p, prec),
                _round(vs[:, :, h // group], prec), precision=_HI,
                preferred_element_type=jnp.float32))
        out = jnp.stack(outs, axis=1)
    else:
        keep = selection(I, topk)

        def head(h):
            g = h // group
            s = jnp.einsum("td,sd->ts", _round(q[:, h], prec),
                           _round(k[:, g], prec), precision=_HI,
                           preferred_element_type=jnp.float32) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return jnp.einsum("ts,sd->td", _round(p, prec),
                              _round(v[:, g], prec), precision=_HI,
                              preferred_element_type=jnp.float32)

        out = jnp.swapaxes(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
    return mm(out.reshape(L, Hq * d), P("attn/o_proj/kernel"), prec)


def routing(u, P, cfg: dict, prec):
    """(ids ``[T, k]`` over all experts, gates ``[T, k]``)."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(u, P("moe/router"), prec), axis=-1)
    ids = jnp.argsort(-probs, axis=-1)[:, :k]
    gates = jnp.take_along_axis(probs, ids, axis=-1)
    return ids, gates / gates.sum(axis=-1, keepdims=True)


def experts(u, P, cfg: dict, prec, literal=False):
    """``u [T, D]`` (normed) -> (the held experts' part of the layer's
    output ``[T, D]``, tokens routed to each of all experts ``[E]``)."""
    T, D = u.shape
    E = cfg["num_experts"]
    first, held = cfg["experts_held"]
    ids, gates = routing(u, P, cfg, prec)
    load = (ids[..., None] == jnp.arange(E)).sum(axis=(0, 1))
    wg, wu, wd = P("moe/w_gate"), P("moe/w_up"), P("moe/w_down")

    def ffn(x, e):
        return mm(silu(mm(x, wg[e], prec)) * mm(x, wu[e], prec), wd[e],
                  prec)

    if _is(literal, "experts"):
        out = jnp.zeros((T, D), jnp.float32)
        for e in range(held):
            gate = jnp.where(ids == first + e, gates, 0.0).sum(axis=-1)
            out = out + gate[:, None] * ffn(u, e)
        return out, load

    # every (token, slot) assignment, sorted by expert: expert e's tokens
    # are then one run, computed WINDOW rows at a time
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
            tok = a // K
            g = jnp.where(live, gates.reshape(T * K)[a], 0.0)
            y = ffn(u[tok], e) * g[:, None]
            return j + 1, out.at[tok].add(y)

        _, out = jax.lax.while_loop(lambda s: s[0] * WINDOW < n,
                                    one_window, (jnp.int32(0), out))
        return out

    out = jax.lax.fori_loop(0, held, one_expert,
                            jnp.zeros((T, D), jnp.float32))
    return out, load


# ---------------------------------------------------------------- forward

class _Params:
    """Looks parameters up by path; in spec mode records their shapes."""

    def __init__(self, values):
        self.values = values
        self.shapes: Dict[str, Tuple[Tuple[int, ...], str]] = {}

    def __call__(self, path: str, shape, kind: str):
        shape = tuple(int(s) for s in shape)
        self.shapes[path] = (shape, kind)
        if self.values is None:
            return jnp.zeros(shape, jnp.float32)
        v = self.values[path]
        if tuple(v.shape) != shape:
            raise ValueError(f"{path}: have {v.shape}, need {shape}")
        return v


def _layer_params(P, i: int, cfg: dict):
    """Declares layer ``i``'s leaves (so that spec mode sees them) and
    returns the lookup by the name inside the layer."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hi, di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    spec = {
        "attn_norm/scale": ((D,), "scale"),
        "attn/q_proj/kernel": ((D, Hq * d), "dense"),
        "attn/k_proj/kernel": ((D, Hkv * d), "dense"),
        "attn/v_proj/kernel": ((D, Hkv * d), "dense"),
        "attn/q_norm/scale": ((d,), "scale"),
        "attn/k_norm/scale": ((d,), "scale"),
        "attn/indexer_q/kernel": ((D, Hi * di), "dense"),
        "attn/indexer_k/kernel": ((D, di), "dense"),
        "attn/indexer_w/kernel": ((D, Hi), "dense"),
        "attn/o_proj/kernel": ((Hq * d, D), "dense"),
        "moe_norm/scale": ((D,), "scale"),
        "moe/router": ((D, E), "dense"),
        "moe/w_gate": ((held, D, F), "dense"),
        "moe/w_up": ((held, D, F), "dense"),
        "moe/w_down": ((held, F, D), "dense"),
    }
    got = {name: P(f"layers_{i}/{name}", shape, kind)
           for name, (shape, kind) in spec.items()}
    return got.__getitem__


def embed(P, batch: dict, cond_mask, cfg: dict, prec):
    """The layers' input ``[B, L, D]``."""
    H, W, p, D = cfg["H"], cfg["W"], cfg["patch"], cfg["hidden_size"]
    B, G = batch["x"].shape[0], cond_mask.shape[0]
    if B % G:
        raise ValueError(f"{G} conditioning rows do not divide {B} examples")
    clip = cfg["logsnr_clip"]
    le = posenc_ddpm(jnp.clip(batch["logsnr"], -clip, clip), cfg["emb_ch"])
    le = mm(le, P("logsnr_mlp_0/kernel", (cfg["emb_ch"], D), "dense"),
            prec) + P("logsnr_mlp_0/bias", (D,), "bias")
    le = mm(silu(le), P("logsnr_mlp_1/kernel", (D, D), "dense"),
            prec) + P("logsnr_mlp_1/bias", (D,), "bias")     # [G, 2, D]
    pos, dirs = camera_rays(batch["R"].astype(jnp.float32),
                            batch["t"].astype(jnp.float32),
                            batch["K"].astype(jnp.float32), H, W)
    rays = jnp.concatenate([posenc_nerf(pos, POS_DEG),
                            posenc_nerf(dirs, DIR_DEG)], axis=-1)
    rays = jnp.where(cond_mask[:, None, None, None, None], rays, 0.0)
    cond = mm(patchify(rays, p),
              P("ray_proj/kernel", (p * p * POSE_CH, D), "dense"),
              prec) + P("ray_proj/bias", (D,), "bias")       # [G,2,L/2,D]
    cond = (cond + le[:, :, None, :]).reshape(G, -1, D)
    pix = jnp.stack([batch["x"], batch["z"]], axis=1).astype(jnp.float32)
    h = mm(patchify(pix, p), P("patch_embed/kernel", (p * p * 3, D),
                               "dense"),
           prec) + P("patch_embed/bias", (D,), "bias")       # [B,2,L/2,D]
    return h.reshape(B, -1, D) + jnp.repeat(cond, B // G, axis=0)


def forward(params, batch: dict, cond_mask, cfg: dict, *,
            prec="float32", literal=False, _p=None):
    """Predicted noise of the target frame ``[B, H, W, 3]`` and, per
    layer, the tokens routed to each expert ``[layers, E]`` (all
    examples of the call).  ``batch`` and ``cond_mask`` as the module
    docstring says."""
    P = _p or _Params(params)
    H, W, p = cfg["H"], cfg["W"], cfg["patch"]
    eps = cfg["rms_norm_eps"]
    h = embed(P, batch, cond_mask, cfg, prec)
    loads = []
    for i in range(cfg["num_hidden_layers"]):
        LP = _layer_params(P, i, cfg)

        def one_example(hb, LP=LP):
            hb = hb + attention(rms_norm(hb, LP("attn_norm/scale"), eps),
                                LP, cfg, prec, literal)
            y, load = experts(rms_norm(hb, LP("moe_norm/scale"), eps), LP,
                              cfg, prec, literal)
            return hb + y, load

        h, load = jax.lax.map(one_example, h)
        loads.append(load.sum(axis=0))
    D = cfg["hidden_size"]
    L = h.shape[1]
    h = rms_norm(h[:, L // 2:], P("final_norm/scale", (D,), "scale"), eps)
    out = mm(h, P("head/kernel", (D, p * p * 3), "dense_zero"),
             prec) + P("head/bias", (p * p * 3,), "bias")
    return unpatchify(out, p, H, W), jnp.stack(loads)


def _dummy_batch(cfg: dict) -> dict:
    H, W = cfg["H"], cfg["W"]
    return {"x": jnp.zeros((1, H, W, 3)), "z": jnp.zeros((1, H, W, 3)),
            "logsnr": jnp.zeros((1, 2)),
            "R": jnp.broadcast_to(jnp.eye(3), (1, 2, 3, 3)),
            "t": jnp.zeros((1, 2, 3)),
            "K": jnp.broadcast_to(jnp.eye(3), (1, 3, 3))}


def param_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``{path: (shape, kind)}`` of every parameter, in forward order."""
    rec = _Params(None)
    jax.eval_shape(lambda: forward(None, _dummy_batch(cfg),
                                   jnp.ones((1,), bool), cfg, literal=True,
                                   _p=rec))
    return dict(rec.shapes)


def make_params(cfg: dict, key, *, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """Seeded float32 parameters, made on the default device: matrices
    N(0, 1/fan_in) (an expert stack ``[E, in, out]`` has fan-in ``in``),
    the head, which the program initialises to zero, at ``zero_gain`` of
    that, norm scales 1 + N(0, 0.1^2), biases N(0, 0.1^2).  One compiled
    generator per distinct layer shape, one draw per leaf: a single draw
    for all leaves would hold the 2.5 B parameters twice."""
    shapes = param_shapes(cfg)

    def place(n, shape, kind):
        if kind in ("dense", "dense_zero"):
            gain = zero_gain if kind == "dense_zero" else 1.0
            return n * (gain / math.sqrt(shape[-2]))
        if kind == "scale":
            return 1.0 + 0.1 * n
        if kind == "bias":
            return 0.1 * n
        raise ValueError(kind)

    groups: Dict[str, list] = {}
    for i, name in enumerate(shapes):
        head = name.split("/")[0]
        groups.setdefault(head if head.startswith("layers_") else "", []
                          ).append((i, name))

    def make_group(k, names):
        out = {}
        for j, (local, shape, kind) in enumerate(names):
            n = jax.random.normal(jax.random.fold_in(k, j), shape,
                                  jnp.float32)
            out[local] = place(n, shape, kind)
        return out

    jitted: Dict[tuple, Callable] = {}

    def all_params():
        out = {}
        for g, (head, members) in enumerate(groups.items()):
            names = tuple((name[len(head) + 1:] if head else name,
                           shapes[name][0], shapes[name][1])
                          for _, name in members)
            if names not in jitted:
                jitted[names] = jax.jit(
                    lambda k, names=names: make_group(k, names))
            got = jitted[names](jax.random.fold_in(key, g))
            for local, v in got.items():
                out[f"{head}/{local}" if head else local] = v
        return out

    return all_params


# --------------------------------------------------------------- sampling

def synthesize_view(params, record_imgs, record_R, record_T, record_len,
                    K, key, mcfg: dict, dcfg: dict, *, steps: int,
                    kind: str = "ddim", prec="float32",
                    literal=False):
    """One novel view of one object for every guidance weight, by this
    model: ``reference/diffusion.py synthesize_view`` with the model call
    at two conditioning rows (the conditional one, then the
    unconditional), the key stream, schedule, guidance and reverse step
    being that file's.  Returns the ``[B, H, W, 3]`` view, the object's
    next key and the largest max-over-mean expert load of any layer of
    any step."""
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
        z, ck, worst = carry
        logsnr, logsnr_next, i = xs
        ck, k_x, k_noise = jax.random.split(ck, 3)
        batch = first_batch(record_imgs[i], z, logsnr, hi,
                            jnp.stack([record_R[i], tgt_R]),
                            jnp.stack([record_T[i], tgt_T]), K, k_x)
        eps, load = forward(params, batch, mask, mcfg, prec=prec,
                            literal=literal)
        eps = rd.guided_eps(eps[:B], eps[B:], w)
        noise = jax.random.normal(k_noise, z.shape, jnp.float32)
        z = rd.reverse_step(eps, z, logsnr, logsnr_next, noise, kind,
                            dcfg["clip_x0"])
        ratio = (load.max(axis=1) / load.mean(axis=1)).max()
        return (z, ck, jnp.maximum(worst, ratio)), None

    (z, _, worst), _ = jax.lax.scan(
        step, (z0, carry_key, jnp.float32(0.0)),
        (logsnrs, logsnr_nexts, idx))
    return z, next_key, worst


def first_batch(cond, z, logsnr, logsnr_max, R, t, K, k_x) -> dict:
    """The model call of one reverse step: ``2B`` examples (``cond [B, H,
    W, 3]`` beside its noise replacement drawn from ``k_x``, ``z`` twice)
    at two conditioning rows."""
    x_un = jax.random.normal(k_x, cond.shape, jnp.float32)
    return {"x": jnp.concatenate([cond, x_un]),
            "z": jnp.concatenate([z, z]),
            "logsnr": jnp.stack([jnp.full((2,), logsnr_max, jnp.float32),
                                 jnp.full((2,), logsnr)], axis=1),
            "R": jnp.broadcast_to(R[None], (2, 2, 3, 3)),
            "t": jnp.broadcast_to(t[None], (2, 2, 3)),
            "K": jnp.broadcast_to(K[None], (2, 3, 3))}
