"""Plain ``jax.numpy`` token denoiser on the decoder block of
Qwen3-Next-80B-A3B (config.json of the public model, ``model_type
qwen3_next``): Gated DeltaNet linear attention three layers to one of
output-gated full attention, every layer's feed-forward softmax-routed
experts beside a sigmoid-gated shared expert.  Written from the equations
in ``configs/qwen3_next_tok128.json`` and the published ``Qwen3Next*``
modules of that ``model_type``.  Nothing of ``diff3d_tpu`` is imported:
this file is the yardstick the timed path is compared with.  Float32,
every contraction at ``Precision.HIGHEST``; ``prec`` rounds the operands
of every contraction as ``reference/xunet.py`` does (the control).  The
parameters may be stored in bfloat16: every leaf is read in float32
(exact) where a layer uses it.

With the zero-centred RMSNorm ``n(x) = x / rms(x) (1 + w)`` (eps from the
config) and no bias in the layers, layer ``i`` is ``full_attention`` where
``(i + 1) % full_attention_interval == 0``, else ``linear_attention``, and
every layer is::

    h <- h + mixer(n_in(h));  h <- h + moe(n_post(h))

    linear_attention (Gated DeltaNet; Hk key heads of dk, Hv value heads
             of dv, value head j reading key head j // (Hv / Hk))
             [q | k | v | z] = W_qkvz u;  [b | a] = W_ba u
             [q | k | v] <- silu(conv([q | k | v])), depthwise, causal,
             linear_conv_kernel_dim taps, no bias
             q <- l2norm(q) / dk^1/2, k <- l2norm(k) per key head (eps
             1e-6); beta = sigmoid(b); g = -exp(A_log) softplus(a +
             dt_bias) per value head
             S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
             v_t^T, o_t = S_t^T q_t from S = 0 -- **a sequential
             recurrence over the tokens** (a ``lax.scan`` over ``t``; no
             chunk and no inverse anywhere), in token order: conditioning
             frame, then target frame
             o <- rmsnorm(o) w * silu(z) per value head (a plain weight);
             W_out o
    full_attention [q_h | gate_h] = (W_q u)_h; k = W_k u, v = W_v u;
             q_h <- n_q(q_h), k_h <- n_k(k_h) per head; the rotary turn
             on dims 0 .. r-1 of every head (r = head_dim x
             partial_rotary_factor), pair i = (x_i, x_{i + r/2}) by t *
             theta^(-2i/r) for token index t, the other dims passed
             through; per head softmax(q_h k_{h // (Hq/Hkv)}^T /
             head_dim^1/2) v over all L keys; o_h <- o_h sigmoid(gate_h);
             W_o
    moe      p = softmax(W_r u) over all experts (float32); the k largest,
             renormalised to sum 1 (norm_topk_prob); the held experts
             among a token's k: sum g_e W_down_e (silu(W_gate_e u) *
             W_up_e u); plus sigmoid(w_g^T u) shared(u), the shared
             expert [a | b] = W_1 u, W_2 (silu(a) * b)

Departures from the published model: a **share of the experts**
(``experts_held = [first, count]``: the router scores all experts, the
sum runs over the held ones alone, what the absent ones would add is
left out and nothing stands in for it); the first pipeline stage's
layers alone; no multi-token-prediction module; bidirectional attention;
the embedding and head of a denoiser (``reference/token_denoiser.py
embed``; the final norm zero-centred); ``W_qkvz`` / ``W_ba`` with each
part one run of columns where the published matrices group them by key
head (a fixed permutation of columns: on seeded weights the same model).

The routed sum is written twice, as ``reference/hybrid_moe_denoiser.py``
writes it: literally (``literal=True``, every held expert on every token)
and by windows of the tokens routed to each held expert.
``tests/test_gdn_moe_denoiser.py`` holds the two to each other.

A view is synthesised in blocks (:func:`make_view_fn`): the reverse steps
a Python loop, ``BLOCK`` examples at a time, and each block through one
compiled program per piece (the embedding, a layer of each kind, the
head), so that a layer's matrices exist in float32 only while that layer
runs.  A linear-attention layer runs its block's examples side by side
(one step of the recurrence moves all of their states).

Two keys no configuration file has plant a fault: ``delta_dropped``
builds the mixer without the delta correction, ``S_t = exp(g_t) S_{t-1}
+ beta_t k_t v_t^T`` (gated linear attention), and ``experts_dropped``
zeroes the held experts' sum.
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
              "full_attention_interval", "num_attention_heads",
              "num_key_value_heads", "head_dim", "partial_rotary_factor",
              "rope_theta", "rms_norm_eps", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "moe_intermediate_size", "num_experts_per_tok", "emb_ch",
              "logsnr_clip")
BLOCK = 4           # examples of one compiled piece of a view (full size)
WINDOW = 256        # rows of one expert computed at a time (full size:
                    # 160 of a held expert's rows an example and layer)


def model_dict(config: dict) -> dict:
    """The reference's view of a ``benchmark/configs`` file of this
    model.  The file keeps the published names: ``num_experts`` is the
    count **held here** (the guide's rule for a share) and
    ``published.num_experts`` the router's width;
    ``shared_expert_intermediate_size`` is the shared expert's width."""
    missing = [k for k in MODEL_KEYS + (
        "model_type", "num_experts", "norm_topk_prob", "decoder_sparse_step",
        "mlp_only_layers", "shared_expert_intermediate_size",
        "experts_held") if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {k: config[k] for k in MODEL_KEYS}
    m["num_experts"] = config.get("published", {}).get(
        "num_experts", config["num_experts"])
    m["experts_held"] = list(config["experts_held"])
    m["shared_intermediate_size"] = config["shared_expert_intermediate_size"]
    m["param_dtype"] = config.get("param_dtype", "float32")
    n = m["full_attention_interval"]
    m["layer_types"] = ["full_attention" if (i + 1) % n == 0
                        else "linear_attention"
                        for i in range(m["num_hidden_layers"])]
    first, held = m["experts_held"]
    if (held != config["num_experts"] or first < 0 or held < 1
            or first + held > m["num_experts"]):
        raise ValueError(
            f"experts_held={m['experts_held']} must be a range of the "
            f"{m['num_experts']} experts, num_experts="
            f"{config['num_experts']} of them")
    if not (config["model_type"] == "qwen3_next" and config["norm_topk_prob"]
            and config["decoder_sparse_step"] == 1
            and not config["mlp_only_layers"]
            and m["shared_intermediate_size"] > 0):
        raise ValueError("this reference has a qwen3_next block with "
                         "renormalised top-k gates and routed experts "
                         "beside a shared expert in every layer")
    return m


def rotary_dim(cfg: dict) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


# ----------------------------------------------------------------- layers

def n(x, w, cfg: dict):
    """The zero-centred RMSNorm ``x / rms(x) (1 + w)``."""
    return rms_norm(x, 1.0 + w, cfg["rms_norm_eps"])


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                     keepdims=True) + eps)


def delta_mixer(u, LP, cfg: dict, prec):
    """``u [B, L, D]`` (normed) of ``B`` examples side by side -> ``[B, L,
    D]``: the Gated DeltaNet mixer, its delta rule token by token."""
    Bn, L, _ = u.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd, K = Hk * dk, Hv * dv, cfg["linear_conv_kernel_dim"]
    qkv, z = jnp.split(mm(u, LP("linear_attn/in_proj_qkvz/kernel"), prec),
                       [2 * kd + vd], axis=-1)
    b, a = jnp.split(mm(u, LP("linear_attn/in_proj_ba/kernel"), prec), 2,
                     axis=-1)
    taps = LP("linear_attn/conv1d/kernel")
    xp = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = silu(sum(taps[j] * xp[:, j:j + L] for j in range(K)))
    q, k, v = jnp.split(qkv, [kd, 2 * kd], axis=-1)
    rep = Hv // Hk
    q = jnp.repeat(l2norm(q.reshape(Bn, L, Hk, dk)), rep, axis=2) / math.sqrt(
        dk)
    k = jnp.repeat(l2norm(k.reshape(Bn, L, Hk, dk)), rep, axis=2)
    v = v.reshape(Bn, L, Hv, dv)
    beta = jax.nn.sigmoid(b)                                   # [B, L, Hv]
    g = -jnp.exp(LP("linear_attn/A_log")) * jax.nn.softplus(
        a + LP("linear_attn/dt_bias"))
    delta = not cfg.get("delta_dropped")

    def dot(x, S):                        # x [B, H, dk], S [B, H, dk, dv]
        # elementwise in float32 and summed: exact, and a product of one
        # row with a matrix a step keeps no matrix unit busy
        return (_round(x, prec)[..., None] * _round(S, prec)).sum(axis=-2)

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = jnp.exp(gt)[..., None, None] * S
        write = vt - dot(kt, S) if delta else vt
        S = S + bt[..., None, None] * kt[..., None] * write[..., None, :]
        return S, dot(qt, S)

    by_t = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((Bn, Hv, dk, dv), jnp.float32),
                        tuple(by_t(x) for x in (q, k, v, g, beta)))
    o = rms_norm(by_t(o), LP("linear_attn/norm/scale"), cfg["rms_norm_eps"])
    o = o * silu(z.reshape(Bn, L, Hv, dv))
    return mm(o.reshape(Bn, L, vd), LP("linear_attn/out_proj/kernel"), prec)


def rope(x, cfg: dict):
    """``x [L, heads, d]`` with its first ``r`` dims turned by the token's
    index as the module docstring says (angles in float64)."""
    L, r = x.shape[0], rotary_dim(cfg)
    half = r // 2
    freq = float(cfg["rope_theta"]) ** (-2.0 * np.arange(half) / r)
    ang = np.arange(L)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def attention(u, LP, cfg: dict, prec):
    """``u [L, D]`` (normed) of one example -> ``[L, D]``, one head's
    ``[L, L]`` scores at a time."""
    L = u.shape[0]
    Hq, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    qg = mm(u, LP("attn/q_proj/kernel"), prec).reshape(L, Hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = mm(u, LP("attn/k_proj/kernel"), prec).reshape(L, Hkv, d)
    v = mm(u, LP("attn/v_proj/kernel"), prec).reshape(L, Hkv, d)
    q = rope(n(q, LP("attn/q_norm/scale"), cfg), cfg)
    k = rope(n(k, LP("attn/k_norm/scale"), cfg), cfg)
    group = Hq // Hkv

    def head(h):
        kv = h // group
        s = jnp.einsum("td,sd->ts", _round(q[:, h], prec),
                       _round(k[:, kv], prec), precision=_HI,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ts,sd->td", _round(p, prec),
                          _round(v[:, kv], prec), precision=_HI,
                          preferred_element_type=jnp.float32)

    out = jnp.swapaxes(jax.lax.map(head, jnp.arange(Hq)), 0, 1)
    out = out * jax.nn.sigmoid(gate)
    return mm(out.reshape(L, Hq * d), LP("attn/o_proj/kernel"), prec)


def routing(u, LP, cfg: dict, prec):
    """(ids ``[T, k]`` over all experts, gates ``[T, k]``): softmax over
    all experts' logits, the ``k`` largest, renormalised to sum 1."""
    p = jax.nn.softmax(mm(u, LP("moe/router"), prec), axis=-1)
    top, ids = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return ids, top / top.sum(axis=-1, keepdims=True)


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
        rows = start[e + 1] - start[e]

        def one_window(state):
            j, out = state
            at = start[e] + j * WINDOW + jnp.arange(WINDOW)
            live = at < start[e + 1]
            a = order[jnp.minimum(at, T * K - 1)]
            g = jnp.where(live, gates.reshape(T * K)[a], 0.0)
            return j + 1, out.at[a // K].add(ffn(u[a // K], e) * g[:, None])

        return jax.lax.while_loop(lambda s: s[0] * WINDOW < rows, one_window,
                                  (jnp.int32(0), out))[1]

    out = jax.lax.fori_loop(0, held, one_expert,
                            jnp.zeros((T, D), jnp.float32))
    return out, load


def shared_expert(u, LP, prec):
    """The shared expert times its gate, ``sigmoid(w_g^T u)`` a token."""
    return rh.mlp(u, LP, prec) * jax.nn.sigmoid(
        mm(u, LP("mlp/gate/kernel"), prec))


def ffn_half(hb, LP, cfg: dict, prec, literal=False):
    """``hb [L, D]`` of one example -> (``hb + moe(n(hb))``, rows routed
    to each held expert ``[held]``)."""
    u = n(hb, LP("moe_norm/scale"), cfg)
    y, load = routed_share(u, LP, cfg, prec, literal)
    if cfg.get("experts_dropped"):
        y = jnp.zeros_like(y)
    return hb + y + shared_expert(u, LP, prec), load


def layer(h, LP, cfg: dict, prec, kind: str, literal=False):
    """``h [B, L, D]`` -> (the layer's output, rows routed to each held
    expert over the ``B`` examples): a linear-attention mixer on all
    ``B`` examples side by side, the rest one example at a time."""
    if kind == "linear_attention":
        h = h + delta_mixer(n(h, LP("linear_attn_norm/scale"), cfg), LP,
                            cfg, prec)
    else:
        h = jax.lax.map(lambda hb: hb + attention(
            n(hb, LP("attn_norm/scale"), cfg), LP, cfg, prec), h)
    h, load = jax.lax.map(lambda hb: ffn_half(hb, LP, cfg, prec, literal),
                          h)
    return h, load.sum(axis=0)


# ---------------------------------------------------------------- forward

def _layer_params(P, i: int, kind: str, cfg: dict):
    """Declares layer ``i``'s leaves and returns the lookup by the name
    inside the layer."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hv = cfg["linear_num_value_heads"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    E, Fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    held, F = cfg["experts_held"][1], cfg["shared_intermediate_size"]
    if kind == "linear_attention":
        spec = {
            "linear_attn_norm/scale": ((D,), "zero"),
            "linear_attn/in_proj_qkvz/kernel": ((D, 2 * kd + 2 * Hv * dv),
                                                "dense"),
            "linear_attn/in_proj_ba/kernel": ((D, 2 * Hv), "dense"),
            "linear_attn/conv1d/kernel": (
                (cfg["linear_conv_kernel_dim"], 2 * kd + Hv * dv), "dense"),
            "linear_attn/dt_bias": ((Hv,), "dt_bias"),
            "linear_attn/A_log": ((Hv,), "a_log_uniform"),
            "linear_attn/norm/scale": ((dv,), "one"),
            "linear_attn/out_proj/kernel": ((Hv * dv, D), "dense"),
        }
    elif kind == "full_attention":
        spec = {
            "attn_norm/scale": ((D,), "zero"),
            "attn/q_proj/kernel": ((D, 2 * Hq * d), "dense"),
            "attn/k_proj/kernel": ((D, Hkv * d), "dense"),
            "attn/v_proj/kernel": ((D, Hkv * d), "dense"),
            "attn/q_norm/scale": ((d,), "zero"),
            "attn/k_norm/scale": ((d,), "zero"),
            "attn/o_proj/kernel": ((Hq * d, D), "dense"),
        }
    else:
        raise ValueError(f"layer {i}: no layer of type {kind!r}")
    spec.update({"moe_norm/scale": ((D,), "zero"),
                 "moe/router": ((D, E), "dense"),
                 "moe/w_gate": ((held, D, Fe), "dense"),
                 "moe/w_up": ((held, D, Fe), "dense"),
                 "moe/w_down": ((held, Fe, D), "expert_down"),
                 "mlp/w1/kernel": ((D, 2 * F), "dense"),
                 "mlp/w2/kernel": ((F, D), "dense"),
                 "mlp/gate/kernel": ((D, 1), "dense")})
    got = {name: P(f"layers_{i}/{name}", shape, k)
           for name, (shape, k) in spec.items()}
    return got.__getitem__


def _head(P, h, cfg: dict, prec):
    """Final (zero-centred) RMSNorm and the linear head on the target
    frame's tokens."""
    D, p = cfg["hidden_size"], cfg["patch"]
    L = h.shape[1]
    h = n(h[:, L // 2:], P("final_norm/scale", (D,), "zero"), cfg)
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
    examples of the call); ``batch`` and ``cond_mask`` as
    ``reference/token_denoiser.py`` documents them."""
    P = _p or _Float32(params)
    h = embed(P, batch, cond_mask, cfg, prec)
    loads = []
    for i, kind in enumerate(cfg["layer_types"]):
        h, load = layer(h, _layer_params(P, i, kind, cfg), cfg, prec, kind,
                        literal)
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
    """The gain of the held experts' ``w_down``: 1.  At one of two chips'
    share (256 of 512) half of a token's assignments land here, and a
    probe on the CPU (tests/test_gdn_moe_denoiser.py) reads the planted
    fault ``experts_dropped`` above the control without a gain; PRs 32
    and 36 needed ``(num_experts / held)^1/2`` at an eighth and a 32nd."""
    return 1.0


def _place(key, shape, kind: str, zero_gain: float):
    """One leaf from its key: ``reference/hybrid_denoiser.py _place``'s
    kinds (``dt_bias`` the inverse softplus of a log-uniform step in
    [1e-3, 1e-1]), and zero-centred norm weights 0 (``zero``), ``A_log =
    log U(0, 16)`` (``a_log_uniform``).  Both as the Gated DeltaNet
    paper's code (flash-linear-attention) initialises them: with ``a``
    about N(0, 1), ``g = -exp(A_log) softplus(a + dt_bias)`` runs from
    about -1e-5 to about -10 a token, so that some heads carry state
    across chunks and the frame boundary and others forget within a few
    tokens.  The published modules' ``dt_bias`` of 1 puts ``g`` near -10
    for all but one head in 32 at ``A_log = log U(0, 16)``: the state
    then holds a token or two, and the planted fault ``delta_dropped``
    read inside the sound runs on the chip (PERF.md section 6, PR 39)."""
    if kind == "zero":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0,
                                          16.0))
    return rh._place(key, shape, kind, zero_gain)


def make_params(cfg: dict, key, *, dtype=jnp.float32, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """() -> the seeded parameters in ``dtype``, made on the default
    device: matrices N(0, 1/fan_in) (an expert stack ``[E, in, out]`` has
    fan-in ``in``; the conv's taps fan-in ``linear_conv_kernel_dim``:
    N(0, 1/4)), the head at ``zero_gain`` of that, biases N(0, 0.1^2),
    zero-centred norm weights 0, the gated norm's weights 1, ``A_log``
    and ``dt_bias`` as :func:`_place` says, the held experts' ``w_down``
    times :func:`_expert_gain`; each leaf drawn in float32 and rounded to
    ``dtype``: one compiled generator per distinct layer, one draw per
    leaf."""
    shapes = param_shapes(cfg)
    gain = _expert_gain(cfg)

    def leaf(k, shape, kind):
        if kind == "expert_down":
            w = gain * _place(k, shape, "dense", zero_gain)
        else:
            w = _place(k, shape, kind, zero_gain)
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
            local = tuple((m[len(head) + 1:] if head else m, *shapes[m])
                          for m in members)
            if local not in jitted:
                jitted[local] = jax.jit(lambda k, local=local: {
                    m: leaf(jax.random.fold_in(k, j), s, kind)
                    for j, (m, s, kind) in enumerate(local)})
            for m, v in jitted[local](jax.random.fold_in(key, g)).items():
                out[f"{head}/{m}" if head else m] = v
        return out

    return all_params


# --------------------------------------------------------------- sampling

def make_view_fn(mcfg: dict, dcfg: dict, *, steps: int, kind: str = "ddim",
                 literal=False) -> Callable:
    """``view(params, record_imgs, record_R, record_T, record_len, K, key,
    prec) -> (image [B, H, W, 3], the object's next key, load)``: one novel
    view of one object for every guidance weight, as
    ``reference/mla_moe_denoiser.py make_view_fn`` computes it (the key
    stream, schedule, guidance and reverse step of
    ``reference/diffusion.py``; the model call at two conditioning rows),
    **in blocks**: the reverse steps a Python loop, a step's ``2 B``
    examples ``BLOCK`` at a time (those of one conditioning row together),
    and a block through one compiled program per piece -- the embedding,
    a layer of each kind (one program for all layers of the kind), the
    head -- each given only its own leaves.  ``prec`` is a name
    (``"float32"``, or ``"fp8"`` for the control), static.
    ``record_len`` is a Python int.  ``load``: the held experts' load over
    the steps' model calls (most rows any held expert got in one layer of
    one call, mean rows a held expert got a layer and call, share of all
    ``T k`` assignments that landed on a held expert)."""
    w = jnp.asarray(dcfg["guidance_weights"], jnp.float32)
    B = w.shape[0]
    H, W = mcfg["H"], mcfg["W"]
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    T = dcfg["timesteps"]
    kinds = mcfg["layer_types"]
    block = math.gcd(B, BLOCK)

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

    def layer_block(kind):
        @static
        def run(mine, h, prec):
            LP = _layer_params(_Float32(mine), 0, kind, mcfg)
            return layer(h, LP, mcfg, prec, kind, literal)
        return run

    blocks = {k: layer_block(k) for k in set(kinds)}

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
        outer = {m: v for m, v in params.items()
                 if not m.startswith("layers_")}
        mine = [{f"layers_0/{m[len(f'layers_{i}/'):]}": v
                 for m, v in params.items()
                 if m.startswith(f"layers_{i}/")}
                for i in range(len(kinds))]
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
                row = {m: batch[m][g:g + 1] for m in ("logsnr", "R", "t",
                                                      "K")}
                for b in range(g * B, (g + 1) * B, block):
                    part = dict(row, x=batch["x"][b:b + block],
                                z=batch["z"][b:b + block])
                    h = embed_block(outer, part, mask[g:g + 1], prec)
                    for j, m in enumerate(mine):
                        h, rows = blocks[kinds[j]](m, h, prec)
                        load.append(rows)
                    eps.append(head_block(outer, h, prec))
            loads.append(jnp.stack(load).reshape(
                -1, len(mine), load[0].shape[0]).sum(0))
            z = finish(jnp.concatenate(eps), z, logsnrs[s], logsnr_nexts[s],
                       k_noise)
        loads = jnp.stack(loads)                  # [steps, layers, held]
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
           "routed_share", "attention", "delta_mixer", "rope", "layer",
           "ffn_half"]
