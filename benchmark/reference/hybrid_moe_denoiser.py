"""Plain ``jax.numpy`` token denoiser on the hybrid mixture-of-experts block
of granite-4.0-h-small (config.json of the public model, ``model_type
granitemoehybrid`` with ``num_local_experts`` 72): the layers of
``reference/hybrid_denoiser.py`` (Mamba-2 state-space mixers as a
sequential recurrence, grouped-query attention without any position;
both imported, not repeated) with the feed-forward of the published
decoder layer where it has experts: ``block_sparse_moe(u) +
shared_mlp(u)`` on one normed input under one residual add.  Written from
the equations of ISSUE 32 / ``configs/granite4_h_small_tok128.json``.
Nothing of ``diff3d_tpu`` is imported: this file is the yardstick the
timed path is compared with.  Float32, every contraction at
``Precision.HIGHEST``; ``prec`` rounds the operands of every contraction
as ``reference/xunet.py`` does (the control).

With ``r = residual_multiplier`` and RMSNorm ``n``, every layer is ``h <-
h + r mixer(n(h))`` (``reference/hybrid_denoiser.py``) and then, with ``u
= n(h)`` computed **once**::

    l = W_r u                      72 logits (the published router width)
    top = the 10 largest logits;  g = softmax over those 10
                                   (``GraniteMoeTopKGating``)
    expert e: W_down_e (silu(W_gate_e u) * W_up_e u)         width 768
    shared:   [a | b] = W_1 u;  W_2 (silu(a) * b)            width 1536
    h <- h + r (sum_{e in top, e held here} g_e expert_e(u) + shared(u))

Departures from the published model, beside those of
``reference/hybrid_denoiser.py`` (bidirectional attention, the
embedding and head of a denoiser, where the outer multipliers stand):

  * **a share of the experts.**  ``experts_held = [first, count]``: the
    router scores all ``num_experts``, the sum runs over the held
    experts alone, what the absent ones would add is left out and nothing
    stands in for it (one of eight chips' share of an expert-parallel
    layer; the shared expert, the router and the norm are whole on every
    chip).  With all experts held it is the published layer.
  * the experts' matrices are three stacks ``w_gate, w_up [E, D, F]``,
    ``w_down [E, F, D]`` where the published module fuses gate and up
    into one ``input_linear``: the same arithmetic.

The routed sum is written twice, as ``reference/token_denoiser.py
experts`` is: literally (``literal=True``: every held expert computes
every token, a masked sum; small sizes and the gradient) and so that the
full size runs in minutes (each held expert computes the tokens routed
to it, ``WINDOW`` rows at a time).  ``tests/test_hybrid_moe_denoiser.py``
holds the two to each other.

A view is synthesised in blocks (:func:`make_view_fn`): the reverse steps
in a Python loop, ``BLOCK`` examples to a compiled forward (3.7 GB of
temporaries beside the 8.1 GB of float32 weights at the full size, by the
chip's compiler; one program over all steps and examples needs 21 GB).

Two keys no configuration file has plant a fault: ``shared_dropped``
leaves the shared expert out (what a layer builds that takes
``num_experts > 0`` for "no dense MLP"), ``experts_dropped`` zeroes the
held experts' sum.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from . import diffusion as rd
from . import hybrid_denoiser as rh
from .token_denoiser import (_Params, _dummy_batch, embed, first_batch, mm,
                             rms_norm, tokens_of, unpatchify)
from .xunet import silu

MODEL_KEYS = rh.MODEL_KEYS + ("num_experts_per_tok",)
SIDE_BY_SIDE = rh.SIDE_BY_SIDE
BLOCK = 2           # examples of one compiled forward of a view (full size)
WINDOW = 1024       # rows of one expert computed at a time (full size)


def model_dict(config: dict) -> dict:
    """The reference's view of a ``benchmark/configs`` file of this
    model.  The file keeps the published names: ``num_local_experts`` is
    the count **held here** (the guide's rule for a share) and
    ``published.num_local_experts`` the router's width;
    ``intermediate_size`` is one expert's width (the config has no key of
    its own for it) and ``shared_intermediate_size`` the shared
    expert's."""
    missing = [k for k in MODEL_KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    m = {k: config[k] for k in MODEL_KEYS}
    m["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    if len(m["layer_types"]) != m["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    if m["mamba_n_groups"] != 1:
        raise ValueError("this reference has one group of B and C")
    m["num_experts"] = config.get("published", {}).get(
        "num_local_experts", config["num_local_experts"])
    m["experts_held"] = list(config["experts_held"])
    m["moe_intermediate_size"] = config["intermediate_size"]
    first, held = m["experts_held"]
    if (held != config["num_local_experts"] or first < 0 or held < 1
            or first + held > m["num_experts"]):
        raise ValueError(
            f"experts_held={m['experts_held']} must be a range of the "
            f"{m['num_experts']} experts, num_local_experts="
            f"{config['num_local_experts']} of them")
    if not (m["shared_intermediate_size"] > 0
            and 1 <= m["num_experts_per_tok"] <= m["num_experts"]):
        raise ValueError("this reference has routed experts and a shared "
                         "expert in every layer")
    return m


# ----------------------------------------------------------------- layers

def routing(u, LP, cfg: dict, prec):
    """(ids ``[T, k]`` over all experts, gates ``[T, k]``): the ``k``
    largest of the router's logits, softmax over those ``k``."""
    logits = mm(u, LP("moe/router"), prec)
    top, ids = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    return ids, jax.nn.softmax(top, axis=-1)


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
            tok = a // K
            g = jnp.where(live, gates.reshape(T * K)[a], 0.0)
            return j + 1, out.at[tok].add(ffn(u[tok], e) * g[:, None])

        return jax.lax.while_loop(lambda s: s[0] * WINDOW < n, one_window,
                                  (jnp.int32(0), out))[1]

    out = jax.lax.fori_loop(0, held, one_expert,
                            jnp.zeros((T, D), jnp.float32))
    return out, load


def feed_forward(hb, LP, cfg: dict, prec, literal=False):
    """``hb [L, D]`` of one example -> (``hb + r (routed(u) +
    shared(u))`` with ``u = n(hb)`` once, the held experts' load)."""
    u = rms_norm(hb, LP("moe_norm/scale"), cfg["rms_norm_eps"])
    y, load = routed_share(u, LP, cfg, prec, literal)
    if cfg.get("experts_dropped"):
        y = jnp.zeros_like(y)
    if not cfg.get("shared_dropped"):
        y = y + rh.mlp(u, LP, prec)
    return hb + cfg["residual_multiplier"] * y, load


# ---------------------------------------------------------------- forward

def _layer_params(P, i: int, kind: str, cfg: dict):
    """Declares layer ``i``'s leaves and returns the lookup by the name
    inside the layer: the mixer's of ``reference/hybrid_denoiser.py``,
    then one norm, the router over all experts, the held experts' three
    stacks and the shared expert."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    H, N = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    di = H * cfg["mamba_d_head"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    held, Fs = cfg["experts_held"][1], cfg["shared_intermediate_size"]
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
    spec.update({"moe_norm/scale": ((D,), "one"),
                 "moe/router": ((D, E), "dense"),
                 "moe/w_gate": ((held, D, F), "dense"),
                 "moe/w_up": ((held, D, F), "dense"),
                 "moe/w_down": ((held, F, D), "expert_down"),
                 "mlp/w1/kernel": ((D, 2 * Fs), "dense"),
                 "mlp/w2/kernel": ((Fs, D), "dense")})
    got = {name: P(f"layers_{i}/{name}", shape, k)
           for name, (shape, k) in spec.items()}
    return got.__getitem__


def forward(params, batch: dict, cond_mask, cfg: dict, *, prec="float32",
            literal=False, _p=None):
    """Predicted noise of the target frame ``[B, H, W, 3]`` and, per
    layer, the rows routed to each held expert ``[layers, held]`` (all
    examples of the call); ``batch`` and ``cond_mask`` as
    ``reference/token_denoiser.py`` documents them."""
    P = _p or _Params(params)
    H, W, p, D = cfg["H"], cfg["W"], cfg["patch"], cfg["hidden_size"]
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = embed(P, batch, cond_mask, cfg, prec) * cfg["embedding_multiplier"]
    B, L, _ = h.shape
    side = math.gcd(B, SIDE_BY_SIDE)
    loads = []
    for i, kind in enumerate(cfg["layer_types"]):
        LP = _layer_params(P, i, kind, cfg)
        if kind == "mamba":
            def some(hs, LP=LP):
                u = rms_norm(hs, LP("mamba_norm/scale"), eps)
                return hs + r * rh.mamba_mixer(u, LP, cfg, prec)
            h = jax.lax.map(some, h.reshape(B // side, side, L, D)
                            ).reshape(B, L, D)
        else:
            def one(hb, LP=LP):
                u = rms_norm(hb, LP("attn_norm/scale"), eps)
                return hb + r * rh.attention(u, LP, cfg, prec)
            h = jax.lax.map(one, h)
        h, load = jax.lax.map(
            lambda hb, LP=LP: feed_forward(hb, LP, cfg, prec, literal), h)
        loads.append(load.sum(axis=0))
    h = rms_norm(h[:, L // 2:], P("final_norm/scale", (D,), "one"), eps)
    out = mm(h, P("head/kernel", (D, p * p * 3), "dense_zero"),
             prec) + P("head/bias", (p * p * 3,), "bias")
    return (unpatchify(out / cfg["logits_scaling"], p, H, W),
            jnp.stack(loads))


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """``{path: (shape, kind)}`` of every parameter, in forward order."""
    rec = _Params(None)
    jax.eval_shape(lambda: forward(None, _dummy_batch(cfg),
                                   jnp.ones((1,), bool), cfg, literal=True,
                                   _p=rec))
    return dict(rec.shapes)


def _expert_gain(cfg: dict) -> float:
    """The gain of the held experts' ``w_down``: ``(num_experts /
    held)^1/2`` (2.83 at 9 of 72).  With N(0, 1/fan_in) matrices one
    expert's output is about as large as the shared expert's, but a
    token's ten gates sum to one and only ``held / num_experts`` of its
    assignments land here: the held share is an eighth of the shared
    expert's amplitude, and leaving it out moved the answer 3.5-3.9 times
    what bf16 rounding does where the control moved it 6.5 times (CPU, a
    10-layer model of hidden 256, ISSUE 32 item 6): a planted fault under
    the control decides the limit in the control's place.  At this gain
    the share's variance is what all ``num_experts_per_tok`` experts of a
    token would add, the fault reads 9-10 times the rounding and the
    control 6 times.  Nothing but the weights' scale: the program and the
    reference compute the same held share."""
    return math.sqrt(cfg["num_experts"] / cfg["experts_held"][1])


def make_params(cfg: dict, key, *, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """() -> the seeded float32 parameters, made on the default device as
    ``reference/hybrid_denoiser.py make_params`` makes them (its draws,
    its two outer gains), an expert stack ``[E, in, out]`` with fan-in
    ``in``, and the held experts' ``w_down`` times :func:`_expert_gain`:
    one compiled generator per distinct layer, one draw per leaf."""
    shapes = param_shapes(cfg)
    gain = _expert_gain(cfg)

    def leaf(k, shape, kind):
        if kind == "expert_down":
            return gain * rh._place(k, shape, "dense", zero_gain)
        return rh._place(k, shape, kind, zero_gain)

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
                out[f"{head}/{n}" if head else n] = (
                    v if head else v * rh._outer_gain(n, cfg))
        return out

    return all_params


# --------------------------------------------------------------- sampling

def make_view_fn(mcfg: dict, dcfg: dict, *, steps: int, kind: str = "ddim",
                 literal=False) -> Callable:
    """``view(params, record_imgs, record_R, record_T, record_len, K, key,
    prec) -> (image [B, H, W, 3], the object's next key, load)``: one novel
    view of one object for every guidance weight, as
    ``reference/hybrid_denoiser.py synthesize_view`` computes it
    (``reference/diffusion.py``'s key stream, schedule, guidance and
    reverse step; the model call at two conditioning rows), **in blocks**:
    the reverse steps are a Python loop, and a step's ``2 B`` examples go
    through the model ``BLOCK`` at a time, the examples of one conditioning
    row together, each block one call of one compiled forward.  One
    program over all steps and examples does not fit the chip beside 8.1
    GB of float32 weights: XLA lifts what depends on the weights alone
    (the control's rounding of every matrix) out of any loop over steps or
    examples and holds it all at once.  ``record_len`` is a Python int.
    ``load``: the held experts' load over the steps' model calls (most
    rows any held expert got in one layer of one call, mean rows a held
    expert got a layer and call, share of all ``T k`` assignments that
    landed on a held expert)."""
    w = jnp.asarray(dcfg["guidance_weights"], jnp.float32)
    B = w.shape[0]
    H, W = mcfg["H"], mcfg["W"]
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    T = dcfg["timesteps"]
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

    @jax.jit
    def block_forward(params, batch, cond_mask, prec):
        return forward(params, batch, cond_mask, mcfg, prec=prec,
                       literal=literal)

    @jax.jit
    def finish(eps, z, logsnr, logsnr_next, k_noise):
        eps = rd.guided_eps(eps[:B], eps[B:], w)
        noise = jax.random.normal(k_noise, z.shape, jnp.float32)
        return rd.reverse_step(eps, z, logsnr, logsnr_next, noise, kind,
                               dcfg["clip_x0"])

    def view(params, record_imgs, record_R, record_T, record_len: int, K,
             key, prec="float32"):
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
            eps = []
            for g in range(2):                   # a conditioning row
                row = {n: batch[n][g:g + 1] for n in ("logsnr", "R", "t",
                                                      "K")}
                for b in range(g * B, (g + 1) * B, BLOCK):
                    part = dict(row, x=batch["x"][b:b + BLOCK],
                                z=batch["z"][b:b + BLOCK])
                    e, load = block_forward(params, part, mask[g:g + 1],
                                            prec)
                    eps.append(e)
                    loads.append(load)
            z = finish(jnp.concatenate(eps), z, logsnrs[s], logsnr_nexts[s],
                       k_noise)
        # [steps, blocks, layers, held] -> rows of a whole call
        loads = jnp.stack(loads).reshape(steps, -1, *loads[0].shape).sum(1)
        assigned = 2 * B * tokens_of(mcfg) * mcfg["num_experts_per_tok"]
        return z, next_key, jnp.stack([
            loads.max(), loads.mean(),
            loads.sum(axis=-1).mean() / assigned])

    return view


def synthesize_view(params, record_imgs, record_R, record_T, record_len,
                    K, key, mcfg: dict, dcfg: dict, *, steps: int,
                    kind: str = "ddim", prec="float32", literal=False):
    """:func:`make_view_fn`'s function, made and called once (its compiled
    forward is not kept: a caller with more than one view to compute keeps
    the function)."""
    return make_view_fn(mcfg, dcfg, steps=steps, kind=kind,
                        literal=literal)(
        params, record_imgs, record_R, record_T, int(record_len), K, key,
        prec)


__all__ = ["model_dict", "tokens_of", "forward", "param_shapes",
           "make_params", "make_view_fn", "synthesize_view", "routing",
           "routed_share", "feed_forward"]
