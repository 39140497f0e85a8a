"""The token denoiser (models/token_denoiser.py, sparse_attention.py,
moe.py) against its plain reference (benchmark/reference/
token_denoiser.py) at the tiny configuration, seeded weights, on the CPU;
``build_model`` at the entry points; the typed refusal of the entry
points that are the X-UNet's alone; the expert axis on a 1 x 2 mesh.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import adapters_tokens  # noqa: E402
from benchmark import traffic as btraffic  # noqa: E402
from benchmark.reference import token_denoiser as rt  # noqa: E402
from benchmark.reference.xunet import flatten, nest  # noqa: E402
from diff3d_tpu.config import MeshConfig  # noqa: E402
from diff3d_tpu.config import test_config as make_tiny_config  # noqa: E402
from diff3d_tpu.config import token_test_config  # noqa: E402
from diff3d_tpu.models import (TokenDenoiser, UnsupportedModelError, XUNet,
                               build_model, build_xunet)  # noqa: E402
from diff3d_tpu.models import moe, sparse_attention  # noqa: E402
from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.ops.pallas_attention import selected_supports  # noqa: E402
from diff3d_tpu.ops.pallas_moe import expert_ffn_supports  # noqa: E402
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

from _token_helpers import make_batch, reference_loss  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "keye_vl2_tok_tiny.json")) as f:
    TINY = json.load(f)


@pytest.fixture(scope="module")
def tiny():
    cfg = adapters_tokens.build_config(TINY)
    mcfg = rt.model_dict(TINY)
    flat = rt.make_params(mcfg, jax.random.PRNGKey(7))()
    return {"cfg": cfg, "mcfg": mcfg, "flat": flat,
            "model": build_model(cfg)}


def run_program(model, flat, batch, mask):
    return jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m))(nest(flat), batch, mask)


# ------------------------------------------------------------ the forward

def test_parameter_tree_is_the_references(tiny):
    adapters_tokens.check_tree(tiny["cfg"], tiny["flat"])


def test_forward_float32_is_the_reference(tiny):
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    mask = jnp.array([True, False])
    got = run_program(tiny["model"], tiny["flat"], batch, mask)
    ref, load = jax.jit(lambda p: rt.forward(p, batch, mask, tiny["mcfg"]))(
        tiny["flat"])
    assert got.shape == (4, 16, 16, 3) and got.dtype == jnp.float32
    assert float(jnp.abs(ref).mean()) > 0.05
    # float32 on both sides: only the order of the sums differs
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)
    assert load.shape == (2, 8) and int(load.sum()) == 2 * 4 * 128 * 2


def test_forward_bfloat16_is_near_and_nearer_than_the_control(tiny):
    """bf16 operands (8 mantissa bits) through 2 layers: the gap to the
    float32 reference is rounding, a few hundredths of the output's
    size; the reference at 3 mantissa bits, the precision below, is
    several times farther."""
    cfg = dataclasses.replace(tiny["cfg"], model=dataclasses.replace(
        tiny["cfg"].model, dtype="bfloat16"))
    batch = make_batch(jax.random.PRNGKey(2), 4, 2)
    mask = jnp.array([True, False])
    got = run_program(build_model(cfg), tiny["flat"], batch, mask)
    ref = rt.forward(tiny["flat"], batch, mask, tiny["mcfg"])[0]
    low = rt.forward(tiny["flat"], batch, mask, tiny["mcfg"],
                     prec="fp8")[0]
    size = float(jnp.abs(ref).mean())
    gap = float(jnp.abs(got - ref).mean())
    control = float(jnp.abs(low - ref).mean())
    assert gap < 0.08 * size, (gap, size)
    assert control > 3 * gap, (control, gap, size)


def test_g_rows_equal_repeated_rows_and_must_divide(tiny):
    batch = make_batch(jax.random.PRNGKey(3), 16, 2)
    mask = jnp.array([True, False])
    shared = run_program(tiny["model"], tiny["flat"], batch, mask)
    rep = dict(batch, **{k: jnp.repeat(batch[k], 8, axis=0)
                         for k in ("logsnr", "R", "t", "K")})
    each = run_program(tiny["model"], tiny["flat"], rep,
                       jnp.repeat(mask, 8))
    np.testing.assert_allclose(shared, each, atol=1e-5, rtol=0)
    with pytest.raises(ValueError, match="must divide|divide"):
        tiny["model"].apply({"params": nest(tiny["flat"])},
                            make_batch(jax.random.PRNGKey(3), 16, 3),
                            cond_mask=jnp.ones((3,), bool))


@pytest.fixture
def kernel_forced(monkeypatch):
    """The registry's policy patched to what it resolves on a TPU process:
    ``sdpa(keep=)``, the experts' blocks and (as where a layer holds a
    share) the rows' way in and back at ``impl='auto'`` take their Pallas
    cores (in interpret mode, this being a CPU process).  No option of
    the program does this."""
    for op in ("sdpa_selected", "expert_rows", "expert_ffn",
               "expert_combine"):
        impls = dispatch._REGISTRY[op]
        monkeypatch.setitem(impls, "xla", impls["pallas"])


@pytest.fixture(scope="module")
def wide():
    """``keye_vl2_tok_tiny`` with the head dim, the hidden size and the
    experts' width at one lane tile (128, the kernels': rotary sections
    and the indexer's dim follow)."""
    config = dict(TINY, head_dim=128, hidden_size=128,
                  moe_intermediate_size=128,
                  rope_scaling=dict(TINY["rope_scaling"],
                                    mrope_section=[16, 24, 24]),
                  sa_config=dict(TINY["sa_config"], indexer_head_dim=64))
    cfg, mcfg = adapters_tokens.build_config(config), rt.model_dict(config)
    m = cfg.model
    assert selected_supports(
        jnp.zeros((1, m.q_chunk, m.num_attention_heads, 128)),
        *[jnp.zeros((1, 128, m.num_key_value_heads, 128))] * 2,
        jnp.ones((1, m.q_chunk, 128), bool))
    sds = jax.ShapeDtypeStruct
    assert expert_ffn_supports(
        sds((9, m.expert_block, 128), jnp.float32), sds((9,), jnp.int32),
        sds((8,), jnp.int32), *[sds((8, 128, 128), jnp.float32)] * 3)
    return {"cfg": cfg, "mcfg": mcfg, "model": build_model(cfg),
            "flat": rt.make_params(mcfg, jax.random.PRNGKey(11))()}


@pytest.mark.parametrize("core", ["xla", "pallas"])
def test_counters_of_a_traced_program(tiny, core, request):
    if core == "pallas":
        request.getfixturevalue("kernel_forced")
        tiny = request.getfixturevalue("wide")
    before = RECORDER.counters()
    batch = make_batch(jax.random.PRNGKey(4), 4, 2)
    jax.eval_shape(lambda p: tiny["model"].apply(
        {"params": p}, batch, cond_mask=jnp.array([True, False])),
        nest(tiny["flat"]))
    after = RECORDER.counters()
    d = {k: after[k] - before.get(k, 0) for k in after}
    assert d["conditioning.groups"] == 2 and d["conditioning.examples"] == 4
    # one per traced sdpa(keep=) site, a site a layer, by the core it took
    other = {"xla": "pallas", "pallas": "xla"}[core]
    assert d[f"sdpa.selected.{core}"] == 2
    assert not d.get(f"sdpa.selected.{other}")
    # and one per traced expert_outputs site, the chunk map's body: a
    # layer
    for site in ("experts", "experts.rows", "experts.combine"):
        assert d[f"{site}.{core}"] == 2
        assert not d.get(f"{site}.{other}")
    # the X-UNet's two, the selection's two, the experts' two, no other
    assert not [k for k, v in d.items() if v and not k.startswith(
        ("conditioning.", "compile.", "sdpa.selected.", "experts."))], d


def test_token_test_model_lowers_as_the_parent_did_on_a_cpu_process(
        monkeypatch):
    """``auto`` resolves to the XLA cores here: the program of the
    ``token_test`` preset is, to the letter, the program with the parent's
    ``sdpa(keep=)`` body and, in the layer, the parent's row gather,
    block scan and gather-and-sum written out, and holds no Pallas
    call."""
    cfg = token_test_config()
    model = build_model(cfg)
    batch = make_batch(jax.random.PRNGKey(5), 2, 2)
    mask = jnp.array([True, False])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batch, cond_mask=mask))["params"]

    def text():
        return jax.jit(lambda p, b, m: model.apply(
            {"params": p}, b, cond_mask=m)).lower(params, batch,
                                                  mask).as_text()
    mine = text()
    assert "tpu_custom_call" not in mine and "pallas" not in mine
    monkeypatch.setattr(
        sparse_attention, "sdpa",
        lambda q, k, v, keep: jax.nn.dot_product_attention(
            q, k, v, mask=keep[:, None]))

    def parents_scan(rows, e_blk, ends, w_gate, w_up, w_down):
        def one_block(_, inp):
            xb, e = inp
            f32 = jnp.float32
            h = (jax.nn.silu(jnp.dot(xb, w_gate[e],
                                     preferred_element_type=f32))
                 * jnp.dot(xb, w_up[e], preferred_element_type=f32))
            return None, jnp.dot(h.astype(xb.dtype), w_down[e])
        return jax.lax.scan(one_block, None, (rows, e_blk))[1]

    def parents_rows(x, token, ends, m):
        x0 = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        return x0[token].reshape(token.shape[0] // m, m, x.shape[1])

    def parents_sum(ys, at, gates, ends):
        n_blocks, m, D = ys.shape
        T, K = gates.shape
        y0 = jnp.concatenate([ys.reshape(n_blocks * m, D),
                              jnp.zeros((1, D), ys.dtype)])
        picked = y0[at].reshape(T, K, D).astype(jnp.float32)
        return (picked * gates[..., None]).sum(axis=1).astype(ys.dtype)
    parents = {"expert_rows": parents_rows, "expert_ffn": parents_scan,
               "expert_combine": parents_sum}
    took = []
    monkeypatch.setattr(
        moe.dispatch, "resolve", lambda op, impl, *a, **kw: took.append(
            (op, impl)) or dispatch.KernelImpl(op, "xla", parents[op]))
    assert mine == text()
    assert took == [(op, "auto") for op in parents] * 2


# ------------------------------------------------- the layers by themselves

def _attention_layer(topk, L=128, seed=0):
    """One SparseAttention at the tiny widths, its reference parameters,
    and tokens without the correlations that make index scores tie."""
    mcfg = dict(rt.model_dict(TINY), indexer_topk=topk)
    cfg = adapters_tokens.build_config(TINY).model
    layer = sparse_attention.SparseAttention(
        hidden=64, num_heads=4, num_kv_heads=2, head_dim=32,
        indexer_heads=4, indexer_dim=16, topk=topk, q_chunk=64,
        grid=(2, 8, 8), rope_theta=cfg.rope_theta,
        mrope_section=(4, 6, 6))
    flat = rt.make_params(mcfg, jax.random.PRNGKey(seed))()
    mine = {k[len("layers_0/"):]: v for k, v in flat.items()
            if k.startswith("layers_0/")}
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, L, 64))
    return layer, mine, mcfg, h


def _ref_attention(mine, mcfg, h, literal):
    def one(hb):
        u = rt.rms_norm(hb, mine["attn_norm/scale"], mcfg["rms_norm_eps"])
        return hb + rt.attention(u, mine.__getitem__, mcfg, "float32",
                                 literal)
    return jnp.stack([one(hb) for hb in h])


def _run_attention(layer, mine, h):
    params = nest({k[len("attn/"):]: v for k, v in mine.items()
                   if k.startswith("attn/")})
    return layer.apply({"params": params}, h, mine["attn_norm/scale"])


def test_sparse_attention_is_dense_gqa_when_every_key_is_kept():
    layer, mine, mcfg, h = _attention_layer(topk=128)
    got = _run_attention(layer, mine, h)
    # dense grouped-query attention, written out
    def dense(hb):
        u = rt.rms_norm(hb, mine["attn_norm/scale"], 1e-6)
        q = rt.mm(u, mine["attn/q_proj/kernel"], "float32").reshape(128, 4, 32)
        k = rt.mm(u, mine["attn/k_proj/kernel"], "float32").reshape(128, 2, 32)
        v = rt.mm(u, mine["attn/v_proj/kernel"], "float32").reshape(128, 2, 32)
        q = rt.rope(rt.rms_norm(q, mine["attn/q_norm/scale"], 1e-6), mcfg,
                    [4, 6, 6])
        k = rt.rope(rt.rms_norm(k, mine["attn/k_norm/scale"], 1e-6), mcfg,
                    [4, 6, 6])
        s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, 2, axis=1),
                       precision="highest") / np.sqrt(32.0)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1),
                       jnp.repeat(v, 2, axis=1), precision="highest")
        return hb + rt.mm(o.reshape(128, 128), mine["attn/o_proj/kernel"],
                          "float32")
    want = jnp.stack([dense(hb) for hb in h])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # and a larger topk than L changes nothing
    layer2 = layer.clone(topk=4096)
    np.testing.assert_allclose(_run_attention(layer2, mine, h), got,
                               atol=0, rtol=0)


def test_sparse_attention_is_the_gathered_reference_when_keys_are_dropped():
    layer, mine, mcfg, h = _attention_layer(topk=32)
    got = _run_attention(layer, mine, h)
    literal = _ref_attention(mine, mcfg, h, literal=True)    # argsort, gather
    masked = _ref_attention(mine, mcfg, h, literal=False)    # threshold mask
    # which keys the reference keeps, to tell the rows without a tie
    keep = jnp.stack([rt.selection(rt.index_matrix(
        rt.rms_norm(hb, mine["attn_norm/scale"], mcfg["rms_norm_eps"]),
        mine.__getitem__, mcfg, "float32"), 32) for hb in h])
    assert keep.shape == (2, 128, 128)
    np.testing.assert_allclose(got, masked, atol=2e-5, rtol=0)
    # "the 32 largest" and "at least the 32nd largest" are the same keys
    # where no index score ties with the 32nd (an all-zero relu does, in
    # a few rows): there the literal gather is comparable
    clean = np.asarray(keep.sum(-1) == 32)
    assert clean.mean() > 0.9 and bool((keep.sum(-1) >= 32).all())
    np.testing.assert_allclose(np.asarray(got)[clean],
                               np.asarray(literal)[clean], atol=2e-5, rtol=0)
    dense = _run_attention(layer.clone(topk=128), mine, h)
    assert float(jnp.abs(got - dense).max()) > 1e-3       # it does select


def test_forward_with_the_kernel_forced_is_the_reference(wide, kernel_forced):
    """The whole model, float32: every ``sdpa(keep=)`` and every chunk of
    routed tokens runs its Pallas core, on the layer's own selection and
    routing, inside its maps over examples, query tiles and chunks."""
    batch = make_batch(jax.random.PRNGKey(12), 4, 2)
    mask = jnp.array([True, False])
    before = RECORDER.counters()
    got = run_program(wide["model"], wide["flat"], batch, mask)
    for name in ("sdpa.selected.pallas", "experts.pallas",
                 "experts.rows.pallas", "experts.combine.pallas"):
        assert RECORDER.counters()[name] > before.get(name, 0)
    ref, _ = jax.jit(lambda p: rt.forward(p, batch, mask, wide["mcfg"]))(
        wide["flat"])
    assert float(jnp.abs(ref).mean()) > 0.05
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)


def test_mrope_turns_each_section_by_its_own_axis():
    cos, sin = sparse_attention.mrope_tables(2, 8, 8, 32, 1e7, (4, 6, 6))
    assert cos.shape == (128, 16)
    n = 1 * 64 + 3 * 8 + 5                               # frame 1, row 3, col 5
    inv = 1e7 ** (-np.arange(16) * 2.0 / 32)
    want = np.concatenate([1 * inv[:4], 3 * inv[4:10], 5 * inv[10:]])
    np.testing.assert_allclose(cos[n], np.cos(want), atol=1e-6)
    np.testing.assert_allclose(sin[n], np.sin(want), atol=1e-6)


def _expert_layer(held):
    return moe.RoutedExperts(num_experts=8, top_k=2, width=32, held=held,
                             token_chunk=128, block=16)


def test_expert_shares_add_up_to_the_uncut_layer():
    mcfg = rt.model_dict(TINY)
    flat = rt.make_params(mcfg, jax.random.PRNGKey(3))()
    mine = {k[len("layers_1/"):]: v for k, v in flat.items()
            if k.startswith("layers_1/")}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    scale = mine["moe_norm/scale"]

    def program(first, count):
        p = {"router": mine["moe/router"]}
        for n in ("w_gate", "w_up", "w_down"):
            p[n] = mine[f"moe/{n}"][first:first + count]
        out = _expert_layer((first, count)).apply({"params": p}, h, scale)
        return out - h

    u = rt.rms_norm(h.reshape(256, 64), scale, mcfg["rms_norm_eps"])
    whole, load = rt.experts(u, mine.__getitem__, mcfg, "float32", True)
    grouped, load2 = rt.experts(u, mine.__getitem__, mcfg, "float32", False)
    np.testing.assert_allclose(grouped, whole, atol=2e-5, rtol=0)
    assert int(load.sum()) == 256 * 2 and bool((load == load2).all())
    assert float(jnp.abs(whole).mean()) > 0.01
    all_held = program(0, 8).reshape(256, 64)
    np.testing.assert_allclose(all_held, whole, atol=2e-5, rtol=0)
    lo, hi = program(0, 4).reshape(256, 64), program(4, 4).reshape(256, 64)
    assert float(jnp.abs(lo).mean()) > 0.003 < float(jnp.abs(hi).mean())
    np.testing.assert_allclose(lo + hi, whole, atol=2e-5, rtol=0)
    # the reference given one share computes that share
    half = dict(mcfg, experts_held=[4, 4])
    cut = {k: (v[4:] if k in ("moe/w_gate", "moe/w_up", "moe/w_down")
               else v) for k, v in mine.items()}
    for literal in (True, False):
        part, _ = rt.experts(u, cut.__getitem__, half, "float32", literal)
        np.testing.assert_allclose(part, hi, atol=2e-5, rtol=0)


def test_experts_drop_no_token_under_any_load():
    """Every token to the same two experts: no capacity, nothing lost."""
    layer = _expert_layer((0, 8))
    h = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    p = layer.init(jax.random.PRNGKey(1), h, jnp.ones((64,)))["params"]
    p = dict(p, router=jnp.zeros((64, 8)).at[:, 2].set(0.0))
    # a constant logit vector routes every token to experts 0 and 1
    # (top_k breaks ties by the lower index), gates 1/2 each
    out = layer.apply({"params": p}, h, jnp.ones((64,))) - h
    u = rt.rms_norm(h, jnp.ones((64,)), 1e-6)

    def ffn(e):
        return (jax.nn.silu(u @ p["w_gate"][e]) * (u @ p["w_up"][e])
                ) @ p["w_down"][e]
    np.testing.assert_allclose(out, 0.5 * (ffn(0) + ffn(1)), atol=2e-5,
                               rtol=0)


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_a_token_that_is_not_finite_stays_its_own(bad):
    """The experts' cast to the compute dtype is tied to the call's first
    token (models/moe.py): what that token holds must not reach the
    weights every other token is multiplied by."""
    layer = _expert_layer((0, 8))
    h = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    p = layer.init(jax.random.PRNGKey(1), h, jnp.ones((64,)))["params"]
    clean = layer.apply({"params": p}, h, jnp.ones((64,)))
    got = layer.apply({"params": p}, h.at[0, 0].set(bad), jnp.ones((64,)))
    assert not bool(jnp.isfinite(got[0]).all())
    np.testing.assert_array_equal(np.asarray(got[1:]), np.asarray(clean[1:]))


def _lane_layer(held, seed=0):
    """A ``RoutedExperts`` at one lane tile of hidden size and expert
    width (what the experts' kernel takes), its parameters cut to the
    experts held, and two chunks of tokens."""
    layer = moe.RoutedExperts(num_experts=8, top_k=2, width=128, held=held,
                              token_chunk=128, block=16)
    h = jax.random.normal(jax.random.PRNGKey(seed), (256, 128))
    whole = moe.RoutedExperts(
        num_experts=8, top_k=2, width=128, held=(0, 8), token_chunk=128,
        block=16).init(jax.random.PRNGKey(seed + 1), h,
                       jnp.ones((128,)))["params"]
    first, count = held
    cut = {k: (v if k == "router" else v[first:first + count])
           for k, v in whole.items()}
    return layer, cut, h


@pytest.mark.parametrize("held", [(0, 8), (0, 4), (4, 4), (7, 1)])
def test_expert_layer_with_the_kernel_forced_is_the_layer(held, request):
    """The layer as the token denoiser calls it (norm, routing, chunks of
    tokens under ``lax.map``), all experts held or a share of them, alone
    and under the sampler's ``vmap`` over two objects."""
    layer, p, h = _lane_layer(held)
    scale = jnp.ones((128,))
    two = jnp.stack([h, h[::-1]])

    def both():                     # traced anew at each call
        run = lambda p, h: layer.apply({"params": p}, h, scale) - h  # noqa
        return run(p, h), jax.vmap(run, in_axes=(None, 0))(p, two)
    want, want_two = both()
    names = ("experts.pallas", "experts.rows.pallas",
             "experts.combine.pallas")
    before = [RECORDER.counters().get(n, 0) for n in names]
    request.getfixturevalue("kernel_forced")
    got, got_two = both()
    assert [RECORDER.counters()[n] for n in names] == [b + 2 for b in before]
    assert float(jnp.abs(want).mean()) > 0.003
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_two, want_two, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_two[1], want[::-1], atol=2e-5, rtol=0)


def test_expert_shares_add_up_with_the_kernel_forced(kernel_forced):
    scale = jnp.ones((128,))
    parts = []
    for held in [(0, 8), (0, 3), (3, 5)]:
        layer, p, h = _lane_layer(held)
        parts.append(layer.apply({"params": p}, h, scale) - h)
    whole, lo, hi = parts
    assert float(jnp.abs(lo).mean()) > 0.003 < float(jnp.abs(hi).mean())
    np.testing.assert_allclose(lo + hi, whole, atol=2e-5, rtol=0)


def test_expert_layer_gradient_with_the_kernel_forced_is_the_layers(
        request):
    """The token train step's pair: forward through the kernel, backward
    through the scan's VJP."""
    layer, p, h = _lane_layer((0, 8), seed=2)
    scale = jnp.ones((128,))

    def loss(p, h):
        return jnp.sum(layer.apply({"params": p}, h, scale) ** 2)
    want = jax.grad(loss, argnums=(0, 1))(p, h)
    request.getfixturevalue("kernel_forced")
    got = jax.grad(loss, argnums=(0, 1))(p, h)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(r).mean()) > 1e-4
        np.testing.assert_allclose(g, r, atol=2e-4, rtol=1e-4)


# ------------------------------------------------------ sampler and trainer

def test_one_synthesized_view_is_the_references(tiny):
    from benchmark import run as brun
    from benchmark.drivers import sample_tokens

    mix = dict(btraffic.load("sample_1obj_2views_ddim8"), steps=4,
               limits={"image_gap": 1e-4})
    d = sample_tokens.Driver(config=TINY, mix=mix, seed=2147484123,
                             chips=1, spans=brun.Spans())
    d.setup()
    window = d.measure(0.0)
    assert window["calls"] == 1 and window["model_steps"] == 4
    assert d.outs[0].shape == (1, 1, 8, 16, 16, 3)
    numbers = dict((n, (v, lim)) for n, v, lim in d.verify())
    assert numbers["image_gap"][0] <= 1e-4, numbers
    assert set(numbers) == {"image_gap"}
    assert len(d.notes["image_gap_by_weight"]) == 8
    assert d.notes["expert_load_max_over_mean"] >= 1.0


def test_three_train_steps_follow_the_references_loss_and_gradient(tiny):
    from diff3d_tpu.train.state import create_train_state
    from diff3d_tpu.train.step import make_train_step

    cfg, mcfg, flat = tiny["cfg"], tiny["mcfg"], tiny["flat"]
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, global_batch=4, warmup_examples=4))
    dcfg = adapters_tokens.diffusion_dict(cfg)
    ds = btraffic.ViewDataset(5, num_objects=4, num_views=4, imgsize=16)
    samples = [ds.sample(i, np.random.default_rng(i)) for i in range(4)]
    batch = {k: np.stack([s[k] for s in samples]) for k in samples[0]}
    batch["imgs"] = np.clip((batch["imgs"] + 1) * 127.5, 0, 255).astype(
        np.uint8)
    step = make_train_step(tiny["model"], cfg, None, donate=False)
    state = create_train_state(nest(flat), cfg.train)
    base = jax.random.PRNGKey(11)
    ref_fn = jax.jit(jax.value_and_grad(
        lambda p, k: reference_loss(
            # the experts' literal loop has a gradient; the selection is
            # a mask
            lambda mb, m: rt.forward(p, mb, m, mcfg,
                                     literal={"experts"})[0],
            batch, k, dcfg)))
    b1 = cfg.train.betas[0]
    for i in range(3):
        params = flatten(adapters_tokens.adapters._plain(state.params))
        loss, grads = ref_fn(params, jax.random.fold_in(base, i))
        new_state, metrics = step(state, batch, base)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * float(loss)
        norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
        assert abs(float(metrics["grad_norm"]) - norm) < 1e-3 * norm
        if i == 0:
            import optax
            mu = next(s.mu for s in new_state.opt_state
                      if isinstance(s, optax.ScaleByAdamState))
            got = {k: v / (1.0 - b1) for k, v in flatten(
                adapters_tokens.adapters._plain(mu)).items()}
            indexer = [k for k in grads if "/indexer_" in k]
            assert len(indexer) == 3 * 2
            for k, g in grads.items():
                scale = float(jnp.abs(g).max())
                if k in indexer:
                    # the selection is a threshold: the epsilon loss has
                    # no gradient for the indexer (docs/DESIGN.md)
                    assert scale == 0.0 and not np.any(np.asarray(got[k]))
                else:
                    assert scale > 0.0, k
                    np.testing.assert_allclose(got[k], g, atol=2e-3 * scale,
                                               rtol=0, err_msg=k)
        state = new_state


# ----------------------------------------------------------- entry points

def test_build_model_reads_the_kind_of_model():
    assert isinstance(build_model(make_tiny_config()), XUNet)
    assert isinstance(build_model(token_test_config()), TokenDenoiser)
    assert isinstance(build_xunet(make_tiny_config(), "here"), XUNet)
    with pytest.raises(UnsupportedModelError, match="here supports"):
        build_xunet(token_test_config(), "here")
    bad = dataclasses.replace(token_test_config(), model=object())
    with pytest.raises(UnsupportedModelError):
        build_model(bad)


@pytest.mark.parametrize("site", ["trainer", "abstract_state", "sampler",
                                  "shardcheck", "rngcheck"])
def test_build_model_at_the_former_xunet_sites(site, tmp_path):
    cfg = token_test_config()
    if site == "trainer":
        from diff3d_tpu.train import Trainer
        assert isinstance(Trainer(cfg, workdir=str(tmp_path)).model,
                          TokenDenoiser)
    elif site == "abstract_state":
        from diff3d_tpu.cli._common import build_abstract_state
        st = build_abstract_state(cfg)
        assert st.params["layers_0"]["moe"]["w_gate"].shape == (8, 64, 32)
    elif site == "sampler":
        from diff3d_tpu.sampling import Sampler
        from diff3d_tpu.train.trainer import init_params
        model = build_model(cfg)
        params = jax.eval_shape(
            lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
        low = Sampler(model, params, cfg, sampler_kind="ddim",
                      steps=2).lower_step_many(1, 2)
        assert "d3d.experts" in low.as_text(debug_info=True)
    elif site == "shardcheck":
        from diff3d_tpu.analysis import shardcheck
        src = open(shardcheck.__file__).read()
        assert "build_model(cfg)" in src and "XUNet(" not in src
    elif site == "rngcheck":
        from diff3d_tpu.analysis import rngcheck
        src = open(rngcheck.__file__).read()
        assert "build_model(cfg)" in src and "XUNet(" not in src


@pytest.mark.parametrize("entry", ["serve_cli", "worker", "cascade",
                                   "convert_cli"])
def test_xunet_only_entry_points_refuse_a_token_config(entry, tmp_path):
    cfg = token_test_config()
    with pytest.raises(UnsupportedModelError, match="X-UNet only"):
        if entry == "serve_cli":
            from diff3d_tpu.cli import serve_cli
            serve_cli.build_service(serve_cli.build_parser().parse_args(
                ["--config", "token_test", "--init", "random",
                 "--port", "0"]))
        elif entry == "worker":
            from diff3d_tpu.serving.worker import boot_worker
            boot_worker(cfg, name="w", devices=[0])
        elif entry == "cascade":
            from diff3d_tpu.cascade import CascadePlan, CascadeSampler
            CascadeSampler(build_model(cfg), {}, cfg, CascadePlan.parse(
                "draft=8:ddim:2,refine=16:ddim:4@t0.5"))
        elif entry == "convert_cli":
            from diff3d_tpu.cli import convert_cli
            convert_cli.main(["--torch_ckpt", str(tmp_path / "none.pt"),
                              "--out", str(tmp_path / "o"),
                              "--config", "token_test"])


def test_train_cli_trains_and_eval_cli_samples_a_token_config(tmp_path):
    from diff3d_tpu.cli import eval_cli, train_cli

    wd = str(tmp_path)
    train_cli.main(["--synthetic", "--config", "token_test", "--steps", "2",
                    "--batch", "8", "--workdir", wd, "--num_workers", "0"])
    with open(os.path.join(wd, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["step"] == 2 and np.isfinite(recs[-1]["loss"])
    out = str(tmp_path / "eval.jsonl")
    eval_cli.main(["--model", os.path.join(wd, "checkpoints"),
                   "--synthetic_scenes", "--config", "token_test",
                   "--objects", "2", "--steps", "2", "--max_views", "2",
                   "--sampler", "ddim", "--out", out])
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    assert rec["objects"] == 2 and np.isfinite(rec["psnr_per_w"]).all()


# ------------------------------------------------------- the expert axis

def test_expert_rule_and_a_1x2_mesh_lowering(tiny):
    from jax.sharding import PartitionSpec as P

    from diff3d_tpu.parallel import make_mesh

    env = make_mesh(MeshConfig(data_parallel=1, model_parallel=2,
                               param_sharding="tp"),
                    devices=jax.devices()[:2])
    params = nest(tiny["flat"])
    table = env.param_spec_table(params)
    for n in ("w_gate", "w_up", "w_down"):
        assert table[f"['layers_0']['moe']['{n}']"] == str(
            ("model", None, None)), table
    assert table["['layers_0']['moe']['router']"] == str((None, None))
    sh = env.params(params)
    assert sh["layers_1"]["moe"]["w_up"].spec == P("model", None, None)
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    mask = jnp.array([True, False])
    fn = jax.jit(lambda p, b, m: tiny["model"].apply(
        {"params": p}, b, cond_mask=m),
        in_shardings=(sh, env.replicated(), env.replicated()),
        out_shardings=env.replicated())
    compiled = fn.lower(params, batch, mask).compile()
    got = compiled(jax.device_put(params, sh), batch, mask)
    want = run_program(tiny["model"], tiny["flat"], batch, mask)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
