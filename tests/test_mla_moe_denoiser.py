"""The token denoiser with multi-head latent attention under sandwich
norms, a leading dense layer and sigmoid-routed experts beside a shared
expert (models/latent_attention.py, models/moe.py ``route(scoring=)`` and
``post_norm``, models/token_denoiser.py ``DecoderLayer(dense=)``) against
its plain reference (benchmark/reference/mla_moe_denoiser.py: float32,
per-head latent attention, the published router) at the tiny
configuration, seeded weights, on the CPU.
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import adapters_mla_moe  # noqa: E402
from benchmark.reference import mla_moe_denoiser as rm  # noqa: E402
from benchmark.reference.xunet import nest  # noqa: E402
from diff3d_tpu.config import MeshConfig, mla_moe_test_config  # noqa: E402
from diff3d_tpu.models import TokenDenoiser, build_model  # noqa: E402
from diff3d_tpu.models import moe  # noqa: E402
from diff3d_tpu.models.token_denoiser import DecoderLayer  # noqa: E402
from diff3d_tpu.ops import attention, dispatch  # noqa: E402
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

from _token_helpers import make_batch  # noqa: E402
from test_hybrid_denoiser import perturbed  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")
with open(os.path.join(CONFIGS, "pangu_ultra_moe_tok_tiny.json")) as f:
    TINY = json.load(f)
MASK = jnp.array([True, False])


@pytest.fixture(scope="module")
def tiny():
    cfg = adapters_mla_moe.build_config(TINY)
    mcfg = rm.model_dict(TINY)
    flat = perturbed(rm.make_params(mcfg, jax.random.PRNGKey(7))(),
                     jax.random.PRNGKey(8))
    return {"cfg": cfg, "mcfg": mcfg, "flat": flat,
            "model": build_model(cfg)}


def run_program(model, flat, batch, mask=MASK):
    return jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m))(nest(flat), batch, mask)


def _layer_params(flat, i):
    pre = f"layers_{i}/"
    return {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}


# ------------------------------------------------------------ the forward

def test_the_preset_is_the_tiny_configuration_and_the_tree_the_references(
        tiny):
    assert tiny["cfg"].model == mla_moe_test_config().model
    assert isinstance(build_model(mla_moe_test_config()), TokenDenoiser)
    adapters_mla_moe.check_tree(tiny["cfg"], tiny["flat"])
    m = tiny["cfg"].model
    assert m.mixers == ("latent_attention",) * 3      # from kv_lora_rank
    assert [m.dense_layer(i) for i in range(3)] == [True, False, False]
    shapes = jax.eval_shape(lambda: tiny["model"].init(
        jax.random.PRNGKey(0), make_batch(jax.random.PRNGKey(0), 2, 2),
        cond_mask=MASK))["params"]
    assert set(shapes["layers_0"]) == {"attn_norm", "attn", "attn_post_norm",
                                       "mlp_norm", "mlp", "mlp_post_norm"}
    assert set(shapes["layers_1"]) == {"attn_norm", "attn", "attn_post_norm",
                                       "moe_norm", "moe", "mlp",
                                       "moe_post_norm"}
    assert set(shapes["layers_1"]["attn"]) == {
        "q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
        "kv_b_proj", "o_proj"}
    assert shapes["layers_0"]["mlp"]["w1"]["kernel"].shape == (256, 768)
    assert shapes["layers_1"]["mlp"]["w1"]["kernel"].shape == (256, 128)
    assert shapes["layers_1"]["attn"]["kv_a_proj"]["kernel"].shape == (
        256, 32 + 16)
    assert shapes["layers_1"]["attn"]["o_proj"]["kernel"].shape == (128, 256)


@pytest.mark.parametrize("literal", [True, False])
def test_forward_float32_is_the_reference(tiny, literal):
    """Float32 operands through the three layers: the same numbers up to
    float32 rounding (the program turns keys and queries before one
    concatenated dot, the reference dots the two parts apart)."""
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    got = run_program(tiny["model"], tiny["flat"], batch)
    ref, load = jax.jit(lambda p: rm.forward(
        p, batch, MASK, tiny["mcfg"], literal=literal))(tiny["flat"])
    assert got.shape == (4, 16, 16, 3) and got.dtype == jnp.float32
    assert float(jnp.abs(ref).mean()) > 0.05
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)
    # the dense layer routes nothing; 8 of 16 experts hold about half of
    # 4 x 128 x 4 rows
    assert load.shape == (3, 8) and int(load[0].sum()) == 0
    assert 0.3 < float(load[1:].sum(axis=1).mean()) / (4 * 128 * 4) < 0.7


def test_forward_bfloat16_is_near_and_nearer_than_the_control_and_faults(
        tiny):
    """bf16 operands and bf16-held parameters, the reference reading the
    same parameters: the gap is rounding; the reference at 3 mantissa
    bits is several times farther, and so are the cell's two planted
    faults (no sandwich norms; no held experts)."""
    cfg = dataclasses.replace(tiny["cfg"], model=dataclasses.replace(
        tiny["cfg"].model, dtype="bfloat16", param_dtype="bfloat16"))
    flat = {k: v.astype(jnp.bfloat16) for k, v in tiny["flat"].items()}
    batch = make_batch(jax.random.PRNGKey(2), 4, 2)
    got = run_program(build_model(cfg), flat, batch)
    fwd = lambda cfg, **kw: rm.forward(flat, batch, MASK, cfg,  # noqa: E731
                                       **kw)[0]
    ref = fwd(tiny["mcfg"])
    size = float(jnp.abs(ref).mean())
    gap = float(jnp.abs(got - ref).mean())
    assert gap < 0.03 * size, (gap, size)
    low = fwd(tiny["mcfg"], prec="fp8")
    assert float(jnp.abs(low - ref).mean()) > 3 * gap
    for fault in ("post_norms_dropped", "experts_dropped"):
        bad = fwd(dict(tiny["mcfg"], **{fault: True}))
        assert float(jnp.abs(bad - ref).mean()) > 2 * gap, fault


def test_the_tree_is_held_in_bfloat16_under_param_dtype(tiny):
    """Every leaf is made in the storage dtype, and the experts' stacks,
    already in the compute dtype, get no float32 round trip (no ``tie``);
    float32 storage keeps it."""
    m = dataclasses.replace(tiny["cfg"].model, dtype="bfloat16",
                            param_dtype="bfloat16")
    batch = make_batch(jax.random.PRNGKey(0), 2, 2)
    model = TokenDenoiser(m)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    dtypes = {str(x.dtype) for x in jax.tree.leaves(params)}
    assert dtypes == {"bfloat16"}
    text = jax.jit(lambda p, b: model.apply(
        {"params": p}, b, cond_mask=MASK)).lower(params, batch).as_text()
    assert "tensor<8x256x64xf32>" not in text
    f32 = dataclasses.replace(m, param_dtype="float32")
    params = jax.eval_shape(lambda: TokenDenoiser(f32).init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    assert {str(x.dtype) for x in jax.tree.leaves(params)} == {"float32"}
    text = jax.jit(lambda p, b: TokenDenoiser(f32).apply(
        {"params": p}, b, cond_mask=MASK)).lower(params, batch).as_text()
    assert "tensor<8x256x64xf32>" in text


@pytest.mark.parametrize("bad", [
    {"param_dtype": "float16"},
    {"param_dtype": "bfloat16"},             # compute float32
    {"first_k_dense_replace": 3},            # no expert layer after
    {"scoring_func": "softmax"},             # 2.5 scales sigmoid gates only
    {"v_head_dim": 0}])
def test_the_config_refuses_what_the_layers_do_not_compute(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(mla_moe_test_config().model, **bad).validate()


# ---------------------------------------------------------- the attention

def test_latent_attention_rotates_by_the_sequence_index(tiny):
    """The layer's first half against the reference's, and the rotary
    key is what it is shared for: turning the tokens' order changes the
    answer, which a layer without position would not."""
    m, mcfg = tiny["cfg"].model, tiny["mcfg"]
    mine = _layer_params(tiny["flat"], 1)
    LP = mine.__getitem__
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 256))
    layer = DecoderLayer(m, "latent_attention")
    got = layer.apply({"params": nest(mine)}, h, method=lambda s, x: s.attn(
        x, s.attn_norm(256), post_scale=s.attn_post_norm(256)))
    want = jnp.stack([rm.attention_half(hb, LP, mcfg, "float32")
                      for hb in h])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    flip = layer.apply({"params": nest(mine)}, h[:, ::-1],
                       method=lambda s, x: s.attn(
                           x, s.attn_norm(256),
                           post_scale=s.attn_post_norm(256)))[:, ::-1]
    assert float(jnp.abs(flip - got).max()) > 1e-2


def test_sdpa_takes_values_narrower_than_queries_and_keys(monkeypatch):
    """``v`` 128 wide beside ``q`` / ``k`` 192 wide (latent attention's
    widths at fewer heads): the XLA expression, float32 scores over
    ``192^-1/2``, against an einsum in float32; every core but an
    explicit ``pallas`` resolves to it, on a TPU process too."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 64, 4, 192))
    kk = jax.random.normal(k[1], (1, 256, 4, 192))
    v = jax.random.normal(k[2], (1, 256, 4, 128))
    s = jnp.einsum("bthd,bshd->bhts", q, kk,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(192)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)
    before = RECORDER.counters().get("sdpa.plain.xla", 0)
    got = attention.sdpa(q, kk, v)
    assert got.shape == (1, 64, 4, 128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert RECORDER.counters()["sdpa.plain.xla"] == before + 1
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    for shape_q, L in (((1, 256, 128, 192), 8192), ((1, 512, 128, 192),
                                                    8192)):
        ops = (jax.ShapeDtypeStruct(shape_q, jnp.bfloat16),
               jax.ShapeDtypeStruct((1, L, 128, 192), jnp.bfloat16),
               jax.ShapeDtypeStruct((1, L, 128, 128), jnp.bfloat16))
        assert dispatch.resolve("sdpa", "auto", *ops).name == "xla"
    with pytest.raises(ValueError, match="explicitly"):
        dispatch.resolve("sdpa", "pallas", *ops)
    # equal widths keep jax.nn.dot_product_attention as they were
    same = attention.sdpa(q, kk, kk)
    np.testing.assert_array_equal(same, jax.nn.dot_product_attention(
        q, kk, kk))


# ----------------------------------------------------------- the router

@pytest.mark.parametrize("E,k", [(256, 8), (16, 4)])
def test_the_sigmoid_gate_normalises_the_top_k_and_picks_the_softmaxs(E, k):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(E), (512, E))
    ids, gates = moe.route(logits, k, "sigmoid", 2.5)
    s = jax.nn.sigmoid(logits)
    top = jnp.take_along_axis(s, ids, axis=-1)
    np.testing.assert_allclose(gates, 2.5 * top / top.sum(-1, keepdims=True),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-6)
    soft_ids, _ = moe.route(logits, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(soft_ids))
    r_ids, r_gates = rm.routing(
        logits, {"moe/router": jnp.eye(E)}.__getitem__,
        {"num_experts_per_tok": k, "routed_scaling_factor": 2.5}, "float32")
    np.testing.assert_array_equal(np.asarray(r_ids), np.asarray(ids))
    np.testing.assert_allclose(r_gates, gates, atol=1e-6, rtol=1e-6)


# ------------------------------------------------ the layer by its shares

def test_two_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """16 experts top-4: the two chips' shares ``(0, 8)`` and ``(8, 8)``
    of one expert layer's feed-forward, taken before the post-norm (a
    layer built without it), with what every chip computes alike (the
    norm, the router, the shared expert) counted once, add up to the
    uncut reference; every chip's shared part is the same number."""
    config = dict(TINY, sandwich_norm=False)
    uncut = rm.model_dict(dict(config, n_routed_experts=16,
                               experts_held=[0, 16]))
    flat = perturbed(rm.make_params(uncut, jax.random.PRNGKey(3))(),
                     jax.random.PRNGKey(4))
    mine = _layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 256))
    want = jnp.stack([rm.ffn_half(hb, mine.__getitem__, uncut, "float32",
                                  False, True)[0] for hb in h]) - h
    assert float(jnp.abs(want).mean()) > 0.05

    def share(first, shared: bool):
        m = adapters_mla_moe.build_config(
            dict(config, experts_held=[first, 8])).model
        if not shared:
            m = dataclasses.replace(m, shared_intermediate_size=0)
        p = {k: (v[first:first + 8] if k in ("moe/w_gate", "moe/w_up",
                                             "moe/w_down") else v)
             for k, v in mine.items() if shared or not k.startswith("mlp/")}
        return DecoderLayer(m, "latent_attention").apply(
            {"params": nest(p)}, h, method="feed_forward") - h
    routed = [share(8 * s, False) for s in range(2)]
    both = [share(8 * s, True) for s in range(2)]
    alike = [b - a for a, b in zip(routed, both)]
    for s in range(2):
        assert float(jnp.abs(routed[s]).mean()) > 1e-3, s
    np.testing.assert_allclose(alike[1], alike[0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(sum(routed) + alike[0], want, atol=3e-5,
                               rtol=0)
    # the reference given one share computes that share
    cut = {k: (v[8:16] if k in ("moe/w_gate", "moe/w_up", "moe/w_down")
               else v) for k, v in mine.items()}
    part = dict(uncut, experts_held=[8, 8])
    for literal in (True, False):
        got = jnp.stack([rm.ffn_half(hb, cut.__getitem__, part, "float32",
                                     False, literal)[0] for hb in h]) - h
        np.testing.assert_allclose(got, both[1], atol=3e-5, rtol=0)


def test_counters_once_per_traced_site(tiny, monkeypatch):
    """``attention.latent`` a latent-attention site, ``router.sigmoid`` a
    sigmoid-routed expert site, ``residual.post_norm`` a half with a
    sandwich norm; beside ``experts.held`` / ``experts.of`` (8 / 16 here,
    8 / 256 at the cell's widths, where every expert op is XLA's on a TPU
    process too: one expert's matrices are past ``expert_ffn``'s VMEM
    budget, and a row of 7680 is no whole number of ``(8, 128)`` tiles
    for the row kernels' DMAs).  The latent core is ``sdpa_latent``'s,
    never plain ``sdpa``'s: its XLA expression here, its kernel on a TPU
    process at the cell's widths."""
    def traced(cfg, model, params, B):
        before = RECORDER.counters()
        batch = jax.eval_shape(lambda: make_batch(
            jax.random.PRNGKey(4), B, 2, H=cfg.model.H))
        jax.eval_shape(lambda p, b: model.apply(
            {"params": p}, b, cond_mask=MASK), params, batch)
        after = RECORDER.counters()
        return {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}
    d = traced(tiny["cfg"], tiny["model"], nest(tiny["flat"]), 4)
    assert d == {"conditioning.groups": 2, "conditioning.examples": 4,
                 "experts.held": 8, "experts.of": 16, "experts.shared": 2,
                 "experts.xla": 2, "experts.rows.xla": 2,
                 "experts.combine.xla": 2, "attention.latent": 3,
                 "router.sigmoid": 2, "residual.post_norm": 6,
                 "sdpa.latent.xla": 3}
    with open(os.path.join(CONFIGS, "pangu_ultra_moe_tok128.json")) as f:
        cfg = adapters_mla_moe.build_config(json.load(f))
    from diff3d_tpu.train.trainer import init_params
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    d = traced(cfg, model, params, 16)
    assert d == {"conditioning.groups": 2, "conditioning.examples": 16,
                 "experts.held": 8, "experts.of": 256, "experts.shared": 4,
                 "experts.xla": 4, "experts.rows.xla": 4,
                 "experts.combine.xla": 4, "attention.latent": 5,
                 "router.sigmoid": 4, "residual.post_norm": 10,
                 "sdpa.latent.pallas": 5}


# ------------------------------------------------------ sampler and CLIs

def test_train_cli_trains_and_eval_cli_samples_the_preset(tmp_path):
    from diff3d_tpu.cli import eval_cli, train_cli

    wd = str(tmp_path)
    train_cli.main(["--synthetic", "--config", "mla_moe_test", "--steps",
                    "2", "--batch", "8", "--workdir", wd, "--num_workers",
                    "0"])
    with open(os.path.join(wd, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["step"] == 2 and np.isfinite(recs[-1]["loss"])
    out = str(tmp_path / "eval.jsonl")
    eval_cli.main(["--model", os.path.join(wd, "checkpoints"),
                   "--synthetic_scenes", "--config", "mla_moe_test",
                   "--objects", "2", "--steps", "2", "--max_views", "2",
                   "--sampler", "ddim", "--out", out])
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    assert rec["objects"] == 2 and np.isfinite(rec["psnr_per_w"]).all()


# ------------------------------------------------------- sharding rules

def test_every_new_parameter_has_a_rule_and_a_1x2_mesh_lowering(tiny):
    """The latent projections whole on every device, the expansions by
    whole heads (column-parallel), ``o_proj`` row-parallel, the norms
    replicated; the dense layer's MLP as the shared expert's."""
    from diff3d_tpu.parallel import make_mesh

    env = make_mesh(MeshConfig(data_parallel=1, model_parallel=2,
                               param_sharding="tp"),
                    devices=jax.devices()[:2])
    params = nest(tiny["flat"])
    table = env.param_spec_table(params)
    want = {
        "['layers_1']['attn']['q_a_proj']['kernel']": (None, None),
        "['layers_1']['attn']['kv_a_proj']['kernel']": (None, None),
        "['layers_1']['attn']['q_b_proj']['kernel']": (None, "model"),
        "['layers_1']['attn']['kv_b_proj']['kernel']": (None, "model"),
        "['layers_1']['attn']['o_proj']['kernel']": ("model", None),
        "['layers_1']['attn']['q_a_norm']['scale']": (None,),
        "['layers_1']['attn']['kv_a_norm']['scale']": (None,),
        "['layers_1']['attn_post_norm']['scale']": (None,),
        "['layers_1']['moe_post_norm']['scale']": (None,),
        "['layers_1']['moe']['w_gate']": ("model", None, None),
        "['layers_0']['mlp']['w1']['kernel']": (None, "model"),
        "['layers_0']['mlp']['w2']['kernel']": ("model", None),
        "['layers_0']['mlp_post_norm']['scale']": (None,),
    }
    for p, spec in want.items():
        assert table[p] == str(spec), p
    new = [p for p in table if re.search(r"_a_proj|_b_proj|post_norm", p)]
    assert len(new) == 3 * 4 + 3 * 2
    sh = env.params(params)
    model = tiny["model"]
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    fn = jax.jit(lambda p, b, m: model.apply({"params": p}, b, cond_mask=m),
                 in_shardings=(sh, env.replicated(), env.replicated()),
                 out_shardings=env.replicated())
    got = fn.lower(params, batch, MASK).compile()(
        jax.device_put(params, sh), batch, MASK)
    np.testing.assert_allclose(got, run_program(model, tiny["flat"], batch),
                               atol=2e-5, rtol=0)
