"""The token denoiser on a ``qwen3_next`` block: Gated DeltaNet mixers
(models/gated_deltanet.py, ops/delta.py) three to one with gated full
attention (models/token_layers.py ``FullAttention(gated=True)``),
zero-centred norms (models/sparse_attention.py ``Scale(centred=True)``),
softmax-routed experts beside a gated shared expert (``GatedMLP(gated=
True)``), against its plain reference (benchmark/reference/
gdn_moe_denoiser.py: float32, the delta rule token by token, the
published router) at the tiny configuration, seeded weights, on the CPU.
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

from benchmark import adapters_gdn_moe  # noqa: E402
from benchmark import traffic as btraffic  # noqa: E402
from benchmark.reference import gdn_moe_denoiser as rm  # noqa: E402
from benchmark.reference.xunet import flatten, nest  # noqa: E402
from diff3d_tpu.config import MeshConfig, gdn_moe_test_config  # noqa: E402
from diff3d_tpu.models import TokenDenoiser, build_model  # noqa: E402
from diff3d_tpu.models.token_denoiser import DecoderLayer  # noqa: E402
from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

from _token_helpers import make_batch  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")
with open(os.path.join(CONFIGS, "qwen3_next_tok_tiny.json")) as f:
    TINY = json.load(f)
MASK = jnp.array([True, False])


def spread(flat, key):
    """``make_params`` makes zero-centred norm weights 0 and the gated
    norm's 1: here each such leaf, and ``dt_bias``, gets a spread, so that
    a weight applied in the wrong place shows."""
    @jax.jit
    def one(v, i):
        return v + 0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                           v.shape)

    return {k: one(v, i) if k.endswith(("/scale", "/dt_bias")) else v
            for i, (k, v) in enumerate(flat.items())}


@pytest.fixture(scope="module")
def tiny():
    cfg = adapters_gdn_moe.build_config(TINY)
    mcfg = rm.model_dict(TINY)
    flat = spread(rm.make_params(mcfg, jax.random.PRNGKey(7))(),
                  jax.random.PRNGKey(8))
    return {"cfg": cfg, "mcfg": mcfg, "flat": flat,
            "model": build_model(cfg)}


_JITTED = {}


def run_program(model, flat, batch):
    """The program's forward, one compiled function a model."""
    if id(model) not in _JITTED:
        _JITTED[id(model)] = (model, jax.jit(lambda p, b: model.apply(
            {"params": p}, b, cond_mask=MASK)))
    return _JITTED[id(model)][1](nest(flat), batch)


def run_reference(mcfg, flat, batch, **kw):
    """The reference's answer and the held experts' load, one compiled
    function a configuration."""
    key = json.dumps([mcfg, kw], sort_keys=True)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda p, b: rm.forward(p, b, MASK, mcfg,
                                                       **kw))
    return _JITTED[key](flat, batch)


def _layer_params(flat, i):
    pre = f"layers_{i}/"
    return {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}


def _half(layer, params, h, half):
    """The layer's ``half`` (``"mixer"`` or ``"ffn"``) on ``h``."""
    def mixer(s, x):
        name = s.halves[0]
        return getattr(s, name)(x, getattr(s, name + "_norm")(x.shape[-1]))
    return jax.jit(lambda p, x: layer.apply({"params": p}, x, method=(
        mixer if half == "mixer" else "feed_forward")))(nest(params), h)


# ------------------------------------------------------------ the forward

def test_the_preset_is_the_tiny_configuration_and_the_tree_the_references(
        tiny):
    assert tiny["cfg"].model == gdn_moe_test_config().model
    assert isinstance(build_model(gdn_moe_test_config()), TokenDenoiser)
    adapters_gdn_moe.check_tree(tiny["cfg"], tiny["flat"])
    m = tiny["cfg"].model
    assert m.mixers == ("linear_attention",) * 3 + ("full_attention",)
    assert m.rotary_dim == 8
    shapes = jax.eval_shape(lambda: tiny["model"].init(
        jax.random.PRNGKey(0), make_batch(jax.random.PRNGKey(0), 2, 2),
        cond_mask=MASK))["params"]
    assert set(shapes["layers_0"]) == {"linear_attn_norm", "linear_attn",
                                       "moe_norm", "moe", "mlp"}
    assert set(shapes["layers_3"]) == {"attn_norm", "attn", "moe_norm",
                                       "moe", "mlp"}
    assert set(shapes["layers_0"]["linear_attn"]) == {
        "in_proj_qkvz", "in_proj_ba", "conv1d", "dt_bias", "A_log", "norm",
        "out_proj"}
    assert set(shapes["layers_0"]["linear_attn"]["conv1d"]) == {"kernel"}
    assert shapes["layers_0"]["linear_attn"]["in_proj_qkvz"][
        "kernel"].shape == (64, 2 * 32 + 2 * 64)
    assert shapes["layers_3"]["attn"]["q_proj"]["kernel"].shape == (64, 256)
    assert shapes["layers_3"]["attn"]["q_norm"]["scale"].shape == (32,)
    assert shapes["layers_1"]["mlp"]["gate"]["kernel"].shape == (64, 1)
    # the program's own init: zero-centred weights 0 (the norm scales by
    # 1 + w), the gated norm's 1, A_log = log U(0, 16)
    from diff3d_tpu.models.gated_deltanet import _a_log_init
    from diff3d_tpu.models.sparse_attention import Scale

    for centred, made, scale in ((True, 0.0, 1.0), (False, 1.0, 1.0)):
        w = Scale(centred=centred).init(jax.random.PRNGKey(0), 8)["params"]
        assert float(jnp.abs(w["scale"] - made).max()) == 0.0
        got = Scale(centred=centred).apply({"params": w}, 8)
        assert float(jnp.abs(got - scale).max()) == 0.0
    a = jnp.exp(_a_log_init(jax.random.PRNGKey(0), (4096,)))
    assert 0.0 < float(a.min()) < 0.1 and 15.9 < float(a.max()) < 16.0


def test_the_full_configuration_has_the_published_count(monkeypatch):
    """3 372 403 788 parameters by the program's own ``init`` at the
    cell's widths, the number the configuration file states: three
    linear layers of 843 225 280, the full layer 836 770 304, embedding
    and head 5 957 644."""
    from diff3d_tpu.train.trainer import init_params

    with open(os.path.join(CONFIGS, "qwen3_next_tok128.json")) as f:
        config = json.load(f)
    cfg = adapters_gdn_moe.build_config(config)
    params = jax.eval_shape(lambda: init_params(
        build_model(cfg), cfg, jax.random.PRNGKey(0)))
    count = lambda t: sum(int(np.prod(x.shape))  # noqa: E731
                          for x in jax.tree.leaves(t))
    assert count(params) == config["parameters"] == 3_372_403_788
    assert count(params["layers_0"]) == 843_225_280
    assert count(params["layers_3"]) == 836_770_304
    assert count(params["layers_0"]["linear_attn"]) == 33_718_464
    assert count(params["layers_3"]["attn"]) == 27_263_488
    assert {str(x.dtype) for x in jax.tree.leaves(params)} == {"bfloat16"}


def test_forward_float32_is_the_reference(tiny):
    """Float32 operands through the four layers: the same numbers up to
    float32 rounding (the program's delta rule is the chunked WY form,
    the reference's the token recurrence).  The reference's routed sum is
    its windowed form here, its literal one in the share test below."""
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    got = run_program(tiny["model"], tiny["flat"], batch)
    ref, load = run_reference(tiny["mcfg"], tiny["flat"], batch)
    assert got.shape == (4, 16, 16, 3) and got.dtype == jnp.float32
    assert float(jnp.abs(ref).mean()) > 0.05
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)
    # 8 of 16 experts hold about half of 4 x 128 x 4 rows in every layer
    assert load.shape == (4, 8)
    share = load.sum(axis=1) / (4 * 128 * 4)
    assert bool(((share > 0.3) & (share < 0.7)).all()), share


def test_forward_bfloat16_is_near_the_reference(tiny):
    """bf16 operands and bf16-held parameters, the reference reading the
    same parameters in float32: the gap is rounding, under 4% of the
    answer's size (3.4% read: bf16 keeps 8 bits, and the residual stream,
    the conv and every activation between contractions are bf16 in the
    program).  The control and the planted faults, each several times
    farther, are benchmark/tests/test_gdn_moe.py's."""
    cfg = dataclasses.replace(tiny["cfg"], model=dataclasses.replace(
        tiny["cfg"].model, dtype="bfloat16", param_dtype="bfloat16"))
    flat = {k: v.astype(jnp.bfloat16) for k, v in tiny["flat"].items()}
    batch = make_batch(jax.random.PRNGKey(2), 4, 2)
    got = run_program(build_model(cfg), flat, batch)
    ref = run_reference(tiny["mcfg"], flat, batch)[0]
    size = float(jnp.abs(ref).mean())
    gap = float(jnp.abs(got - ref).mean())
    assert 0.0 < gap < 0.04 * size, (gap, size)


def test_the_config_refuses_what_the_layers_do_not_compute():
    m = gdn_moe_test_config().model
    for bad in ({"linear_num_value_heads": 3},      # not a multiple of 2
                {"partial_rotary_factor": 0.0},
                {"delta_chunk": 0},
                {"param_dtype": "bfloat16"},         # compute float32
                {"layer_types": ("linear_attention",) * 3 + ("lightning",)}):
        with pytest.raises(ValueError):
            dataclasses.replace(m, **bad).validate()
    bf16 = dataclasses.replace(m, dtype="bfloat16", param_dtype="bfloat16")
    bf16.validate()
    with pytest.raises(ValueError, match="only a stack"):
        dataclasses.replace(bf16, layer_types=("linear_attention",) * 3
                            + ("attention",)).validate()


# ---------------------------------------------------------- the norms

def test_the_zero_centred_norm_scales_by_one_plus_w(tiny, monkeypatch):
    """With random weights the program is the reference, whose norms
    scale by ``1 + w``; the same program with its zero-centred norms
    scaling by ``w`` alone, on the same weights, is far from it."""
    import flax.linen as nn

    from diff3d_tpu.models import token_denoiser, token_layers
    from diff3d_tpu.models.sparse_attention import Scale

    class WAlone(Scale):
        @nn.compact
        def __call__(self, n: int) -> jnp.ndarray:
            return self.param("scale", nn.initializers.zeros, (n,),
                              self.param_dtype).astype(jnp.float32)

    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    want = run_reference(tiny["mcfg"], tiny["flat"], batch)[0]
    got = run_program(tiny["model"], tiny["flat"], batch)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    for module in (token_denoiser, token_layers):
        monkeypatch.setattr(module, "Scale", WAlone)
    other = run_program(TokenDenoiser(tiny["cfg"].model), tiny["flat"],
                        batch)
    assert float(jnp.abs(other - want).mean()) > 0.1 * float(
        jnp.abs(want).mean())


# ------------------------------------------------------- the attention

def test_the_rotary_turn_takes_the_first_quarter_of_a_head(tiny):
    """Dims 8..31 of every ``q`` and ``k`` head (24 of 32; 192 of 256 at
    the cell's widths) leave the turn as the zero-centred norm gave them,
    dims 0..7 turn by the token's index, token 0 does not turn, and the
    gate is the second half of each head's columns, untouched."""
    from diff3d_tpu.models.moe import rms_norm

    layer = DecoderLayer(tiny["cfg"].model, "full_attention")
    mine = _layer_params(tiny["flat"], 3)
    L, d = 128, 32
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    q = jax.random.normal(k[0], (L, 4 * 2 * d))
    kk = jax.random.normal(k[1], (L, 2, d))

    def parts(s, q, kk):
        return s.attn._gated_parts(q, kk, s.attn.q_norm(d), s.attn.k_norm(d))

    tq, gate, tk = jax.jit(lambda p, q, kk: layer.apply(
        {"params": p}, q, kk, method=parts))(nest(mine), q, kk)
    heads = q.reshape(L, 4, 2 * d)
    nq = rms_norm(heads[..., :d], 1.0 + mine["attn/q_norm/scale"], 1e-6)
    nk = rms_norm(kk, 1.0 + mine["attn/k_norm/scale"], 1e-6)
    for a, b in ((tq, nq), (tk, nk)):
        np.testing.assert_allclose(a[..., 8:], b[..., 8:], atol=1e-6, rtol=0)
        np.testing.assert_allclose(a[0], b[0], atol=1e-6, rtol=0)
        assert float(jnp.abs(a[1:, :, :8] - b[1:, :, :8]).mean()) > 0.1
    np.testing.assert_array_equal(gate, heads[..., d:])


def test_the_output_gate_and_the_shared_gate_are_the_references(tiny):
    """The attention half and the feed-forward half of the tiny stack
    against the reference's; zeroing the gate's columns of ``W_q`` (or the
    shared expert's gate) moves the answer, and the reference with the
    same columns zeroed follows it: the gates are computed, and where."""
    m, mcfg = tiny["cfg"].model, tiny["mcfg"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 64))
    mine = _layer_params(tiny["flat"], 3)
    layer = DecoderLayer(m, "full_attention")

    @jax.jit
    def ref_attn(p):
        LP = p.__getitem__
        return jax.lax.map(lambda hb: hb + rm.attention(rm.n(
            hb, LP("attn_norm/scale"), mcfg), LP, mcfg, "float32"), h)

    @jax.jit
    def ref_ffn(p):
        return jax.lax.map(lambda hb: rm.ffn_half(
            hb, p.__getitem__, mcfg, "float32")[0], h)

    q = mine["attn/q_proj/kernel"].reshape(64, 4, 2, 32)
    no_gate = dict(mine, **{"attn/q_proj/kernel": q.at[:, :, 1].set(
        0.0).reshape(64, 256)})
    no_shared_gate = dict(mine, **{"mlp/gate/kernel": jnp.zeros((64, 1))})
    for half, ref, off in (("mixer", ref_attn, no_gate),
                           ("ffn", ref_ffn, no_shared_gate)):
        got = _half(layer, mine, h, half)
        np.testing.assert_allclose(got, ref(mine), atol=2e-5, rtol=0)
        moved = _half(layer, off, h, half)
        np.testing.assert_allclose(moved, ref(off), atol=2e-5, rtol=0)
        assert float(jnp.abs(moved - got).mean()) > 1e-2, half


# ------------------------------------------------ the layer by its shares

def test_two_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
    """16 experts top-4: the two chips' shares ``(0, 8)`` and ``(8, 8)``
    of one layer's feed-forward, with what every chip computes alike (the
    norm, the router, the gated shared expert) counted once, add up to
    the uncut reference's whole expert layer; every chip's shared part is
    the same number."""
    uncut = rm.model_dict(dict(TINY, num_experts=16, experts_held=[0, 16]))
    flat = spread(rm.make_params(uncut, jax.random.PRNGKey(3))(),
                  jax.random.PRNGKey(4))
    mine = _layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
    want, windowed = (jax.jit(lambda p: jax.lax.map(lambda hb: rm.ffn_half(
        hb, p.__getitem__, uncut, "float32", literal)[0], h))(mine) - h
        for literal in (True, False))
    assert float(jnp.abs(want).mean()) > 0.05
    np.testing.assert_allclose(windowed, want, atol=3e-5, rtol=0)

    def share(first, shared: bool):
        m = adapters_gdn_moe.build_config(
            dict(TINY, experts_held=[first, 8])).model
        if not shared:
            m = dataclasses.replace(m, shared_intermediate_size=0)
        p = {k: (v[first:first + 8] if k in ("moe/w_gate", "moe/w_up",
                                             "moe/w_down") else v)
             for k, v in mine.items() if shared or not k.startswith("mlp/")}
        return jax.jit(lambda p, x: DecoderLayer(m, "linear_attention").apply(
            {"params": p}, x, method="feed_forward"))(nest(p), h) - h
    routed = [share(8 * s, False) for s in range(2)]
    both = [share(8 * s, True) for s in range(2)]
    alike = [b - a for a, b in zip(routed, both)]
    for s in range(2):
        assert float(jnp.abs(routed[s]).mean()) > 1e-3, s
    np.testing.assert_allclose(alike[1], alike[0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(sum(routed) + alike[0], want, atol=3e-5,
                               rtol=0)


# -------------------------------------------------------------- counters

def test_counters_once_per_traced_site(tiny, monkeypatch):
    """``delta_scan.xla`` a delta-rule site, ``attention.gated`` a gated
    attention site, ``experts.shared_gate`` a gated shared expert; beside
    them the experts' (8 / 16 here; 256 / 512 at the cell's widths, where
    a TPU process takes the row kernels and ``expert_ffn``, and the gated
    attention's tiles ``plain_attention`` at head dim 256)."""
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
                 "experts.held": 8, "experts.of": 16, "experts.shared": 4,
                 "experts.shared_gate": 4, "experts.xla": 4,
                 "experts.rows.xla": 4, "experts.combine.xla": 4,
                 "delta_scan.xla": 3, "attention.gated": 1,
                 "sdpa.plain.xla": 1}
    with open(os.path.join(CONFIGS, "qwen3_next_tok128.json")) as f:
        cfg = adapters_gdn_moe.build_config(json.load(f))
    from diff3d_tpu.train.trainer import init_params
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    d = traced(cfg, model, params, 16)
    assert d == {"conditioning.groups": 2, "conditioning.examples": 16,
                 "experts.held": 256, "experts.of": 512, "experts.shared": 4,
                 "experts.shared_gate": 4, "experts.pallas": 4,
                 "experts.rows.pallas": 4, "experts.combine.pallas": 4,
                 "delta_scan.xla": 3, "attention.gated": 1,
                 "sdpa.plain.pallas": 1}


# ------------------------------------------------------------ training

def test_a_train_step_gives_a_finite_loss_and_gradient(tiny):
    """One step of ``make_train_step`` on the preset: a finite loss, and
    every leaf's gradient finite and nonzero, ``A_log`` and ``dt_bias``
    among them (the decays' exponents stay at most 0 under ``grad``
    too; ops/delta.py's gradient against the token recurrence's is
    tests/test_delta_scan.py's)."""
    import optax

    from benchmark.adapters import _plain as plain
    from diff3d_tpu.train.state import create_train_state
    from diff3d_tpu.train.step import make_train_step

    cfg, flat = tiny["cfg"], tiny["flat"]
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, global_batch=4, warmup_examples=4))
    ds = btraffic.ViewDataset(5, num_objects=4, num_views=4, imgsize=16)
    samples = [ds.sample(i, np.random.default_rng(i)) for i in range(4)]
    batch = {k: np.stack([s[k] for s in samples]) for k in samples[0]}
    batch["imgs"] = np.clip((batch["imgs"] + 1) * 127.5, 0, 255).astype(
        np.uint8)
    step = make_train_step(tiny["model"], cfg, None, donate=False)
    state = create_train_state(nest(flat), cfg.train)
    new_state, metrics = step(state, batch, jax.random.PRNGKey(11))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["loss"]) > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    mu = next(s.mu for s in new_state.opt_state
              if isinstance(s, optax.ScaleByAdamState))
    grads = flatten(plain(mu))
    assert set(grads) == set(flat)
    for name in ("A_log", "dt_bias", "conv1d/kernel", "norm/scale"):
        assert any(k.endswith(f"linear_attn/{name}") for k in grads), name
    for k, g in grads.items():
        assert bool(jnp.isfinite(g).all()), k
        assert float(jnp.abs(g).max()) > 0.0, k        # every leaf trains


# ------------------------------------------------------- sharding rules

def test_every_new_parameter_has_a_rule_and_a_1x2_mesh_lowering(tiny):
    """The Gated DeltaNet's leaves whole on every device (its ``[q | k |
    v | z]`` columns would have to be regrouped by key head first), the
    gated attention's ``q_proj`` (queries and gates by head) column- and
    ``o_proj`` row-parallel, its per-head norms and the shared expert's
    gate replicated, the experts by whole experts."""
    from diff3d_tpu.parallel import make_mesh

    env = make_mesh(MeshConfig(data_parallel=1, model_parallel=2,
                               param_sharding="tp"),
                    devices=jax.devices()[:2])
    params = nest(tiny["flat"])
    table = env.param_spec_table(params)
    whole = [p for p in table if "['linear_attn']" in p]
    assert len(whole) == 3 * 7
    assert {table[p] for p in whole} == {"(None,)", "(None, None)"}
    want = {
        "['layers_3']['attn']['q_proj']['kernel']": (None, "model"),
        "['layers_3']['attn']['o_proj']['kernel']": ("model", None),
        "['layers_3']['attn']['q_norm']['scale']": (None,),
        "['layers_3']['attn']['k_norm']['scale']": (None,),
        "['layers_1']['mlp']['gate']['kernel']": (None, None),
        "['layers_1']['moe']['w_gate']": ("model", None, None),
        "['layers_1']['linear_attn_norm']['scale']": (None,),
    }
    for p, spec in want.items():
        assert table[p] == str(spec), p
    sh = env.params(params)
    model = tiny["model"]
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    fn = jax.jit(lambda p, b, m: model.apply({"params": p}, b, cond_mask=m),
                 in_shardings=(sh, env.replicated(), env.replicated()),
                 out_shardings=env.replicated())
    got = fn.lower(params, batch, MASK).compile()(
        jax.device_put(params, sh), batch, MASK)
    # float32 sums reordered by the split matmuls: rounding, no more
    np.testing.assert_allclose(got, run_program(model, tiny["flat"], batch),
                               atol=5e-5, rtol=0)
    assert not [p for p in table if re.search(r"linear_attn.*model", p)]
