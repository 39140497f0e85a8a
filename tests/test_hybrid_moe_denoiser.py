"""The hybrid token denoiser whose feed-forward has two branches on one
normed input (a share of routed experts beside a shared expert:
models/moe.py ``RoutedExperts(beside=)``, models/token_layers.py
``GatedMLP.branch``, wired by models/token_denoiser.py ``DecoderLayer``)
against its plain reference (benchmark/reference/hybrid_moe_denoiser.py:
float32, the router the published way, the held share, one norm, one add)
at the tiny configuration, seeded weights, on the CPU.
"""

import dataclasses
import hashlib
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

from benchmark import adapters_hybrid_moe  # noqa: E402
from benchmark import traffic as btraffic  # noqa: E402
from benchmark.reference import hybrid_denoiser as rh  # noqa: E402
from benchmark.reference import hybrid_moe_denoiser as rm  # noqa: E402
from benchmark.reference.xunet import flatten, nest  # noqa: E402
from diff3d_tpu.config import (MeshConfig, hybrid_moe_test_config,  # noqa: E402
                               hybrid_test_config)
from diff3d_tpu.models import (TokenDenoiser, UnsupportedModelError,  # noqa: E402
                               build_model)
from diff3d_tpu.models import moe  # noqa: E402
from diff3d_tpu.models.token_denoiser import DecoderLayer  # noqa: E402
from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

from _token_helpers import make_batch, reference_loss  # noqa: E402
from test_hybrid_denoiser import perturbed  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")
with open(os.path.join(CONFIGS, "granite4_h_small_tok_tiny.json")) as f:
    TINY = json.load(f)
MASK = jnp.array([True, False])


@pytest.fixture(scope="module")
def tiny():
    cfg = adapters_hybrid_moe.build_config(TINY)
    mcfg = rm.model_dict(TINY)
    flat = perturbed(rm.make_params(mcfg, jax.random.PRNGKey(7))(),
                     jax.random.PRNGKey(8))
    return {"cfg": cfg, "mcfg": mcfg, "flat": flat,
            "model": build_model(cfg)}


def run_program(model, flat, batch, mask=MASK):
    return jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m))(nest(flat), batch, mask)


# ------------------------------------------------------------ the forward

def test_the_preset_is_the_tiny_configuration_and_the_tree_the_references(
        tiny):
    assert tiny["cfg"].model == hybrid_moe_test_config().model
    assert isinstance(build_model(hybrid_moe_test_config()), TokenDenoiser)
    adapters_hybrid_moe.check_tree(tiny["cfg"], tiny["flat"])
    m = tiny["cfg"].model
    assert (m.num_experts, m.experts_held, m.num_experts_per_tok) == (
        24, (0, 3), 4)                       # a true share: 3 of 24


def test_a_config_with_both_widths_builds_both_feed_forwards(tiny):
    """The parent built the experts and silently left the shared expert
    out: the tree has both, under the experts' one norm."""
    m = tiny["cfg"].model
    assert m.num_experts > 0 and m.shared_intermediate_size > 0
    batch = make_batch(jax.random.PRNGKey(0), 2, 2)
    shapes = jax.eval_shape(lambda: tiny["model"].init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    assert set(shapes["layers_0"]) == {"mamba", "mamba_norm", "moe",
                                       "moe_norm", "mlp"}
    assert set(shapes["layers_2"]) == {"attn", "attn_norm", "moe",
                                       "moe_norm", "mlp"}
    assert shapes["layers_0"]["mlp"]["w1"]["kernel"].shape == (64, 96)
    assert shapes["layers_0"]["moe"]["w_gate"].shape == (3, 64, 32)
    assert shapes["layers_0"]["moe"]["router"].shape == (64, 24)
    # and either width alone builds that feed-forward alone
    only = dataclasses.replace(m, shared_intermediate_size=0)
    shapes = jax.eval_shape(lambda: TokenDenoiser(only).init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    assert set(shapes["layers_0"]) == {"mamba", "mamba_norm", "moe",
                                       "moe_norm"}


@pytest.mark.parametrize("literal", [True, False])
def test_forward_float32_is_the_reference(tiny, literal):
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    got = run_program(tiny["model"], tiny["flat"], batch)
    ref, load = jax.jit(lambda p: rm.forward(
        p, batch, MASK, tiny["mcfg"], literal=literal))(tiny["flat"])
    assert got.shape == (4, 16, 16, 3) and got.dtype == jnp.float32
    assert float(jnp.abs(ref).mean()) > 0.05
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)
    # 5 layers; 3 of 24 experts hold about an eighth of 4 x 128 x 4 rows
    assert load.shape == (5, 3)
    assert 0.04 < float(load.sum(axis=1).mean()) / (4 * 128 * 4) < 0.3


def test_forward_bfloat16_is_near_and_nearer_than_the_control_and_faults(
        tiny):
    """bf16 operands through 5 layers: the gap to the float32 reference
    is rounding; the reference at 3 mantissa bits is several times
    farther, and so is the reference without either branch of the
    feed-forward (the cell's two planted faults)."""
    cfg = dataclasses.replace(tiny["cfg"], model=dataclasses.replace(
        tiny["cfg"].model, dtype="bfloat16"))
    batch = make_batch(jax.random.PRNGKey(2), 4, 2)
    got = run_program(build_model(cfg), tiny["flat"], batch)
    fwd = lambda **kw: rm.forward(tiny["flat"], batch, MASK,  # noqa: E731
                                  **kw)[0]
    ref = fwd(cfg=tiny["mcfg"])
    size = float(jnp.abs(ref).mean())
    gap = float(jnp.abs(got - ref).mean())
    assert gap < 0.03 * size, (gap, size)
    low = fwd(cfg=tiny["mcfg"], prec="fp8")
    assert float(jnp.abs(low - ref).mean()) > 3 * gap
    for fault in ("shared_dropped", "experts_dropped"):
        bad = fwd(cfg=dict(tiny["mcfg"], **{fault: True}))
        assert float(jnp.abs(bad - ref).mean()) > 2 * gap, fault


def test_g_rows_equal_repeated_rows(tiny):
    batch = make_batch(jax.random.PRNGKey(3), 16, 2)
    shared = run_program(tiny["model"], tiny["flat"], batch)
    rep = dict(batch, **{k: jnp.repeat(batch[k], 8, axis=0)
                         for k in ("logsnr", "R", "t", "K")})
    each = run_program(tiny["model"], tiny["flat"], rep, jnp.repeat(MASK, 8))
    np.testing.assert_allclose(shared, each, atol=1e-5, rtol=0)


# ----------------------------------------------------------- the router

@pytest.mark.parametrize("E,k", [(72, 10), (24, 4), (8, 8)])
def test_route_is_the_published_top_k_then_softmax(E, k):
    """``moe.route`` (softmax over all, the ``k`` largest, renormalised)
    against ``GraniteMoeTopKGating`` (the ``k`` largest logits, softmax
    over those): the same experts in the same order, the same gates."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(E), (512, E))
    ids, gates = moe.route(logits, k)
    top, want_ids = jax.lax.top_k(logits, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(gates, jax.nn.softmax(top, axis=-1),
                               atol=2e-7, rtol=1e-6)
    # the reference's router is the published one (its weight the identity)
    r_ids, r_gates = rm.routing(logits,
                                {"moe/router": jnp.eye(E)}.__getitem__,
                                {"num_experts_per_tok": k}, "float32")
    np.testing.assert_array_equal(np.asarray(r_ids), np.asarray(ids))
    np.testing.assert_allclose(r_gates, gates, atol=2e-7, rtol=1e-6)


# ------------------------------------------------ the layer by its halves

def _layer_params(flat, i):
    pre = f"layers_{i}/"
    return {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}


def test_the_shared_expert_reads_the_norm_of_the_layers_input(tiny):
    """One norm, one residual add: ``h1 + r (routed(u) + shared(u))``,
    ``u = n(h1)``.  Running the two existing halves in turn would feed
    the shared expert ``n(h1 + r routed(u))``: that is a different
    number, and the layer is not it."""
    m, mcfg = tiny["cfg"].model, tiny["mcfg"]
    mine = _layer_params(tiny["flat"], 0)
    LP = mine.__getitem__
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 64))
    r, eps = mcfg["residual_multiplier"], mcfg["rms_norm_eps"]
    got = DecoderLayer(m, "mamba").apply({"params": nest(mine)}, h)
    h1 = h + r * rh.mamba_mixer(rh.rms_norm(h, LP("mamba_norm/scale"), eps),
                                LP, mcfg, "float32")
    want = jnp.stack([rm.feed_forward(hb, LP, mcfg, "float32", True)[0]
                      for hb in h1])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)

    def in_turn(hb):
        u = rh.rms_norm(hb, LP("moe_norm/scale"), eps)
        h2 = hb + r * rm.routed_share(u, LP, mcfg, "float32", True)[0]
        return h2 + r * rh.mlp(rh.rms_norm(h2, LP("moe_norm/scale"), eps),
                               LP, "float32")
    turn = jnp.stack([in_turn(hb) for hb in h1])
    assert float(jnp.abs(turn - want).max()) > 100 * 2e-5
    # the feed-forward half alone is the reference's too
    half = DecoderLayer(m, "mamba").apply(
        {"params": nest(mine)}, h1, method="feed_forward")
    np.testing.assert_allclose(half, want, atol=2e-5, rtol=0)


@pytest.fixture
def kernel_forced(monkeypatch):
    """The registry's policy patched to what it resolves on a TPU process:
    the experts' blocks and, the layer holding a share, the rows' way in
    and back at ``impl='auto'`` take their Pallas cores (in interpret
    mode, this being a CPU process)."""
    for op in ("expert_rows", "expert_ffn", "expert_combine"):
        impls = dispatch._REGISTRY[op]
        monkeypatch.setitem(impls, "xla", impls["pallas"])


@pytest.mark.parametrize("core", ["xla", "pallas"])
def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(
        core, request):
    """72 experts top-10 at one lane tile of hidden size and widths: the
    eight chips' shares ``(0, 9) ... (63, 9)`` of one layer's
    feed-forward, with what every chip computes alike (the norm, the
    router, the shared expert) counted once, add up to the uncut
    reference layer; every chip's shared part is the same number."""
    if core == "pallas":
        request.getfixturevalue("kernel_forced")
    config = dict(TINY, hidden_size=128, intermediate_size=128,
                  shared_intermediate_size=128, mamba_n_heads=16,
                  num_local_experts=9, experts_held=[0, 9],
                  num_experts_per_tok=10, published={"num_local_experts": 72})
    uncut = rm.model_dict(dict(config, num_local_experts=72,
                               experts_held=[0, 72]))
    flat = perturbed(rm.make_params(uncut, jax.random.PRNGKey(3))(),
                     jax.random.PRNGKey(4))
    mine = _layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 128))
    want = jnp.stack([rm.feed_forward(hb, mine.__getitem__, uncut,
                                      "float32", True)[0] for hb in h]) - h
    assert float(jnp.abs(want).mean()) > 0.05
    names = [f"experts.{core}", f"experts.rows.{core}",
             f"experts.combine.{core}"]
    before = [RECORDER.counters().get(n, 0) for n in names]

    def share(first, shared: bool):
        m = adapters_hybrid_moe.build_config(
            dict(config, experts_held=[first, 9])).model
        if not shared:
            m = dataclasses.replace(m, shared_intermediate_size=0)
        p = {k: (v[first:first + 9] if k in ("moe/w_gate", "moe/w_up",
                                             "moe/w_down") else v)
             for k, v in mine.items() if shared or not k.startswith("mlp/")}
        return DecoderLayer(m, "mamba").apply(
            {"params": nest(p)}, h, method="feed_forward") - h
    routed = [share(9 * s, False) for s in range(8)]
    both = [share(9 * s, True) for s in range(8)]
    assert [RECORDER.counters()[n] for n in names] == [b + 16
                                                       for b in before]
    alike = [b - a for a, b in zip(routed, both)]
    for s in range(8):
        assert float(jnp.abs(routed[s]).mean()) > 1e-3, s
        np.testing.assert_allclose(alike[s], alike[0], atol=2e-6, rtol=0)
    np.testing.assert_allclose(sum(routed) + alike[0], want, atol=3e-5,
                               rtol=0)
    # the reference given one share computes that share
    for s in (0, 5):
        cut = {k: (v[9 * s:9 * s + 9] if k in (
            "moe/w_gate", "moe/w_up", "moe/w_down") else v)
            for k, v in mine.items()}
        part = dict(uncut, experts_held=[9 * s, 9])
        for literal in (True, False):
            got = jnp.stack([rm.feed_forward(
                hb, cut.__getitem__, part, "float32", literal)[0]
                for hb in h]) - h
            np.testing.assert_allclose(got, both[s], atol=3e-5, rtol=0)


def test_counters_once_per_traced_site(tiny, monkeypatch):
    """A traced program counts ``experts.held`` / ``experts.of`` once
    (like ``conditioning.groups``), ``experts.shared`` and
    ``experts.<core>``, ``experts.rows.<core>`` and
    ``experts.combine.<core>`` once per layer, beside the mixers'
    counters; at the cell's widths, resolved as a TPU process resolves
    them: ten expert sites (blocks, rows in, gated sum back) and the
    attention site on their kernels."""
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
                 "experts.held": 3, "experts.of": 24, "experts.shared": 5,
                 "experts.xla": 5, "experts.rows.xla": 5,
                 "experts.combine.xla": 5, "ssm_scan.xla": 4,
                 "sdpa.plain.xla": 1}
    with open(os.path.join(CONFIGS, "granite4_h_small_tok128.json")) as f:
        cfg = adapters_hybrid_moe.build_config(json.load(f))
    from diff3d_tpu.train.trainer import init_params
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    d = traced(cfg, model, params, 16)
    assert d == {"conditioning.groups": 2, "conditioning.examples": 16,
                 "experts.held": 9, "experts.of": 72, "experts.shared": 10,
                 "experts.pallas": 10, "experts.rows.pallas": 10,
                 "experts.combine.pallas": 10, "ssm_scan.pallas": 9,
                 "sdpa.plain.pallas": 1}
    # Keye's layers hold every expert they route over: the rows move by
    # XLA's gathers there, the blocks through the kernel as before
    from benchmark import adapters_tokens
    with open(os.path.join(CONFIGS, "keye_vl2_tok128.json")) as f:
        kcfg = adapters_tokens.build_config(json.load(f))
    kmodel = build_model(kcfg)
    d = traced(kcfg, kmodel, jax.eval_shape(
        lambda: init_params(kmodel, kcfg, jax.random.PRNGKey(0))), 16)
    assert {k: v for k, v in d.items() if k.startswith("experts.")} == {
        "experts.held": 128, "experts.of": 128, "experts.pallas": 4,
        "experts.rows.xla": 4, "experts.combine.xla": 4}
    assert d["sdpa.selected.pallas"] == 4
    # a model without experts counts none of them; one without a shared
    # expert no ``experts.shared``
    hcfg = hybrid_test_config()
    d = traced(hcfg, build_model(hcfg), jax.eval_shape(
        lambda: init_params(build_model(hcfg), hcfg,
                            jax.random.PRNGKey(0))), 4)
    assert not [k for k in d if k.startswith("experts.")], d


# ------------------------------------------------------ sampler and trainer

def test_one_synthesized_view_is_the_references():
    from benchmark import run as brun
    from benchmark.drivers import sample_hybrid_moe

    mix = dict(btraffic.load("sample_1obj_2views_ddim4_hybrid_moe"),
               limits={"image_gap": 1e-4})
    d = sample_hybrid_moe.Driver(config=TINY, mix=mix, seed=2147484123,
                                 chips=1, spans=brun.Spans())
    d.setup()
    window = d.measure(0.0)
    assert window["calls"] == 1 and window["model_steps"] == 4
    assert d.outs[0].shape == (1, 1, 8, 16, 16, 3)
    numbers = dict((n, (v, lim)) for n, v, lim in d.verify())
    assert numbers["image_gap"][0] <= 1e-4, numbers
    assert set(numbers) == {"image_gap"}
    assert len(d.notes["image_gap_by_weight"]) == 8
    # 16 examples x 128 tokens x top-4 a call: an eighth of them at even
    # load on the three held experts
    assert 0.02 < d.notes["held_share_of_assignments"] < 0.4
    assert d.notes["held_expert_rows_max"] >= d.notes[
        "held_expert_rows_mean"] > 0


def test_three_train_steps_follow_the_references_loss_and_gradient(tiny):
    """The two-branch feed-forward under ``jax.grad`` inside the train
    step: the router, the held experts, the shared expert and their one
    norm all get the reference's gradient."""
    import optax

    from diff3d_tpu.train.state import create_train_state
    from diff3d_tpu.train.step import make_train_step

    cfg, mcfg, flat = tiny["cfg"], tiny["mcfg"], tiny["flat"]
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, global_batch=4, warmup_examples=4))
    dcfg = adapters_hybrid_moe.diffusion_dict(cfg)
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
            lambda mb, m: rm.forward(p, mb, m, mcfg, literal=True)[0],
            batch, k, dcfg)))
    b1 = cfg.train.betas[0]
    from benchmark.adapters import _plain as plain
    for i in range(3):
        params = flatten(plain(state.params))
        loss, grads = ref_fn(params, jax.random.fold_in(base, i))
        new_state, metrics = step(state, batch, base)
        assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * float(loss)
        norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values())))
        assert abs(float(metrics["grad_norm"]) - norm) < 1e-3 * norm
        if i == 0:
            mu = next(s.mu for s in new_state.opt_state
                      if isinstance(s, optax.ScaleByAdamState))
            got = {k: v / (1.0 - b1) for k, v in flatten(plain(mu)).items()}
            assert set(got) == set(grads)
            for k, g in grads.items():
                scale = float(jnp.abs(g).max())
                assert scale > 0.0, k          # every leaf is trained
                np.testing.assert_allclose(got[k], g, atol=2e-3 * scale,
                                           rtol=0, err_msg=k)
        state = new_state


def test_train_cli_trains_and_eval_cli_samples_the_preset(tmp_path):
    from diff3d_tpu.cli import eval_cli, train_cli

    wd = str(tmp_path)
    train_cli.main(["--synthetic", "--config", "hybrid_moe_test", "--steps",
                    "2", "--batch", "8", "--workdir", wd, "--num_workers",
                    "0"])
    with open(os.path.join(wd, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["step"] == 2 and np.isfinite(recs[-1]["loss"])
    out = str(tmp_path / "eval.jsonl")
    eval_cli.main(["--model", os.path.join(wd, "checkpoints"),
                   "--synthetic_scenes", "--config", "hybrid_moe_test",
                   "--objects", "2", "--steps", "2", "--max_views", "2",
                   "--sampler", "ddim", "--out", out])
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    assert rec["objects"] == 2 and np.isfinite(rec["psnr_per_w"]).all()


@pytest.mark.parametrize("entry", ["serve_cli", "worker", "cascade",
                                   "convert_cli"])
def test_xunet_only_entry_points_refuse_the_config(entry, tmp_path):
    cfg = hybrid_moe_test_config()
    with pytest.raises(UnsupportedModelError, match="X-UNet only"):
        if entry == "serve_cli":
            from diff3d_tpu.cli import serve_cli
            serve_cli.build_service(serve_cli.build_parser().parse_args(
                ["--config", "hybrid_moe_test", "--init", "random",
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
                              "--config", "hybrid_moe_test"])


# --------------------------------------------- what must not have moved

#: sha256 of the ``hybrid_test`` model's parameter tree (paths and shapes)
#: and of its lowered text on a CPU process, taken from the parent commit
#: (fd8cc8e, jax 0.9.0) before the feed-forward got its second branch;
#: ``token_test``'s are tests/test_hybrid_denoiser.py's.  A JAX upgrade
#: moves the texts: take them anew from a tree whose tests pass.
HYBRID_TREE = "433087474870df4f95d4120fd607127dadca1f0a37613f8a1fca1fa6a77dd73b"
HYBRID_TEXT = "cfd14b67f3ba5bccf4edd754aa6c8772558b2c0eef92760ee36038b638b31275"
#: the same of ``hybrid_moe_test`` (3 of 24 experts held), taken from
#: commit c132263 before the rows' way in and back became ops of the
#: registry: a CPU process resolves both to the expressions it had
MOE_TREE = "89f38fcfa91661377fe93c69a9e72023020d673ba996c36796bee200ff78751c"
MOE_TEXT = "736d90cd019782bd217b9378e8daa7779467ab9b97f7c71cf6473f36a258eb08"
#: the same of ``mla_moe_test`` (latent attention under sandwich norms),
#: taken from commit eeff66a before the qwen3_next block's edits of the
#: shared layers (``Scale``, ``FullAttention``, ``GatedMLP``,
#: ``causal_conv``, the config's pattern)
MLA_TREE = "e9dedecaa4a19b5698f1e133f9f3c8d12f797e2fb96ec267853d8688c89a8c1a"
MLA_TEXT = "d7a10e46dbff378d1f14bab74d4a864b1cbeb12e49ae1fc584faf104df50d731"


def digests(cfg):
    model = build_model(cfg)
    batch = make_batch(jax.random.PRNGKey(5), 2, 2)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    tree = sorted((jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
                  for k, v in jax.tree_util.tree_flatten_with_path(params)[0])
    text = jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m)).lower(params, batch, MASK).as_text()
    return (hashlib.sha256(repr(tree).encode()).hexdigest(),
            hashlib.sha256(text.encode()).hexdigest())


@pytest.mark.parametrize("preset", ["token_test", "hybrid_test",
                                    "hybrid_moe_test", "mla_moe_test"])
def test_a_presets_tree_and_lowered_text_are_the_parents(preset):
    from diff3d_tpu.config import named_config
    from test_hybrid_denoiser import KEYE_TEXT, KEYE_TREE

    want = {"token_test": (KEYE_TREE, KEYE_TEXT),
            "hybrid_test": (HYBRID_TREE, HYBRID_TEXT),
            "hybrid_moe_test": (MOE_TREE, MOE_TEXT),
            "mla_moe_test": (MLA_TREE, MLA_TEXT)}[preset]
    assert digests(named_config(preset)) == want


# ------------------------------------------------------- sharding rules

def test_every_new_parameter_has_a_rule_and_a_1x2_mesh_lowering(tiny):
    """The shared expert's two leaves beside an expert stack, in a layer
    whose mixer is replicated: column- then row-parallel like the dense
    MLP's; the experts by whole experts where the held count divides."""
    from jax.sharding import PartitionSpec as P

    from diff3d_tpu.parallel import make_mesh

    env = make_mesh(MeshConfig(data_parallel=1, model_parallel=2,
                               param_sharding="tp"),
                    devices=jax.devices()[:2])
    config = dict(TINY, num_local_experts=4, experts_held=[4, 4])
    cfg, mcfg = (adapters_hybrid_moe.build_config(config),
                 rm.model_dict(config))
    flat = rm.make_params(mcfg, jax.random.PRNGKey(7))()
    params = nest(flat)
    table = env.param_spec_table(params)
    want = {
        "['layers_0']['mlp']['w1']['kernel']": (None, "model"),
        "['layers_0']['mlp']['w2']['kernel']": ("model", None),
        "['layers_0']['moe']['router']": (None, None),
        "['layers_0']['moe']['w_gate']": ("model", None, None),
        "['layers_0']['moe']['w_up']": ("model", None, None),
        "['layers_0']['moe']['w_down']": ("model", None, None),
        "['layers_0']['moe_norm']['scale']": (None,),
    }
    new = [p for p in table if re.search(r"\['(moe|mlp)", p)]
    assert {re.sub(r"layers_\d", "layers_0", p) for p in new} == set(want)
    for p in new:
        assert table[p] == str(want[re.sub(r"layers_\d", "layers_0", p)]), p
    assert "['layers_0']['mamba']['in_proj']['kernel']" in table
    # three held experts do not divide over two devices: whole everywhere
    odd = env.param_spec_table(nest(tiny["flat"]))
    assert odd["['layers_0']['moe']['w_gate']"] == str((None, None, None))
    sh = env.params(params)
    assert sh["layers_2"]["mlp"]["w2"]["kernel"].spec == P("model", None)
    model = build_model(cfg)
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    fn = jax.jit(lambda p, b, m: model.apply({"params": p}, b, cond_mask=m),
                 in_shardings=(sh, env.replicated(), env.replicated()),
                 out_shardings=env.replicated())
    compiled = fn.lower(params, batch, MASK).compile()
    got = compiled(jax.device_put(params, sh), batch, MASK)
    np.testing.assert_allclose(got, run_program(model, flat, batch),
                               atol=2e-5, rtol=0)
