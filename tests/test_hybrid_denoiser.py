"""The hybrid token denoiser (models/mamba.py, token_layers.py, ops/ssd.py
behind models/token_denoiser.py's layer pattern) against its plain
reference (benchmark/reference/hybrid_denoiser.py: float32, the
state-space layers as a sequential recurrence) at the tiny configuration,
seeded weights, on the CPU.
"""

import dataclasses
import hashlib
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

from benchmark import adapters_hybrid  # noqa: E402
from benchmark import traffic as btraffic  # noqa: E402
from benchmark.reference import hybrid_denoiser as rh  # noqa: E402
from benchmark.reference.xunet import flatten, nest  # noqa: E402
from diff3d_tpu.config import (MeshConfig, hybrid_test_config,  # noqa: E402
                               token_test_config)
from diff3d_tpu.models import (TokenDenoiser, UnsupportedModelError,  # noqa: E402
                               build_model)
from diff3d_tpu.models import mamba, token_layers  # noqa: E402
from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.ops.ssd import ssd  # noqa: E402
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

from _token_helpers import make_batch, reference_loss  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "granite4_h_tok_tiny.json")) as f:
    TINY = json.load(f)
MASK = jnp.array([True, False])


def perturbed(flat, key):
    """``make_params`` leaves norm weights, ``D`` and the layers' scale at
    1: here every such leaf gets a spread, so that a weight applied in
    the wrong place shows."""
    out = {}
    for i, (k, v) in enumerate(flat.items()):
        if k.endswith(("/scale", "/D")):
            v = v * (1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), v.shape))
        out[k] = v
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = adapters_hybrid.build_config(TINY)
    mcfg = rh.model_dict(TINY)
    flat = perturbed(rh.make_params(mcfg, jax.random.PRNGKey(7))(),
                     jax.random.PRNGKey(8))
    return {"cfg": cfg, "mcfg": mcfg, "flat": flat,
            "model": build_model(cfg)}


def run_program(model, flat, batch, mask=MASK):
    return jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m))(nest(flat), batch, mask)


# ------------------------------------------------------------ the forward

def test_the_preset_is_the_tiny_configuration_and_the_tree_the_references(
        tiny):
    assert tiny["cfg"].model == hybrid_test_config().model
    assert isinstance(build_model(hybrid_test_config()), TokenDenoiser)
    adapters_hybrid.check_tree(tiny["cfg"], tiny["flat"])
    assert tiny["cfg"].model.mixers == ("mamba", "mamba", "attention",
                                         "mamba", "mamba")


def test_forward_float32_is_the_reference(tiny):
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    got = run_program(tiny["model"], tiny["flat"], batch)
    ref = jax.jit(lambda p: rh.forward(p, batch, MASK, tiny["mcfg"]))(
        tiny["flat"])
    assert got.shape == (4, 16, 16, 3) and got.dtype == jnp.float32
    assert float(jnp.abs(ref).mean()) > 0.05
    # float32 on both sides: a chunked scan against a recurrence, tiles
    # against whole rows; only the order of the sums differs
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=0)


def test_forward_bfloat16_is_near_and_nearer_than_the_control(tiny):
    """bf16 operands through 5 layers: the gap to the float32 reference
    is rounding; the reference at 3 mantissa bits, the precision below,
    is several times farther, and so is the reference that drops the
    state at every chunk boundary (the cell's planted fault)."""
    cfg = dataclasses.replace(tiny["cfg"], model=dataclasses.replace(
        tiny["cfg"].model, dtype="bfloat16"))
    batch = make_batch(jax.random.PRNGKey(2), 4, 2)
    got = run_program(build_model(cfg), tiny["flat"], batch)
    ref = rh.forward(tiny["flat"], batch, MASK, tiny["mcfg"])
    low = rh.forward(tiny["flat"], batch, MASK, tiny["mcfg"], prec="fp8")
    dropped = rh.forward(tiny["flat"], batch, MASK, dict(
        tiny["mcfg"], state_reset_every=tiny["mcfg"]["mamba_chunk_size"]))
    size = float(jnp.abs(ref).mean())
    gap = float(jnp.abs(got - ref).mean())
    assert gap < 0.03 * size, (gap, size)
    assert float(jnp.abs(low - ref).mean()) > 3 * gap
    assert float(jnp.abs(dropped - ref).mean()) > 2 * gap


def test_forward_with_the_attention_kernel_forced_is_the_reference(
        monkeypatch):
    """The whole model at head dim 64, float32: the attention layer's
    ``sdpa`` runs its Pallas core (interpret mode, this being a CPU
    process) inside the layer's maps over examples and query tiles, on
    a ``q`` that carries ``attention_multiplier``.  The registry's policy
    is patched to what it resolves on a TPU process at the cell's
    widths; no option of the program does this."""
    from diff3d_tpu.ops.pallas_attention import plain_supports

    config = dict(TINY, hidden_size=256, mamba_n_heads=32)
    cfg, mcfg = adapters_hybrid.build_config(config), rh.model_dict(config)
    assert mcfg["head_dim"] == 64 and plain_supports(
        jnp.zeros((1, 64, 4, 64)), *[jnp.zeros((1, 128, 2, 64))] * 2)
    flat = perturbed(rh.make_params(mcfg, jax.random.PRNGKey(7))(),
                     jax.random.PRNGKey(8))
    impls = dispatch._REGISTRY["sdpa"]
    monkeypatch.setitem(impls, "xla", impls["pallas"])
    before = RECORDER.counters().get("sdpa.plain.pallas", 0)
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    got = run_program(build_model(cfg), flat, batch)
    assert RECORDER.counters()["sdpa.plain.pallas"] == before + 1
    ref = jax.jit(lambda p: rh.forward(p, batch, MASK, mcfg))(flat)
    assert float(jnp.abs(ref).mean()) > 0.05
    np.testing.assert_allclose(got, ref, atol=5e-5, rtol=0)


def test_g_rows_equal_repeated_rows(tiny):
    batch = make_batch(jax.random.PRNGKey(3), 16, 2)
    shared = run_program(tiny["model"], tiny["flat"], batch)
    rep = dict(batch, **{k: jnp.repeat(batch[k], 8, axis=0)
                         for k in ("logsnr", "R", "t", "K")})
    each = run_program(tiny["model"], tiny["flat"], rep, jnp.repeat(MASK, 8))
    np.testing.assert_allclose(shared, each, atol=1e-5, rtol=0)


# ------------------------------------------------------ the scan by itself

def sequential(x, dt, A, B, C, D, reset=0):
    """The recurrence, token by token (float32)."""
    def step(S, inp):
        t, xt, dtt, Bt, Ct = inp
        if reset:
            S = jnp.where(t % reset == 0, 0.0, S)
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * Bt[None, None, :])
        return S, (S * Ct).sum(-1) + D[:, None] * xt
    H, P = x.shape[1:]
    return jax.lax.scan(step, jnp.zeros((H, P, B.shape[-1])),
                        (jnp.arange(x.shape[0]), x, dt, B, C))[1]


def scan_inputs(L=128, H=4, P=8, N=16, decay="mixed", seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (L, H, P))
    B = jax.random.normal(k[1], (L, N))
    C = jax.random.normal(k[2], (L, N))
    # per-token decay exp(dt A): near 1 (0.9999), near 0 (e^-8), a memory
    # of some fifty tokens (0.98), or each head its own between the ends
    dt = {"near_one": 1e-4, "near_zero": 8.0, "slow": 0.02,
          "mixed": jnp.logspace(-4, 0.9, H)}[decay] * jnp.exp(
              0.3 * jax.random.normal(k[3], (L, H)))
    return x, dt, -jnp.ones((H,)), B, C, jnp.linspace(0.5, 1.5, H)


@pytest.mark.parametrize("decay", ["near_one", "near_zero", "mixed"])
@pytest.mark.parametrize("chunk", [32, 48, 128, 1000, 1])
def test_chunked_scan_is_the_sequential_recurrence(chunk, decay):
    """Chunks that divide the 128 tokens (32, 128, 1), that do not (48:
    two whole chunks and a part) and one longer than the sequence."""
    args = scan_inputs(decay=decay)
    want = sequential(*args)
    got = ssd(*args, chunk)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=3e-5 * scale, rtol=0)


def test_the_state_crosses_chunk_and_frame_boundaries():
    """Zeroing the state at a chunk boundary, or between the two frames,
    changes the result, and the chunked scan follows the recurrence that
    keeps it."""
    args = scan_inputs(decay="slow")
    want = sequential(*args)
    got = ssd(*args, 32)
    size = float(jnp.abs(want).mean())
    for every in (32, 64):             # chunk boundary; frame boundary
        cut = sequential(*args, reset=every)
        assert float(jnp.abs(cut - want).mean()) > 0.05 * size
        assert float(jnp.abs(cut - got).mean()) > 0.05 * size
        # the first span has no boundary behind it
        np.testing.assert_allclose(cut[:every], want[:every], atol=1e-5)
    assert float(jnp.abs(got - want).mean()) < 1e-5 * size


def test_scan_gradient_is_the_recurrences_and_vmap_is_a_loop():
    x, dt, A, B, C, D = scan_inputs(decay="mixed", seed=1)

    def loss(fn, x, dt, A, B, C, D):
        return jnp.sum(fn(x, dt, A, B, C, D) ** 2)
    want = jax.grad(lambda *a: loss(sequential, *a),
                    argnums=range(6))(x, dt, A, B, C, D)
    got = jax.grad(lambda *a: loss(lambda *b: ssd(*b, 48), *a),
                   argnums=range(6))(x, dt, A, B, C, D)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(g, w, atol=5e-3 * float(jnp.abs(w).max()),
                                   rtol=0)
    two = jnp.stack([x, x[::-1]])
    both = jax.vmap(lambda xe: ssd(xe, dt, A, B, C, D, 48))(two)
    np.testing.assert_allclose(both[0], ssd(x, dt, A, B, C, D, 48),
                               atol=1e-5)
    np.testing.assert_allclose(both[1], sequential(x[::-1], dt, A, B, C, D),
                               atol=3e-4)


# ----------------------------------------------- the layers by themselves

def _layer(tiny, i):
    """Layer ``i``'s reference parameters by their names inside it."""
    pre = f"layers_{i}/"
    return {k[len(pre):]: v for k, v in tiny["flat"].items()
            if k.startswith(pre)}


def _mixer(tiny, i, **over):
    m = tiny["cfg"].model
    mine = _layer(tiny, i)
    if m.mixers[i] == "mamba":
        layer = mamba.Mamba2Mixer(
            hidden=64, n_heads=m.mamba_n_heads, d_head=m.mamba_d_head,
            d_state=m.mamba_d_state, d_conv=m.mamba_d_conv,
            chunk=m.mamba_chunk_size, eps=m.rms_norm_eps, **over)
        name = "mamba"
    else:
        layer = token_layers.FullAttention(
            hidden=64, num_heads=4, num_kv_heads=2, head_dim=16, q_chunk=64,
            eps=m.rms_norm_eps, **dict({"scale": m.attention_multiplier},
                                       **over))
        name = "attn"
    params = nest({k[len(name) + 1:]: v for k, v in mine.items()
                   if k.startswith(name + "/")})
    scale = mine[f"{name}_norm/scale"]
    return lambda h: layer.apply({"params": params}, h, scale)


def test_a_mamba_layer_is_causal_and_the_attention_layer_is_not(tiny):
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 64))
    t = 70                               # a token of the target frame
    h2 = h.at[:, t].add(1.0)
    for i, kind in enumerate(tiny["cfg"].model.mixers):
        run = _mixer(tiny, i)
        a, b = run(h), run(h2)
        moved = np.asarray(jnp.abs(a - b).max(axis=(0, 2)))
        if kind == "mamba":
            assert moved[:t].max() == 0.0, kind     # to the last bit
            # ... and it reaches every later token through the state
            assert (moved[t:] > 0).all()
        else:
            assert (moved > 1e-6).all(), kind       # every token sees it


def test_the_attention_scale_is_the_multiplier_not_the_head_dims_root(tiny):
    """``attention_multiplier`` 1/16 at head dim 16, where ``d^-1/2`` is
    1/4: a layer that scaled by the root would be 4x (at the full width:
    64^1/2 = 8x) off in the scores."""
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
    mine = _layer(tiny, 2)
    mcfg = tiny["mcfg"]

    def ref(mult):
        cfg = dict(mcfg, attention_multiplier=mult)
        return jnp.stack([hb + rh.attention(
            rh.rms_norm(hb, mine["attn_norm/scale"], cfg["rms_norm_eps"]),
            mine.__getitem__, cfg, "float32") for hb in h])
    got = _mixer(tiny, 2)(h)
    np.testing.assert_allclose(got, ref(1.0 / 16), atol=2e-5, rtol=0)
    root = _mixer(tiny, 2, scale=None)(h)          # d^-1/2
    np.testing.assert_allclose(root, ref(0.25), atol=2e-5, rtol=0)
    assert float(jnp.abs(got - root).mean()) > 1e-3


def test_a_layer_kind_follows_the_pattern_and_the_scalars_are_applied(tiny):
    """One model, three patterns: the mixers of the tree follow
    ``layer_types``; and each of the four scalars moves the result."""
    m = tiny["cfg"].model
    batch = make_batch(jax.random.PRNGKey(9), 2, 2)
    base = run_program(tiny["model"], tiny["flat"], batch)
    for key, value in (("embedding_multiplier", 1.0),
                       ("residual_multiplier", 1.0),
                       ("attention_multiplier", None),
                       ("logits_scaling", 1.0)):
        other = TokenDenoiser(dataclasses.replace(m, **{key: value}))
        got = run_program(other, tiny["flat"], batch)
        assert float(jnp.abs(got - base).mean()) > 1e-4, key
    swapped = dataclasses.replace(
        m, layer_types=("attention", "mamba", "mamba", "mamba", "mamba"))
    shapes = jax.eval_shape(lambda: TokenDenoiser(swapped).init(
        jax.random.PRNGKey(0), batch, cond_mask=MASK))["params"]
    assert set(shapes["layers_0"]) == {"attn", "attn_norm", "mlp",
                                       "mlp_norm"}
    assert set(shapes["layers_1"]) == {"mamba", "mamba_norm", "mlp",
                                       "mlp_norm"}
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(m, layer_types=("mamba", "conv") * 2 + ("mamba",)
                            ).validate()
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(m, layer_types=("mamba",)).validate()
    with pytest.raises(ValueError, match="mamba_expand"):
        dataclasses.replace(m, mamba_n_heads=7).validate()
    with pytest.raises(ValueError, match="shared_intermediate_size"):
        dataclasses.replace(m, shared_intermediate_size=0).validate()


def test_the_residual_multiplier_reaches_the_keye_layers_too():
    """``SparseAttention`` and ``RoutedExperts`` apply ``r`` to what they
    add (at 1, the plain add they lowered to before)."""
    from diff3d_tpu.models import moe

    layer = moe.RoutedExperts(num_experts=8, top_k=2, width=32, held=(0, 8),
                              token_chunk=128, block=16)
    h = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    one = jnp.ones((64,))
    p = layer.init(jax.random.PRNGKey(1), h, one)["params"]
    whole = layer.apply({"params": p}, h, one) - h
    part = layer.clone(residual=0.25).apply({"params": p}, h, one) - h
    assert float(jnp.abs(whole).mean()) > 0.01
    np.testing.assert_allclose(part, 0.25 * whole, atol=1e-6, rtol=0)


def test_counters_one_per_traced_scan_site(tiny, monkeypatch):
    """``ssm_scan.xla``: 4 in the tiny model's program, 9 in the
    full-width cell's (its nine Mamba-2 layers, unrolled);
    ``sdpa.plain.<core>``: one, the attention layer's tile (``xla`` on
    this CPU process, ``pallas`` as a TPU process resolves the cell's);
    no counter of the Keye layers moves."""
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
    assert d.pop("ssm_scan.xla") == 4 and d.pop("sdpa.plain.xla") == 1
    assert set(d) <= {"conditioning.groups", "conditioning.examples"}, d
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite4_h_micro_tok128.json")) as f:
        cfg = adapters_hybrid.build_config(json.load(f))
    from diff3d_tpu.train.trainer import init_params
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    d = traced(cfg, model, params, 16)
    assert d["ssm_scan.xla"] == 9 and d["sdpa.plain.xla"] == 1
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    d = traced(cfg, model, params, 16)
    assert d["sdpa.plain.pallas"] == 1 and "sdpa.plain.xla" not in d


# ------------------------------------------------------ sampler and trainer

def test_one_synthesized_view_is_the_references():
    from benchmark import run as brun
    from benchmark.drivers import sample_hybrid

    mix = dict(btraffic.load("sample_1obj_2views_ddim8_hybrid"), steps=4,
               limits={"image_gap": 1e-4})
    d = sample_hybrid.Driver(config=TINY, mix=mix, seed=2147484123,
                             chips=1, spans=brun.Spans())
    d.setup()
    window = d.measure(0.0)
    assert window["calls"] == 1 and window["model_steps"] == 4
    assert d.outs[0].shape == (1, 1, 8, 16, 16, 3)
    numbers = dict((n, (v, lim)) for n, v, lim in d.verify())
    assert numbers["image_gap"][0] <= 1e-4, numbers
    assert set(numbers) == {"image_gap"}
    assert len(d.notes["image_gap_by_weight"]) == 8


def test_three_train_steps_follow_the_references_loss_and_gradient(tiny):
    """The scan under ``jax.grad`` inside the train step, against the
    gradient of the sequential recurrence."""
    import optax

    from diff3d_tpu.train.state import create_train_state
    from diff3d_tpu.train.step import make_train_step

    cfg, mcfg, flat = tiny["cfg"], tiny["mcfg"], tiny["flat"]
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, global_batch=4, warmup_examples=4))
    dcfg = adapters_hybrid.diffusion_dict(cfg)
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
            lambda mb, m: rh.forward(p, mb, m, mcfg), batch, k, dcfg)))
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


def test_train_cli_trains_and_eval_cli_samples_the_hybrid_preset(tmp_path):
    from diff3d_tpu.cli import eval_cli, train_cli

    wd = str(tmp_path)
    train_cli.main(["--synthetic", "--config", "hybrid_test", "--steps", "2",
                    "--batch", "8", "--workdir", wd, "--num_workers", "0"])
    with open(os.path.join(wd, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["step"] == 2 and np.isfinite(recs[-1]["loss"])
    out = str(tmp_path / "eval.jsonl")
    eval_cli.main(["--model", os.path.join(wd, "checkpoints"),
                   "--synthetic_scenes", "--config", "hybrid_test",
                   "--objects", "2", "--steps", "2", "--max_views", "2",
                   "--sampler", "ddim", "--out", out])
    rec = json.loads(open(out).read().strip().splitlines()[-1])
    assert rec["objects"] == 2 and np.isfinite(rec["psnr_per_w"]).all()


@pytest.mark.parametrize("entry", ["serve_cli", "worker", "cascade",
                                   "convert_cli"])
def test_xunet_only_entry_points_refuse_the_hybrid_config(entry, tmp_path):
    cfg = hybrid_test_config()
    with pytest.raises(UnsupportedModelError, match="X-UNet only"):
        if entry == "serve_cli":
            from diff3d_tpu.cli import serve_cli
            serve_cli.build_service(serve_cli.build_parser().parse_args(
                ["--config", "hybrid_test", "--init", "random",
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
                              "--config", "hybrid_test"])


# --------------------------------------------- what must not have moved

#: sha256 of the ``token_test`` model's parameter tree (paths and shapes)
#: and of its lowered text on a CPU process, taken from the parent
#: commit (7513bbc, jax 0.9.0) before the layer stack was opened up.  A
#: JAX upgrade moves the second: take both anew from a tree whose
#: tests/test_token_denoiser.py passes.
KEYE_TREE = "bebc18ddeeecc0bbcc4fae5f340576b4a4ea844be11d52d0e233ebb049b70876"
KEYE_TEXT = "69f6677c0e1f2e57b78447878252cd3545d7dd06c7331355b430f684ace69de6"


def keye_digests():
    cfg = token_test_config()
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


def test_the_keye_presets_tree_and_lowered_text_are_the_parents():
    tree, text = keye_digests()
    assert tree == KEYE_TREE
    assert text == KEYE_TEXT


# ------------------------------------------------------- sharding rules

def test_every_new_parameter_has_a_rule_and_a_1x2_mesh_lowering(tiny):
    from jax.sharding import PartitionSpec as P

    from diff3d_tpu.parallel import make_mesh

    env = make_mesh(MeshConfig(data_parallel=1, model_parallel=2,
                               param_sharding="tp"),
                    devices=jax.devices()[:2])
    params = nest(tiny["flat"])
    table = env.param_spec_table(params)
    want = {
        # the state-space mixer whole on every device: its fused
        # projection's three parts do not split at one boundary
        "['layers_0']['mamba']['in_proj']['kernel']": (None, None),
        "['layers_0']['mamba']['out_proj']['kernel']": (None, None),
        "['layers_0']['mamba']['conv']['kernel']": (None, None),
        "['layers_0']['mamba']['conv']['bias']": (None,),
        "['layers_0']['mamba']['dt_bias']": (None,),
        "['layers_0']['mamba']['A_log']": (None,),
        "['layers_0']['mamba']['D']": (None,),
        "['layers_0']['mamba']['norm']['scale']": (None,),
        "['layers_0']['mamba_norm']['scale']": (None,),
        # the MLP column- then row-parallel, the attention heads split
        "['layers_0']['mlp']['w1']['kernel']": (None, "model"),
        "['layers_0']['mlp']['w2']['kernel']": ("model", None),
        "['layers_0']['mlp_norm']['scale']": (None,),
        "['layers_2']['attn']['q_proj']['kernel']": (None, "model"),
        "['layers_2']['attn']['k_proj']['kernel']": (None, "model"),
        "['layers_2']['attn']['v_proj']['kernel']": (None, "model"),
        "['layers_2']['attn']['o_proj']['kernel']": ("model", None),
        "['layers_2']['attn_norm']['scale']": (None,),
    }
    import re

    def canon(p):       # a layer's leaves by its kind, whichever layer
        return re.sub(r"layers_\d",
                      "layers_2" if "['attn" in p else "layers_0", p)
    new = [p for p in table if re.search(r"\['(mamba|mlp|attn)", p)]
    assert {canon(p) for p in new} == set(want)
    for p in new:
        assert table[p] == str(want[canon(p)]), (p, table[p])
    sh = env.params(params)
    assert sh["layers_2"]["mlp"]["w2"]["kernel"].spec == P("model", None)
    batch = make_batch(jax.random.PRNGKey(1), 4, 2)
    fn = jax.jit(lambda p, b, m: tiny["model"].apply(
        {"params": p}, b, cond_mask=m),
        in_shardings=(sh, env.replicated(), env.replicated()),
        out_shardings=env.replicated())
    compiled = fn.lower(params, batch, MASK).compile()
    got = compiled(jax.device_put(params, sh), batch, MASK)
    want_out = run_program(tiny["model"], tiny["flat"], batch)
    np.testing.assert_allclose(got, want_out, atol=2e-5, rtol=0)
