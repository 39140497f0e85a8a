import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.config import ModelConfig
from diff3d_tpu.models import XUNet
from diff3d_tpu.models.layers import (AttnBlock, FiLM, FrameGroupNorm,
                                      ResnetBlock, XUNetBlock,
                                      avgpool_downsample,
                                      nearest_neighbor_upsample)


def tiny_cfg(**kw):
    base = dict(H=16, W=16, ch=8, ch_mult=(1, 2, 2, 4), emb_ch=32,
                num_res_blocks=1, attn_levels=(2, 3, 4), attn_heads=2,
                dropout=0.0, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def make_batch(B, H, W, key=0):
    rng = np.random.RandomState(key)
    return {
        "x": jnp.asarray(rng.randn(B, H, W, 3), jnp.float32),
        "z": jnp.asarray(rng.randn(B, H, W, 3), jnp.float32),
        "logsnr": jnp.asarray(np.stack([np.full(B, 20.0),
                                        rng.uniform(-20, 20, B)], 1),
                              jnp.float32),
        "R": jnp.broadcast_to(jnp.eye(3), (B, 2, 3, 3)),
        "t": jnp.asarray(rng.randn(B, 2, 3), jnp.float32),
        "K": jnp.broadcast_to(
            jnp.array([[20.0, 0, H / 2], [0, 20.0, H / 2], [0, 0, 1]]),
            (B, 3, 3)),
    }


def test_resample_helpers():
    h = jnp.arange(2 * 2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 2, 4, 4, 3)
    up = nearest_neighbor_upsample(h)
    assert up.shape == (2, 2, 8, 8, 3)
    np.testing.assert_allclose(np.asarray(up[:, :, ::2, ::2]), np.asarray(h))
    down = avgpool_downsample(h)
    assert down.shape == (2, 2, 2, 2, 3)
    np.testing.assert_allclose(float(down[0, 0, 0, 0, 0]),
                               np.asarray(h[0, 0, :2, :2, 0]).mean())


def test_groupnorm_normalizes_per_frame():
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (2, 2, 8, 8, 16)) * 5 + 3
    gn = FrameGroupNorm()
    out, _ = gn.init_with_output(rng, h)
    # per (batch, frame) the output is ~standardised at init
    m = np.asarray(out).reshape(4, -1)
    np.testing.assert_allclose(m.mean(1), 0.0, atol=1e-4)
    np.testing.assert_allclose(m.std(1), 1.0, atol=1e-2)


def test_film_zero_emb_is_identity_at_init():
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (2, 2, 4, 4, 8))
    emb = jnp.zeros((2, 2, 4, 4, 16))
    film = FiLM(features=8)
    out, _ = film.init_with_output(rng, h, emb)
    # Dense bias is zero-init -> scale=shift=0 -> identity
    np.testing.assert_allclose(np.asarray(out), np.asarray(h), atol=1e-6)


@pytest.mark.parametrize("resample,expect_hw", [(None, 8), ("down", 4),
                                                ("up", 16)])
def test_resnet_block_shapes(resample, expect_hw):
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (2, 2, 8, 8, 8))
    emb = jax.random.normal(rng, (2, 2, 8, 8, 16))
    blk = ResnetBlock(features=12, resample=resample)
    out, _ = blk.init_with_output(rng, h, emb)
    assert out.shape == (2, 2, expect_hw, expect_hw, 12)
    assert np.isfinite(np.asarray(out)).all()


def test_resnet_block_zero_init_residual():
    # At init conv2 is zero, so (pre-resample) output = (film_path + skip)/√2
    # with identity channels -> for same-width block with zero emb the block
    # output equals h_in/√2 exactly IF the first conv path contributed 0 to
    # conv2's output (it does: conv2 weights are zero).
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (1, 2, 4, 4, 8))
    emb = jnp.zeros((1, 2, 4, 4, 16))
    blk = ResnetBlock(features=8)
    out, _ = blk.init_with_output(rng, h, emb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h) / np.sqrt(2),
                               atol=1e-5)


@pytest.mark.parametrize("attn_type", ["self", "cross"])
def test_attn_block_residual_at_init(attn_type):
    # zero-init out conv -> block is h/√2 at init
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (2, 2, 4, 4, 8))
    blk = AttnBlock(attn_type, num_heads=2, attn_impl="xla")
    out, _ = blk.init_with_output(rng, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h) / np.sqrt(2),
                               atol=1e-5)


def test_attn_cross_differs_from_self():
    rng = jax.random.PRNGKey(0)
    h = jax.random.normal(rng, (2, 2, 4, 4, 8))
    out_s, vs = AttnBlock("self", 2, "xla").init_with_output(rng, h)
    out_c, vc = AttnBlock("cross", 2, "xla").init_with_output(rng, h)
    # same params (same rng/shape); different wiring must change activations
    # of the attention layer itself (check pre-out-conv by perturbing):
    # instead, simply run apply with a non-zero out conv.
    params_s = jax.tree.map(lambda x: x + 0.1, vs["params"])
    a = AttnBlock("self", 2, "xla").apply({"params": params_s}, h)
    b = AttnBlock("cross", 2, "xla").apply({"params": params_s}, h)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-6


# Tier-1 budget: the canonical B=2 tiny-XUNet init (~6s on CPU) is
# identical across three tests below (same cfg, batch seed, rng key);
# cache the one result — everything returned is immutable.
@functools.lru_cache(maxsize=1)
def _canonical_init():
    cfg = tiny_cfg()
    model = XUNet(cfg)
    B = 2
    batch = make_batch(B, cfg.H, cfg.W)
    variables = model.init(jax.random.PRNGKey(0), batch,
                           cond_mask=jnp.ones(B, bool))
    return cfg, model, batch, variables


def test_xunet_forward_shape_and_param_structure():
    cfg, model, batch, variables = _canonical_init()
    B = 2
    out = model.apply(variables, batch, cond_mask=jnp.ones(B, bool))
    assert out.shape == (B, cfg.H, cfg.W, 3)
    assert np.isfinite(np.asarray(out)).all()
    # zero-init head -> output is exactly zero at init
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_xunet_cond_mask_changes_output():
    cfg, model, batch, variables = _canonical_init()
    B = 2
    # nudge head conv away from zero so outputs are informative
    params = jax.tree.map(lambda x: x + 0.01, variables["params"])
    on = model.apply({"params": params}, batch,
                     cond_mask=jnp.ones(B, bool))
    off = model.apply({"params": params}, batch,
                      cond_mask=jnp.zeros(B, bool))
    assert np.abs(np.asarray(on) - np.asarray(off)).max() > 1e-6


# Tier-1 budget: jitted forward+grad finiteness through the same tiny
# XUNet is superseded in tier 1 by test_train_step_overfits_fixed_batch
# (60 jitted grad steps with a loss-decrease assertion) and the exact
# 25-step pin in test_multi_step_trajectory_equality[fsdp].
@pytest.mark.slow
def test_xunet_jit_and_grad():
    cfg = tiny_cfg()
    model = XUNet(cfg)
    B = 2
    batch = make_batch(B, cfg.H, cfg.W)
    variables = model.init(jax.random.PRNGKey(0), batch,
                           cond_mask=jnp.ones(B, bool))

    @jax.jit
    def loss_fn(params):
        out = model.apply({"params": params}, batch,
                          cond_mask=jnp.ones(B, bool))
        return jnp.mean(out ** 2)

    # Nudge the zero-init head so the loss has a live gradient path.
    params = variables["params"]
    params = jax.tree.map(lambda x: x + 0.01, params)
    g = jax.grad(loss_fn)(params)
    leaves = jax.tree.leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    total = sum(float(jnp.sum(jnp.abs(l))) for l in leaves)
    assert total > 0


def test_xunet_dropout_rng_path():
    cfg = tiny_cfg(dropout=0.5)
    model = XUNet(cfg)
    B = 2
    batch = make_batch(B, cfg.H, cfg.W)
    variables = model.init(jax.random.PRNGKey(0), batch,
                           cond_mask=jnp.ones(B, bool))
    out = model.apply(variables, batch, cond_mask=jnp.ones(B, bool),
                      deterministic=False,
                      rngs={"dropout": jax.random.PRNGKey(1)})
    assert out.shape == (B, cfg.H, cfg.W, 3)


# The applies and the grad are jitted: eagerly, remat dispatches every
# checkpointed block op-by-op (~60 s for the SAME assertions); under jit
# the programs land in the persistent compile cache.
@pytest.mark.parametrize("policy", ["nothing", "dots"])
def test_xunet_remat_matches(policy):
    cfg, _, batch, v = _canonical_init()
    cfg_r = tiny_cfg(remat=True, remat_policy=policy)
    B = 2

    @jax.jit
    def fwd_plain(v):
        return XUNet(cfg).apply(v, batch, cond_mask=jnp.ones(B, bool))

    @jax.jit
    def fwd_remat(v):
        return XUNet(cfg_r).apply(v, batch, cond_mask=jnp.ones(B, bool))

    a = fwd_plain(v)
    b = fwd_remat(v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # The policy must also hold up under differentiation (the whole point
    # of remat is the backward pass).
    def loss(params):
        return jnp.mean(XUNet(cfg_r).apply(
            {"params": params}, batch, cond_mask=jnp.ones(B, bool)) ** 2)

    g = jax.jit(jax.grad(loss))(
        jax.tree.map(lambda x: x + 0.01, v["params"]))
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


@pytest.mark.parametrize("bad, match", [
    (dict(H=10), "divisible"),
    (dict(attn_impl="bogus"), "attn_impl"),
    (dict(attn_impl="ring:"), "attn_impl"),
])
def test_xunet_rejects_bad_config(bad, match):
    H = bad.get("H", 16)
    with pytest.raises(ValueError, match=match):
        XUNet(tiny_cfg(**bad)).init(
            jax.random.PRNGKey(0), make_batch(1, H, 16),
            cond_mask=jnp.ones(1, bool))


# Tier-1 budget (870s): the remat numeric-equality pin stays in tier 1
# (test_xunet_remat_matches[dots]); this dropout-under-remat regression
# smoke runs under --runslow / RUN_SLOW=1.
@pytest.mark.slow
def test_xunet_remat_with_dropout_trains():
    # regression: remat static_argnums must mark `deterministic` (argnum 3
    # counting self) static, or dropout>0 under remat raises
    # TracerBoolConversionError.
    cfg = tiny_cfg(dropout=0.1, remat=True)
    model = XUNet(cfg)
    B = 1
    batch = make_batch(B, cfg.H, cfg.W)
    variables = model.init(jax.random.PRNGKey(0), batch,
                           cond_mask=jnp.ones(B, bool))
    out = model.apply(variables, batch, cond_mask=jnp.ones(B, bool),
                      deterministic=False,
                      rngs={"dropout": jax.random.PRNGKey(1)})
    assert out.shape == (B, cfg.H, cfg.W, 3)


def test_conditioning_encodings_stay_float32_in_bf16():
    # regression: posenc sinusoid args reach ~2e4; computed in bf16 they
    # lose all phase info (logsnr 4.0 vs 4.01 become identical).
    from diff3d_tpu.models.conditioning import ConditioningProcessor
    cp = ConditioningProcessor(emb_ch=32, H=8, W=8, num_resolutions=2,
                               dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)

    def batch_with_logsnr(v):
        return {
            "x": jnp.zeros((1, 8, 8, 3)),
            "logsnr": jnp.array([[20.0, v]]),
            "R": jnp.broadcast_to(jnp.eye(3), (1, 2, 3, 3)),
            "t": jnp.asarray(rng.randn(1, 2, 3), jnp.float32),
            "K": jnp.broadcast_to(jnp.eye(3), (1, 3, 3)),
        }

    b1 = batch_with_logsnr(4.0)
    variables = cp.init(jax.random.PRNGKey(0), b1, jnp.ones(1, bool))
    e1, _ = cp.apply(variables, b1, jnp.ones(1, bool))
    e2, _ = cp.apply(variables, batch_with_logsnr(4.01), jnp.ones(1, bool))
    assert np.abs(np.asarray(e1, np.float32)
                  - np.asarray(e2, np.float32)).max() > 1e-3


# --------------------------------------------------------------------------
# grouped conditioning: G conditioning rows for B examples, G | B
# --------------------------------------------------------------------------

_COND_KEYS = ("logsnr", "R", "t", "K")


@functools.lru_cache(maxsize=None)
def _grouped_model():
    """The `test`-config model with non-trivial weights (zero-init head
    and second convs would make every output 0), and its jitted apply."""
    from diff3d_tpu.config import test_config as make_tiny_config

    cfg = make_tiny_config().model
    model = XUNet(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 make_batch(1, cfg.H, cfg.W),
                                 cond_mask=jnp.ones(1, bool))["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        p + 0.02 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)])
    return model, params


def _apply(model, params, batch, mask):
    return jax.jit(lambda p, b, m: model.apply({"params": p}, b,
                                               cond_mask=m))(
        params, batch, mask)


def _grouped_case(G, n):
    """A batch whose conditioning has G rows for B = G*n examples, and the
    same batch with those rows repeated to B (group-major)."""
    model, params = _grouped_model()
    B = G * n
    full = make_batch(B, model.cfg.H, model.cfg.W, key=3)
    rows = make_batch(G, model.cfg.H, model.cfg.W, key=4)
    # a per-row rotation, so that R is not the same in every row either
    ang = jnp.linspace(0.1, 1.0, G * 2).reshape(G, 2)
    c, s, o, l = jnp.cos(ang), jnp.sin(ang), jnp.zeros_like(ang), \
        jnp.ones_like(ang)
    rows["R"] = jnp.stack([c, -s, o, s, c, o, o, o, l], -1).reshape(
        G, 2, 3, 3)
    grouped = dict(full, **{k: rows[k] for k in _COND_KEYS})
    repeated = dict(full, **{k: jnp.repeat(rows[k], n, axis=0)
                             for k in _COND_KEYS})
    mask = jnp.arange(G) % 2 == 0          # mixed wherever G > 1
    return model, params, grouped, repeated, mask


@pytest.mark.parametrize("G,n", [(1, 4), (2, 4), (4, 1)])
def test_grouped_conditioning_equals_repeated_rows(G, n):
    """Conditioning at G rows == the same rows repeated to B = G*n
    (example b reads row b // n), with a mixed cond_mask."""
    model, params, grouped, repeated, mask = _grouped_case(G, n)
    out_g = _apply(model, params, grouped, mask)
    out_r = _apply(model, params, repeated, jnp.repeat(mask, n))
    assert out_g.shape == (G * n, 16, 16, 3)
    assert float(jnp.max(jnp.abs(out_r))) > 1e-2        # not vacuous
    # Not bitwise: the FiLM dense and the level convs run at another
    # batch size, where the CPU's matmul orders its sums differently, and
    # 19 blocks of GroupNorm carry that rounding to the output (read:
    # 1.3e-6 on outputs of 0.5-0.7; computed in float64 the two agree to
    # the last float32 bit).  1e-5 is eight times that and far below any
    # wrong row (the swap below moves the output by > 1e-3).
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)
    if G > 1:   # and the rows do differ: row order matters
        swapped = dict(grouped, **{k: grouped[k][::-1] for k in _COND_KEYS})
        out_s = _apply(model, params, swapped, mask[::-1])
        assert float(jnp.max(jnp.abs(out_s - out_g))) > 1e-3


def test_grouped_conditioning_rejects_indivisible_batch():
    model, params, grouped, _, mask = _grouped_case(2, 4)
    odd = dict(grouped, x=grouped["x"][:7], z=grouped["z"][:7])
    with pytest.raises(ValueError, match="divide"):
        model.apply({"params": params}, odd, cond_mask=mask)
    # conditioning inputs that disagree on G among themselves
    with pytest.raises(ValueError, match="rows"):
        model.apply({"params": params},
                    dict(grouped, K=jnp.repeat(grouped["K"], 4, axis=0)),
                    cond_mask=mask)


def test_grouped_conditioning_pallas_interpret_agrees():
    """The fused GroupNorm->FiLM kernel (interpret mode on the CPU) gets
    scale/shift broadcast from G rows and agrees with the XLA path."""
    import dataclasses

    model_x, params, grouped, _, mask = _grouped_case(2, 4)
    model_p = XUNet(dataclasses.replace(model_x.cfg, kernels="pallas"))
    out_x = _apply(model_x, params, grouped, mask)
    out_p = _apply(model_p, params, grouped, mask)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               atol=1e-5, rtol=1e-5)
