"""Fused GroupNorm->FiLM/SiLU Pallas kernels vs the XLA reference.

Runs the exact TPU tile program in Pallas interpret mode on CPU
(conftest's virtual-device platform), checking forward and backward
against the unfused XLA composition over the channel widths the X-UNet
actually uses — the four srn64/srn128 level widths (128/256/512/1024)
plus lane- and sublane-padding edges (C=96, C=144, row counts off the
tile grid) — in both "fire" (FiLM/SiLU epilogues active) and "silent"
(plain GN) modes, f32 and bf16.  Also pinned here: the dispatch
registry's resolution rules, zero-retrace dispatch, the param-tree
identity between kernel backends, whole-model forward/backward parity,
and sharded step_many end-to-end parity with kernels='pallas'.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.config import MeshConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.models.layers import FrameGroupNorm
from diff3d_tpu.models.xunet import XUNet
from diff3d_tpu.ops import dispatch
from diff3d_tpu.ops.pallas_film import (fused_groupnorm, supports,
                                        xla_groupnorm)

# (N, L, C, groups): the four real level widths at deep-level token
# counts, plus padding edges.  L=256 is the 16x16 levels' true token
# count; interpret mode makes the 64x64 shallow levels too slow to run
# per-test, and the kernel's tiling is identical there (same C_pad,
# more row tiles — which the L=1000 case exercises harder anyway).
SHAPES = [
    (2, 256, 128, 32),    # srn64 level 0/1 width
    (2, 256, 256, 32),    # srn64 level 2/3 + srn128 level 0/1 width
    (1, 256, 512, 32),    # srn64 deepest / srn128 level 2 width
    (1, 64, 1024, 32),    # srn128 deepest width
    (2, 64, 96, 32),      # channel pad 96 -> 128 (partial lane tile)
    (1, 1000, 144, 24),   # C pad 144 -> 256 + rows off the tile grid
]
MODES = ["gn", "gn_silu", "gn_film", "gn_film_silu"]


def _cross(shapes, core):
    """Full shape x mode cross, with only the ``core`` (shape-index,
    mode) pairs in tier 1 — the rest ride the slow lane.  Core keeps
    every shape and every mode covered, with the all-features-on
    ``gn_film_silu`` variant on each shape (it subsumes the others'
    code paths; the remaining combos guard mode-specific branches and
    run nightly)."""
    out = []
    for si, s in enumerate(shapes):
        for m in MODES:
            if (si, m) in core:
                out.append(pytest.param(s, m, id=f"shape{si}-{m}"))
            else:
                out.append(pytest.param(s, m, id=f"shape{si}-{m}",
                                        marks=pytest.mark.slow))
    return out


def _inputs(shape, dtype, seed=0, film=False):
    rng = np.random.RandomState(seed)
    N, L, C, G = shape
    x = jnp.asarray(rng.randn(N, L, C), dtype)
    gamma = jnp.asarray(rng.randn(C), jnp.float32)
    beta = jnp.asarray(rng.randn(C), jnp.float32)
    kw = dict(num_groups=G)
    if film:
        kw["scale"] = jnp.asarray(0.3 * rng.randn(N, L, C), dtype)
        kw["shift"] = jnp.asarray(0.3 * rng.randn(N, L, C), dtype)
    return x, gamma, beta, kw


def _mode_kw(mode):
    return dict(film="film" in mode, silu="silu" in mode)


@pytest.mark.parametrize(
    "shape,mode",
    _cross(SHAPES, core={(0, "gn_film_silu"), (1, "gn"), (1, "gn_silu"),
                         (1, "gn_film"), (1, "gn_film_silu"),
                         (2, "gn_film_silu"), (3, "gn_film_silu"),
                         (4, "gn_film_silu"), (5, "gn_film_silu")}))
def test_forward_parity_f32(shape, mode):
    m = _mode_kw(mode)
    x, gamma, beta, kw = _inputs(shape, jnp.float32, film=m["film"])
    kw["silu"] = m["silu"]
    ref = xla_groupnorm(x, gamma, beta, **kw)
    out = fused_groupnorm(x, gamma, beta, interpret=True, **kw)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize(
    "shape,mode",
    _cross([SHAPES[1], SHAPES[4], SHAPES[5]],
           core={(0, "gn_silu"), (1, "gn_film_silu"), (2, "gn"),
                 (2, "gn_film")}))
def test_forward_parity_bf16(shape, mode):
    """bf16 inputs, f32 accumulation.  The fused kernel rounds once at
    the end where the reference rounds between GN and the epilogues, so
    agreement is to a couple of bf16 ULP at the output magnitude."""
    m = _mode_kw(mode)
    x, gamma, beta, kw = _inputs(shape, jnp.bfloat16, film=m["film"])
    kw["silu"] = m["silu"]
    ref = xla_groupnorm(x, gamma, beta, **kw).astype(jnp.float32)
    out = fused_groupnorm(x, gamma, beta, interpret=True,
                          **kw).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) + 1.0
    np.testing.assert_allclose(out / scale, ref / scale, atol=2e-2)


@pytest.mark.parametrize(
    "shape,mode",
    _cross([SHAPES[0], SHAPES[3], SHAPES[4], SHAPES[5]],
           core={(0, "gn_film_silu"), (1, "gn_film_silu"),
                 (2, "gn"), (2, "gn_silu"), (2, "gn_film"),
                 (2, "gn_film_silu"), (3, "gn_film_silu")}))
def test_backward_parity_f32(shape, mode):
    m = _mode_kw(mode)
    x, gamma, beta, kw = _inputs(shape, jnp.float32, film=m["film"])
    film = m["film"]

    def loss(fn, interpret):
        def f(*args):
            call = dict(num_groups=kw["num_groups"], silu=m["silu"])
            if film:
                call["scale"], call["shift"] = args[3], args[4]
            if interpret is not None:
                call["interpret"] = interpret
            return jnp.mean(fn(args[0], args[1], args[2], **call) ** 2)
        return f

    prim = (x, gamma, beta) + ((kw["scale"], kw["shift"]) if film else ())
    argnums = tuple(range(len(prim)))
    g_ref = jax.grad(loss(xla_groupnorm, None), argnums=argnums)(*prim)
    g_out = jax.grad(loss(fused_groupnorm, True), argnums=argnums)(*prim)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-3)


def test_backward_parity_bf16():
    x, gamma, beta, kw = _inputs(SHAPES[4], jnp.bfloat16, film=True)

    def loss(fn, interpret):
        def f(x, s, t):
            call = dict(num_groups=kw["num_groups"], silu=True,
                        scale=s, shift=t)
            if interpret is not None:
                call["interpret"] = interpret
            return jnp.mean(fn(x, gamma, beta, **call)
                            .astype(jnp.float32) ** 2)
        return f

    prim = (x, kw["scale"], kw["shift"])
    g_ref = jax.grad(loss(xla_groupnorm, None), argnums=(0, 1, 2))(*prim)
    g_out = jax.grad(loss(fused_groupnorm, True), argnums=(0, 1, 2))(*prim)
    for a, b in zip(g_out, g_ref):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(b))) + 1e-3
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-2)


# ---------------------------------------------------------------------------
# dispatch registry
# ---------------------------------------------------------------------------


def test_supports_predicate():
    x = jnp.zeros((2, 64, 96), jnp.float32)
    assert supports(x, num_groups=32)
    assert not supports(jnp.zeros((2, 64, 96), jnp.float16), num_groups=32)
    assert not supports(jnp.zeros((2, 2, 64, 96)), num_groups=32)   # 4D
    assert not supports(x, num_groups=7)                 # 96 % 7 != 0
    assert not supports(jnp.zeros((1, 8, 8192)), num_groups=32)  # > MAX_C


def test_resolve_rules(monkeypatch):
    x = jax.ShapeDtypeStruct((2, 256, 128), jnp.float32)
    # explicit pallas: honoured when supported...
    assert dispatch.resolve("groupnorm", "pallas", x,
                            num_groups=32).name == "pallas"
    # ...and raises, naming op and shape, when not: an explicit request
    # is never swapped for xla behind the caller's back.
    bad = jax.ShapeDtypeStruct((2, 256, 128), jnp.float16)
    with pytest.raises(ValueError, match=r"groupnorm.*float16\[2, 256, 128\]"):
        dispatch.resolve("groupnorm", "pallas", bad, num_groups=32)
    # 'auto' may choose: the same operands resolve to xla, even on TPU.
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert dispatch.resolve("groupnorm", "auto", bad,
                            num_groups=32).name == "xla"
    assert dispatch.resolve("groupnorm", "xla", x,
                            num_groups=32).name == "xla"
    # 'auto' keys off the process-default backend.
    monkeypatch.setattr(dispatch, "default_backend", lambda: "cpu")
    assert dispatch.resolve("groupnorm", "auto", x,
                            num_groups=32).name == "xla"
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert dispatch.resolve("groupnorm", "auto", x,
                            num_groups=32).name == "pallas"
    tiny = jax.ShapeDtypeStruct((2, 8, 128), jnp.float32)  # auto-policy no
    assert dispatch.resolve("groupnorm", "auto", tiny,
                            num_groups=32).name == "xla"
    with pytest.raises(ValueError, match="requested"):
        dispatch.resolve("groupnorm", "cuda", x, num_groups=32)
    with pytest.raises(KeyError, match="no implementations"):
        dispatch.resolve("nonesuch", "xla", x)


def test_sdpa_shares_registry():
    """attention.py registers through the same registry: both ops are
    visible and sdpa's auto policy matches the measured rule."""
    import diff3d_tpu.ops.attention  # noqa: F401 - registers 'sdpa'

    assert set(dispatch.implementations("sdpa")) == {"pallas", "xla"}
    assert set(dispatch.implementations("groupnorm")) == {"pallas", "xla"}


@pytest.mark.compile_budget(1)
def test_dispatch_adds_zero_retraces(compile_sentinel):
    """Dispatch resolution is trace-time static: repeated calls through
    the fused path with fresh data never mint a second executable."""
    x, gamma, beta, kw = _inputs(SHAPES[4], jnp.float32, film=True)

    @jax.jit
    def run(x, gamma, beta, scale, shift):
        return dispatch.dispatch("groupnorm", "pallas", x, gamma, beta,
                                 num_groups=kw["num_groups"],
                                 scale=scale, shift=shift, silu=True,
                                 interpret=True)

    compile_sentinel.track("fused_gn", run)
    for seed in range(3):
        x2, _, _, kw2 = _inputs(SHAPES[4], jnp.float32, seed=seed,
                                film=True)
        run(x2, gamma, beta, kw2["scale"], kw2["shift"])
    assert compile_sentinel.counts()["fused_gn"] == 1


# ---------------------------------------------------------------------------
# model wiring: param-tree identity + whole-model parity
# ---------------------------------------------------------------------------


def _tiny_batch(B=2, size=8, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "x": jnp.asarray(rs.randn(B, size, size, 3), jnp.float32),
        "z": jnp.asarray(rs.randn(B, size, size, 3), jnp.float32),
        "logsnr": jnp.asarray(rs.randn(B, 2), jnp.float32),
        "R": jnp.broadcast_to(jnp.eye(3), (B, 2, 3, 3)),
        "t": jnp.asarray(rs.randn(B, 2, 3), jnp.float32),
        "K": jnp.broadcast_to(
            jnp.asarray([[8.0, 0, 4], [0, 8, 4], [0, 0, 1]]), (B, 3, 3)),
    }


def _random_params(model, batch, cond_mask, seed=7):
    """Random NON-ZERO params: the X-UNet's output conv is zero-init, so
    freshly initialised params make every output (and gradient) exactly
    zero — parity would pass vacuously."""
    p0 = model.init(jax.random.PRNGKey(0), batch, cond_mask=cond_mask)
    leaves, treedef = jax.tree_util.tree_flatten(p0)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [0.1 * jax.random.normal(k, l.shape, l.dtype)
                  for k, l in zip(keys, leaves)])


def test_param_tree_identical_across_backends():
    """A checkpoint trained with either backend restores into the other:
    same tree structure, same leaf shapes/dtypes, same inits."""
    h = jnp.zeros((1, 2, 8, 8, 16))
    mx = FrameGroupNorm(kernels="xla", silu=True)
    mp = FrameGroupNorm(kernels="pallas", silu=True)
    px = mx.init(jax.random.PRNGKey(0), h)
    pp = mp.init(jax.random.PRNGKey(0), h)
    assert jax.tree_util.tree_structure(px) == \
        jax.tree_util.tree_structure(pp)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(px),
            jax.tree_util.tree_leaves_with_path(pp)):
        assert jax.tree_util.keystr(ka) == jax.tree_util.keystr(kb)
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)

    # Whole-model tree: eval_shape'd init (free) — leaf VALUES are
    # already proven equal above on FrameGroupNorm, the only module
    # whose parameter emission changed.
    cfg = make_tiny_config(imgsize=8, ch=8)
    batch = _tiny_batch()
    cm = jnp.ones((2,), bool)
    t_x = jax.eval_shape(
        lambda: XUNet(cfg.model).init(
            jax.random.PRNGKey(0), batch, cond_mask=cm))
    t_p = jax.eval_shape(
        lambda: XUNet(dataclasses.replace(
            cfg.model, kernels="pallas")).init(
                jax.random.PRNGKey(0), batch, cond_mask=cm))
    assert jax.tree_util.tree_structure(t_x) == \
        jax.tree_util.tree_structure(t_p)
    for a, b in zip(jax.tree_util.tree_leaves(t_x),
                    jax.tree_util.tree_leaves(t_p)):
        assert a.shape == b.shape and a.dtype == b.dtype


# Tier-1 budget: whole-model forward parity is superseded in tier 1 by
# test_step_many_sharded_pallas_parity, which drives the same kernels
# through every GN/FiLM/SiLU site inside the sharded, scanned sampler
# and compares against the default-kernel runtime end-to-end.
@pytest.mark.slow
def test_xunet_forward_parity():
    """Whole-model check: kernels='pallas' reproduces the default graph's
    outputs through every GN/FiLM/SiLU site (the ResnetBlock entry
    GN->SiLU, the FiLM epilogue, AttnBlock GNs and the head's last_gn).
    Per-parameter gradient parity through the same sites is the
    slow-lane companion below; the per-site custom_vjp itself is pinned
    tier-1 by ``test_backward_parity_f32``."""
    cfg = make_tiny_config(imgsize=8, ch=8)
    m_x = XUNet(cfg.model)
    m_p = XUNet(dataclasses.replace(cfg.model, kernels="pallas"))
    batch = _tiny_batch()
    cm = jnp.ones((2,), bool)
    params = _random_params(m_x, batch, cm)

    out_x = m_x.apply(params, batch, cond_mask=cm)
    out_p = m_p.apply(params, batch, cond_mask=cm)
    assert float(jnp.max(jnp.abs(out_x))) > 1e-3   # not vacuous
    np.testing.assert_allclose(out_p, out_x, atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_xunet_backward_parity():
    """Whole-model gradient sweep (slow lane: differentiating the
    interpret-mode kernels through every site takes minutes of tracing):
    kernels='pallas' reproduces every parameter gradient."""
    cfg = make_tiny_config(imgsize=8, ch=8)
    m_x = XUNet(cfg.model)
    m_p = XUNet(dataclasses.replace(cfg.model, kernels="pallas"))
    batch = _tiny_batch()
    cm = jnp.ones((2,), bool)
    params = _random_params(m_x, batch, cm)

    def loss(m, p):
        return jnp.mean(m.apply(p, batch, cond_mask=cm) ** 2)

    g_x = jax.grad(lambda p: loss(m_x, p))(params)
    g_p = jax.grad(lambda p: loss(m_p, p))(params)
    for (k, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(g_x),
                              jax.tree_util.tree_leaves_with_path(g_p)):
        np.testing.assert_allclose(
            b, a, atol=1e-5, rtol=1e-3,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(k)}")


def test_default_kernels_graph_unchanged():
    """kernels='xla' (the default) lowers to a jaxpr with no pallas
    call and no structural drift — pre-kernel-layer checkpoints and the
    pinned analysis manifests stay valid without re-conversion."""
    cfg = make_tiny_config(imgsize=8, ch=8)
    model = XUNet(cfg.model)
    batch = _tiny_batch()
    cm = jnp.ones((2,), bool)
    params = model.init(jax.random.PRNGKey(0), batch, cond_mask=cm)
    text = jax.jit(lambda p: model.apply(p, batch, cond_mask=cm)).lower(
        params).as_text()
    assert "pallas" not in text.lower()


# ---------------------------------------------------------------------------
# sharded end-to-end: step_many with kernels='pallas'
# ---------------------------------------------------------------------------


def test_step_many_sharded_pallas_parity():
    """End-to-end on the CPU mesh (data=2 slice of conftest's 8 virtual
    devices): synthesize_many with kernels='pallas' — interpret-mode
    fused kernels inside the sharded, scanned, donated step_many program
    — matches the unsharded default-kernel sampler per-object."""
    from diff3d_tpu.data import SyntheticDataset
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.train.trainer import init_params

    # Shallow 2-level model (tier-1 budget): the claim — fused kernels
    # inside the sharded/scanned/donated step_many match the default
    # runtime — is depth-independent, and both shallow levels hit every
    # fused-GN site kind (ResnetBlock entry, FiLM epilogue, attention).
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=8)
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(4)]

    ref = Sampler(model, params, cfg).synthesize_many(views, keys,
                                                      max_views=3)

    cfg_p = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kernels="pallas"))
    env = make_mesh(MeshConfig(data_parallel=2, model_parallel=1),
                    devices=jax.devices()[:2])
    got = Sampler(XUNet(cfg_p.model), params, cfg_p,
                  mesh=env).synthesize_many(views, keys, max_views=3)
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
