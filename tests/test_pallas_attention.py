"""Pallas flash-attention kernel vs the XLA reference.

Runs the exact TPU tile program in Pallas interpret mode on CPU (the
tests' virtual-device platform), checking forward and backward against
``jax.nn.dot_product_attention`` over the shapes the X-UNet actually uses
(token counts 64..1024, head dims 32..128, including the padded /
non-square cases).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.ops.attention import multi_head_attention, sdpa
from diff3d_tpu.ops.pallas_attention import flash_attention, supports

SHAPES = [
    # (B, Lq, Lk, H, D): xunet attention shapes (SURVEY.md §3.4) + padding
    (2, 64, 64, 4, 64),      # 8x8 tokens, 256ch/4heads
    (2, 256, 256, 4, 128),   # 16x16 tokens, 512ch/4heads
    (1, 200, 200, 2, 32),    # non-multiple-of-128 seq (padded)
    (1, 96, 160, 2, 64),     # cross attention, Lq != Lk
    (1, 256, 256, 2, 256),   # srn128 deep level: D spans two lane tiles
    (1, 64, 64, 2, 160),     # D padded up to two lane tiles (160 -> 256)
]


def _qkv(shape, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    B, Lq, Lk, H, D = shape
    q = jnp.asarray(rng.randn(B, Lq, H, D), dtype)
    k = jnp.asarray(rng.randn(B, Lk, H, D), dtype)
    v = jnp.asarray(rng.randn(B, Lk, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", SHAPES)
def test_forward_matches_xla(shape):
    q, k, v = _qkv(shape)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("shape", SHAPES[:2] + SHAPES[4:6])
def test_backward_matches_xla(shape):
    q, k, v = _qkv(shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(loss(jax.nn.dot_product_attention),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(b, a, atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("shape", SHAPES[:2] + SHAPES[3:])
def test_lse_output_matches_logsumexp(shape):
    from diff3d_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _qkv(shape)
    o, lse = flash_attention_lse(q, k, v, interpret=True)
    np.testing.assert_allclose(o, jax.nn.dot_product_attention(q, k, v),
                               atol=1e-2, rtol=1e-2)
    D = q.shape[-1]
    s = jnp.einsum("blhd,bmhd->blhm", q, k) / np.sqrt(D)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)     # [B, Lq, H]
    assert lse.shape == ref_lse.shape
    np.testing.assert_allclose(lse, ref_lse, atol=1e-3, rtol=1e-3)


def test_lse_gradients_including_lse_cotangent():
    """Both outputs' cotangents flow: compare against autodiff of the
    same (attention, logsumexp) pair composed from jnp primitives."""
    from diff3d_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _qkv((1, 64, 64, 2, 32), seed=3)
    D = q.shape[-1]

    def ref_fn(q, k, v):
        s = jnp.einsum("blhd,bmhd->blhm", q, k) / np.sqrt(D)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("blhm,bmhd->blhd", p, v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def fl_fn(q, k, v):
        o, lse = flash_attention_lse(q, k, v, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    g_ref = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(b, a, atol=5e-3, rtol=5e-3)


def test_bf16_forward():
    q, k, v = _qkv((2, 128, 128, 4, 64), dtype=jnp.bfloat16)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=5e-2, rtol=5e-2)


def test_supports_gating():
    q, k, v = _qkv((1, 64, 64, 2, 64))
    assert supports(q, k, v)
    # multi-lane-tile head dims up to MAX_D=512 are handled (srn128's
    # deep levels run D=256); beyond that the dispatcher falls back
    d256 = jnp.zeros((1, 64, 2, 256))
    assert supports(d256, d256, d256)
    huge = jnp.zeros((1, 64, 2, 640))
    assert not supports(huge, huge, huge)
    assert not supports(q.astype(jnp.float16), k, v)


def test_dispatcher_jit_consistency():
    """sdpa under jit: pallas and xla backends agree."""
    q, k, v = _qkv((2, 64, 64, 4, 64))

    @jax.jit
    def f(q, k, v):
        return sdpa(q, k, v, impl="xla")

    ref = f(q, k, v)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, interpret=True))(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-2, rtol=1e-2)


def test_multi_head_attention_wrapper():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 64, 128), jnp.float32)
    out = multi_head_attention(x, x, x, num_heads=4, impl="xla")
    assert out.shape == (2, 64, 128)


def test_resolve_auto_policy(monkeypatch):
    """'auto' routes per measured policy: XLA off-TPU always; on TPU the
    plain kernel from 2048 keys on at the head dims it has (64, whole
    lane tiles), never ``flash_attention``."""
    from diff3d_tpu.ops import attention as att

    def q(L, D):
        return jnp.zeros((1, L, 4, D))

    monkeypatch.setattr(att.jax, "default_backend", lambda: "cpu")
    assert att._resolve_auto(q(16384, 128)) == "xla"  # off-TPU: always xla

    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    assert att._resolve_auto(q(4096, 32)) == "xla"    # no kernel wins here
    assert att._resolve_auto(q(4096, 64)) == "pallas"
    assert att._resolve_auto(q(4096, 128)) == "pallas"
    assert att._resolve_auto(q(2048, 128)) == "pallas"
    assert att._resolve_auto(q(1024, 128)) == "xla"   # short seq
    assert att._resolve_auto(q(4096, 160)) == "xla"   # flash_attention's


# --------------------------------------------------------------------------
# attention under a selection (selected_attention, sdpa(keep=))
# --------------------------------------------------------------------------

from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.ops.pallas_attention import (selected_attention,  # noqa: E402
                                             selected_reference,
                                             selected_supports)


def _selection(rng, B, Lq, Lk, topk):
    """``keep [B, Lq, Lk]`` as the token denoiser makes it (scores at
    least the ``topk``-th largest of their row), with what the kernel
    must survive: scores drawn from 40 values, so keys tie with the
    ``topk``-th and rows keep different numbers; the second key block of
    128 kept by no row; the first kept by no row of the first half."""
    scores = rng.randint(0, 40, (B, Lq, Lk)).astype(np.float32)
    scores[:, :, 128:256] = -1.0
    scores[:, :Lq // 2, :128] = -1.0
    kth = np.sort(scores, axis=-1)[..., Lk - topk]
    keep = scores >= kth[..., None]
    assert not keep[:, :, 128:256].any() and not keep[:, :Lq // 2, :128].any()
    assert len(set(keep.sum(-1).ravel().tolist())) > 1 \
        and keep.sum(-1).min() >= topk
    return jnp.asarray(keep)


def _selected_operands(dtype, group, B=2, Lq=64, Lk=384, Hkv=1, D=128,
                       seed=0):
    """Lq != Lk; 384 keys are three key blocks of 128."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Lq, Hkv * group, D), dtype)
    k = jnp.asarray(rng.randn(B, Lk, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, Lk, Hkv, D), dtype)
    return q, k, v, _selection(rng, B, Lq, Lk, topk=48)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_selected_attention_matches_xla(dtype, group):
    Hkv = 2 if group == 4 else 1
    q, k, v, keep = _selected_operands(dtype, group, Hkv=Hkv)
    assert selected_supports(q, k, v, keep)
    out = selected_attention(q, k, v, keep, interpret=True)
    ref = selected_reference(q, k, v, keep)
    assert out.shape == ref.shape and out.dtype == dtype
    # float32: only the order of the sums differs; bf16: the unnormalised
    # probabilities are rounded for PV where XLA rounds the normalised
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(_f32(out), _f32(ref), atol=tol, rtol=0)
    # and against the float32 softmax over the kept keys, written out
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, jnp.repeat(kf, group, axis=2),
                   precision="highest") / np.sqrt(128.0)
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(vf, group, axis=2),
                      precision="highest")
    np.testing.assert_allclose(_f32(out), want, atol=tol, rtol=0)


def test_selected_attention_row_without_a_kept_key_averages_all_keys():
    """No caller makes such a row (``topk >= 1``); the kernel then does
    what the XLA expression does, and never divides by zero."""
    q, k, v, keep = _selected_operands(jnp.float32, 4, B=1)
    keep = keep.at[0, 3].set(False)
    out = selected_attention(q, k, v, keep, interpret=True)
    np.testing.assert_allclose(out, selected_reference(q, k, v, keep),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        out[0, 3], jnp.repeat(v[0].mean(axis=0), 4, axis=0), atol=2e-5)


@pytest.mark.parametrize("how", ["vmap", "map", "vmap_of_map"])
def test_selected_attention_under_the_call_paths_transformations(how):
    """The sampler vmaps the view program over objects and the layer maps
    over examples and query tiles: a leading axis by ``jax.vmap`` (Pallas
    adds a grid axis), by ``lax.map``, and the map inside the vmap."""
    q, k, v, keep = _selected_operands(jnp.float32, 4, B=3)
    one = lambda q, k, v, keep: selected_attention(           # noqa: E731
        q[None], k[None], v[None], keep[None], interpret=True)[0]
    tiles = lambda q, k, v, keep: jax.lax.map(                # noqa: E731
        lambda a: one(a[0], k, v, a[1]),
        (q.reshape(2, 32, *q.shape[1:]), keep.reshape(2, 32, -1))
    ).reshape(q.shape)
    if how == "vmap":
        out = jax.jit(jax.vmap(one))(q, k, v, keep)
    elif how == "map":
        out = jax.jit(lambda *a: jax.lax.map(lambda b: one(*b), a))(
            q, k, v, keep)
    else:
        out = jax.jit(jax.vmap(tiles))(q, k, v, keep)
    np.testing.assert_allclose(out, selected_reference(q, k, v, keep),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_selected_attention_gradient_is_the_xla_expressions(dtype):
    """No backward kernel: the cotangents are the XLA expression's VJP at
    the saved operands, to the last bit, also under ``jax.grad``."""
    q, k, v, keep = _selected_operands(dtype, 4)
    g = jnp.asarray(np.random.RandomState(5).randn(*q.shape), dtype)
    _, vjp = jax.vjp(lambda q, k, v: selected_attention(
        q, k, v, keep, interpret=True), q, k, v)
    _, want = jax.vjp(lambda q, k, v: selected_reference(q, k, v, keep),
                      q, k, v)
    for a, b in zip(vjp(g), want(g)):
        assert a.dtype == dtype and float(jnp.abs(_f32(b)).max()) > 0
        np.testing.assert_array_equal(_f32(a), _f32(b))
    loss = lambda fn: jax.grad(lambda q: jnp.sum(                 # noqa: E731
        fn(q, k, v, keep).astype(jnp.float32) * g.astype(jnp.float32)))
    np.testing.assert_array_equal(
        _f32(loss(lambda *a: selected_attention(*a, interpret=True))(q)),
        _f32(loss(selected_reference)(q)))


UNSUPPORTED = {
    "head_dim_32": dict(D=32),
    "keys_not_whole_blocks": dict(Lk=200),
    "queries_not_whole_blocks": dict(Lq=40),
    "kv_heads_do_not_divide": dict(Hq=4, Hkv=3),
    "float16": dict(dtype=jnp.float16),
}


@pytest.mark.parametrize("case", list(UNSUPPORTED))
def test_sdpa_pallas_with_keep_on_unsupported_operands_raises(case):
    p = dict(Lq=64, Lk=256, Hq=4, Hkv=2, D=128, dtype=jnp.float32)
    p.update(UNSUPPORTED[case])
    q = jnp.zeros((1, p["Lq"], p["Hq"], p["D"]), p["dtype"])
    k = jnp.zeros((1, p["Lk"], p["Hkv"], p["D"]), p["dtype"])
    keep = jnp.ones((1, p["Lq"], p["Lk"]), bool)
    assert not selected_supports(q, k, k, keep)
    with pytest.raises(ValueError, match="sdpa_selected.*'pallas' was "
                                         "requested explicitly"):
        sdpa(q, k, k, impl="pallas", keep=keep)
    # 'auto' may choose, and off the TPU it chooses the XLA expression
    assert dispatch.resolve("sdpa_selected", "auto", q, k, k,
                            keep).name == "xla"


def test_sdpa_with_keep_routes_by_request():
    q, k, v, keep = _selected_operands(jnp.float32, 4, B=1)
    ref = selected_reference(q, k, v, keep)
    np.testing.assert_array_equal(sdpa(q, k, v, impl="xla", keep=keep), ref)
    np.testing.assert_array_equal(sdpa(q, k, v, keep=keep), ref)  # CPU: xla
    np.testing.assert_allclose(sdpa(q, k, v, impl="pallas", keep=keep), ref,
                               atol=2e-5, rtol=0)
    with pytest.raises(ValueError, match="ring:model"):
        sdpa(q, k, v, impl="ring:model", keep=keep)


def test_selected_auto_takes_the_kernel_on_a_tpu_process(monkeypatch):
    q, k, v, keep = _selected_operands(jnp.bfloat16, 8, B=1)
    small = jnp.zeros((1, 64, 4, 32), jnp.bfloat16)
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert dispatch.resolve("sdpa_selected", "auto", q, k, v,
                            keep).name == "pallas"
    assert dispatch.resolve("sdpa_selected", "auto", small, small, small,
                            jnp.ones((1, 64, 64), bool)).name == "xla"


def test_sdpa_with_keep_on_a_cpu_process_lowers_to_the_xla_expression():
    """The text a CPU process lowers ``sdpa(keep=)`` to is the text of
    ``jax.nn.dot_product_attention(mask=)``, what the parent lowered: no
    kernel, in interpret mode or otherwise."""
    q, k, v, keep = _selected_operands(jnp.float32, 4, B=1)

    def core(q, k, v, keep):
        return sdpa(q, k, v, keep=keep)
    mine = jax.jit(core).lower(q, k, v, keep).as_text()

    def core(q, k, v, keep):                                  # noqa: F811
        return jax.nn.dot_product_attention(q, k, v, mask=keep[:, None])
    assert mine == jax.jit(core).lower(q, k, v, keep).as_text()
    assert "custom_call" not in mine and "pallas" not in mine
    forced = jax.jit(lambda *a: sdpa(*a[:3], impl="pallas", keep=a[3])
                     ).lower(q, k, v, keep).as_text()
    assert forced != mine


# --------------------------------------------------------------------------
# plain grouped-query attention (plain_attention, sdpa without keep)
# --------------------------------------------------------------------------

from diff3d_tpu.ops import attention as att  # noqa: E402
from diff3d_tpu.ops.pallas_attention import (plain_attention,  # noqa: E402
                                             plain_supports)
from diff3d_tpu.utils.profiling import RECORDER  # noqa: E402

MULTIPLIER = 1.0 / 64       # granite-4.0-h-micro's attention_multiplier


def _plain_operands(dtype, group, D, B=2, Lq=64, Lk=384, Hkv=2, seed=0):
    """``q`` carries ``attention_multiplier * D^1/2``, as ``FullAttention``
    hands it to ``sdpa``; Lq != Lk; 384 keys are three key blocks."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Lq, Hkv * group, D) * 8, dtype)
    k = jnp.asarray(rng.randn(B, Lk, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, Lk, Hkv, D), dtype)
    return q * jnp.asarray(MULTIPLIER * D ** 0.5, dtype), k, v


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_plain_attention_matches_xla(dtype, group, D):
    q, k, v = _plain_operands(dtype, group, D)
    assert plain_supports(q, k, v)
    out = plain_attention(q, k, v, interpret=True)
    ref = jax.nn.dot_product_attention(q, k, v)
    assert out.shape == ref.shape and out.dtype == dtype
    # float32: only the order of the sums differs; bf16: the unnormalised
    # probabilities are rounded for PV where XLA rounds the normalised
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(_f32(out), _f32(ref), atol=tol, rtol=0)
    # and against the float32 softmax over all keys, written out
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, jnp.repeat(kf, group, axis=2),
                   precision="highest") / np.sqrt(float(D))
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                      jnp.repeat(vf, group, axis=2), precision="highest")
    assert float(jnp.abs(want).max()) > 0.1        # not a uniform average
    np.testing.assert_allclose(_f32(out), want, atol=tol, rtol=0)


def test_plain_attention_odd_group_at_head_dim_64():
    """Three query heads to a key-value head: the heads of one lane tile
    then attend different key-value heads."""
    q, k, v = _plain_operands(jnp.float32, 3, 64)
    np.testing.assert_allclose(
        plain_attention(q, k, v, interpret=True),
        jax.nn.dot_product_attention(q, k, v), atol=2e-5, rtol=0)


@pytest.mark.parametrize("how", ["vmap", "map", "vmap_of_map"])
def test_plain_attention_under_the_call_paths_transformations(how):
    """As ``FullAttention`` under the sampler: a leading axis by
    ``jax.vmap`` (objects), by ``lax.map`` (examples), and ``lax.map``
    over query tiles inside the vmap."""
    q, k, v = _plain_operands(jnp.float32, 4, 64, B=3)
    one = lambda q, k, v: plain_attention(                    # noqa: E731
        q[None], k[None], v[None], interpret=True)[0]
    tiles = lambda q, k, v: jax.lax.map(                      # noqa: E731
        lambda a: one(a, k, v), q.reshape(2, 32, *q.shape[1:])
    ).reshape(q.shape)
    if how == "vmap":
        out = jax.jit(jax.vmap(one))(q, k, v)
    elif how == "map":
        out = jax.jit(lambda *a: jax.lax.map(lambda b: one(*b), a))(q, k, v)
    else:
        out = jax.jit(jax.vmap(tiles))(q, k, v)
    np.testing.assert_allclose(out, jax.nn.dot_product_attention(q, k, v),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_plain_attention_gradient_is_the_xla_expressions(dtype):
    """No backward kernel: the cotangents are the XLA expression's VJP at
    the saved operands, to the last bit, also under ``jax.grad``."""
    q, k, v = _plain_operands(dtype, 4, 64)
    g = jnp.asarray(np.random.RandomState(5).randn(*q.shape), dtype)
    _, vjp = jax.vjp(lambda *a: plain_attention(*a, interpret=True), q, k, v)
    _, want = jax.vjp(jax.nn.dot_product_attention, q, k, v)
    for a, b in zip(vjp(g), want(g)):
        assert a.dtype == dtype and float(jnp.abs(_f32(b)).max()) > 0
        np.testing.assert_array_equal(_f32(a), _f32(b))
    loss = lambda fn: jax.grad(lambda q: jnp.sum(                 # noqa: E731
        fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)))
    np.testing.assert_array_equal(
        _f32(loss(lambda *a: plain_attention(*a, interpret=True))(q)),
        _f32(loss(jax.nn.dot_product_attention)(q)))


# the hybrid cell's site and the X-UNet's (tests/test_chip_compile.py
# ATTN_SITES): (Lq, Lk, Hq, Hkv, D)
HYBRID_SITE = (512, 8192, 32, 8, 64)
XUNET_SITES = [(256, 256, 4, 4, 64), (64, 64, 4, 4, 128),
               (1024, 1024, 4, 4, 128), (256, 256, 4, 4, 256)]


def _site(Lq, Lk, Hq, Hkv, D, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((1, Lq, Hq, D), dtype),
            *[jax.ShapeDtypeStruct((1, Lk, Hkv, D), dtype)] * 2)


PLAIN_UNSUPPORTED = {
    "keys_not_whole_blocks": dict(Lk=200),
    "queries_not_whole_blocks": dict(Lq=40),
    "kv_heads_do_not_divide": dict(Hq=4, Hkv=3),
    "head_dim_64_odd_kv_heads": dict(Hq=4, Hkv=1, D=64),
    "head_dim_32": dict(D=32),
}


@pytest.mark.parametrize("case", list(PLAIN_UNSUPPORTED))
def test_sdpa_pallas_on_operands_neither_kernel_supports_raises(case):
    """Grouped heads are ``plain_attention``'s alone: where it cannot run
    (``flash_attention`` wants a key-value head per query head) an
    explicit ``'pallas'`` raises and ``'auto'`` falls to XLA, also on a
    TPU process."""
    p = dict(Lq=64, Lk=2048, Hq=4, Hkv=2, D=128)
    p.update(PLAIN_UNSUPPORTED[case])
    q, k, v = _site(**p, dtype=jnp.float32)
    assert not plain_supports(q, k, v) and not supports(q, k, v)
    with pytest.raises(ValueError, match="sdpa.*'pallas' was requested "
                                         "explicitly"):
        jax.eval_shape(lambda *a: sdpa(*a, impl="pallas"), q, k, v)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "default_backend", lambda: "tpu")
        assert dispatch.resolve("sdpa", "auto", q, k, v).name == "xla"


def _counted(fn, *args):
    before = RECORDER.counters()
    jax.eval_shape(lambda *a: fn(*a), *args)    # a new trace every time
    after = RECORDER.counters()
    return {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("sdpa.") and after[k] != before.get(k, 0)}


def test_the_rule_on_a_tpu_process(monkeypatch):
    """Backend TPU, whole blocks, ``Hkv`` dividing ``Hq``, 2048 keys or
    more: the hybrid site takes the kernel and counts it once per traced
    site; the X-UNet's four sites and ``sdpa(keep=)`` resolve as they
    did."""
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    site = _site(*HYBRID_SITE)
    assert dispatch.resolve("sdpa", "auto", *site).name == "pallas"
    assert _counted(sdpa, *site) == {"sdpa.plain.pallas": 1}
    for Lk in (2048, 4096):
        assert dispatch.resolve("sdpa", "auto", *_site(
            512, Lk, 32, 8, 64)).name == "pallas"
    assert dispatch.resolve("sdpa", "auto", *_site(
        512, 1024, 32, 8, 64)).name == "xla"
    for xunet in XUNET_SITES:
        assert dispatch.resolve("sdpa", "auto", *_site(*xunet)).name == "xla"
        assert _counted(sdpa, *_site(*xunet)) == {"sdpa.plain.xla": 1}
    q, k, v, keep = _selected_operands(jnp.bfloat16, 8, B=1)
    assert _counted(lambda *a: sdpa(*a[:3], keep=a[3]), q, k, v, keep) \
        == {"sdpa.selected.pallas": 1}


def test_the_rule_on_a_cpu_process():
    """Every site resolves to XLA here, counts ``sdpa.plain.xla``, and
    lowers to the text of ``jax.nn.dot_product_attention``: no kernel, in
    interpret mode or otherwise."""
    for site in [HYBRID_SITE] + XUNET_SITES:
        assert dispatch.resolve("sdpa", "auto", *_site(*site)).name == "xla"
        assert _counted(sdpa, *_site(*site)) == {"sdpa.plain.xla": 1}
    q, k, v = _plain_operands(jnp.float32, 4, 64, B=1, Lk=2048)
    mine = jax.jit(lambda *a: sdpa(*a)).lower(q, k, v).as_text()
    want = jax.jit(lambda *a: jax.nn.dot_product_attention(*a)).lower(
        q, k, v).as_text()
    assert mine == want
    assert "custom_call" not in mine and "pallas" not in mine


def test_sdpa_pallas_by_hand_picks_the_kernel_by_shape(monkeypatch):
    """``impl='pallas'`` at the X-UNet's sites is ``flash_attention``
    with its backward kernels; from 2048 keys on, on operands it
    supports, and for grouped heads at any length, ``plain_attention``."""
    from diff3d_tpu.ops import pallas_attention as pa

    called = []
    for name in ("flash_attention", "plain_attention"):
        monkeypatch.setattr(pa, name, lambda q, k, v, name=name: (
            called.append(name), q)[1])
    for site in XUNET_SITES:
        jax.eval_shape(lambda *a: sdpa(*a, impl="pallas"), *_site(*site))
    assert called == ["flash_attention"] * 4
    jax.eval_shape(lambda *a: sdpa(*a, impl="pallas"), *_site(*HYBRID_SITE))
    jax.eval_shape(lambda *a: sdpa(*a, impl="pallas"),
                   *_site(200, 4096, 4, 4, 128))       # ragged queries
    jax.eval_shape(lambda *a: sdpa(*a, impl="pallas"),
                   *_site(64, 256, 4, 2, 64))          # grouped heads
    assert called[4:] == ["plain_attention", "flash_attention",
                          "plain_attention"]


def test_sdpa_without_keep_routes_by_request():
    q, k, v = _plain_operands(jnp.float32, 4, 64, B=1, Lk=2048)
    ref = jax.nn.dot_product_attention(q, k, v)
    np.testing.assert_array_equal(sdpa(q, k, v, impl="xla"), ref)
    np.testing.assert_array_equal(sdpa(q, k, v), ref)         # CPU: xla
    np.testing.assert_allclose(sdpa(q, k, v, impl="pallas"), ref,
                               atol=2e-5, rtol=0)
    assert att.PLAIN_MIN_KEYS == 2048
