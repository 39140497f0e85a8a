"""Latent attention's core as one Pallas forward kernel
(``ops/pallas_attention.latent_attention``) behind op ``sdpa_latent``
(``ops/attention.py``), in interpret mode, against the XLA expression it
stands for: ``_xla_narrow_values`` on ``q = [q_nope | q_pe]`` and ``k =
[k_nope | k_pe]`` with the one rotary key broadcast to every head.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.ops import dispatch
from diff3d_tpu.ops.attention import (_xla_latent, _xla_narrow_values,
                                      sdpa_latent)
from diff3d_tpu.ops.pallas_attention import (_latent_fwd, latent_attention,
                                             latent_supports)
from diff3d_tpu.utils.profiling import RECORDER

# latent attention's widths: queries and keys 128 + a 64-wide rotary part,
# values 128 (openPangu-Ultra-MoE, DeepSeek-V3)
DN, DR, DV = 128, 64, 128


def _f32(x):
    return np.asarray(x, np.float32)


def _operands(dtype, H, B=1, Lq=64, Lk=384, dn=DN, dr=DR, dv=DV, seed=0):
    """Lq != Lk; 384 keys are three key blocks by the rule."""
    rng = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)
    return (arr(B, Lq, H, dn), arr(B, Lq, H, dr), arr(B, Lk, H, dn),
            arr(B, Lk, dr), arr(B, Lk, H, dv))


def _written_out(q_nope, q_pe, k_nope, k_pe, v):
    """The float32 softmax over all keys, each head's score written as
    its two parts."""
    qn, qp, kn, kp, vf = (a.astype(jnp.float32)
                          for a in (q_nope, q_pe, k_nope, k_pe, v))
    s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, precision="highest")
         + jnp.einsum("bqhd,bkd->bhqk", qp, kp, precision="highest"))
    s = s / np.sqrt(float(qn.shape[-1] + qp.shape[-1]))
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vf,
                      precision="highest")


# (query block, key block): the rule's (64 x 128: one query block, three
# key blocks) and two query blocks against all keys at once
BLOCKS = {"rule_64x128": None, "two_query_blocks_32x384": (32, 384)}


@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("H", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_latent_attention_matches_xla(dtype, H, blocks):
    ops = _operands(dtype, H)
    assert latent_supports(*ops)
    out = _latent_fwd(*ops, interpret=True, blocks=BLOCKS[blocks])
    ref = _xla_latent(*ops)
    assert out.shape == ref.shape == (1, 64, H, DV) and out.dtype == dtype
    # float32: only the order of the sums differs; bf16: the unnormalised
    # probabilities are rounded for PV where XLA rounds the normalised
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(_f32(out), _f32(ref), atol=tol, rtol=0)
    want = _written_out(*ops)
    assert float(jnp.abs(want).max()) > 0.1        # not a uniform average
    np.testing.assert_allclose(_f32(out), want, atol=tol, rtol=0)


def test_the_rotary_key_is_shared_and_its_scale_is_the_whole_widths():
    """One ``k_pe`` for all heads: turning one head's ``q_pe`` changes that
    head's output alone; the scale is ``(Dn + Dr)^-1/2``, not ``Dn``'s."""
    ops = list(_operands(jnp.float32, 4))
    out = latent_attention(*ops, interpret=True)
    ops[1] = ops[1].at[:, :, 2].multiply(-1.0)
    moved = jnp.abs(latent_attention(*ops, interpret=True) - out).max(
        axis=(0, 1, 3))
    assert float(moved[2]) > 0.1
    assert float(moved[jnp.array([0, 1, 3])].max()) == 0.0
    q = jnp.concatenate(ops[:2], axis=-1)
    k = jnp.concatenate([ops[2], jnp.broadcast_to(
        ops[3][:, :, None], ops[2].shape[:3] + (DR,))], axis=-1)
    np.testing.assert_allclose(latent_attention(*ops, interpret=True),
                               _xla_narrow_values(q, k, ops[4]),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("how", ["vmap", "map"])
def test_latent_attention_under_the_call_paths_transformations(how):
    """As the mixer under the sampler: a leading axis of objects by
    ``jax.vmap`` and of examples by ``lax.map``."""
    ops = _operands(jnp.float32, 4, B=3)
    one = lambda *a: latent_attention(                         # noqa: E731
        *(x[None] for x in a), interpret=True)[0]
    if how == "vmap":
        out = jax.jit(jax.vmap(one))(*ops)
    else:
        out = jax.jit(lambda *a: jax.lax.map(lambda b: one(*b), a))(*ops)
    np.testing.assert_allclose(out, _xla_latent(*ops), atol=2e-5, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_latent_attention_gradient_is_the_xla_expressions(dtype):
    """No backward kernel: the cotangents are the XLA expression's VJP at
    the saved operands, to the last bit, also under ``jax.grad``."""
    ops = _operands(dtype, 4)
    g = jnp.asarray(np.random.RandomState(5).randn(1, 64, 4, DV), dtype)
    _, vjp = jax.vjp(lambda *a: latent_attention(*a, interpret=True), *ops)
    _, want = jax.vjp(_xla_latent, *ops)
    for a, b in zip(vjp(g), want(g)):
        assert a.dtype == dtype and float(jnp.abs(_f32(b)).max()) > 0
        np.testing.assert_array_equal(_f32(a), _f32(b))

    def loss(fn):
        return jax.grad(lambda qp: jnp.sum(
            fn(ops[0], qp, *ops[2:]).astype(jnp.float32)
            * g.astype(jnp.float32)))(ops[1])
    np.testing.assert_array_equal(
        _f32(loss(lambda *a: latent_attention(*a, interpret=True))),
        _f32(loss(_xla_latent)))


def test_the_xla_core_by_query_tiles_is_the_whole_expression():
    """``q_chunk`` tiles the XLA expression alone: by tiles or whole, the
    same numbers as ``_xla_narrow_values`` on the concatenated operands."""
    ops = _operands(jnp.float32, 2, B=2, Lq=128)
    whole = _xla_latent(*ops)
    for C in (32, 64, 128, 512):
        np.testing.assert_allclose(_xla_latent(*ops, q_chunk=C), whole,
                                   atol=1e-6, rtol=0)
    np.testing.assert_allclose(whole, _written_out(*ops), atol=2e-5, rtol=0)


def _site(dtype=jnp.bfloat16, B=1, Lq=8192, Lk=8192, H=128, dn=DN, dr=DR,
          dv=DV, pe_heads=False):
    sds = lambda *s: jax.ShapeDtypeStruct(s, dtype)             # noqa: E731
    k_pe = sds(B, Lk, H, dr) if pe_heads else sds(B, Lk, dr)
    return (sds(B, Lq, H, dn), sds(B, Lq, H, dr), sds(B, Lk, H, dn), k_pe,
            sds(B, Lk, H, dv))


UNSUPPORTED = {
    "nope_not_lane_tiles": dict(dn=96),
    "values_not_lane_tiles": dict(dv=64),
    "rotary_wider_than_a_lane_tile": dict(dr=192),
    "keys_not_whole_blocks": dict(Lk=200),
    "queries_not_whole_blocks": dict(Lq=40),
    "rotary_key_per_head": dict(pe_heads=True),
    "int8": dict(dtype=jnp.int8),
}


@pytest.mark.parametrize("case", list(UNSUPPORTED))
def test_sdpa_latent_pallas_on_unsupported_operands_raises(case):
    """Where the kernel cannot run an explicit ``'pallas'`` raises and
    ``'auto'`` falls to XLA, also on a TPU process."""
    p = dict(Lq=64, Lk=2048, H=4)
    p.update(UNSUPPORTED[case])
    ops = _site(**p)
    assert latent_supports(*_site(Lq=64, Lk=2048, H=4))
    assert not latent_supports(*ops)
    with pytest.raises(ValueError, match="sdpa_latent.*'pallas' was "
                                         "requested explicitly"):
        jax.eval_shape(lambda *a: sdpa_latent(*a, impl="pallas"), *ops)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dispatch, "default_backend", lambda: "tpu")
        assert dispatch.resolve("sdpa_latent", "auto", *ops).name == "xla"


def _counted(fn, *args):
    before = RECORDER.counters()
    jax.eval_shape(lambda *a: fn(*a), *args)    # a new trace every time
    after = RECORDER.counters()
    return {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("sdpa.") and after[k] != before.get(k, 0)}


def test_the_rule_and_the_counters_on_a_tpu_process(monkeypatch):
    """Backend TPU: the cell's site (128 heads, 8192 x 8192, bf16) takes
    the kernel and counts ``sdpa.latent.pallas`` once per traced site, a
    ``q_chunk`` or not; so does a float32 site."""
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    site = _site()
    assert dispatch.resolve("sdpa_latent", "auto", *site).name == "pallas"
    assert _counted(sdpa_latent, *site) == {"sdpa.latent.pallas": 1}
    assert _counted(lambda *a: sdpa_latent(*a, q_chunk=256), *site) \
        == {"sdpa.latent.pallas": 1}
    assert _counted(sdpa_latent, *_site(jnp.float32, Lq=512)) \
        == {"sdpa.latent.pallas": 1}


def test_the_rule_and_the_counters_on_a_cpu_process():
    """Every site resolves to XLA here, counts ``sdpa.latent.xla`` once
    per traced site (the tile map traces its body once), and lowers to
    the text of ``_xla_latent``: no kernel, in interpret mode or
    otherwise."""
    site = _site()
    assert dispatch.resolve("sdpa_latent", "auto", *site).name == "xla"
    assert _counted(lambda *a: sdpa_latent(*a, q_chunk=256), *site) \
        == {"sdpa.latent.xla": 1}
    ops = _operands(jnp.float32, 2, Lq=128)
    mine = jax.jit(lambda *a: sdpa_latent(*a, q_chunk=64)).lower(
        *ops).as_text()
    want = jax.jit(lambda *a: _xla_latent(*a, q_chunk=64)).lower(
        *ops).as_text()
    assert mine == want
    assert "custom_call" not in mine and "pallas" not in mine


def test_sdpa_latent_routes_by_request():
    ops = _operands(jnp.float32, 4)
    ref = _xla_latent(*ops)
    np.testing.assert_array_equal(sdpa_latent(*ops, impl="xla"), ref)
    np.testing.assert_array_equal(sdpa_latent(*ops), ref)      # CPU: xla
    np.testing.assert_allclose(sdpa_latent(*ops, impl="pallas"), ref,
                               atol=2e-5, rtol=0)
