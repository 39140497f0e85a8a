"""The gated delta rule's chunked WY form (ops/delta.py, op
``'delta_scan'``) against the token-by-token recurrence it stands for,
in float32 on the CPU: chunk sizes, lengths no chunk divides, write
strengths near 0 and near 1, decays from -1e-4 to -20 a token, chunks
of near-parallel keys, the triangular inverse against forward
substitution, the gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.ops import delta, dispatch
from diff3d_tpu.utils.profiling import RECORDER

_HI = jax.lax.Precision.HIGHEST
chunked = jax.jit(delta.delta_chunked, static_argnums=5)


@jax.jit
def recurrence(q, k, v, g, beta):
    """``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, one token at a time."""
    rep = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(a, rep, axis=1) for a in (q, k))

    def step(S, inp):
        qt, kt, vt, gt, bt = inp                  # [H, dk], [H, dv], [H]
        S = jnp.exp(gt)[:, None, None] * S
        pred = jnp.einsum("hk,hkv->hv", kt, S, precision=_HI)
        S = S + bt[:, None, None] * kt[..., None] * (vt - pred)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=_HI)

    S0 = jnp.zeros((v.shape[1], q.shape[-1], v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def operands(key, L, Hk=2, Hv=4, dk=16, dv=8, g_range=(-1.0, -1e-4),
             beta_range=(0.0, 1.0)):
    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (L, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (L, Hk, dk)))
    v = jax.random.normal(ks[2], (L, Hv, dv))
    lo, hi = np.log(-np.array(g_range[::-1]))          # log |g|
    g = -jnp.exp(jax.random.uniform(ks[3], (L, Hv), minval=lo, maxval=hi))
    beta = jax.random.uniform(ks[4], (L, Hv), minval=beta_range[0],
                              maxval=beta_range[1])
    return q, k, v, g, beta


@pytest.mark.parametrize("L,chunk", [(64, 8), (64, 16), (64, 64),
                                     (100, 16), (37, 64), (96, 24)])
def test_the_chunked_form_is_the_recurrence(L, chunk):
    ops = operands(jax.random.PRNGKey(L + chunk), L)
    got = chunked(*ops, chunk)
    want = recurrence(*ops)
    assert got.shape == (L, 4, 8) and got.dtype == jnp.float32
    assert float(jnp.abs(want).mean()) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("beta_range", [(0.0, 1e-3), (0.999, 1.0)])
def test_writes_near_nothing_and_near_whole(beta_range):
    """beta near 0 leaves the state near its decay alone; near 1 each
    key's slot is overwritten by its value almost entirely, the
    delta rule's distinctive case (plain linear attention adds)."""
    ops = operands(jax.random.PRNGKey(3), 96, beta_range=beta_range,
                   g_range=(-1e-2, -1e-4))
    got = chunked(*ops, 32)
    want = recurrence(*ops)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("g_range", [(-1e-4, -1e-4), (-20.0, -20.0),
                                     (-20.0, -1e-4)])
def test_decays_from_a_ten_thousandth_to_twenty_a_token_stay_finite(
        g_range):
    ops = operands(jax.random.PRNGKey(4), 128, g_range=g_range)
    got = chunked(*ops, 64)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, recurrence(*ops), atol=2e-5, rtol=0)
    # gradients too: no exp of a positive number anywhere
    grads = jax.jit(jax.grad(lambda *a: delta.delta_chunked(*a, 64).sum(),
                             argnums=(0, 1, 2, 3, 4)))(*ops)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


@pytest.mark.parametrize("C", [2, 3, 24, 64])
def test_the_inverse_is_forward_substitutions(C):
    """``(I + A)^-1`` of strictly lower ``A``, against forward
    substitution written out in float64, for entries of the size a chunk
    of unit keys gives (|A_ij| <= 1)."""
    a = jax.random.uniform(jax.random.PRNGKey(C), (5, C, C), minval=-1.0,
                           maxval=1.0) / np.sqrt(C)
    A = jnp.tril(a, -1)
    An = np.asarray(A, np.float64)
    want = np.zeros((5, C, C))
    for b in range(5):
        for j in range(C):                      # column j of the inverse
            x = np.zeros(C)
            x[j] = 1.0
            for i in range(j + 1, C):
                x[i] = -An[b, i, :i] @ x[:i]
            want[b, :, j] = x
    np.testing.assert_allclose(delta.unit_lower_inverse(A), want,
                               atol=1e-5, rtol=0)


def test_near_parallel_keys_written_whole_stay_the_recurrence():
    """A chunk of near-parallel keys at ``beta`` 0.99 and next to no
    decay: ``A`` is near the strictly lower matrix of ones, whose powers
    reach ~1e18 (the doubling product ``(I - A)(I + A^2)...`` read 4e21
    off here), while its inverse and the outputs stay near 1."""
    L, d = 128, 16
    k0 = jax.random.normal(jax.random.PRNGKey(0), (1, 2, d))
    k = k0 + 0.05 * jax.random.normal(jax.random.PRNGKey(1), (L, 2, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(jax.random.PRNGKey(2), (L, 4, 8))
    g, beta = jnp.full((L, 4), -1e-3), jnp.full((L, 4), 0.99)
    ops = (k * d ** -0.5, k, v, g, beta)
    want = recurrence(*ops)
    np.testing.assert_allclose(chunked(*ops, 64), want, atol=2e-5, rtol=0)


def test_the_gradient_is_the_recurrences():
    ops = operands(jax.random.PRNGKey(6), 40)
    loss = lambda f: lambda *a: (f(*a) ** 2).sum()  # noqa: E731
    got = jax.jit(jax.grad(loss(lambda *a: delta.delta_chunked(*a, 16)),
                           argnums=(0, 1, 2, 3, 4)))(*ops)
    want = jax.jit(jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4)))(*ops)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_bfloat16_operands_keep_a_float32_state():
    ops = operands(jax.random.PRNGKey(7), 128)
    q, k, v = (a.astype(jnp.bfloat16) for a in ops[:3])
    got = chunked(q, k, v, *ops[3:], 64)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(a.astype(jnp.float32) for a in (q, k, v)), *ops[3:])
    gap = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert gap < 0.03 * float(jnp.abs(want).max()), gap


def test_the_op_has_one_core_and_counts_each_traced_site(monkeypatch):
    assert set(dispatch.implementations("delta_scan")) == {"xla"}
    ops = operands(jax.random.PRNGKey(8), 64)
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    before = RECORDER.counters().get("delta_scan.xla", 0)
    jax.eval_shape(lambda *a: delta.delta_scan(*a, 64), *ops)
    assert RECORDER.counters()["delta_scan.xla"] == before + 1
