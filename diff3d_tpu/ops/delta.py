"""The gated delta rule of a Gated DeltaNet mixer (Yang, Kautz and
Hatamizadeh, arXiv:2412.06464) as a chunked scan in the WY form, a pure
function of one example.

Per value head ``h`` with state ``S [dk, dv]`` (``S = 0`` before the first
token), over the tokens ``t`` of the example in order::

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q`` and ``k`` are given as the mixer makes them (L2-normed, ``q``
scaled); value head ``h`` reads key head ``h // (Hv / Hk)``.  The sequence
is cut into chunks of ``chunk`` tokens (zero-padded at the end where
``chunk`` does not divide ``L``: a padded token has ``g = 0`` and ``beta
= 0``, so it neither decays nor writes the state, and its output is
dropped).  With ``gam`` the inclusive sum of ``g`` inside a chunk and
``S_0`` the state the chunk starts from, the state after token ``i`` is
``exp(gam_i) S_0 + sum_{j <= i} exp(gam_i - gam_j) k_j n_j^T`` for new
values ``n = u - w S_0`` that solve one unit-lower-triangular system a
chunk:

  1. ``A_ij = beta_i exp(gam_i - gam_j) (k_i . k_j)`` for ``j < i``;
     ``T = (I + A)^-1``; ``u = T (beta v)``, ``w = T (beta exp(gam) k)``
     (every chunk at once);
  2. a recurrence over the chunks (``L / chunk`` steps: 128 at ``L`` 8192,
     chunk 64) that reads ``n = u - w S``, the chunk's outputs ``o_i =
     exp(gam_i) S^T q_i + sum_{j <= i} exp(gam_i - gam_j) (q_i . k_j) n_j``
     and the state at its end, ``exp(gam_end) S + sum_j exp(gam_end -
     gam_j) k_j n_j^T``.

Every exponent is a difference that is at most 0 (``gam_i - gam_j`` for
``i >= j``, ``gam_end - gam_j``, ``gam_i`` itself): nothing is divided
out, so decays of -20 a token underflow to 0 and stay finite.  ``g``,
``beta``, the cumulative sums, ``A``, its inverse, ``u``, ``w`` and the
state are float32, and what feeds the state (``u``, ``w``, ``w S`` and the
state's update) is computed at ``Precision.HIGHEST``; the products that
only read (``q . k``, ``k . k``, the outputs) take their operands in the
compute dtype and give float32.

The inverse is ``solve_triangular`` of ``I + A`` against ``I`` (forward
substitution).  Not the doubling product ``(I + A)^-1 = (I - A)(I +
A^2)(I + A^4)...`` of the nilpotent ``A``, which is twice as slow on the
v5e (7.26 ms against 3.88 for an example-layer's 128 x 32 blocks of 64 x
64) and unstable where it matters: a chunk of near-parallel keys written
at ``beta`` near 1 has ``A`` near the strictly lower matrix of ones,
whose powers reach ``C(63, 31) ~ 1e18`` before they cancel to an inverse
of entries near 1 (docs/DESIGN.md section 28).

Op ``'delta_scan'`` of the kernel registry (:mod:`diff3d_tpu.ops.dispatch`)
has one core, ``'xla'`` (:func:`delta_chunked`); each traced
:func:`delta_scan` site adds 1 to the recorder's ``delta_scan.<core>``.
It is differentiable and indifferent to ``vmap``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from diff3d_tpu.ops import dispatch
from diff3d_tpu.utils.profiling import count

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    """``a @ b`` over the last two axes, float32 at full precision."""
    return jnp.matmul(a, b, precision=_HI)


def unit_lower_inverse(A: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1`` of strictly lower-triangular float32 ``A [..., C,
    C]``, by forward substitution."""
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    return jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
        unit_diagonal=True)


def delta_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  g: jnp.ndarray, beta: jnp.ndarray,
                  chunk: int) -> jnp.ndarray:
    """``q, k [L, Hk, dk]``, ``v [L, Hv, dv]`` (compute dtype), ``g, beta
    [L, Hv]`` float32 -> ``o [L, Hv, dv]`` in ``v``'s dtype."""
    L, Hv, dv = v.shape
    dtype, f32 = v.dtype, jnp.float32
    rep = Hv // q.shape[1]
    q, k = (jnp.repeat(a, rep, axis=1) for a in (q, k))
    C = min(chunk, L)
    pad = -L % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (L + pad) // C

    def by_chunk(a):                         # [L, H, ...] -> [n, H, C, ...]
        return jnp.swapaxes(a.reshape(n, C, Hv, *a.shape[2:]), 1, 2)

    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v)
    gam = jnp.cumsum(by_chunk(g.astype(f32)), axis=-1)        # [n, H, C]
    bc = by_chunk(beta.astype(f32))

    # 1. every chunk's triangular system
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))                     # 0 where j > i
    kk = jnp.einsum("nhid,nhjd->nhij", kc, kc, preferred_element_type=f32)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict, bc[..., None] * kk * decay, 0.0)
    T = unit_lower_inverse(A)
    u = _mm(T, bc[..., None] * vc.astype(f32))
    w = _mm(T, (bc * jnp.exp(gam))[..., None] * kc.astype(f32))
    P = (jnp.einsum("nhid,nhjd->nhij", qc, kc, preferred_element_type=f32)
         * decay).astype(dtype)
    qd = (jnp.exp(gam)[..., None] * qc.astype(f32)).astype(dtype)
    kd = jnp.exp(gam[..., -1:] - gam)[..., None] * kc.astype(f32)
    keep = jnp.exp(gam[..., -1])                              # [n, H]

    # 2. the chunks in order, the state carried from one to the next
    def step(S, inp):
        u_c, w_c, P_c, qd_c, kd_c, keep_c = inp
        new = u_c - _mm(w_c, S)                               # [H, C, dv]
        o = (jnp.matmul(qd_c, S.astype(dtype), preferred_element_type=f32)
             + jnp.matmul(P_c, new.astype(dtype),
                          preferred_element_type=f32))
        S = keep_c[:, None, None] * S + _mm(jnp.swapaxes(kd_c, -1, -2), new)
        return S, o

    S0 = jnp.zeros((Hv, kc.shape[-1], dv), f32)
    _, o = jax.lax.scan(step, S0, (u, w, P, qd, kd, keep))   # [n, H, C, dv]
    return jnp.swapaxes(o, 1, 2).reshape(n * C, Hv, dv)[:L].astype(dtype)


dispatch.register("delta_scan", "xla", delta_chunked)


def delta_scan(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               g: jnp.ndarray, beta: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """The recurrence of the module docstring over one example, by the
    core the registry resolves (shapes as :func:`delta_chunked`)."""
    core = dispatch.resolve("delta_scan", "auto", q, k, v, g, beta, chunk)
    count(f"delta_scan.{core.name}")
    return core.fn(q, k, v, g, beta, chunk)
