"""The selective state-space recurrence of a Mamba-2 mixer as a chunked
scan (the "state-space dual" form), a pure function of one example.

Per head ``h`` with state ``S [P, N]`` (``S = 0`` before the first
token), over the tokens ``t`` of the example in order::

    S_t = exp(dt[t, h] * A[h]) * S_{t-1} + dt[t, h] * x[t, h] B[t]^T
    y[t, h] = S_t C[t] + D[h] * x[t, h]

``B`` and ``C`` are shared by all heads (one group).  The sequence is cut
into chunks of ``chunk`` tokens (zero-padded at the end where ``chunk``
does not divide ``L``: a padded token has ``dt = 0``, so it neither
decays nor feeds the state).  With ``a = dt * A`` and ``cum`` its
inclusive sum inside a chunk:

  1. inside each chunk, token ``i`` reads token ``j <= i`` with weight
     ``exp(cum_i - cum_j) dt_j (C_i . B_j)``: the decay-masked ``(C B^T)
     x`` product, one ``[H, chunk, chunk]`` tile a chunk;
  2. each chunk's own contribution to the state at its end,
     ``sum_j exp(cum_end - cum_j) dt_j x_j B_j^T``;
  3. a recurrence over the chunks' states (``L / chunk`` steps of an
     elementwise update: 32 at ``L`` 8192, chunk 256);
  4. the state a chunk starts from, read by its tokens:
     ``exp(cum_i) C_i . S``.

Decays, ``dt``, cumulative sums and states are float32; the operands of
the four contractions are in ``x``'s dtype (the compute dtype), their
results float32.  Every decay is ``exp`` of a non-positive number: no
factor is ever divided out, so strong decays (``a`` of -6 a token) and
weak ones (-1e-4) are both exact to rounding.

Op ``'ssm_scan'`` of the kernel registry (:mod:`diff3d_tpu.ops.dispatch`)
has this XLA core alone today; each traced :func:`ssd` site adds 1 to the
recorder's ``ssm_scan.<core>``.  Differentiable, and indifferent to
``vmap``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from diff3d_tpu.ops import dispatch
from diff3d_tpu.utils.profiling import count


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, D: jnp.ndarray,
                chunk: int) -> jnp.ndarray:
    """``x [L, H, P]``, ``dt [L, H]`` float32 (positive), ``A [H]``
    float32 (negative), ``B, C [L, N]``, ``D [H]`` -> ``y [L, H, P]`` in
    ``x``'s dtype."""
    L, H, P = x.shape
    dtype, f32 = x.dtype, jnp.float32
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (L + pad) // Q
    xc = x.reshape(n, Q, H, P)
    Bc, Cc = B.reshape(n, Q, -1), C.reshape(n, Q, -1)
    dtc = dt.astype(f32).reshape(n, Q, H)
    cum = jnp.cumsum(dtc * A.astype(f32), axis=1)            # [n, Q, H]

    # 1. inside the chunks
    G = jnp.einsum("cin,cjn->cij", Cc, Bc, preferred_element_type=f32)
    cum_h = jnp.swapaxes(cum, 1, 2)                          # [n, H, Q]
    seg = cum_h[:, :, :, None] - cum_h[:, :, None, :]        # i - j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))        # 0 where j > i
    M = G[:, None] * decay * jnp.swapaxes(dtc, 1, 2)[:, :, None, :]
    y = jnp.einsum("chij,cjhp->cihp", M.astype(dtype), xc,
                   preferred_element_type=f32)

    # 2. each chunk's contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc             # [n, Q, H]
    xw = (xc.astype(f32) * to_end[..., None]).astype(dtype)
    own = jnp.einsum("cjhp,cjn->chpn", xw, Bc, preferred_element_type=f32)

    # 3. the state each chunk starts from
    def carry(S, inp):
        keep, add = inp
        return keep[:, None, None] * S + add, S
    _, before = jax.lax.scan(carry, jnp.zeros(own.shape[1:], f32),
                             (jnp.exp(cum[:, -1, :]), own))

    # 4. what that state gives the chunk's tokens
    y = y + (jnp.einsum("cin,chpn->cihp", Cc, before.astype(dtype),
                        preferred_element_type=f32)
             * jnp.exp(cum)[..., None])
    y = y + D.astype(f32)[:, None] * xc.astype(f32)
    return y.reshape(n * Q, H, P)[:L].astype(dtype)


dispatch.register("ssm_scan", "xla", ssd_chunked)


def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
        C: jnp.ndarray, D: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """The recurrence of the module docstring over one example, by the
    core the registry resolves (shapes as :func:`ssd_chunked`)."""
    core = dispatch.resolve("ssm_scan", "auto", x, dt, A, B, C, D, chunk)
    count(f"ssm_scan.{core.name}")
    return core.fn(x, dt, A, B, C, D, chunk)
