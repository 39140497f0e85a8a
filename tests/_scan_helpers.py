"""Shared by tests/test_hybrid_denoiser.py and tests/test_pallas_ssd.py: the
state-space recurrence token by token, and seeded operands for a scan."""

import jax
import jax.numpy as jnp


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
