"""Shared by tests/test_token_denoiser.py and tests/test_hybrid_denoiser.py:
a model batch on the forward contract, and the epsilon loss of one training
batch by a plain reference model."""

import jax
import jax.numpy as jnp


def make_batch(key, B, G, H=16):
    k = jax.random.split(key, 6)
    s = float(H)
    K = jnp.array([[1.2 * s, 0, s / 2], [0, 1.2 * s, s / 2], [0, 0, 1.0]])
    R = jnp.linalg.qr(jax.random.normal(k[2], (G, 2, 3, 3)))[0]
    return {"x": jax.random.normal(k[0], (B, H, H, 3)),
            "z": jax.random.normal(k[1], (B, H, H, 3)),
            "logsnr": jnp.stack([jnp.full((G,), 20.0),
                                 jax.random.uniform(k[3], (G,), minval=-5,
                                                    maxval=5)], axis=1),
            "R": R, "t": 2.0 * jax.random.normal(k[4], (G, 2, 3)),
            "K": jnp.broadcast_to(K, (G, 3, 3))}


def reference_loss(forward, batch, key, dcfg):
    """The epsilon loss of one batch by the reference model ``forward(mb,
    cond_mask) -> eps``, on the key stream of ``train/step.py`` /
    ``diffusion.p_losses`` (the stream ``reference/diffusion.py
    block_loss`` documents)."""
    from benchmark.reference import diffusion as rd

    imgs = batch["imgs"].astype(jnp.float32) / 127.5 - 1.0
    B = imgs.shape[0]
    x, z = imgs[:, 0], imgs[:, 1]
    key, _ = jax.random.split(key)
    k_t, k_noise, k_mask, k_xn = jax.random.split(key, 4)
    logsnr = rd.logsnr_cosine(jax.random.uniform(k_t, (B,)), -20.0, 20.0)
    noise = jax.random.normal(k_noise, z.shape, jnp.float32)
    alpha, sigma = rd.alpha_sigma(logsnr)
    z_noisy = (alpha[:, None, None, None] * z
               + sigma[:, None, None, None] * noise)
    cond_mask = jax.random.uniform(k_mask, (B,)) > dcfg["cond_prob"]
    x_cond = jnp.where(cond_mask[:, None, None, None], x,
                       jax.random.normal(k_xn, x.shape, jnp.float32))
    mb = {"x": x_cond, "z": z_noisy,
          "logsnr": jnp.stack([jnp.full((B,), 20.0), logsnr], axis=1),
          "R": batch["R"], "t": batch["T"], "K": batch["K"]}
    return jnp.mean(jnp.square(noise - forward(mb, cond_mask)))
