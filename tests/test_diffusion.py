import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.diffusion import (alpha_sigma, logsnr_schedule_cosine,
                                  make_model_batch, p_losses,
                                  p_mean_variance, q_sample, sample_loop)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_schedule_endpoints():
    # t=0 -> logsnr_max, t=1 -> logsnr_min (closed form of
    # -2 log(tan(a t + b))).
    np.testing.assert_allclose(float(logsnr_schedule_cosine(jnp.array(0.0))),
                               20.0, atol=5e-3)
    np.testing.assert_allclose(float(logsnr_schedule_cosine(jnp.array(1.0))),
                               -20.0, atol=5e-3)


def test_schedule_monotone_and_midpoint():
    t = jnp.linspace(0.0, 1.0, 101)
    ls = np.asarray(logsnr_schedule_cosine(t))
    assert (np.diff(ls) < 0).all()
    # closed-form midpoint
    b = np.arctan(np.exp(-10.0))
    a = np.arctan(np.exp(10.0)) - b
    np.testing.assert_allclose(ls[50], -2 * np.log(np.tan(a * 0.5 + b)),
                               rtol=1e-5, atol=1e-5)


def test_alpha_sigma_variance_preserving():
    logsnr = jnp.linspace(-20, 20, 11)
    a, s = alpha_sigma(logsnr)
    np.testing.assert_allclose(np.asarray(a ** 2 + s ** 2), 1.0, rtol=1e-6)


def test_q_sample_closed_form():
    B, H, W = 3, 4, 4
    z = jnp.ones((B, H, W, 3)) * 0.5
    noise = jnp.ones((B, H, W, 3)) * 2.0
    logsnr = jnp.array([-5.0, 0.0, 5.0])
    out = np.asarray(q_sample(z, logsnr, noise))
    for i, l in enumerate([-5.0, 0.0, 5.0]):
        expect = (np.sqrt(_sigmoid(l)) * 0.5 + np.sqrt(_sigmoid(-l)) * 2.0)
        np.testing.assert_allclose(out[i], expect, rtol=1e-5)


def test_make_model_batch_cond_logsnr_is_max():
    B = 4
    x = jnp.zeros((B, 8, 8, 3))
    batch = make_model_batch(x, x, jnp.full((B,), -3.0),
                             jnp.zeros((B, 2, 3, 3)), jnp.zeros((B, 2, 3)),
                             jnp.zeros((B, 3, 3)))
    assert batch["logsnr"].shape == (B, 2)
    # conditioning frame is clean: logsnr = schedule max = 20
    np.testing.assert_allclose(np.asarray(batch["logsnr"][:, 0]), 20.0)
    np.testing.assert_allclose(np.asarray(batch["logsnr"][:, 1]), -3.0)


def test_p_mean_variance_closed_form():
    B, H, W = 2, 4, 4
    rng = np.random.RandomState(0)
    z = rng.randn(B, H, W, 3).astype(np.float32)
    ec = rng.randn(B, H, W, 3).astype(np.float32)
    eu = rng.randn(B, H, W, 3).astype(np.float32)
    logsnr, logsnr_next = 1.5, 2.5
    w = np.array([0.0, 3.0], np.float32)

    mean, var = p_mean_variance(jnp.asarray(ec), jnp.asarray(eu),
                                jnp.asarray(z), jnp.array(logsnr),
                                jnp.array(logsnr_next), jnp.asarray(w))

    # independent numpy reproduction of the ancestral step
    c = -np.expm1(logsnr - logsnr_next)
    alpha = np.sqrt(_sigmoid(logsnr))
    sigma = np.sqrt(_sigmoid(-logsnr))
    alpha_next = np.sqrt(_sigmoid(logsnr_next))
    eps = (1 + w[:, None, None, None]) * ec - w[:, None, None, None] * eu
    z0 = np.clip((z - sigma * eps) / alpha, -1, 1)
    expect_mean = alpha_next * (z * (1 - c) / alpha + c * z0)
    expect_var = _sigmoid(-logsnr_next) * c

    np.testing.assert_allclose(np.asarray(mean), expect_mean, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(float(var), expect_var, rtol=1e-5)


def test_p_losses_zero_when_perfect():
    # a denoiser that returns the true noise gives (near-)zero loss; we use
    # the identity that loss is mse(noise, eps_hat).
    B, H, W = 4, 8, 8
    imgs = jnp.zeros((B, 2, H, W, 3))
    R = jnp.broadcast_to(jnp.eye(3), (B, 2, 3, 3))
    T = jnp.zeros((B, 2, 3))
    K = jnp.broadcast_to(jnp.eye(3), (B, 3, 3))

    captured = {}

    def perfect_denoiser(batch, cond_mask):
        # recover the noise from z_noisy = alpha*0 + sigma*eps
        logsnr = batch["logsnr"][:, 1]
        _, sigma = alpha_sigma(logsnr)
        captured["cond_mask"] = cond_mask
        return batch["z"] / sigma[:, None, None, None]

    loss = p_losses(perfect_denoiser, imgs, R, T, K,
                    jax.random.PRNGKey(0), cond_prob=0.5)
    assert float(loss) < 1e-6
    assert captured["cond_mask"].shape == (B,)


def test_p_losses_types():
    B, H, W = 2, 4, 4
    imgs = jnp.zeros((B, 2, H, W, 3))
    R = jnp.broadcast_to(jnp.eye(3), (B, 2, 3, 3))
    T = jnp.zeros((B, 2, 3))
    K = jnp.broadcast_to(jnp.eye(3), (B, 3, 3))

    def zero_denoiser(batch, cond_mask):
        return jnp.zeros_like(batch["z"])

    for lt in ("l1", "l2", "huber"):
        loss = p_losses(zero_denoiser, imgs, R, T, K, jax.random.PRNGKey(1),
                        loss_type=lt)
        assert np.isfinite(float(loss)) and float(loss) > 0


def test_sample_loop_shapes_and_finiteness():
    B, H, W, N = 3, 8, 8, 5

    def fake_denoiser(batch, cond_mask):
        # 2B folded batch comes in; return zeros (model predicts no noise)
        return jnp.zeros_like(batch["z"])

    out = sample_loop(
        fake_denoiser,
        record_imgs=jnp.zeros((N, B, H, W, 3)),
        record_R=jnp.broadcast_to(jnp.eye(3), (N, 3, 3)),
        record_T=jnp.zeros((N, 3)),
        record_len=jnp.array(2),
        target_R=jnp.eye(3),
        target_T=jnp.ones(3),
        K=jnp.eye(3),
        w=jnp.arange(B, dtype=jnp.float32),
        rng=jax.random.PRNGKey(0),
        timesteps=4)
    assert out.shape == (B, H, W, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_sample_loop_jits():
    B, H, W, N = 2, 8, 8, 3

    def fake_denoiser(batch, cond_mask):
        return jnp.zeros_like(batch["z"])

    f = jax.jit(lambda rng: sample_loop(
        fake_denoiser,
        record_imgs=jnp.zeros((N, B, H, W, 3)),
        record_R=jnp.broadcast_to(jnp.eye(3), (N, 3, 3)),
        record_T=jnp.zeros((N, 3)),
        record_len=jnp.array(1),
        target_R=jnp.eye(3), target_T=jnp.ones(3), K=jnp.eye(3),
        w=jnp.arange(B, dtype=jnp.float32), rng=rng, timesteps=3))
    out = f(jax.random.PRNGKey(1))
    assert out.shape == (B, H, W, 3)


@pytest.mark.parametrize("hoist", [True, False])
def test_sample_loop_scan_passes_two_conditioning_rows(hoist):
    """The guidance weights of a view share one pose and one logSNR: the
    scan hands the model `x`, `z` at 2B rows and every conditioning input
    at 2 (the conditional row, then the unconditional one), un-broadcast —
    the model's G-divides-B contract does the sharing."""
    from diff3d_tpu.diffusion.core import (sample_loop_prepare,
                                           sample_loop_scan)

    B, H, W, N = 3, 8, 8, 4
    seen = {}

    def spy(batch, cond_mask):
        seen.update({k: v.shape for k, v in batch.items()},
                    cond_mask=cond_mask.shape, mask=cond_mask)
        return jnp.zeros_like(batch["z"])

    rec_R = jnp.stack([jnp.eye(3) * (i + 1) for i in range(N)])
    state, xs = sample_loop_prepare(
        record_len=jnp.array(1), rng=jax.random.PRNGKey(0), timesteps=2,
        shape=(B, H, W, 3), logsnr_min=-20.0, logsnr_max=20.0)
    out = sample_loop_scan(
        spy, state, xs, record_imgs=jnp.zeros((N, B, H, W, 3)),
        record_R=rec_R, record_T=jnp.zeros((N, 3)),
        target_R=jnp.eye(3) * 7, target_T=jnp.ones(3), K=jnp.eye(3),
        w=jnp.arange(B, dtype=jnp.float32), logsnr_max=20.0, clip_x0=True,
        hoist_cond=hoist)
    assert out.img.shape == (B, H, W, 3)
    assert seen["x"] == seen["z"] == (2 * B, H, W, 3)
    assert seen["logsnr"] == (2, 2)
    assert seen["R"] == (2, 2, 3, 3)
    assert seen["t"] == (2, 2, 3)
    assert seen["K"] == (2, 3, 3)
    assert seen["cond_mask"] == (2,)
    assert seen.get("cam_dirs") == ((2, 1, H, W, 3) if hoist else None)
    # the mask is a trace-time constant: row 0 conditional, row 1 not
    assert np.asarray(seen["mask"]).tolist() == [True, False]
