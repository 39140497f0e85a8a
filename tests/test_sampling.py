import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import SyntheticDataset
from diff3d_tpu.models import XUNet
from diff3d_tpu.sampling import Sampler
from diff3d_tpu.sampling.runtime import to_uint8
from diff3d_tpu.train.trainer import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = make_tiny_config(imgsize=8, ch=8)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=8)
    return cfg, model, params, ds


def test_to_uint8_range():
    img = np.array([[-1.0, 0.0, 1.0]])
    np.testing.assert_array_equal(to_uint8(img), [[0, 127, 255]])
    assert to_uint8(np.array([[-5.0, 5.0]])).tolist() == [[0, 255]]


def test_sampler_synthesize_shapes_and_outputs(setup, tmp_path):
    cfg, model, params, ds = setup
    views = ds.all_views(0)
    sampler = Sampler(model, params, cfg)
    out = sampler.synthesize(views, jax.random.PRNGKey(0),
                             out_dir=str(tmp_path / "sampling"),
                             max_views=3)
    B = len(cfg.diffusion.guidance_weights)
    assert out.shape == (2, B, 8, 8, 3)
    assert np.isfinite(out).all()
    # reference output layout: sampling/{step}/{gt,i}.png
    for step in (1, 2):
        assert os.path.exists(tmp_path / "sampling" / str(step) / "gt.png")
        for i in range(B):
            assert os.path.exists(
                tmp_path / "sampling" / str(step) / f"{i}.png")


def test_sampler_synthesize_many_matches_sequential(setup):
    """The object-batched path must reproduce the sequential path
    per-object when given the same per-object keys (eval_cli relies on
    this to batch objects without changing the scores)."""
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg)
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(4)]
    seq = np.stack([sampler.synthesize(v, k, max_views=3)
                    for v, k in zip(views, keys)])
    many = sampler.synthesize_many(views, keys, max_views=3)
    B = len(cfg.diffusion.guidance_weights)
    assert many.shape == (2, 2, B, 8, 8, 3)
    np.testing.assert_allclose(many, seq, atol=1e-5, rtol=1e-5)
    # objects must not leak into each other: object 1 alone == object 1
    # in the batch
    solo = sampler.synthesize_many([views[1]], [keys[1]], max_views=3)
    np.testing.assert_allclose(solo[0], many[1], atol=1e-5, rtol=1e-5)


def test_sampler_autoregressive_record_grows(setup):
    """Later views must condition on generated entries: with 3 views the
    second scan samples cond indices in [0, 2) — exercised by max_views=3
    above; here check determinism given the same rng."""
    cfg, model, params, ds = setup
    views = ds.all_views(1)
    sampler = Sampler(model, params, cfg)
    a = sampler.synthesize(views, jax.random.PRNGKey(7), max_views=2)
    b = sampler.synthesize(views, jax.random.PRNGKey(7), max_views=2)
    np.testing.assert_array_equal(a, b)
    c = sampler.synthesize(views, jax.random.PRNGKey(8), max_views=2)
    assert not np.array_equal(a, c)


def test_sampler_chunked_scan_matches_single(setup):
    """scan_chunks splits the reverse diffusion into several device
    executions; the carried rng makes the result BIT-identical to the
    one-scan path (the property that lets a caller chunk the full-width
    128^2 sampler without changing the protocol)."""
    cfg, model, params, ds = setup
    views = ds.all_views(0)
    one = Sampler(model, params, cfg).synthesize(
        views, jax.random.PRNGKey(7), max_views=3)
    # test config has timesteps=4 -> 2 chunks of 2 steps
    chunked = Sampler(model, params, cfg, scan_chunks=2).synthesize(
        views, jax.random.PRNGKey(7), max_views=3)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(chunked))


def test_sampler_chunked_many_matches_single(setup):
    cfg, model, params, ds = setup
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(5), jax.random.PRNGKey(6)]
    one = Sampler(model, params, cfg).synthesize_many(views, keys,
                                                      max_views=3)
    chunked = Sampler(model, params, cfg,
                      scan_chunks=2).synthesize_many(views, keys,
                                                     max_views=3)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(chunked))


def test_sampler_rejects_indivisible_chunks(setup):
    cfg, model, params, _ = setup
    with pytest.raises(ValueError):
        Sampler(model, params, cfg, scan_chunks=3)  # timesteps=4
