"""Sharded + device-resident sampling runtime.

Three contracts pinned here, on the 8-virtual-device CPU mesh (conftest):

  * PARITY — ``synthesize_many`` on a mesh (object axis sharded over
    ``data``, params replicated/fsdp) matches the unsharded path
    per-object to float tolerance, including when N must be padded up to
    the data-axis size.
  * ONE PROGRAM — a full ``synthesize_many`` run compiles exactly one
    view-step executable (the autoregressive loop re-enters the same
    jitted function with identical shapes; any per-view recompile is a
    bug that would multiply sampling cost by the compile time).
    Enforced by the ``compile_sentinel`` fixture and the
    ``@pytest.mark.compile_budget`` marker from
    ``diff3d_tpu.analysis.pytest_plugin``.
  * DEVICE RESIDENCE — after the first view step, the record carry never
    crosses the host boundary: a second step under
    ``analysis.runtime.no_host_transfers()`` runs clean, and the donated
    input buffer is actually consumed (``assert_consumed``), i.e. the
    update is in place rather than a device-side copy.

Plus the serving-side divisibility rules (``lane_count`` rounding and the
engine's mesh-quantised ``max_batch``) and an end-to-end sharded engine
run checked against the unsharded offline sampler.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.analysis.runtime import (assert_consumed, assert_live,
                                         no_host_transfers, owned)
from diff3d_tpu.config import MeshConfig, ServingConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import SyntheticDataset
from diff3d_tpu.models import XUNet
from diff3d_tpu.parallel import make_mesh
from diff3d_tpu.sampling import Sampler, record_capacity
from diff3d_tpu.serving import ServingService, ViewRequest
from diff3d_tpu.serving.engine import lane_count
from diff3d_tpu.train.trainer import init_params


@pytest.fixture(scope="module")
def setup():
    # Tier-1 budget: shallow 2-level model — every claim in this file is
    # about the sharded RUNTIME (padding, donation, lane math, compile
    # count, fsdp placement), depth-independent per test_config's
    # shallow contract; all comparisons are in-process.
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    ds = SyntheticDataset(num_objects=3, num_views=4, imgsize=8)
    return cfg, model, params, ds


def _mesh(data: int):
    return make_mesh(MeshConfig(data_parallel=data, model_parallel=1),
                     devices=jax.devices()[:data])


# ---------------------------------------------------------------------------
# Sharded parity
# ---------------------------------------------------------------------------


def test_sharded_synthesize_many_matches_unsharded(setup):
    """Object axis over a data=2 mesh: per-object results must match the
    unsharded runtime to float tolerance (same per-object key stream; XLA
    may tile differently, so not bitwise)."""
    cfg, model, params, ds = setup
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(4)]
    plain = Sampler(model, params, cfg)
    ref = plain.synthesize_many(views, keys, max_views=3)

    env = _mesh(2)
    sharded = Sampler(model, params, cfg, mesh=env)
    assert sharded.lane_multiple == 2
    got = sharded.synthesize_many(views, keys, max_views=3)
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_sharded_synthesize_many_pads_to_lane_multiple(setup):
    """N=3 objects on a data=2 mesh: the runtime pads the object axis
    3 -> 4 internally and the padding never contaminates the live
    objects' results.  (The full-8-device pad 3 -> 8 is the slow-lane
    variant below — same pad code path, 4x the compile.)"""
    cfg, model, params, ds = setup
    views = [ds.all_views(i) for i in range(3)]
    keys = [jax.random.PRNGKey(10 + i) for i in range(3)]
    plain = Sampler(model, params, cfg)
    ref = plain.synthesize_many(views, keys, max_views=3)

    env = _mesh(2)
    sharded = Sampler(model, params, cfg, mesh=env)
    assert sharded.lane_multiple == 2
    got = sharded.synthesize_many(views, keys, max_views=3)
    assert got.shape[0] == 3               # padding lanes dropped
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_sharded_synthesize_many_pads_full_mesh(setup):
    """N=3 objects on the full 8-device data mesh: pad 3 -> 8."""
    cfg, model, params, ds = setup
    views = [ds.all_views(i) for i in range(3)]
    keys = [jax.random.PRNGKey(10 + i) for i in range(3)]
    ref = Sampler(model, params, cfg).synthesize_many(views, keys,
                                                      max_views=3)
    env = make_mesh(MeshConfig())          # all 8 devices on 'data'
    sharded = Sampler(model, params, cfg, mesh=env)
    assert sharded.lane_multiple == 8
    got = sharded.synthesize_many(views, keys, max_views=3)
    assert got.shape[0] == 3               # padding lanes dropped
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_sharded_fsdp_params_match(setup):
    """The fsdp param policy must not change results, only placement."""
    cfg, model, params, ds = setup
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(1), jax.random.PRNGKey(2)]
    ref = Sampler(model, params, cfg).synthesize_many(views, keys,
                                                      max_views=3)
    cfg_fsdp = dataclasses.replace(
        cfg, mesh=dataclasses.replace(cfg.mesh, param_sharding="fsdp"))
    env = _mesh(2)
    got = Sampler(model, params, cfg_fsdp, mesh=env).synthesize_many(
        views, keys, max_views=3)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_step_many_rejects_non_multiple_batch(setup):
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg, mesh=_mesh(2))
    cap = record_capacity(3)
    B = len(cfg.diffusion.guidance_weights)
    with pytest.raises(ValueError, match="multiple"):
        sampler.step_many(
            np.zeros((3, cap, B, 8, 8, 3), np.float32),
            np.zeros((3, cap, 3, 3), np.float32),
            np.zeros((3, cap, 3), np.float32),
            np.ones((3,), np.int32),
            np.stack([np.eye(3, dtype=np.float32)] * 3),
            np.stack([np.asarray(jax.random.PRNGKey(i))
                      for i in range(3)]))


# ---------------------------------------------------------------------------
# One compiled program per synthesize_many run
# ---------------------------------------------------------------------------


@pytest.mark.compile_budget(1)
def test_synthesize_many_compiles_exactly_once(setup, compile_sentinel):
    """The whole autoregressive run (3 view steps here) re-enters ONE
    compiled executable — record_len is a traced argument, not a shape,
    so no view index triggers its own program.  The marker enforces the
    budget at teardown; the inline check pins that exactly one program
    exists (not zero) and that the second run re-enters it."""
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg, mesh=_mesh(2))
    compile_sentinel.track("view_step", sampler._run_view_many)
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(0), jax.random.PRNGKey(1)]
    sampler.synthesize_many(views, keys, max_views=4)
    assert compile_sentinel.counts()["view_step"] == 1
    # A second run with the same shapes stays on the same program.
    sampler.synthesize_many(views, keys, max_views=4)
    assert compile_sentinel.counts()["view_step"] == 1


# ---------------------------------------------------------------------------
# Device residence: no per-view host re-upload, donated in-place update
# ---------------------------------------------------------------------------


def _device_record(sampler, views, cfg, n_views):
    imgs = np.asarray(views["imgs"], np.float32)
    rec_i, rec_R, rec_T = sampler._record_init(
        imgs[0], np.asarray(views["R"], np.float32),
        np.asarray(views["T"], np.float32), n_views)
    # owned(), not bare jnp.asarray: the record carry is DONATED, and
    # asarray may zero-copy alias the numpy buffer — donating an aliased
    # buffer leaves the carry pointing at freed host memory (the same
    # contract Sampler._owned enforces for the public step API).
    return (owned(rec_i), jnp.asarray(rec_R),
            jnp.asarray(rec_T),
            jnp.asarray(np.asarray(views["K"], np.float32)))


def test_step_loop_runs_under_transfer_guard(setup):
    """Steady-state view steps move NOTHING across the host boundary:
    after one warmup step, further steps on the returned carry run under
    ``no_host_transfers()`` (scoped transfer_guard: faults on any
    implicit host->device or device->host transfer)."""
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg)
    rec_i, rec_R, rec_T, K = _device_record(sampler, ds.all_views(0), cfg,
                                            n_views=4)
    step = jnp.asarray(1, jnp.int32)
    rng = jnp.asarray(jax.random.PRNGKey(0))
    # Warmup: compiles the program and commits every operand to device.
    out, rec_i, step, rng = sampler.step(rec_i, rec_R, rec_T, step, K, rng)
    jax.block_until_ready(out)
    with no_host_transfers():
        out, rec_i, step, rng = sampler.step(rec_i, rec_R, rec_T, step, K,
                                             rng)
        out2, rec_i, step, rng = sampler.step(rec_i, rec_R, rec_T, step,
                                              K, rng)
    np.testing.assert_array_equal(np.asarray(step), 4)
    assert np.isfinite(np.asarray(out2)).all()


def test_step_donates_record_buffer(setup):
    """The record buffer is donated: the input device buffer is consumed
    (in-place dynamic_update_slice), not copied."""
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg)
    rec_i, rec_R, rec_T, K = _device_record(sampler, ds.all_views(0), cfg,
                                            n_views=4)
    _, new_rec, _, _ = sampler.step(rec_i, rec_R, rec_T,
                                    jnp.asarray(1, jnp.int32), K,
                                    jnp.asarray(jax.random.PRNGKey(0)))
    jax.block_until_ready(new_rec)
    assert_consumed(rec_i)
    assert_live(new_rec)


def test_step_loop_bitwise_matches_synthesize(setup):
    """Driving the public step API by hand reproduces ``synthesize``
    BITWISE — same program, same carried rng stream (this is the contract
    the serving engine's bit-parity guarantee stands on)."""
    cfg, model, params, ds = setup
    sampler = Sampler(model, params, cfg)
    views = ds.all_views(1)
    ref = sampler.synthesize(views, jax.random.PRNGKey(9), max_views=4)

    rec_i, rec_R, rec_T, K = _device_record(sampler, views, cfg, n_views=4)
    step = jnp.asarray(1, jnp.int32)
    rng = jnp.asarray(jax.random.PRNGKey(9))
    outs = []
    for _ in range(3):
        out, rec_i, step, rng = sampler.step(rec_i, rec_R, rec_T, step, K,
                                             rng)
        outs.append(np.asarray(out))
    np.testing.assert_array_equal(np.stack(outs), ref)
    # ...and the committed record holds the same views.
    np.testing.assert_array_equal(np.asarray(rec_i[1:4]), ref)


# ---------------------------------------------------------------------------
# Serving: bucket/lane divisibility under a mesh
# ---------------------------------------------------------------------------


def test_lane_count_rounding():
    assert lane_count(0, 8) == 0
    assert lane_count(1, 8) == 1
    assert lane_count(3, 8) == 4
    assert lane_count(5, 8) == 8
    assert lane_count(9, 8) == 8          # clamped at the ceiling
    # Mesh quantum: pow2 first, then up to the multiple.
    assert lane_count(1, 8, 2) == 2
    assert lane_count(3, 8, 2) == 4
    assert lane_count(3, 12, 3) == 6
    assert lane_count(5, 6, 3) == 6


def test_engine_rounds_max_batch_to_lane_multiple(setup):
    cfg, model, params, ds = setup
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        port=0, max_batch=3, max_queue=8, max_views=6))
    sampler = Sampler(model, params, cfg, mesh=_mesh(2))
    service = ServingService(sampler, cfg)
    assert service.engine.lane_multiple == 2
    assert service.engine.max_batch == 4   # 3 rounded up to a multiple
    assert service.health()["lane_multiple"] == 2


def test_sharded_engine_serves_divisible_lanes(setup):
    """End-to-end on a data=2 mesh: a single request launches 2 lanes
    (padded, not a 1-lane recompile), completes, and matches the
    unsharded offline sampler to float tolerance."""
    cfg, model, params, ds = setup
    cfg = dataclasses.replace(cfg, serving=ServingConfig(
        port=0, max_batch=4, max_queue=8, max_wait_ms=100, max_views=6))
    sampler = Sampler(model, params, cfg, mesh=_mesh(2))
    service = ServingService(sampler, cfg).start(serve_http=False)
    try:
        v = ds.all_views(2)
        req = ViewRequest(
            {"imgs": np.asarray(v["imgs"]), "R": np.asarray(v["R"]),
             "T": np.asarray(v["T"]), "K": np.asarray(v["K"])},
            seed=5, n_views=3)
        service.engine.submit(req)
        out = req.result(timeout=120)

        direct = Sampler(model, params, cfg).synthesize(
            v, jax.random.PRNGKey(5), max_views=3)
        np.testing.assert_allclose(out, direct, atol=1e-5, rtol=1e-5)

        stats = service.engine.programs.stats()["programs"]
        assert list(stats) == [f"H8xW8xcap4xlanes2"]
        snap = service.metrics_snapshot()
        assert snap["counters"]["serving_host_upload_bytes_total"] > 0
        assert snap["counters"]["serving_host_fetch_bytes_total"] > 0
    finally:
        service.stop()
