"""Context-parallel / ring-attention correctness at srn64-REALISTIC
shapes on the 8-virtual-device CPU mesh.

The fast suite (test_train, test_parallel, the driver dryrun) proves
sharded == replicated at toy geometry (imgsize 8-16).  GSPMD conv halo
exchanges and GroupNorm reductions are shape-sensitive: a halo that is
correct at 16x16 with 2-row shards can still be wrong at 64x64 where
downsampling produces 64->32->16->8 feature maps whose shard boundaries
fall differently.  These tests (the whole train step slow-marked) run
the real srn64 spatial geometry (H=W=64, the full (1,2,2,4) ch_mult,
attention at levels 2/3/4) with reduced channel width — halos and reductions depend on
spatial dims and block structure, not on channel count.

Reference hot spot being re-derived: 4096-token attention at 64^2
(/root/reference/xunet.py:199-208); the reference never shards it
(SURVEY.md §5.7).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from diff3d_tpu.config import MeshConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
from diff3d_tpu.models import XUNet
from diff3d_tpu.parallel import make_mesh, ring_sdpa, ulysses_sdpa
from diff3d_tpu.train import TrainState, create_train_state, make_train_step
from diff3d_tpu.train.trainer import init_params


def srn64_geometry_cfg():
    """srn64 spatial structure, narrow channels: H=W=64, 4-level
    (1,2,2,4) ch_mult, attention at levels 2/3/4 — ch=16 instead of 128
    (channel width does not move shard boundaries)."""
    cfg = make_tiny_config(imgsize=64, ch=16)
    model = dataclasses.replace(
        cfg.model, emb_ch=64,
        ch_mult=(1, 2, 2, 4), attn_levels=(2, 3, 4))
    assert model.H == 64 and model.num_resolutions == 4
    return dataclasses.replace(cfg, model=model)


def _batch(cfg, B):
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H,
                          seed=0)
    b = next(InfiniteLoader(ds, B, seed=0, num_workers=0))
    return {"imgs": jnp.asarray(b["imgs"]), "R": jnp.asarray(b["R"]),
            "T": jnp.asarray(b["T"]), "K": jnp.asarray(b["K"])}


@pytest.mark.slow
def test_cp_train_step_matches_replicated_at_srn64_shapes():
    """One GSPMD context-parallel train step at 64x64 over the 8-device
    mesh (data=4, model=2; spatial axis 2-way sharded -> per-level
    feature maps 64/32/16/8 all split mid-image) == the unsharded step,
    loss and updated params."""
    cfg = srn64_geometry_cfg()
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, global_batch=8))
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    batch = _batch(cfg, B=8)

    s1 = create_train_state(params, cfg.train)
    f1 = make_train_step(model, cfg, env=None, donate=False)
    s1, m1 = f1(s1, batch, rng)

    cp = dataclasses.replace(
        cfg, mesh=MeshConfig(model_parallel=2, context_parallel=True))
    env = make_mesh(cp.mesh)
    assert dict(env.mesh.shape) == {"data": 4, "model": 2}
    s2 = create_train_state(params, cfg.train)
    s2 = jax.device_put(
        s2, TrainState(step=env.replicated(), params=env.params(s2.params),
                       opt_state=env.params(s2.opt_state),
                       ema_params=env.params(s2.ema_params)))
    f2 = make_train_step(model, cp, env, donate=False)
    s2, m2 = f2(s2, jax.device_put(batch, env.batch()), rng)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_cp_forward_matches_unsharded_at_srn64_shapes():
    """Plain forward (no optimizer) under context-parallel activation
    constraints at 64x64 == unsharded forward, to fp32 tolerance —
    isolates the halo/reduction question from Adam arithmetic."""
    cfg = srn64_geometry_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    batch = _batch(cfg, B=8)

    B = 8
    inp = {
        "x": batch["imgs"][:, 0], "z": batch["imgs"][:, 1],
        "logsnr": jnp.stack([jnp.full((B,), 20.0),
                             jnp.linspace(-18.0, 18.0, B)], 1),
        "R": batch["R"], "t": batch["T"], "K": batch["K"],
    }
    cond = jnp.ones((B,), bool)
    params = jax.jit(
        lambda r: model.init({"params": r}, inp, cond_mask=cond)
    )(rng)["params"]
    ref = jax.jit(
        lambda p: model.apply({"params": p}, inp, cond_mask=cond))(params)

    cp = MeshConfig(model_parallel=2, context_parallel=True)
    env = make_mesh(cp)
    constrain = env.activation_constraint()

    p_sh = jax.device_put(params, env.params(params))
    i_sh = jax.device_put(inp, env.batch())
    c_sh = jax.device_put(cond, env.batch())
    out = jax.jit(
        lambda p, i, c: model.apply({"params": p}, i, cond_mask=c,
                                    constrain=constrain)
    )(p_sh, i_sh, c_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("core,n_shards", [("ring", 8), ("ulysses", 4)])
def test_seq_parallel_attention_at_srn64_token_count(core, n_shards):
    """Ring / Ulysses attention over the REAL srn64 token count — L=4096
    (= 64^2 spatial tokens, the reference's unsharded hot loop at
    xunet.py:199-208) with the srn64 deep-level head dim D=128 and the
    real head count H=4 (4*ch=512 over 4 heads) — == dense attention.
    Ring shards 8-way; Ulysses needs H % n == 0, so 4-way."""
    B, L, H, D = 1, 64 * 64, 4, 128
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D) * 0.1, jnp.float32)
               for _ in range(3))
    ref = jax.nn.dot_product_attention(q, k, v)

    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("seq",))
    spec = P(None, "seq")
    fn = {"ring": ring_sdpa, "ulysses": ulysses_sdpa}[core]
    sharded = shard_map(lambda q, k, v: fn(q, k, v, "seq"),
                        mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)
    out = jax.jit(sharded)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
