import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.config import MeshConfig, TrainConfig
from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
from diff3d_tpu.models import XUNet
from diff3d_tpu.parallel import make_mesh
from diff3d_tpu.train import (CheckpointManager, TrainState, Trainer,
                              create_train_state, ema_decay_per_step,
                              make_train_step, warmup_schedule)
from diff3d_tpu.train.trainer import init_params


def tiny_cfg(**train_kw):
    # shallow 2-level UNet: these tests assert train-step PROPERTIES
    # (equality across shardings, NaN guards, accumulation, resume),
    # none of which depend on UNet depth — and it halves the dominant
    # cost of this file, XLA-compiling ~20 block graphs per mesh config.
    # Depth-sensitive coverage lives in test_model / test_torch_parity /
    # the driver dryrun, all on the full 4-level shape.
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    if train_kw:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **train_kw))
    return cfg


def make_batch(cfg, B=8, seed=0):
    ds = SyntheticDataset(num_objects=2, num_views=4,
                          imgsize=cfg.model.H, seed=seed)
    b = next(InfiniteLoader(ds, B, seed=seed, num_workers=0))
    return {"imgs": jnp.asarray(b["imgs"]), "R": jnp.asarray(b["R"]),
            "T": jnp.asarray(b["T"]), "K": jnp.asarray(b["K"])}


def test_warmup_schedule_linear_then_flat():
    cfg = TrainConfig(lr=1e-4, warmup_examples=1000, global_batch=100)
    sched = warmup_schedule(cfg)  # 10 warmup steps, (step+1)/10 ramp
    np.testing.assert_allclose(float(sched(0)), 1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(sched(4)), 5e-5, rtol=1e-5)
    np.testing.assert_allclose(float(sched(9)), 1e-4, rtol=1e-5)
    np.testing.assert_allclose(float(sched(1000)), 1e-4, rtol=1e-5)


def test_ema_decay_halflife():
    cfg = TrainConfig(global_batch=128, ema_halflife_examples=500_000)
    d = ema_decay_per_step(cfg)
    halflife_steps = 500_000 / 128
    np.testing.assert_allclose(d ** halflife_steps, 0.5, rtol=1e-6)


def test_train_step_overfits_fixed_batch():
    """Overfit-one-batch integration check (SURVEY.md §7 test plan): with a
    fast lr (tiny-config default warmup spans the whole horizon at ~zero
    lr) the loss trend over repeated steps on one batch must fall clearly.
    Windowed means, not two single draws — the per-step diffusion loss is
    noisy in the sampled logsnr."""
    cfg = tiny_cfg(lr=1e-3, warmup_examples=8)
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    state = create_train_state(params, cfg.train)
    step_fn = make_train_step(model, cfg, env=None)
    batch = make_batch(cfg)
    # Host copy of the init: the donated step invalidates the device
    # buffers `params` aliases.
    params0 = jax.device_get(params)

    losses = []
    for _ in range(60):
        state, metrics = step_fn(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert int(state.step) == 60
    head, tail = np.mean(losses[:10]), np.mean(losses[-10:])
    assert tail < head * 0.9, (head, tail)

    # EMA semantics, on the same 60-step run: the shadow moved off its
    # initial copy of the params but trails them (decay < 1), i.e. it
    # is neither frozen nor a live alias.
    ema_vs_params = jax.tree.leaves(jax.tree.map(
        lambda e, p: float(jnp.max(jnp.abs(e - p))),
        state.ema_params, state.params))
    ema_vs_init = jax.tree.leaves(jax.tree.map(
        lambda e, p0: float(np.max(np.abs(np.asarray(e) - p0))),
        state.ema_params, params0))
    assert any(v > 0 for v in ema_vs_params)
    assert any(v > 0 for v in ema_vs_init)


def test_train_step_updates_ema_toward_params():
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None)
    batch = make_batch(cfg)
    state2, _ = step_fn(state, batch, rng)
    # EMA moved but is not equal to the new params
    diffs = jax.tree.map(
        lambda e, p: float(jnp.max(jnp.abs(e - p))),
        state2.ema_params, state2.params)
    assert any(v > 0 for v in jax.tree.leaves(diffs))


@pytest.mark.parametrize("policy", ["replicated", "fsdp"])
def test_sharded_train_step_on_mesh(policy):
    cfg = tiny_cfg()
    env = make_mesh(MeshConfig(param_sharding=policy))
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    state = jax.device_put(
        state, TrainState(step=env.replicated(),
                          params=env.params(state.params),
                          opt_state=env.params(state.opt_state),
                          ema_params=env.params(state.ema_params)))
    step_fn = make_train_step(model, cfg, env)
    batch = jax.device_put(make_batch(cfg), env.batch())
    state, metrics = step_fn(state, batch, rng)
    state, metrics = step_fn(state, batch, rng)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 2


def test_replicated_and_sharded_steps_agree():
    """DP over the mesh computes the same update as single-device (the
    correctness property the reference's DDP path loses, SURVEY.md §2.7)."""
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    batch = make_batch(cfg)

    s1 = create_train_state(params, cfg.train)
    f1 = make_train_step(model, cfg, env=None, donate=False)
    s1, m1 = f1(s1, batch, rng)

    env = make_mesh()
    s2 = create_train_state(params, cfg.train)
    s2 = jax.device_put(
        s2, TrainState(step=env.replicated(), params=env.params(s2.params),
                       opt_state=env.params(s2.opt_state),
                       ema_params=env.params(s2.ema_params)))
    f2 = make_train_step(model, cfg, env, donate=False)
    s2, m2 = f2(s2, jax.device_put(batch, env.batch()), rng)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    a = jax.tree.leaves(s1.params)[0]
    b = jax.tree.leaves(s2.params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


_TRAJ_REF_CACHE = []


# fsdp+tp re-proves the same 25-step chain at over a minute on the CPU
# mesh; its single-step mesh equality stays in tier 1
# (test_fsdp_tp_train_step_runs).
@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(param_sharding="fsdp"),
    pytest.param(MeshConfig(model_parallel=2, param_sharding="fsdp+tp"),
                 marks=pytest.mark.slow),
    MeshConfig(model_parallel=2, context_parallel=True),
], ids=["fsdp", "fsdp+tp", "context-parallel"])
def test_multi_step_trajectory_equality(mesh_cfg):
    """25-step TRAJECTORY equality: the sharded step must track the
    single-device step through a long chain of Adam/EMA updates and
    step-folded rng draws, not just agree on one update
    (1-2-step equality can hide slow divergence from e.g. a sharding-
    dependent reduction order or a mis-folded per-step rng)."""
    import dataclasses

    n_steps = 25
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    # A 3-batch cycle gives data variation across steps without paying
    # loader overhead 25 times.
    batches = [make_batch(cfg, seed=s) for s in range(3)]

    def run(env, cfg_run):
        s = create_train_state(params, cfg_run.train)
        if env is not None:
            s = jax.device_put(s, env.state_shardings(s))
        f = make_train_step(model, cfg_run, env, donate=False)
        losses = []
        for i in range(n_steps):
            b = batches[i % len(batches)]
            if env is not None:
                b = jax.device_put(b, env.batch())
            s, m = f(s, b, rng)
            losses.append(float(m["loss"]))
        return (np.asarray(losses), jax.device_get(s.params),
                jax.device_get(s.ema_params))

    # The unsharded reference trajectory is identical for every mesh
    # parametrization (same PRNGKey(0) init, same batch cycle; threefry
    # is partitionable by default), so compute it once per module
    # run instead of once per parametrization — recomputing it tripled
    # the reference cost for no extra coverage.
    if not _TRAJ_REF_CACHE:
        _TRAJ_REF_CACHE.append(run(None, cfg))
    ref_losses, ref_params, ref_ema = _TRAJ_REF_CACHE[0]
    cfg_sharded = dataclasses.replace(cfg, mesh=mesh_cfg)
    env = make_mesh(mesh_cfg)
    losses, params_s, ema_s = run(env, cfg_sharded)

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(params_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(ref_ema), jax.tree.leaves(ema_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_checkpoint_roundtrip(tmp_path):
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None, donate=False)
    state, _ = step_fn(state, make_batch(cfg), rng)

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    assert mgr.save(state, force=True)
    mgr.wait()
    assert mgr.latest_step() == 1

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = mgr.restore(abstract)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_checkpoint_ema_bf16_mode(tmp_path):
    """ema_bf16 saves ~1/16 the bytes (bf16 EMA only), restores via
    restore_ema from a marker-detected directory, and the trainer
    warm-restarts from it (params == ema == restored EMA, step kept)."""
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None, donate=False)
    state, _ = step_fn(state, make_batch(cfg), rng)

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2, mode="ema_bf16")
    assert mgr.save(state, force=True)
    mgr.wait()
    mgr.close()

    # A fresh manager with no mode argument detects ema_bf16 via marker.
    mgr2 = CheckpointManager(str(tmp_path / "ckpt"))
    assert mgr2.mode == "ema_bf16"
    with pytest.raises(ValueError):
        mgr2.restore(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))
    abstract_params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    step, ema = mgr2.restore_ema(abstract_params)
    assert step == 1
    for a, b in zip(jax.tree.leaves(state.ema_params),
                    jax.tree.leaves(ema)):
        assert np.asarray(b).dtype == np.asarray(a).dtype  # upcast back
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=0.008, rtol=0.008)  # bf16
    mgr2.close()

    # An unmarked directory that already holds FULL checkpoints must not
    # be relabelable as ema_bf16 (that would wedge restores of the
    # existing steps behind a wrong marker).
    full = CheckpointManager(str(tmp_path / "full"))
    assert full.save(state, force=True)
    full.wait()
    full.close()
    with pytest.raises(ValueError, match="refusing to relabel"):
        CheckpointManager(str(tmp_path / "full"), mode="ema_bf16")


# Tier-1 budget (870s): exact same-mesh roundtrip is subsumed by the
# resharded roundtrip in test_elastic.py (same restore path, stronger
# topology contract) + the guards test's roundtrip assert below.
@pytest.mark.slow
def test_checkpoint_full_sliced_exact_roundtrip(tmp_path):
    """full_sliced streams the state leaf-by-leaf but keeps full-mode
    semantics: EXACT resume (params, EMA, Adam moments, step all
    bit-equal), marker auto-detection, retention, and the trainer's
    ordinary restore path (mode branches on != ema_bf16)."""
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None, donate=False)
    state, _ = step_fn(state, make_batch(cfg), rng)

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2,
                            mode="full_sliced")
    assert mgr.save(state)
    mgr.wait()
    assert mgr.latest_step() == 1
    assert not mgr.save(state)          # same step: no duplicate write

    # marker auto-detection + EXACT restore of every leaf incl. opt_state
    mgr2 = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    assert mgr2.mode == "full_sliced"
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = mgr2.restore(abstract)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):      # no EMA-only view of full data
        mgr2.restore_ema(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            state.params))

    # retention: keep=2 prunes the oldest of 3 saved steps
    state2, _ = step_fn(state, make_batch(cfg), rng)
    state3, _ = step_fn(state2, make_batch(cfg), rng)
    assert mgr2.save(state2) and mgr2.save(state3)
    assert mgr2._sliced_steps() == [2, 3]

    # the restored state continues the optimizer trajectory exactly:
    # one more step from the restored state == one more step from the
    # original (Adam moments included in the equality)
    cont, _ = step_fn(restored, make_batch(cfg), rng)
    for a, b in zip(jax.tree.leaves(state2), jax.tree.leaves(cont)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a full-mode (Orbax) directory must refuse full_sliced relabeling
    full = CheckpointManager(str(tmp_path / "full"))
    assert full.save(state, force=True)
    full.wait()
    full.close()
    with pytest.raises(ValueError, match="refusing to relabel"):
        CheckpointManager(str(tmp_path / "full"), mode="full_sliced")


def test_checkpoint_full_sliced_guards(tmp_path):
    """full_sliced error surfaces: a missing explicit step names the
    available ones (not a raw FileNotFoundError), a saved-vs-target dtype
    mismatch is a config error (not a silent cast), and
    save_interval_steps/force gate saves like the Orbax modes."""
    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None, donate=False)
    state, _ = step_fn(state, make_batch(cfg), rng)      # step 1

    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=3,
                            save_interval_steps=2, mode="full_sliced")
    # interval gating: step 1 % 2 != 0 -> skipped unless forced
    assert not mgr.save(state)
    assert mgr._sliced_steps() == []
    assert mgr.save(state, force=True)
    state2, _ = step_fn(state, make_batch(cfg), rng)     # step 2
    assert mgr.save(state2)                              # 2 % 2 == 0

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    # explicit missing/pruned step: ValueError naming what IS there
    with pytest.raises(ValueError, match=r"available steps: \[1, 2\]"):
        mgr.restore(abstract, step=7)
    # dtype mismatch = config mismatch, loudly (no silent .astype)
    wrong = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
        state)
    with pytest.raises(ValueError, match="config mismatch"):
        mgr.restore(wrong, step=1)
    # ...and the matching restore still round-trips exactly
    restored = mgr.restore(abstract, step=1)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# Tier-1 budget: the manager-level ema_bf16 roundtrip stays in tier 1
# (test_checkpoint_ema_bf16_mode); this trainer-level warm-restart
# wiring runs under --runslow / RUN_SLOW=1.
@pytest.mark.slow
def test_trainer_warm_restart_from_ema_bf16(tmp_path):
    cfg = tiny_cfg(max_steps=2, ckpt_every=2, log_every=1,
                   ckpt_mode="ema_bf16")
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)
    loader = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                            num_workers=0)
    tr = Trainer(cfg, loader, workdir=str(tmp_path))
    state = tr.train()
    ema = jax.device_get(state.ema_params)

    loader2 = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                             num_workers=0, start_step=2)
    cfg2 = tiny_cfg(max_steps=3, ckpt_every=10, log_every=1,
                    ckpt_mode="ema_bf16")
    tr2 = Trainer(cfg2, loader2, workdir=str(tmp_path), transfer=True)
    assert int(tr2.state.step) == 2
    for a, b in zip(jax.tree.leaves(ema),
                    jax.tree.leaves(jax.device_get(tr2.state.params))):
        np.testing.assert_allclose(a, b, atol=0.008, rtol=0.008)
    # warm restart: params seeded from EMA
    for a, b in zip(jax.tree.leaves(jax.device_get(tr2.state.params)),
                    jax.tree.leaves(jax.device_get(tr2.state.ema_params))):
        np.testing.assert_array_equal(a, b)
    # ... and training actually CONTINUES: the restored params and ema
    # must be distinct buffers (the step donates the state; aliased
    # leaves fail at execute time), which only running a step proves.
    state2 = tr2.train()
    assert int(state2.step) == 3


def test_trainer_end_to_end(tmp_path):
    import json

    cfg = tiny_cfg(max_steps=3, ckpt_every=3, log_every=1, eval_every=3)
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)
    loader = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                            num_workers=0)
    tr = Trainer(cfg, loader, workdir=str(tmp_path))
    tr.val_loader = InfiniteLoader(
        SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H,
                         seed=1),
        cfg.train.global_batch, num_workers=0)
    state = tr.train()
    assert int(state.step) == 3
    assert os.path.exists(tmp_path / "metrics.jsonl")
    assert tr.ckpt.latest_step() == 3
    # eval_every scored EMA params on the val loader into metrics.jsonl
    # (the reference's unfinished TODO #1, README.md:32).
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    vals = [r for r in recs if "val_loss" in r]
    assert vals and np.isfinite(vals[0]["val_loss"])

    # resume path (--transfer semantics, reference train.py:244-251)
    loader2 = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                             num_workers=0, start_step=3)
    tr2 = Trainer(cfg, loader2, workdir=str(tmp_path), transfer=True)
    assert int(tr2.state.step) == 3


def test_config_validate_rejects_clip_schedule_mismatch():
    import dataclasses
    from diff3d_tpu.config import DiffusionConfig
    cfg = tiny_cfg()
    bad = dataclasses.replace(
        cfg, diffusion=dataclasses.replace(cfg.diffusion, logsnr_max=15.0))
    with pytest.raises(ValueError, match="logsnr_clip"):
        bad.validate()


def test_step_timer_and_profile_window(tmp_path):
    import time

    from diff3d_tpu.utils import StepTimer, profile_window

    t = StepTimer()
    assert t.summary() == {}
    for _ in range(4):
        t.tick()
        time.sleep(0.002)
    s = t.summary()
    assert s["step_ms_mean"] >= 1.0
    assert s["step_ms_p95"] >= s["step_ms_p50"]

    # disabled window is a no-op; enabled window writes a trace dir
    with profile_window(str(tmp_path / "prof_off"), enabled=False):
        pass
    assert not os.path.exists(tmp_path / "prof_off")
    with profile_window(str(tmp_path / "prof")):
        jnp.zeros(8).block_until_ready()
    assert os.path.isdir(tmp_path / "prof")
    # ... and its reduction to seconds by block class beside it
    import json

    by_scope = json.loads((tmp_path / "prof" / "by_scope.json").read_text())
    assert set(by_scope) == {"by_class", "unscoped_s", "unscoped_top",
                             "idle_by_span", "busy_s", "window_s"}


def test_trainer_halts_on_nonfinite_loss(tmp_path):
    cfg = tiny_cfg(max_steps=2, ckpt_every=10, log_every=1)
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)

    class PoisonLoader:
        def __init__(self):
            self._it = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                                      num_workers=0)

        def __next__(self):
            b = next(self._it)
            b["imgs"] = b["imgs"] * np.nan
            return b

    tr = Trainer(cfg, PoisonLoader(), workdir=str(tmp_path))
    with pytest.raises(FloatingPointError, match="non-finite"):
        tr.train()


def test_trainer_emergency_checkpoint_on_crash(tmp_path):
    cfg = tiny_cfg(max_steps=5, ckpt_every=100, log_every=100)
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)

    class CrashLoader:
        def __init__(self):
            self.n = 0
            self._it = InfiniteLoader(ds, cfg.train.global_batch, seed=0,
                                      num_workers=0)

        def __next__(self):
            self.n += 1
            if self.n > 2:
                raise KeyboardInterrupt  # simulated preemption
            return next(self._it)

    tr = Trainer(cfg, CrashLoader(), workdir=str(tmp_path))
    with pytest.raises(KeyboardInterrupt):
        tr.train()
    tr.ckpt.wait()
    # the 2 completed steps were preserved by the emergency save
    assert tr.ckpt.latest_step() == 2


def test_grad_accumulation_step():
    """accum_steps=2 scans two microbatches per optimizer step: same state
    pytree, one step counter increment, loss decreases while training.
    (warmup shortened: the default tiny-config warmup spans the whole test
    horizon at near-zero lr, hiding any progress.)"""
    cfg = tiny_cfg(accum_steps=2, lr=1e-3, warmup_examples=8)
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    state = create_train_state(init_params(model, cfg, rng), cfg.train)
    step_fn = make_train_step(model, cfg, env=None)
    batch = make_batch(cfg)  # B=8 -> 2 microbatches of 4

    first = None
    for _ in range(25):
        state, metrics = step_fn(state, batch, rng)
        if first is None:
            first = float(metrics["loss"])
    assert int(state.step) == 25
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first


def test_grad_accumulation_rejects_indivisible_batch():
    cfg = tiny_cfg(accum_steps=3)  # global_batch=8 not divisible by 3
    with pytest.raises(ValueError, match="accum_steps"):
        cfg.validate()


def test_context_parallel_step_matches_replicated():
    """GSPMD context parallelism (spatial axis sharded over the model
    axis via activation constraints) computes the same update as the
    unsharded step — XLA's halo exchange / GN reduction / KV gathers are
    semantics-preserving by construction; this pins it."""
    import dataclasses

    cfg = tiny_cfg()
    model = XUNet(cfg.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    batch = make_batch(cfg)

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
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_val_loss_logged(tmp_path):
    """eval_every scores EMA params on val batches into metrics.jsonl —
    the reference's own unfinished TODO #1 (README.md:32)."""
    import json

    cfg = tiny_cfg(max_steps=2, eval_every=2, ckpt_every=2, log_every=1)
    env = make_mesh()
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)
    tr = Trainer(cfg, InfiniteLoader(ds, cfg.train.global_batch,
                                     num_workers=0),
                 env, workdir=str(tmp_path))
    tr.val_loader = InfiniteLoader(
        SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H,
                         seed=1),
        cfg.train.global_batch, num_workers=0)
    tr.train()
    recs = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    vals = [r for r in recs if "val_loss" in r]
    assert vals and np.isfinite(vals[0]["val_loss"])


def test_preemption_checkpoints_and_stops(tmp_path):
    """A preemption signal makes the loop checkpoint the current step and
    return (graceful TPU spot/maintenance handling; the reference dies
    mid-step and loses up to 50 steps)."""
    cfg = tiny_cfg(max_steps=50, ckpt_every=100, log_every=100)
    env = make_mesh()
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=cfg.model.H)

    class PreemptAfter:
        """Loader that raises the flag after a few batches."""

        def __init__(self, inner, trainer_box, after):
            self.inner, self.box, self.n, self.after = inner, trainer_box, 0, after

        def __iter__(self):
            return self

        def __next__(self):
            self.n += 1
            if self.n == self.after:
                self.box[0]._preempted.set()   # what the signal handler does
            return next(self.inner)

    box = [None]
    loader = PreemptAfter(
        InfiniteLoader(ds, cfg.train.global_batch, num_workers=0), box, 3)
    tr = Trainer(cfg, loader, env, workdir=str(tmp_path))
    box[0] = tr
    state = tr.train()
    assert int(state.step) == 3          # stopped right after the flag
    assert tr.preempt_observed_step == 3  # observed step is recorded
    tr.ckpt.wait()
    assert tr.ckpt.latest_step() == 3    # exact-step checkpoint exists

    # resume picks up at the preempted step
    tr2 = Trainer(cfg, None, env, workdir=str(tmp_path), transfer=True)
    assert int(tr2.state.step) == 3


def test_preemption_handler_sigint_and_uninstall(tmp_path):
    """install_preemption_handler also covers SIGINT (a ^C must behave
    like a preemption: checkpoint + clean stop, not a stack trace), and
    the returned uninstall handle restores the previous handlers without
    clobbering one somebody else installed in the meantime."""
    import signal
    import time

    cfg = tiny_cfg(max_steps=2, ckpt_every=10, log_every=0)
    tr = Trainer(cfg, None, workdir=str(tmp_path))
    prev_int = signal.getsignal(signal.SIGINT)
    prev_term = signal.getsignal(signal.SIGTERM)
    uninstall = tr.install_preemption_handler()
    try:
        # a real SIGINT sets the flag instead of raising KeyboardInterrupt
        os.kill(os.getpid(), signal.SIGINT)
        deadline = time.monotonic() + 5
        while not tr._preempted.is_set() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert tr._preempted.is_set()
    finally:
        uninstall()
    assert signal.getsignal(signal.SIGINT) is prev_int
    assert signal.getsignal(signal.SIGTERM) is prev_term
    uninstall()                           # idempotent

    # uninstall must not stomp a handler installed after ours
    tr2 = Trainer(cfg, None, workdir=str(tmp_path), transfer=False)
    uninstall2 = tr2.install_preemption_handler()

    def foreign(signum, frame):           # pragma: no cover - never fired
        pass

    try:
        signal.signal(signal.SIGTERM, foreign)
        uninstall2()
        assert signal.getsignal(signal.SIGTERM) is foreign
        assert signal.getsignal(signal.SIGINT) is prev_int
    finally:
        signal.signal(signal.SIGTERM, prev_term)


def test_preemption_handler_idempotent_install_and_reentrant(tmp_path):
    """The elasticity-loop contract: double-install returns the SAME
    uninstaller (no handler chained onto itself), double-uninstall is a
    no-op, and a signal delivered while the handler is already running
    only sets the stop flag instead of recursing into the chain."""
    import signal

    cfg = tiny_cfg(max_steps=2, ckpt_every=10, log_every=0)
    tr = Trainer(cfg, None, workdir=str(tmp_path))
    prev_term = signal.getsignal(signal.SIGTERM)

    chained = []
    signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        uninstall = tr.install_preemption_handler()
        assert tr.install_preemption_handler() is uninstall
        handler = signal.getsignal(signal.SIGTERM)

        # Signal-during-signal: a second delivery while the handler is
        # mid-flight must not re-enter the chained previous handler.
        tr._in_handler = True
        try:
            handler(signal.SIGTERM, None)
        finally:
            tr._in_handler = False
        assert tr._preempted.is_set()
        assert chained == []              # chain suppressed while nested

        tr._preempted.clear()
        handler(signal.SIGTERM, None)     # normal delivery chains once
        assert tr._preempted.is_set()
        assert chained == [signal.SIGTERM]
        assert tr._in_handler is False    # guard cleared on the way out

        uninstall()
        assert len(chained) == 1
        uninstall()                       # second uninstall: no-op
        # A fresh install after uninstall works (new chain, new handle).
        uninstall3 = tr.install_preemption_handler()
        assert uninstall3 is not uninstall
        uninstall3()
    finally:
        signal.signal(signal.SIGTERM, prev_term)


# Tier-1 budget: this same-topology contract is pinned (stronger) by
# test_chaos.py::test_trainer_sigterm_async_checkpoint_exact_resume
# (real SIGTERM, async writer, bit-identical next-K) and extended to
# topology changes by test_elastic.py.
@pytest.mark.slow
def test_full_sliced_deterministic_resume(tmp_path):
    """The ISSUE-6 satellite pin: checkpoint at step N (through the
    default ASYNC writer), restore into a fresh trainer with the loader
    sought to N, and the next K steps are bit-identical to a run that was
    never interrupted — params, EMA, Adam moments, step counter, and the
    data-loader position all line up exactly."""
    ds = SyntheticDataset(num_objects=2, num_views=4, imgsize=8)

    def loader(start=0):
        return InfiniteLoader(ds, 8, seed=0, num_workers=0,
                              start_step=start)

    cfg_a = tiny_cfg(max_steps=3, ckpt_every=3, log_every=0,
                     ckpt_mode="full_sliced")
    tr = Trainer(cfg_a, loader(), workdir=str(tmp_path / "resumed"))
    tr.train()
    tr.ckpt.wait()
    assert tr.ckpt.latest_step() == 3

    cfg_b = tiny_cfg(max_steps=6, ckpt_every=100, log_every=0,
                     ckpt_mode="full_sliced")
    tr2 = Trainer(cfg_b, loader(start=3), workdir=str(tmp_path / "resumed"),
                  transfer=True)
    assert int(tr2.state.step) == 3
    resumed = jax.device_get(tr2.train())

    tr3 = Trainer(cfg_b, loader(), workdir=str(tmp_path / "oracle"))
    oracle = jax.device_get(tr3.train())

    assert int(resumed.step) == 6 and int(oracle.step) == 6
    for a, b in zip(jax.tree.leaves(oracle), jax.tree.leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_context_parallel_requires_model_axis():
    import dataclasses

    cfg = tiny_cfg()
    cfg = dataclasses.replace(cfg, mesh=MeshConfig(context_parallel=True))
    with pytest.raises(ValueError, match="model_parallel"):
        cfg.validate()


def test_trainer_ckpt_every_zero_disables_periodic_saves(tmp_path):
    """ckpt_every=0 means 'no periodic saves' (final-step save still
    runs) — it used to crash with a modulo-by-zero inside the loop."""
    cfg = tiny_cfg(max_steps=2, ckpt_every=0, log_every=0)
    ds = SyntheticDataset(num_objects=2, num_views=4,
                          imgsize=cfg.model.H)
    loader = InfiniteLoader(ds, cfg.train.global_batch, num_workers=0)
    tr = Trainer(cfg, loader, workdir=str(tmp_path))
    tr.train()
    assert int(tr.state.step) == 2
    # the end-of-run save still happened
    assert tr.ckpt.latest_step() == 2
