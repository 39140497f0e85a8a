"""The plain reference against the program at ``test_config`` size on the
CPU, with shared seeded weights: forward, the training steps (dropout,
accumulation, remat, Adam) and one sampled view.  Run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import adapters
from benchmark.reference import diffusion as rd
from benchmark.reference import xunet as rx


def tiny(dropout=0.0, remat=False, accum=1, imgsize=16, ch=8):
    """``test_config`` widths.  At 16 px and 8 channels the deepest level
    normalises groups of 4 values, which amplifies any rounding a
    hundredfold; the tests that compare precisions or follow many steps
    take 32 px and 32 channels."""
    from diff3d_tpu.config import test_config as make_tiny_config
    cfg = make_tiny_config(imgsize=imgsize, ch=ch)
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, dropout=dropout, remat=remat),
        train=dataclasses.replace(cfg.train, accum_steps=accum,
                                  warmup_examples=cfg.train.global_batch))


def model_batch(key, B, H):
    ks = jax.random.split(key, 6)
    q, _ = np.linalg.qr(np.asarray(jax.random.normal(ks[3], (B, 2, 3, 3))))
    K = np.array([[H * 1.2, 0, H / 2], [0, H * 1.2, H / 2], [0, 0, 1]],
                 np.float32)
    return {"x": jax.random.normal(ks[0], (B, H, H, 3)),
            "z": jax.random.normal(ks[1], (B, H, H, 3)),
            "logsnr": jax.random.uniform(ks[2], (B, 2), minval=-10,
                                         maxval=10),
            "R": jnp.asarray(q, jnp.float32),
            "t": jax.random.normal(ks[4], (B, 2, 3)),
            "K": jnp.broadcast_to(K, (B, 3, 3))}


def test_param_tree_matches_the_program():
    cfg = tiny()
    mcfg = adapters.model_dict(cfg)
    from diff3d_tpu.models import XUNet
    from diff3d_tpu.train.trainer import init_params
    theirs = rx.flatten(jax.tree.map(
        lambda x: x, dict(init_params(XUNet(cfg.model), cfg,
                                      jax.random.PRNGKey(0)))))
    ours = rx.param_shapes(mcfg)
    assert set(ours) == set(theirs)
    for k, (shape, _) in ours.items():
        assert tuple(theirs[k].shape) == shape, k


def test_forward_matches_flax():
    cfg = tiny()
    mcfg = adapters.model_dict(cfg)
    from diff3d_tpu.models import XUNet
    flat = rx.make_params(mcfg, jax.random.PRNGKey(3))()
    batch = model_batch(jax.random.PRNGKey(4), 3, 16)
    mask = jnp.asarray([True, False, True])
    ours = rx.forward(flat, batch, mask, mcfg)
    theirs = XUNet(cfg.model).apply({"params": rx.nest(flat)}, batch,
                                    cond_mask=mask)
    assert float(jnp.abs(ours).mean()) > 1e-2     # weights are not zero-init
    # float32 against float32; the pose encoding's sin(2^14 x) turns a last-
    # bit difference in the ray arithmetic into ~1e-3 of phase
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=2e-3)


def test_lower_precision_moves_the_forward():
    cfg = tiny(imgsize=32, ch=32)
    mcfg = adapters.model_dict(cfg)
    flat = rx.make_params(mcfg, jax.random.PRNGKey(3))()
    batch = model_batch(jax.random.PRNGKey(4), 2, 32)
    mask = jnp.asarray([True, True])
    f32 = rx.forward(flat, batch, mask, mcfg)
    gaps = [float(jnp.abs(rx.forward(flat, batch, mask, mcfg, prec=p)
                          - f32).mean()) for p in ("bfloat16", "fp8")]
    assert 0 < gaps[0] < gaps[1] / 3


@pytest.mark.parametrize("dropout,remat,accum",
                         [(0.1, False, 1), (0.1, True, 2)])
def test_training_steps_match_the_program(dropout, remat, accum):
    cfg = tiny(dropout, remat, accum, imgsize=32, ch=32)
    mcfg, dcfg, tcfg = (adapters.model_dict(cfg),
                        adapters.diffusion_dict(cfg),
                        adapters.train_dict(cfg))
    flat = rx.make_params(mcfg, jax.random.PRNGKey(5))()
    B = cfg.train.global_batch
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(3):
        mb = model_batch(jax.random.PRNGKey(int(rng.integers(1 << 30))),
                         B, 32)
        imgs = np.clip(np.stack([mb["x"], mb["z"]], 1) * 0.4, -1, 1)
        batches.append({
            "imgs": np.clip((imgs + 1) * 127.5 + 0.5, 0, 255).astype(
                np.uint8),
            "R": np.asarray(mb["R"]), "T": np.asarray(mb["t"]),
            "K": np.asarray(mb["K"])})
    prog = adapters.TrainProgram(cfg)
    prog.load(jax.tree.map(jnp.copy, flat))    # the step donates its state
    base_key = prog.base_key
    seen = adapters.drive_first_steps(prog, iter(batches), 3)
    ref = rd.TrainReference(mcfg, dcfg, tcfg, block=B // accum // 2).run(
        lambda: flat, batches, base_key)
    # float32 on both sides: the first loss agrees to rounding (same noise,
    # masks and dropout); later ones follow updates whose Adam step is
    # +-lr wherever a gradient is near nought, so they agree less closely
    np.testing.assert_allclose(seen["losses"][0], ref["losses"][0], rtol=1e-5)
    np.testing.assert_allclose(seen["losses"], ref["losses"], rtol=1e-3)
    skip = rd.nought_gradient_leaves(ref["first_grad"])
    gap, at = rd.worst_leaf_gap(seen["first_grad"], ref["first_grad"], skip)
    assert gap < 1e-2, (gap, at)
    start = {k: np.asarray(v) for k, v in flat.items()}
    delta = lambda p: {k: np.asarray(p[k]) - start[k] for k in start}
    gap, at = rd.worst_leaf_gap(delta(seen["params"]), delta(ref["params"]),
                                skip)
    assert gap < 0.3, (gap, at)


def test_sampled_view_matches_the_program():
    cfg = tiny(imgsize=32, ch=32)
    mcfg, dcfg = adapters.model_dict(cfg), adapters.diffusion_dict(cfg)
    flat = rx.make_params(mcfg, jax.random.PRNGKey(6))()
    from diff3d_tpu.models import XUNet
    from diff3d_tpu.sampling import Sampler
    from benchmark import traffic
    ds = traffic.ViewDataset(seed=1, num_objects=2, num_views=4, imgsize=32)
    views = [ds.all_views(i) for i in range(2)]
    keys = [np.asarray(jax.random.PRNGKey(10 + i)) for i in range(2)]
    sampler = Sampler(XUNet(cfg.model), rx.nest(flat), cfg)
    outs = np.asarray(sampler.synthesize_many(views, keys, max_views=3))
    B = len(dcfg["guidance_weights"])
    for obj in range(2):
        v = views[obj]
        rec = np.zeros((4, B, 32, 32, 3), np.float32)
        rec[0] = v["imgs"][0][None]
        R = np.zeros((4, 3, 3), np.float32); R[:3] = v["R"][:3]
        T = np.zeros((4, 3), np.float32); T[:3] = v["T"][:3]
        key = jnp.asarray(keys[obj])
        for view in (1, 2):
            img, key = rd.synthesize_view(
                flat, jnp.asarray(rec), jnp.asarray(R), jnp.asarray(T),
                view, jnp.asarray(v["K"]), key, mcfg, dcfg,
                steps=dcfg["timesteps"])
            diff = np.abs(np.asarray(img) - outs[obj, view - 1])
            # float32 on both sides; a pixel at the edge of the x0 clip
            # can move by a few thousandths
            assert diff.mean() < 1e-4 and diff.max() < 2e-2, (
                diff.mean(), diff.max())
            rec[view] = outs[obj, view - 1]


@pytest.mark.parametrize("name", ["srn64", "srn128"])
def test_param_tree_matches_the_program_at_the_cells_sizes(name):
    """Shapes only (``eval_shape``): a layer renamed or resized in the
    program shows here, not as a silent mismatch on the chip."""
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "configs", f"{name}.json")) as f:
        config = json.load(f)
    cfg = adapters.build_config(config)
    shapes = rx.param_shapes(adapters.model_dict(cfg))
    adapters.check_tree(cfg, {
        k: jax.ShapeDtypeStruct(s, jnp.float32) for k, (s, _) in shapes.items()})
    assert abs(sum(int(np.prod(s)) for s, _ in shapes.values())
               - config["parameters"]) < 0.001 * config["parameters"]
