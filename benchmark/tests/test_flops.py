"""The closed-form FLOP function against two independent counts of the
same forward pass: the repository's recorded op mix (``runs/op_mix_*``,
made by walking the Flax model) and XLA's cost analysis of the lowered
default forward.  Counts, not times."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import adapters, flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["srn64", "srn128"])
def test_against_recorded_op_mix(name):
    path = os.path.join(ROOT, "runs", f"op_mix_{name}.json")
    if not os.path.exists(path):
        pytest.skip("no recorded op mix")
    with open(path) as f:
        rep = json.load(f)["report"]
    per_example = rep["total_fwd_gflops"] * 1e9 / rep["microbatch"]
    ours = flops.forward_total(config(name))
    # theirs also counts the fused norm/FiLM elementwise work (~0.2%)
    assert abs(ours - per_example) / per_example < 0.01


def test_against_xla_cost_analysis_of_the_lowered_forward():
    c = config("srn64")
    cfg = adapters.build_config(c)
    from diff3d_tpu.models import XUNet
    from diff3d_tpu.train.trainer import init_params
    model = XUNet(cfg.model)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    B, H = 2, c["H"]
    sds = jax.ShapeDtypeStruct
    batch = {"x": sds((B, H, H, 3), jnp.float32),
             "z": sds((B, H, H, 3), jnp.float32),
             "logsnr": sds((B, 2), jnp.float32),
             "R": sds((B, 2, 3, 3), jnp.float32),
             "t": sds((B, 2, 3), jnp.float32),
             "K": sds((B, 3, 3), jnp.float32)}
    lowered = jax.jit(lambda p, b, m: model.apply(
        {"params": p}, b, cond_mask=m)).lower(
            params, batch, sds((B,), jnp.bool_))
    xla = lowered.cost_analysis()["flops"] / B
    ours = flops.forward_total(c)
    # XLA adds every elementwise op, and leaves out the taps of a 3x3
    # convolution that fall into the zero padding (2% at 64x64, 16% at
    # 8x8), which the closed form counts as the hardware executes them
    assert abs(xla - ours) / ours < 0.04, (ours, xla)


def test_step_and_view_counts_follow_the_forward():
    c = config("srn64")
    f = flops.forward_total(c)
    assert flops.train_step_flops(c, 128) == 3 * 128 * f
    assert flops.sample_view_flops(c, 256, 8) == 256 * 16 * f
    by_class = flops.forward_flops(c)
    assert by_class["film"] > 0.2 * f       # per-pixel FiLM denses are large
