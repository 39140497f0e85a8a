"""The token-denoiser cell on the CPU at its tiny configuration: the
closed-form FLOP count against XLA's count of loop-free formulations of
each class, the planted faults and the control failing what decides
``correct`` under the cell's own limits, and a ``--rehearse`` run of the
cell through ``run.py``."""

import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_tokens
from benchmark import run as harness
from benchmark.reference import token_denoiser as rt

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye_vl2_tok128_sample_ddim8"


def xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float((cost[0] if isinstance(cost, (list, tuple)) else cost)
                 ["flops"])


def test_flops_of_each_class_against_xla_cost_analysis():
    """Each class as one loop-free contraction at a small size with wide
    contractions (so that XLA's count, which adds the elementwise work,
    is dominated by what the closed form counts)."""
    cfg = {"H": 16, "W": 16, "patch": 2, "hidden_size": 256, "head_dim": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "indexer_num_heads": 4, "indexer_head_dim": 32,
           "indexer_topk": 32, "num_experts": 8, "num_experts_per_tok": 2,
           "moe_intermediate_size": 128, "experts_held": [0, 8],
           "num_hidden_layers": 1, "emb_ch": 64}
    L, D, d, k = 128, 256, 64, 32
    Hq, Hkv, Hi, di, E, F, K = 4, 2, 4, 32, 8, 128, 2
    f32 = jnp.float32
    u = jnp.ones((L, D), f32)
    want = flops_tokens.layer_flops(cfg)

    def attention(u, wq, wk, wv, wo):
        return (u @ wq) @ wo, u @ wk, u @ wv
    got = xla_flops(attention, u, jnp.ones((D, Hq * d)), jnp.ones((D, Hkv * d)),
                    jnp.ones((D, Hkv * d)), jnp.ones((Hq * d, D)))
    assert abs(got - want["attention"]) / want["attention"] < 0.02

    def indexer(u, wq, wk, ww):
        qi, ki, w = (u @ wq).reshape(L, Hi, di), u @ wk, u @ ww
        dots = jnp.einsum("thd,sd->ths", qi, ki)
        return jnp.einsum("ths,th->ts", jnp.maximum(dots, 0), w)
    got = xla_flops(indexer, u, jnp.ones((D, Hi * di)), jnp.ones((D, di)),
                    jnp.ones((D, Hi)))
    # XLA counts the relu and the weighted sum over heads too
    assert 0 < (got - want["indexer"]) / want["indexer"] < 0.08

    def selected(q, ks, vs):        # keys and values already gathered
        s = jnp.einsum("thd,tnhd->thn", q, ks)
        return jnp.einsum("thn,tnhd->thd", s, vs)
    got = xla_flops(selected, jnp.ones((L, Hq, d)), jnp.ones((L, k, Hq, d)),
                    jnp.ones((L, k, Hq, d)))
    assert abs(got - want["sparse_attention"]) / want["sparse_attention"] \
        < 0.02

    got = xla_flops(lambda u, w: u @ w, u, jnp.ones((D, E)))
    assert abs(got - want["moe_router"]) / want["moe_router"] < 0.02

    def experts(u, wg, wu, wd):     # each token's K experts, gathered
        h = (jnp.einsum("td,tkdf->tkf", u, wg)
             * jnp.einsum("td,tkdf->tkf", u, wu))
        return jnp.einsum("tkf,tkfd->td", h, wd)
    got = xla_flops(experts, u, jnp.ones((L, K, D, F)),
                    jnp.ones((L, K, D, F)), jnp.ones((L, K, F, D)))
    assert abs(got - want["experts"]) / want["experts"] < 0.02

    # half the experts held: half the expert work, the router's unchanged
    half = flops_tokens.layer_flops(dict(cfg, experts_held=[4, 4]))
    assert half["experts"] == want["experts"] / 2
    assert half["moe_router"] == want["moe_router"]
    # the whole: per example and per conditioning row
    ex = flops_tokens.example_flops(cfg)
    assert set(ex) == set(want) | {"patch_embed"}
    total = flops_tokens.sample_view_flops(cfg, steps=8, weights=8)
    assert total == 8 * (16 * sum(ex.values())
                         + 2 * flops_tokens.row_flops(cfg))
    assert set(flops_tokens.layer_bytes(cfg)) == {
        "indexer", "sparse_attention", "experts"}


def test_full_width_arithmetic_of_the_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_tok128.json")) as f:
        config = json.load(f)
    m = rt.model_dict(config)
    shapes = rt.param_shapes(m)
    n = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert n == config["parameters"] == 2507484172
    per_layer = sum(int(np.prod(s)) for k, (s, _) in shapes.items()
                    if k.startswith("layers_0/"))
    # attention 18.87 M + indexer 2.26 M + router 0.26 M + experts 603.98 M
    assert per_layer == 625_377_280 + 2048 * 2 + 128 * 2   # + the norms
    lf = flops_tokens.layer_flops(m)
    assert rt.tokens_of(m) == 8192
    # ISSUE 26's reckoning per layer and example, TFLOP
    assert round(lf["attention"] / 1e12, 2) == 0.31
    assert round(lf["sparse_attention"] / 1e12, 2) == 0.27
    assert round(lf["indexer"] / 1e12, 2) == 0.17
    assert round(lf["experts"] / 1e12, 2) == 0.62


def build(seed, fault=None):
    bench, cell, config, mix = harness.load_cell(CELL, rehearse=True)
    mod = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    driver = mod.Driver(config=config, mix=mix, seed=seed, chips=1,
                        spans=harness.Spans())
    driver.fault = fault
    return driver, bench, cell, config, mix


def drive(seed, fault=None):
    driver, bench, cell, config, mix = build(seed, fault)
    result, _ = harness.run_cell(
        driver, bench, cell, config, mix, seconds=0.5, trace=0,
        peak={"flops_per_s": float("nan")}, devices=jax.devices())
    return result


def test_the_program_is_correct_at_test_size():
    result = drive(seed=2147483659)
    assert result["correct"], result["compared"]
    assert set(result["compared"]) == {"image_gap", "compiles_in_window",
                                       "failed"}
    assert all(v["limit"] is not None for v in result["compared"].values())
    notes = result["notes"]
    assert notes["expert_load_max_over_mean"] >= 1.0
    assert max(notes["image_gap_by_weight"]) == \
        result["compared"]["image_gap"]["value"]


@pytest.mark.parametrize("fault", ["answer_altered", "selection_halved"])
def test_a_planted_fault_is_not_correct(fault):
    result = drive(seed=77, fault=fault)
    assert not result["correct"], result["compared"]
    c = result["compared"]["image_gap"]
    assert c["value"] > c["limit"], result["compared"]


def test_the_control_and_the_fault_fail_the_limit():
    driver, *_ = build(seed=5)
    driver.setup()
    got = driver.readings(0.1, control=True)
    driver.release()
    lim = driver.mix["limits"]["image_gap"]
    assert max(got["control"][0]) > lim, got
    assert max(got["fault_topk_halved"][0]) > lim, got
    assert max(got["program"][0]) <= lim, got


def test_a_rehearsal_run_of_the_cell_through_run_py():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"], line["compared"]
    m = line["metrics"]
    # the accepted sampler metrics read this cell by the readers they have
    assert m["sample_model_call_ms"]["value"] > 0
    assert m["sample_host_ms_per_call"]["value"] > 0
    assert not [k for k in m if k.startswith("train_")], m
    assert list(line)[-1] == "compared"
