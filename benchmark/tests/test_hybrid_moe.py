"""The hybrid mixture-of-experts token-denoiser cell on the CPU at its tiny
configuration: the full-width arithmetic of the configuration, the
closed-form FLOP count of the two classes it adds against XLA's count, the
planted faults and the control failing what decides ``correct`` under the
cell's own limit, and a ``--rehearse`` run of the cell through
``run.py``."""

import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_hybrid, flops_hybrid_moe
from benchmark import run as harness
from benchmark.reference import hybrid_moe_denoiser as rm
from benchmark.tests.test_hybrid import xla_flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite4_h_small_tok128_sample_ddim4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def full_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite4_h_small_tok128.json")) as f:
        return json.load(f)


def test_flops_of_the_experts_classes_against_xla_cost_analysis():
    """``moe_router`` and ``experts`` as loop-free contractions at a small
    size, the experts on the rows an even load sends to the held share;
    the mixers' classes and ``mlp`` are ``flops_hybrid.py``'s, to the
    number."""
    cfg = {"H": 16, "W": 16, "patch": 2, "hidden_size": 256, "head_dim": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 128,
           "mamba_d_conv": 4, "mamba_chunk_size": 32,
           "shared_intermediate_size": 512, "moe_intermediate_size": 128,
           "num_experts": 24, "num_experts_per_tok": 4,
           "experts_held": [3, 6],
           "layer_types": ["mamba", "attention", "mamba"], "emb_ch": 64}
    L, D, F, E, k, held = 128, 256, 128, 24, 4, 6
    rows = L * k * held // E                       # 128 of the 512
    want = flops_hybrid_moe.layer_flops(cfg, "mamba")
    base = flops_hybrid.layer_flops(cfg, "mamba")
    assert set(want) == set(base) | {"moe_router", "experts"}
    assert {c: want[c] for c in base} == base

    got = xla_flops(lambda u, w: u @ w, jnp.ones((L, D)), jnp.ones((D, E)))
    assert abs(got - want["moe_router"]) / want["moe_router"] < 0.02

    def experts(x, wg, wu, g, wd):
        return x @ wg, x @ wu, g @ wd
    got = xla_flops(experts, jnp.ones((rows, D)), jnp.ones((D, F)),
                    jnp.ones((D, F)), jnp.ones((rows, F)), jnp.ones((F, D)))
    assert abs(got - want["experts"]) / want["experts"] < 0.02

    att = flops_hybrid_moe.layer_flops(cfg, "attention")
    assert set(att) == {"attention", "mlp", "moe_router", "experts"}
    ex = flops_hybrid_moe.example_flops(cfg)
    assert ex["experts"] == 3 * want["experts"]
    assert ex["mlp"] == 3 * base["mlp"]
    assert ex["ssm_scan"] == 2 * base["ssm_scan"]
    assert ex["patch_embed"] == flops_hybrid.example_flops(cfg)[
        "patch_embed"]
    total = flops_hybrid_moe.sample_view_flops(cfg, steps=4, weights=8)
    assert total == 4 * (16 * sum(ex.values())
                         + 2 * flops_hybrid_moe.row_flops(cfg))
    assert set(flops_hybrid_moe.layer_bytes(cfg, "mamba")) == set(want)
    assert set(flops_hybrid_moe.layer_bytes(cfg, "attention")) == set(att)
    with pytest.raises(ValueError):
        flops_hybrid_moe.layer_flops(cfg, "sparse_attention")


def test_full_width_arithmetic_of_the_configuration():
    config = full_config()
    m = rm.model_dict(config)
    shapes = rm.param_shapes(m)
    count = lambda pre: sum(int(np.prod(s)) for k, (s, _) in shapes.items()  # noqa: E731
                            if k.startswith(pre))
    # ISSUE 32's count: outside the routed experts a Mamba-2 layer has
    # 102 286 976 (mixer) + 8 192 (two norms) + 18 874 368 (shared) +
    # 294 912 (router), an attention layer 41 943 040 + the same; one
    # expert 9 437 184, nine held; embedding and head 20 303 884
    assert count("") == config["parameters"] == 2_023_950_988
    assert count("layers_0/") == 121_464_448 + 9 * 9_437_184
    assert count("layers_5/") == 61_120_512 + 9 * 9_437_184
    assert count("layers_0/mamba/") == 102_286_976
    assert count("layers_0/mamba/in_proj") == 4096 * 16768
    assert count("layers_5/attn/") == 41_943_040
    assert count("layers_0/mlp/") == 18_874_368
    assert count("layers_0/moe/router") == 4096 * 72
    assert shapes["layers_0/moe/w_gate"][0] == (9, 4096, 768)
    assert count("") == 9 * 206_399_104 + 146_055_168 + 20_303_884
    assert (m["num_experts"], m["experts_held"], m["num_experts_per_tok"],
            m["moe_intermediate_size"], m["shared_intermediate_size"]) == (
        72, [0, 9], 10, 768, 1536)
    assert m["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert rm.tokens_of(m) == 8192 and m["head_dim"] == 128
    assert abs(rm._expert_gain(m) - 8 ** 0.5) < 1e-12
    # ISSUE 32's reckoning of one 16-example call by class, TFLOP
    ex = flops_hybrid_moe.example_flops(m)
    call = {c: round(16 * v / 1e12, 1) for c, v in ex.items()}
    assert call == {"patch_embed": 0.0, "mlp": 49.5, "ssm_proj": 241.2,
                    "ssm_conv": 0.1, "ssm_scan": 10.0, "moe_router": 0.8,
                    "experts": 30.9, "attention": 28.6}
    assert round(flops_hybrid_moe.forward_flops(m, 16, 2) / 1e12) == 361
    view = flops_hybrid_moe.sample_view_flops(m, steps=4, weights=8)
    assert round(view / 1e15, 2) == 1.44
    # the layout's static bound a chunk of the configuration's tiles
    t = config["tiles"]
    assert t["expert_token_chunk"] * 10 // t["expert_block"] + 9 == 169


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog row's ``config`` stands in the file under
    its name with its value, but the depth and the pattern, cut to the
    first period, and the experts held, one of eight chips' share: all
    three listed in ``reduced`` with the published value beside them."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    config = full_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_local_experts"]
    for k, v in row["config"].items():
        if k in config["reduced"]:
            assert config["published"][k] == v
        else:
            assert config[k] == v, k
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["num_local_experts"] == config["experts_held"][1] == 9
    for k in ("deployment", "assumed", "parameters"):
        assert config[k]
    for k in ("head_dim", "intermediate_size", "feed_forward",
              "bidirectional_attention", "recurrence_order", "multipliers",
              "embedding", "head", "dtype", "weights"):
        assert config["assumed"][k], k
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite4_h_small_tok128")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["traffic"] == "sample_1obj_2views_ddim4_hybrid_moe"


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
    assert max(notes["image_gap_by_weight"]) == \
        result["compared"]["image_gap"]["value"]
    assert 0 < notes["held_share_of_assignments"] < 1
    assert notes["held_expert_rows_max"] >= notes["held_expert_rows_mean"]


@pytest.mark.parametrize("fault", ["answer_altered", "shared_dropped",
                                   "experts_dropped"])
def test_a_planted_fault_is_not_correct(fault):
    # at test size (float32, 3 of 24 experts) the two dropped branches
    # read 0.033-0.048 and 0.017-0.027 by the seed
    result = drive(seed=5, fault=fault)
    assert not result["correct"], result["compared"]
    c = result["compared"]["image_gap"]
    assert c["value"] > c["limit"], result["compared"]


def test_the_control_and_both_faults_fail_the_limit():
    driver, *_ = build(seed=5)
    driver.setup()
    got = driver.readings(0.1, control=True)
    driver.release()
    lim = driver.mix["limits"]["image_gap"]
    assert max(got["control"][0]) > lim, got
    assert max(got["fault_shared_dropped"][0]) > lim, got
    assert max(got["fault_experts_dropped"][0]) > lim, got
    assert max(got["program"][0]) <= lim, got
    assert len(got["held_expert_load"][0]) == 3


def test_a_tree_that_differs_fails_before_any_weight(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    layer builds the experts and leaves the shared expert out, so the two
    trees differ by the ``mlp`` leaves; the driver's constructor says so
    before a weight is made (here the difference is planted on the
    reference's side)."""
    shapes = rm.param_shapes

    def without_shared(mcfg):
        return {k: v for k, v in shapes(mcfg).items() if "/mlp/" not in k}

    def no_weights(*a, **k):
        raise AssertionError("weights were made")
    monkeypatch.setattr(rm, "param_shapes", without_shared)
    monkeypatch.setattr(rm, "make_params", no_weights)
    with pytest.raises(RuntimeError, match="parameter trees differ.*mlp"):
        build(seed=5)


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
