"""The latent-attention mixture-of-experts token-denoiser cell on the CPU at
its tiny configuration: the full-width arithmetic of the configuration, the
closed-form FLOP count of its classes against XLA's count, the planted
faults and the control failing what decides ``correct`` under the cell's
own limit, what the parent tree does with the cell, and a ``--rehearse``
run of the cell through ``run.py``."""

import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_mla_moe
from benchmark import run as harness
from benchmark.reference import mla_moe_denoiser as rm
from benchmark.tests.test_hybrid import xla_flops
from benchmark.tests.test_hybrid_moe import CATALOG

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "pangu_ultra_moe_tok128_sample_ddim2"


def full_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "pangu_ultra_moe_tok128.json")) as f:
        return json.load(f)


def test_flops_of_the_classes_against_xla_cost_analysis():
    """``mla_q``, ``mla_kv``, ``attention`` (core and ``W_o``), ``mlp``,
    ``moe_router`` and ``experts`` as loop-free contractions at a small
    size, the experts on the rows an even load sends to the held share."""
    cfg = {"H": 16, "W": 16, "patch": 2, "hidden_size": 256,
           "num_attention_heads": 4, "q_lora_rank": 64, "kv_lora_rank": 32,
           "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
           "dense_intermediate_size": 384, "shared_intermediate_size": 64,
           "moe_intermediate_size": 64, "num_experts": 16,
           "num_experts_per_tok": 4, "experts_held": [0, 8],
           "first_k_dense_replace": 1, "num_hidden_layers": 3, "emb_ch": 32}
    L, D, H = 128, 256, 4
    ones = jnp.ones
    want = flops_mla_moe.layer_flops(cfg, dense=False)
    dense = flops_mla_moe.layer_flops(cfg, dense=True)
    assert set(dense) == {"mla_q", "mla_kv", "attention", "mlp"}
    assert set(want) == set(dense) | {"moe_router", "experts"}

    def close(got, c):
        assert abs(got - want[c]) / want[c] < 0.02, (c, got, want[c])
    close(xla_flops(lambda u, a, b: (u @ a) @ b, ones((L, D)),
                    ones((D, 64)), ones((64, H * 48))), "mla_q")
    close(xla_flops(lambda u, a, c, b: (u @ a, c @ b), ones((L, D)),
                    ones((D, 48)), ones((L, 32)), ones((32, H * 64))),
          "mla_kv")
    close(xla_flops(lambda q, k, v, p, o, w: (
        jnp.einsum("thd,shd->hts", q, k), jnp.einsum("hts,shd->thd", p, v),
        o @ w), ones((L, H, 48)), ones((L, H, 48)), ones((L, H, 32)),
        ones((H, L, L)), ones((L, H * 32)), ones((H * 32, D))), "attention")
    close(xla_flops(lambda u, a, g, b: (u @ a, g @ b), ones((L, D)),
                    ones((D, 2 * 64)), ones((L, 64)), ones((64, D))), "mlp")
    close(xla_flops(lambda u, w: u @ w, ones((L, D)), ones((D, 16))),
          "moe_router")
    rows = L * 4 * 8 // 16
    close(xla_flops(lambda x, g, u, h, d: (x @ g, x @ u, h @ d),
                    ones((rows, D)), ones((D, 64)), ones((D, 64)),
                    ones((rows, 64)), ones((64, D))), "experts")
    assert dense["mlp"] == 3 * 2.0 * L * D * 384
    ex = flops_mla_moe.example_flops(cfg)
    assert ex["experts"] == 2 * want["experts"]
    assert ex["attention"] == 3 * want["attention"]
    assert ex["mlp"] == dense["mlp"] + 2 * want["mlp"]
    total = flops_mla_moe.sample_view_flops(cfg, steps=2, weights=8)
    assert total == 2 * (16 * sum(ex.values())
                         + 2 * flops_mla_moe.row_flops(cfg))
    assert set(flops_mla_moe.layer_bytes(cfg, False)) == set(want)
    assert set(flops_mla_moe.layer_bytes(cfg, True)) == set(dense)


def test_full_width_arithmetic_of_the_configuration():
    config = full_config()
    m = rm.model_dict(config)
    shapes = rm.param_shapes(m)
    count = lambda pre: sum(int(np.prod(s)) for k, (s, _) in shapes.items()  # noqa: E731
                            if k.startswith(pre))
    # the configuration's count: latent attention 196 577 280 a layer
    # (W_qa 11 796 480, W_qb 37 748 736, W_kva 4 423 680, W_kvb
    # 16 777 216, W_o 125 829 120, two norms 2 048), four norms 30 720,
    # the dense MLP 424 673 280; an expert layer's router 1 966 080,
    # shared expert and each routed expert 47 185 920; embedding and head
    # 65 594 892
    assert count("") == config["parameters"] == 3_179_865_612
    assert count("layers_0/attn/") == 196_577_280
    assert count("layers_0/attn/q_b_proj") == 1536 * 128 * 192
    assert count("layers_0/attn/kv_b_proj") == 512 * 128 * 256
    assert count("layers_0/") == 621_281_280
    assert count("layers_1/") == 623_247_360
    assert count("layers_1/moe/router") == 7680 * 256
    assert shapes["layers_1/moe/w_gate"][0] == (8, 7680, 2048)
    assert count("layers_1/mlp/") == 47_185_920
    assert count("layers_0/mlp/") == 424_673_280
    assert count("") - count("layers_") == 65_594_892
    assert (m["num_experts"], m["experts_held"], m["num_experts_per_tok"],
            m["shared_intermediate_size"], m["dense_intermediate_size"]) == (
        256, [0, 8], 8, 2048, 18432)
    assert rm.tokens_of(m) == 8192
    assert abs(rm._expert_gain(m) - 32 ** 0.5) < 1e-12
    # one 16-example call by class, TFLOP: latent attention 80%, its core
    # (440.1 of the 604.7 with W_o) half of the call
    ex = flops_mla_moe.example_flops(m)
    call = {c: round(16 * v / 1e12, 1) for c, v in ex.items()}
    assert call == {"patch_embed": 0.0, "mla_q": 64.9, "mla_kv": 27.8,
                    "attention": 604.7, "mlp": 160.8, "moe_router": 2.1,
                    "experts": 12.4}
    per_token = flops_mla_moe.forward_flops(m, 16, 2) / (16 * 8192)
    assert round(per_token / 1e9, 2) == 6.66
    assert round(flops_mla_moe.forward_flops(m, 16, 2) / 1e12, 1) == 872.9
    # the layout's static bound a chunk of the configuration's tiles
    t = config["tiles"]
    assert t["expert_token_chunk"] * 8 // t["expert_block"] + 8 == 136


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog row's ``config`` stands in the file under
    its name with its value, but the depth, the leading dense layers and
    the experts held, one of 32 chips' share: all three listed in
    ``reduced`` with the published value beside them."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    config = full_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "first_k_dense_replace", "n_routed_experts"]
    for k, v in row["config"].items():
        if k in config["reduced"]:
            assert config["published"][k] == v
        else:
            assert config[k] == v, k
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"]) == (5, 1, 8)
    assert config["experts_held"] == [0, 8]
    for k in ("deployment", "assumed", "parameters"):
        assert config[k]
    for k in ("scoring_func", "rope", "mtp", "bidirectional_attention",
              "embedding", "head", "sandwich_norm", "param_dtype",
              "weights"):
        assert config["assumed"][k], k
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "pangu_ultra_moe_tok128")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["traffic"] == "sample_1obj_2views_ddim2_mla_moe"


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
    notes = result["notes"]
    assert max(notes["image_gap_by_weight"]) == \
        result["compared"]["image_gap"]["value"]
    # 8 of 16 experts: about half the expert layers' assignments
    assert 0.3 < notes["held_share_of_assignments"] < 0.7
    assert notes["held_expert_rows_max"] >= notes["held_expert_rows_mean"]


@pytest.mark.parametrize("fault", ["answer_altered", "post_norms_dropped",
                                   "experts_dropped"])
def test_a_planted_fault_is_not_correct(fault):
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
    assert max(got["fault_post_norms_dropped"][0]) > lim, got
    assert max(got["fault_experts_dropped"][0]) > lim, got
    assert max(got["program"][0]) <= lim, got
    assert len(got["held_expert_load"][0]) == 3


def test_the_parent_tree_fails_before_any_weight(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    ``TokenModelConfig`` has no latent-attention fields, so the
    configuration is refused in the driver's constructor, before a
    weight is made (here a field is planted that no program has)."""
    from benchmark import adapters_mla_moe

    model_dict = rm.model_dict

    def no_weights(*a, **k):
        raise AssertionError("weights were made")
    monkeypatch.setattr(rm, "make_params", no_weights)
    monkeypatch.setattr(rm, "model_dict",
                        lambda c: dict(model_dict(c), absent_field=1))
    with pytest.raises(TypeError, match="absent_field"):
        build(seed=5)
    assert adapters_mla_moe.rm is rm


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
    assert m["sample_model_call_ms"]["value"] > 0
    assert m["sample_host_ms_per_call"]["value"] > 0
    assert not [k for k in m if k.startswith("train_")], m
    assert list(line)[-1] == "compared"
