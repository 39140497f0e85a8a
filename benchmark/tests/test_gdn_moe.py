"""The Gated DeltaNet mixture-of-experts token-denoiser cell on the CPU at
its tiny configuration: the full-width arithmetic of the configuration,
the closed-form FLOP count of its classes against XLA's count, the
planted faults and the control failing what decides ``correct`` under
the cell's own limit, what the parent tree does with the cell, and a
``--rehearse`` run of the cell through ``run.py``."""

import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_gdn_moe
from benchmark import run as harness
from benchmark.reference import gdn_moe_denoiser as rm
from benchmark.tests.test_hybrid import xla_flops
from benchmark.tests.test_hybrid_moe import CATALOG

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3_next_tok128_sample_ddim8"


def full_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_tok128.json")) as f:
        return json.load(f)


SMALL = {"H": 16, "W": 16, "patch": 2, "hidden_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_key_head_dim": 64, "linear_value_head_dim": 64,
         "linear_conv_kernel_dim": 4, "delta_chunk": 32,
         "shared_intermediate_size": 64, "moe_intermediate_size": 64,
         "num_experts": 16, "num_experts_per_tok": 4, "experts_held": [0, 8],
         "layer_types": ["linear_attention"] * 3 + ["full_attention"],
         "emb_ch": 32}


def test_flops_of_the_classes_against_xla_cost_analysis():
    """``delta_proj``, ``attention`` (projections, core and ``W_o``),
    ``mlp`` (the shared expert and its gate), ``moe_router``, ``experts``
    and the contractions of ``delta_scan`` as loop-free contractions at a
    small size; the scan's triangular solve by its closed form, ``C^3 /
    6`` multiply-adds a block."""
    cfg = SMALL
    L, D, C, d = 128, 256, 32, 64
    n, H = L // C, 4
    ones = jnp.ones
    lin = flops_gdn_moe.layer_flops(cfg, "linear_attention")
    full = flops_gdn_moe.layer_flops(cfg, "full_attention")
    ffn = {"mlp", "moe_router", "experts", "residual"}
    assert set(lin) == {"delta_proj", "delta_conv", "delta_scan",
                        "delta_gate"} | ffn
    assert set(full) == {"attention"} | ffn
    assert lin["delta_gate"] == lin["residual"] == 0.0

    def close(got, want, c):
        assert abs(got - want[c]) / want[c] < 0.02, (c, got, want[c])
    close(xla_flops(lambda u, a, b, o, w: (u @ a, u @ b, o @ w),
                    ones((L, D)), ones((D, 2 * 128 + 2 * 256)),
                    ones((D, 8)), ones((L, 256)), ones((256, D))),
          lin, "delta_proj")
    blocks = (n, H, C)
    scan = xla_flops(
        lambda k, q, v, T, w, S, P, kd: (
            jnp.einsum("nhid,nhjd->nhij", k, k),
            jnp.einsum("nhid,nhjd->nhij", q, k), T @ v, T @ k, w @ S,
            q @ S, jnp.swapaxes(kd, -1, -2) @ v, P @ v),
        ones((*blocks, d)), ones((*blocks, d)), ones((*blocks, d)),
        ones((*blocks, C)), ones((*blocks, d)), ones((n, H, d, d)),
        ones((*blocks, C)), ones((*blocks, d)))
    close(scan + 2.0 * n * H * C ** 3 / 6, lin, "delta_scan")
    close(xla_flops(lambda u, q, k, v, p, o, w: (
        u @ q, u @ k, jnp.einsum("thd,shd->hts", v, v),
        jnp.einsum("hts,shd->thd", p, v), o @ w),
        ones((L, D)), ones((D, 2 * H * d)), ones((D, 2 * 2 * d)),
        ones((L, H, d)), ones((H, L, L)), ones((L, H * d)),
        ones((H * d, D))), full, "attention")
    close(xla_flops(lambda u, a, g, b, s: (u @ a, g @ b, u @ s),
                    ones((L, D)), ones((D, 2 * 64)), ones((L, 64)),
                    ones((64, D)), ones((D, 1))), lin, "mlp")
    close(xla_flops(lambda u, w: u @ w, ones((L, D)), ones((D, 16))),
          lin, "moe_router")
    rows = L * 4 * 8 // 16
    close(xla_flops(lambda x, g, u, h, dn: (x @ g, x @ u, h @ dn),
                    ones((rows, D)), ones((D, 64)), ones((D, 64)),
                    ones((rows, 64)), ones((64, D))), lin, "experts")
    assert lin["delta_conv"] == 2.0 * L * (2 * 128 + 256) * 4
    ex = flops_gdn_moe.example_flops(cfg)
    assert ex["experts"] == 4 * lin["experts"]
    assert ex["delta_scan"] == 3 * lin["delta_scan"]
    assert ex["attention"] == full["attention"]
    total = flops_gdn_moe.sample_view_flops(cfg, steps=8, weights=8)
    assert total == 8 * (16 * sum(ex.values())
                         + 2 * flops_gdn_moe.row_flops(cfg))
    assert set(flops_gdn_moe.layer_bytes(cfg, "linear_attention")) == set(lin)
    assert set(flops_gdn_moe.layer_bytes(cfg, "full_attention")) == set(full)


def test_full_width_arithmetic_of_the_configuration():
    config = full_config()
    m = rm.model_dict(config)
    shapes = rm.param_shapes(m)
    count = lambda pre: sum(int(np.prod(s)) for k, (s, _) in shapes.items()  # noqa: E731
                            if k.startswith(pre))
    # the configuration's count: a linear layer's mixer 33 718 464
    # (W_qkvz 25 165 824, W_ba 131 072, conv 32 768, A_log and dt_bias 64,
    # gated norm 128, W_out 8 388 608), the full layer's attention
    # 27 263 488 (W_q 16 777 216, W_k and W_v 2 x 1 048 576, W_o 8 388 608,
    # q / k norms 512); each layer's router 1 048 576, shared expert
    # 3 145 728, its gate 2 048, two norms 4 096, 256 held experts of
    # 3 145 728; embedding and head 5 957 644
    assert count("") == config["parameters"] == 3_372_403_788
    assert count("layers_0/linear_attn/") == 33_718_464
    assert count("layers_0/linear_attn/in_proj_qkvz") == 2048 * 12288
    assert count("layers_3/attn/") == 27_263_488
    assert count("layers_3/attn/q_proj") == 2048 * 8192
    assert count("layers_0/") == 843_225_280
    assert count("layers_3/") == 836_770_304
    assert count("layers_1/moe/router") == 2048 * 512
    assert shapes["layers_1/moe/w_gate"][0] == (256, 2048, 512)
    assert count("layers_1/mlp/") == 3_145_728 + 2048
    assert count("") - count("layers_") == 5_957_644
    assert (m["num_experts"], m["experts_held"], m["num_experts_per_tok"],
            m["shared_intermediate_size"], m["layer_types"]) == (
        512, [0, 256], 10, 512, ["linear_attention"] * 3
        + ["full_attention"])
    assert rm.tokens_of(m) == 8192 and rm.rotary_dim(m) == 64
    assert rm._expert_gain(m) == 1.0
    # one 16-example call by class, TFLOP: the three delta-rule layers
    # 38.7%, attention 33.2% (its core 23.6), the feed-forwards 28.1
    t = config["tiles"]
    ex = flops_gdn_moe.example_flops(dict(m, **t))
    call = {c: round(16 * v / 1e12, 2) for c, v in ex.items()}
    assert call == {"patch_embed": 0.01, "delta_proj": 26.49,
                    "delta_conv": 0.03, "delta_scan": 2.28,
                    "delta_gate": 0.0, "mlp": 3.3, "moe_router": 1.1,
                    "experts": 16.49, "residual": 0.0, "attention": 24.74}
    assert round(flops_gdn_moe.forward_flops(dict(m, **t), 16, 2) / 1e12,
                 2) == 74.48
    # the layout of the configuration's tiles: a held expert's even share
    # of a chunk (16384 x 10 / 512 = 320 rows) fills 3 blocks of 128, 1.2
    # blocks in use for every block of rows; the static bound 1536 blocks
    rows = t["expert_token_chunk"] * 10 / 512
    assert -(-rows // t["expert_block"]) * t["expert_block"] / rows == 1.2
    assert t["expert_token_chunk"] * 10 // t["expert_block"] + 256 == 1536
    assert t["delta_chunk"] == 64 and 8192 // t["delta_chunk"] == 128


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog row's ``config`` stands in the file under
    its name with its value, but the depth and the experts held, one of
    two chips' share: both listed in ``reduced`` with the published value
    beside them."""
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    config = full_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    for k, v in row["config"].items():
        if k in config["reduced"]:
            assert config["published"][k] == v
        else:
            assert config[k] == v, k
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 256)
    assert config["experts_held"] == [0, 256]
    for k in ("deployment", "assumed", "parameters"):
        assert config[k]
    for k in ("layer_pattern", "gated_deltanet", "qkvz_layout",
              "gated_attention", "zero_centred_norm", "routing", "mtp",
              "bidirectional_attention", "embedding", "head", "param_dtype",
              "weights"):
        assert config["assumed"][k], k
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3_next_tok128")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert cell["traffic"] == "sample_1obj_2views_ddim8_gdn_moe"
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"].startswith(("sample_", "setup_", "device_idle_pct.s")):
            assert CELL in m.get("workloads", [CELL]), m["name"]


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
    assert set(result["compared"]) == {"image_gap", "image_gap_w0",
                                       "compiles_in_window", "failed"}
    notes = result["notes"]
    assert max(notes["image_gap_by_weight"]) == \
        result["compared"]["image_gap"]["value"]
    assert notes["image_gap_by_weight"][0] == \
        result["compared"]["image_gap_w0"]["value"]
    # 8 of 16 experts: about half the expert layers' assignments
    assert 0.3 < notes["held_share_of_assignments"] < 0.7
    assert notes["held_expert_rows_max"] >= notes["held_expert_rows_mean"]


@pytest.mark.parametrize("fault", ["answer_altered", "delta_dropped",
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
    lim, w0 = (driver.mix["limits"][k] for k in ("image_gap",
                                                 "image_gap_w0"))
    for name in ("control", "fault_delta_dropped", "fault_experts_dropped"):
        assert max(got[name][0]) > lim, got
        assert got[name][0][0] > w0, got
    assert max(got["program"][0]) <= lim, got
    assert got["program"][0][0] <= w0, got
    assert len(got["held_expert_load"][0]) == 3


def test_the_parent_tree_fails_before_any_weight(monkeypatch):
    """What the parent commit does under this PR's benchmark files: its
    ``TokenModelConfig`` has no Gated DeltaNet fields, so the
    configuration is refused in the driver's constructor, before a
    weight is made (here a field is planted that no program has)."""
    from benchmark import adapters_gdn_moe

    model_dict = rm.model_dict

    def no_weights(*a, **k):
        raise AssertionError("weights were made")
    monkeypatch.setattr(rm, "make_params", no_weights)
    monkeypatch.setattr(rm, "model_dict",
                        lambda c: dict(model_dict(c), absent_field=1))
    with pytest.raises(TypeError, match="absent_field"):
        build(seed=5)
    assert adapters_gdn_moe.rm is rm


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
