"""memcheck (the HLO-level memory/recompute analyzer), tested from both
sides like the other pillars: for every detector a fixture that must
FIRE and a fixture that must stay SILENT — on synthetic StableHLO/HLO
text for the parsers and the while-loop invariance pass, and on real
lowered programs for the end-to-end path.  Then the two seeded
regressions the issue demands (a requested donation that silently
copies, an injected loop-invariant matmul in a scan body), the manifest
round-trip + MC405 + suppression grammar, the ``memory_budget`` marker
(incl. vacuous-pass protection, via an in-process sub-pytest), and the
repo-clean gate: the committed manifests under ``runs/memcheck/`` for
the tier-1 programs must match what the current tree compiles.
"""

import dataclasses
import json
import os
import textwrap
import types
import warnings

import jax
import jax.numpy as jnp
import pytest

from diff3d_tpu.analysis import mem
from diff3d_tpu.analysis import membudgets as mb
from diff3d_tpu.analysis import memcheck as mc
from diff3d_tpu.analysis import shardcheck as sc
from diff3d_tpu.analysis.membudgets import (MemBudget, Suppression,
                                            check_report,
                                            check_report_against_dir,
                                            load_manifest,
                                            manifest_from_report,
                                            manifest_path, write_manifest)
from diff3d_tpu.analysis.pytest_plugin import MemCheck

pytest_plugins = ["pytester"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mem_report(**kw):
    base = dict(name="prog")
    base.update(kw)
    return mem.MemoryReport(**base)


def _donation(idx, requested=True, lowered=True, effective=True, **kw):
    base = dict(arg_index=idx, type="8x8xf32", bytes=256,
                requested=requested, lowered=lowered, effective=effective,
                output_index=0 if effective else None)
    base.update(kw)
    return mem.DonationEntry(**base)


def _live(findings, rule=None):
    out = [f for f in findings if not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------------------
# Parsers on synthetic StableHLO / HLO text
# ---------------------------------------------------------------------------


def test_tensor_numel_dtype_and_bytes():
    assert mem._tensor_numel_dtype("8x4x8xf32") == (256, "f32")
    assert mem._tensor_numel_dtype("i32") == (1, "i32")
    assert mem._tensor_bytes("4x4xbf16") == 32
    assert mem._tensor_bytes("f64") == 8


_SHLO_DONATE = textwrap.dedent("""\
    module @jit_f attributes {mhlo.num_partitions = 1 : i32} {
      func.func public @main(%arg0: tensor<8x8xf32> {tf.aliasing_output = 0 : i32}, %arg1: tensor<8x8xf32>, %arg2: tensor<4xf32> {jax.buffer_donor = true}) -> (tensor<8x8xf32>, tensor<8x8xf32>) {
        %0 = stablehlo.add %arg0, %arg1 : tensor<8x8xf32>
        %1 = stablehlo.multiply %arg1, %arg1 : tensor<8x8xf32>
        return %0, %1 : tensor<8x8xf32>, tensor<8x8xf32>
      }
    }
""")

_HLO_ALIASED = ("HloModule jit_f, is_scheduled=true, "
                "input_output_alias={ {0}: (0, {}, may-alias) }, "
                "entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}"
                "\n\nENTRY %main { ROOT %x = f32[] parameter(0) }\n")


def test_parse_arg_donations_attrs():
    attrs = mem.parse_arg_donations(_SHLO_DONATE)
    assert attrs[0]["aliasing_output"] == 0
    assert not attrs[0]["buffer_donor"]
    assert attrs[1]["aliasing_output"] is None
    assert attrs[2]["buffer_donor"]
    assert attrs[0]["type"] == "8x8xf32"


def test_parse_input_output_aliases_fire_and_silent():
    (a,) = mem.parse_input_output_aliases(_HLO_ALIASED)
    assert a == {"output_index": 0, "param": 0, "kind": "may-alias"}
    clean = _HLO_ALIASED.replace(
        "input_output_alias={ {0}: (0, {}, may-alias) }, ", "")
    assert mem.parse_input_output_aliases(clean) == []


def test_donation_table_joins_three_sources():
    attrs = mem.parse_arg_donations(_SHLO_DONATE)
    aliases = mem.parse_input_output_aliases(_HLO_ALIASED)
    table = mem.donation_table([True, False, True], attrs, aliases)
    by_idx = {d.arg_index: d for d in table}
    # arg0: requested, lowered, XLA committed the alias.
    assert by_idx[0].requested and by_idx[0].lowered
    assert by_idx[0].effective and by_idx[0].output_index == 0
    # arg2: requested + donor-marked, but XLA never aliased it.
    assert by_idx[2].requested and by_idx[2].lowered
    assert not by_idx[2].effective
    # arg1: never part of the donation story.
    assert 1 not in by_idx
    # No mhlo.sharding annotations: global == per-device bytes.
    assert by_idx[0].shard_count == 1 and by_idx[0].bytes == 256


def test_shard_count_parses_hlo_sharding_annotations():
    # Fire: tiled shardings divide.
    assert mem._shard_count("{devices=[8,1,1,1,1,1]<=[8]}") == 8
    assert mem._shard_count("{devices=[2,2,2]0,1,2,3,4,5,6,7}") == 8
    # Trailing replicate / subgroup dims do not tile.
    assert mem._shard_count(
        "{devices=[2,1,4]<=[8] last_tile_dim_replicate}") == 2
    assert mem._shard_count(
        "{devices=[2,2,2]<=[8] last_tile_dims={manual, replicated}}") == 2
    # Silent: replicated / maximal / absent keep the full tensor.
    assert mem._shard_count(None) == 1
    assert mem._shard_count("{replicated}") == 1
    assert mem._shard_count("{maximal device=3}") == 1


def test_donation_bytes_are_per_device_on_sharded_args():
    """The StableHLO @main type is the GLOBAL shape while
    memory_analysis() accounts per-device bytes; the donation table
    must divide by the mhlo.sharding shard count or the alias discount
    (and the pinned peak) is off by the mesh size on sharded programs —
    the unit-mixing regression this PR's review caught."""
    sharded = _SHLO_DONATE.replace(
        '%arg0: tensor<8x8xf32> {tf.aliasing_output = 0 : i32}',
        '%arg0: tensor<8x8xf32> {mhlo.sharding = "{devices=[8,1]<=[8]}",'
        ' tf.aliasing_output = 0 : i32}')
    attrs = mem.parse_arg_donations(sharded)
    assert attrs[0]["sharding"] == "{devices=[8,1]<=[8]}"
    table = mem.donation_table(
        [True, False, False], attrs,
        mem.parse_input_output_aliases(_HLO_ALIASED))
    by_idx = {d.arg_index: d for d in table}
    assert by_idx[0].shard_count == 8
    assert by_idx[0].bytes == 256 // 8          # per-device, not global
    # Silent: a replicated arg keeps its full size.
    replicated = _SHLO_DONATE.replace(
        '%arg0: tensor<8x8xf32> {tf.aliasing_output = 0 : i32}',
        '%arg0: tensor<8x8xf32> {mhlo.sharding = "{replicated}",'
        ' tf.aliasing_output = 0 : i32}')
    table = mem.donation_table(
        [True, False, False], mem.parse_arg_donations(replicated),
        mem.parse_input_output_aliases(_HLO_ALIASED))
    (d0,) = [d for d in table if d.arg_index == 0]
    assert d0.shard_count == 1 and d0.bytes == 256


# ---------------------------------------------------------------------------
# The while-loop invariance pass on synthetic StableHLO (the exact
# pretty-printed shape jax 0.4.x emits for a lax.scan whose body is
# outlined into a private callee)
# ---------------------------------------------------------------------------

_SHLO_SCAN = textwrap.dedent("""\
    module @jit_h attributes {mhlo.num_partitions = 1 : i32} {
      func.func public @main(%arg0: tensor<4x4xf32>, %arg1: tensor<10x4x4xf32>) -> (tensor<f32> {jax.result_info = ""}) {
        %cst = stablehlo.constant dense<0.000000e+00> : tensor<f32>
        %c = stablehlo.constant dense<0> : tensor<i32>
        %0:4 = stablehlo.while(%iterArg = %arg1, %iterArg_0 = %arg0, %iterArg_1 = %c, %iterArg_2 = %cst) : tensor<10x4x4xf32>, tensor<4x4xf32>, tensor<i32>, tensor<f32>
         cond {
          %c_3 = stablehlo.constant dense<10> : tensor<i32>
          %1 = stablehlo.compare  LT, %iterArg_1, %c_3,  SIGNED : (tensor<i32>, tensor<i32>) -> tensor<i1>
          stablehlo.return %1 : tensor<i1>
        } do {
          %c_5 = stablehlo.constant dense<0> : tensor<i32>
          %5 = stablehlo.dynamic_slice %iterArg, %iterArg_1, %c_5, %c_5, sizes = [1, 4, 4] : (tensor<10x4x4xf32>, tensor<i32>, tensor<i32>, tensor<i32>) -> tensor<1x4x4xf32>
          %6 = stablehlo.reshape %5 : (tensor<1x4x4xf32>) -> tensor<4x4xf32>
          %7 = func.call @None(%iterArg_0, %iterArg_2, %6) : (tensor<4x4xf32>, tensor<f32>, tensor<4x4xf32>) -> tensor<f32>
          %c_6 = stablehlo.constant dense<1> : tensor<i32>
          %8 = stablehlo.add %iterArg_1, %c_6 : tensor<i32>
          stablehlo.return %iterArg, %iterArg_0, %8, %7 : tensor<10x4x4xf32>, tensor<4x4xf32>, tensor<i32>, tensor<f32>
        }
        return %0#3 : tensor<f32>
      }
      func.func private @None(%arg0: tensor<4x4xf32>, %arg1: tensor<f32>, %arg2: tensor<4x4xf32>) -> tensor<f32> {
        %0 = stablehlo.dot_general %arg0, %arg0, contracting_dims = [1] x [0], precision = [DEFAULT, DEFAULT] : (tensor<4x4xf32>, tensor<4x4xf32>) -> tensor<4x4xf32>
        %1 = stablehlo.tanh %0 : tensor<4x4xf32>
        %2 = stablehlo.multiply %arg2, %1 : tensor<4x4xf32>
        %cst = stablehlo.constant dense<0.000000e+00> : tensor<f32>
        %3 = stablehlo.reduce(%2 init: %cst) applies stablehlo.add across dimensions = [0, 1] : (tensor<4x4xf32>, tensor<f32>) -> tensor<f32>
        %4 = stablehlo.convert %arg1 : tensor<f32>
        %5 = stablehlo.add %4, %3 : tensor<f32>
        return %5 : tensor<f32>
      }
    }
""")


def test_scan_invariance_fires_on_invariant_matmul():
    (loop,) = mem.analyze_scan_invariants(_SHLO_SCAN)
    assert loop.trip_count == 10
    # The dot_general contracts the invariant %arg0 with itself:
    # 2 * 16 * 4 = 128 FLOPs, plus tanh's 16 — both hoistable.
    assert loop.invariant_flops == 128 + 16
    assert loop.hoistable_flops_total == (128 + 16) * 10
    # The tanh result (64 bytes) is the invariant frontier consumed by
    # the variant multiply (plus a few scalar loop constants).
    assert 64 <= loop.invariant_bytes < 128
    assert loop.total_flops > loop.invariant_flops
    tops = [t["op"] for t in loop.top_invariant]
    assert tops[0] == "dot_general"


def test_scan_invariance_silent_when_body_is_all_variant():
    # Same loop, but the callee contracts the VARIANT %arg2 instead of
    # the invariant %arg0 — nothing in the body is hoistable.
    variant = _SHLO_SCAN.replace(
        "stablehlo.dot_general %arg0, %arg0,",
        "stablehlo.dot_general %arg2, %arg2,").replace(
        "%2 = stablehlo.multiply %arg2, %1",
        "%2 = stablehlo.multiply %1, %1")
    (loop,) = mem.analyze_scan_invariants(variant)
    assert loop.invariant_flops == 0
    # Only scalar loop constants remain on the invariant frontier.
    assert loop.invariant_bytes < 64


def test_scan_invariance_no_loops_in_plain_module():
    assert mem.analyze_scan_invariants(_SHLO_DONATE) == []


# ---------------------------------------------------------------------------
# Live lowered programs: donation + scan analysis end to end
# ---------------------------------------------------------------------------


def test_live_donation_effective():
    def f(x, y):
        return x + y, y * 2.0

    lowered = jax.jit(f, donate_argnums=(0,)).lower(
        _sds((8, 8)), _sds((8, 8)))
    rep = mem.analyze_lowered_memory("donate_ok", lowered)
    (d,) = rep.donations
    assert d.requested and d.lowered and d.effective
    assert rep.ineffective_donations == []
    assert rep.available and rep.peak_bytes > 0


def test_live_donation_ineffective_fires():
    # No output matches the donated (16,16) buffer: jax warns and drops
    # the pairing — exactly the silent copy MC402 exists for.
    def g(x, y):
        return jnp.sum(x) + jnp.sum(y)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = jax.jit(g, donate_argnums=(0,)).lower(
            _sds((16, 16)), _sds((4,)))
    rep = mem.analyze_lowered_memory("donate_bad", lowered)
    assert rep.ineffective_donations == [0]
    (d,) = rep.donations
    assert d.requested and not d.lowered and not d.effective


def test_live_scan_invariant_branch_quantified():
    def h(c, xs):
        def body(carry, x):
            inv = jnp.tanh(c @ c)        # loop-invariant conditioning
            return carry + jnp.sum(x * inv), jnp.sum(x)
        s, ys = jax.lax.scan(body, 0.0, xs)
        return s, ys

    rep = mem.analyze_lowered_memory(
        "scan_live", jax.jit(h).lower(_sds((32, 32)), _sds((10, 32, 32))))
    (loop,) = rep.scan_loops
    assert loop.trip_count == 10
    # The invariant matmul alone is 2*32^3 = 65536 FLOPs/step.
    assert loop.invariant_flops >= 2 * 32 ** 3
    assert rep.hoistable_flops_total >= 10 * 2 * 32 ** 3
    assert loop.total_flops > loop.invariant_flops


# ---------------------------------------------------------------------------
# Budget checking on synthetic reports (each MC rule, fire + silent)
# ---------------------------------------------------------------------------


def test_mc401_peak_over_budget():
    good = _mem_report(argument_bytes=100, temp_bytes=50)
    m = manifest_from_report(good)
    assert not _live(check_report(good, m, "m.json"))
    fat = _mem_report(argument_bytes=100, temp_bytes=51)
    (f,) = _live(check_report(fat, m, "m.json"), "MC401")
    assert "peak HBM" in f.message and "+1" in f.message


def test_mc402_requested_but_ineffective_names_the_stage():
    ok = _mem_report(donations=[_donation(0)])
    m = manifest_from_report(ok)
    assert not _live(check_report(ok, m, "m.json"))
    assert m.budgets.effective_donations == [0]
    dropped_at_lowering = _mem_report(
        donations=[_donation(0, lowered=False, effective=False)])
    (f,) = _live(check_report(dropped_at_lowering, m, "m.json"), "MC402")
    assert "lowering time" in f.message
    dropped_by_xla = _mem_report(
        donations=[_donation(0, lowered=True, effective=False)])
    (f2,) = _live(check_report(dropped_by_xla, m, "m.json"), "MC402")
    assert "XLA declined" in f2.message
    # An unrequested, un-aliased arg is nobody's bug.
    bystander = _mem_report(
        donations=[_donation(0, requested=False, lowered=False,
                             effective=False)])
    assert not _live(check_report(bystander, m, "m.json"), "MC402")


def test_mc403_temp_bytes_over_budget():
    m = manifest_from_report(_mem_report(temp_bytes=1000))
    ok = _mem_report(temp_bytes=1000)
    assert not _live(check_report(ok, m, "m.json"), "MC403")
    fat = _mem_report(temp_bytes=1200)
    hits = _live(check_report(fat, m, "m.json"), "MC403")
    assert hits and "temp bytes 1200" in hits[0].message


def test_mc404_hoistable_flops_over_budget():
    def scan_rep(flops):
        return _mem_report(scan_loops=[mem.ScanLoopReport(
            index=0, trip_count=8, body_ops=10, invariant_ops=2,
            invariant_flops=flops, invariant_bytes=64,
            total_flops=flops * 2)])

    m = manifest_from_report(scan_rep(1000.0))
    assert not _live(check_report(scan_rep(1000.0), m, "m.json"))
    (f,) = _live(check_report(scan_rep(2000.0), m, "m.json"), "MC404")
    assert "scan-invariant" in f.message and "every denoise step" \
        in f.message


def test_mc002_reasonless_manifest_suppression_warns():
    m = manifest_from_report(_mem_report())
    m.suppressions.append(Suppression("MC402", "3", reason=None))
    (f,) = _live(check_report(_mem_report(), m, "m.json"), "MC002")
    assert f.severity == "warning" and "no reason" in f.message


def test_suppression_key_scoping_and_silencing():
    supp = Suppression("MC402", "3", "layout blocks the alias, reviewed")
    assert supp.covers("MC402", "3")
    assert not supp.covers("MC402", "4")
    assert not supp.covers("MC401", "3")
    assert Suppression("MC402", "*", "r").covers("MC402", "9")
    bad = _mem_report(donations=[_donation(3, effective=False)])
    m = manifest_from_report(_mem_report(), [supp])
    findings = check_report(bad, m, "m.json")
    assert not _live(findings, "MC402")
    assert any(f.rule == "MC402" and f.suppressed and f.suppress_reason
               for f in findings)


# ---------------------------------------------------------------------------
# Seeded regression 1: a donation that silently copies, over a pinned
# manifest (the issue's copy-instead-of-alias case)
# ---------------------------------------------------------------------------


def test_mc402_seeded_donation_regression_through_manifest():
    def healthy(x, y):                     # donated x aliases output 0
        return x + y, jnp.sum(y)

    def regressed(x, y):                   # output half the size: no alias
        return (x + y)[:4], jnp.sum(y)

    lowered = jax.jit(healthy, donate_argnums=(0,)).lower(
        _sds((8, 8)), _sds((8, 8)))
    good = mem.analyze_lowered_memory("donation_seed", lowered)
    manifest = manifest_from_report(good)
    assert manifest.budgets.effective_donations == [0]
    assert not _live(check_report(good, manifest, "m.json"))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered_bad = jax.jit(regressed, donate_argnums=(0,)).lower(
            _sds((8, 8)), _sds((8, 8)))
    bad = mem.analyze_lowered_memory("donation_seed", lowered_bad)
    assert bad.ineffective_donations == [0]
    hits = _live(check_report(bad, manifest, "m.json"), "MC402")
    assert hits and "silently copied" in hits[0].message


# ---------------------------------------------------------------------------
# Seeded regression 2: an injected loop-invariant recompute in a scan
# body, over a pinned manifest
# ---------------------------------------------------------------------------


def test_mc404_injected_scan_recompute_through_manifest():
    def lean(c, xs):
        def body(carry, x):
            return carry + jnp.sum(x * 2.0), ()
        s, _ = jax.lax.scan(body, 0.0, xs)
        return s

    def recomputing(c, xs):
        def body(carry, x):
            inv = jnp.tanh(c @ c)          # re-run every step, same value
            return carry + jnp.sum(x * inv), ()
        s, _ = jax.lax.scan(body, 0.0, xs)
        return s

    args = (_sds((32, 32)), _sds((10, 32, 32)))
    good = mem.analyze_lowered_memory(
        "recompute_seed", jax.jit(lean).lower(*args))
    manifest = manifest_from_report(good)
    assert not _live(check_report(good, manifest, "m.json"))

    bad = mem.analyze_lowered_memory(
        "recompute_seed", jax.jit(recomputing).lower(*args))
    assert bad.hoistable_flops_per_step >= 2 * 32 ** 3
    hits = _live(check_report(bad, manifest, "m.json"), "MC404")
    assert hits and "scan-invariant" in hits[0].message


# ---------------------------------------------------------------------------
# Manifest round-trip, MC405, update-preserves-suppressions
# ---------------------------------------------------------------------------


def test_manifest_round_trip(tmp_path):
    r = _mem_report(
        name="rt_prog", argument_bytes=512, output_bytes=128,
        temp_bytes=256, generated_code_bytes=64, alias_bytes=32,
        donations=[_donation(2)],
        scan_loops=[mem.ScanLoopReport(
            index=0, trip_count=4, body_ops=6, invariant_ops=1,
            invariant_flops=100.0, invariant_bytes=16,
            total_flops=300.0)])
    m = manifest_from_report(
        r, [Suppression("MC403", "*", "chunked path, reviewed")])
    path = manifest_path("rt_prog", str(tmp_path))
    write_manifest(path, m)
    loaded = load_manifest(path)
    assert loaded.program == "rt_prog"
    assert loaded.budgets.peak_bytes == r.peak_bytes == 928
    assert loaded.budgets.temp_bytes == 256
    assert loaded.budgets.hoistable_flops_per_step == 100.0
    assert loaded.budgets.effective_donations == [2]
    assert loaded.suppressions[0].reason == "chunked path, reviewed"
    assert loaded.observed["hoistable_flops_total"] == 400.0
    assert not _live(check_report_against_dir(r, str(tmp_path)))


def test_mc405_missing_and_unreadable_manifest(tmp_path):
    r = _mem_report(name="ghost")
    (f,) = check_report_against_dir(r, str(tmp_path))
    assert f.rule == "MC405" and "--update" in f.message
    with open(manifest_path("ghost", str(tmp_path)), "w") as fh:
        fh.write("{not json")
    (f2,) = check_report_against_dir(r, str(tmp_path))
    assert f2.rule == "MC405" and "unreadable" in f2.message
    with open(manifest_path("ghost", str(tmp_path)), "w") as fh:
        json.dump({"version": 1, "tool": "shardcheck"}, fh)
    (f3,) = check_report_against_dir(r, str(tmp_path))
    assert f3.rule == "MC405"


def test_update_preserves_suppressions(tmp_path, monkeypatch):
    d = str(tmp_path)
    supp = Suppression("MC402", "1", "psum layout blocks it, reviewed")
    write_manifest(manifest_path("train_step", d),
                   manifest_from_report(_mem_report(name="train_step"),
                                        [supp]))
    monkeypatch.setitem(
        sc.REGISTRY, "train_step",
        dataclasses.replace(
            sc.REGISTRY["train_step"],
            build=lambda: types.SimpleNamespace(
                memory=_mem_report(name="train_step", temp_bytes=7))))
    mc.update_manifests(["train_step"], d)
    loaded = load_manifest(manifest_path("train_step", d))
    assert loaded.suppressions == [supp]
    assert loaded.budgets.temp_bytes == 7


# ---------------------------------------------------------------------------
# The memory_budget marker
# ---------------------------------------------------------------------------


def test_mem_check_violations_aggregate_and_default_forbid():
    check = MemCheck()
    check.add(_mem_report(argument_bytes=300, temp_bytes=100))
    check.add(_mem_report(
        temp_bytes=50,
        donations=[_donation(4, effective=False)],
        scan_loops=[mem.ScanLoopReport(
            index=0, trip_count=2, body_ops=3, invariant_ops=1,
            invariant_flops=500.0, invariant_bytes=8,
            total_flops=600.0)]))
    # Within budget (ineffective donation explicitly allowed).
    assert check.violations({"peak_bytes": 450, "temp_bytes": 150,
                             "hoistable_flops_per_step": 500,
                             "ineffective_donations": 1}) == []
    v = check.violations({"peak_bytes": 449, "temp_bytes": 149,
                          "hoistable_flops_per_step": 499})
    assert len(v) == 4          # 3 ceilings + default-forbidden donation
    assert any("ineffective_donations: 1 > budget 0" in s for s in v)
    assert any("arg 4" in s for s in v)


@pytest.mark.memory_budget(peak_bytes=1 << 30,
                           hoistable_flops_per_step=1 << 40)
def test_memory_budget_marker_e2e(mem_check):
    r = mem_check.analyze(
        "marker_fixture",
        jax.jit(lambda x, y: (x + y, y * 2.0),
                donate_argnums=(0,)).lower(_sds((8, 8)), _sds((8, 8))))
    assert r.peak_bytes > 0          # the budget is non-vacuous


def test_memory_budget_vacuous_pass_protection(pytester):
    pytester.makepyfile(textwrap.dedent("""\
        import pytest

        @pytest.mark.memory_budget(peak_bytes=1)
        def test_never_registers(mem_check):
            pass
    """))
    result = pytester.runpytest_inprocess(
        "-p", "diff3d_tpu.analysis.pytest_plugin",
        "-p", "no:cacheprovider", "-p", "no:randomly")
    assert result.ret != 0
    result.stdout.fnmatch_lines(["*vacuously*"])


def test_memory_budget_marker_rejects_bad_usage(pytester):
    pytester.makepyfile(textwrap.dedent("""\
        import pytest

        @pytest.mark.memory_budget(flux_capacitor=1)
        def test_unknown_key(mem_check):
            pass

        @pytest.mark.memory_budget(peak_bytes=1)
        def test_no_fixture():
            pass

        @pytest.mark.memory_budget()
        def test_no_limits(mem_check):
            pass
    """))
    result = pytester.runpytest_inprocess(
        "-p", "diff3d_tpu.analysis.pytest_plugin",
        "-p", "no:cacheprovider", "-p", "no:randomly")
    assert result.ret != 0
    result.stdout.fnmatch_lines(["*unknown keys flux_capacitor*"])
    result.stdout.fnmatch_lines(["*requires the mem_check fixture*"])
    result.stdout.fnmatch_lines(["*no limits*"])


# ---------------------------------------------------------------------------
# CLI + registry plumbing
# ---------------------------------------------------------------------------


def test_cli_list_and_bad_invocation(capsys):
    assert mc.main(["--list"]) == 0
    out = capsys.readouterr().out
    for nm in sc.REGISTRY:
        assert nm in out
    assert mc.main(["--program", "train_step", "--programs-tier1"]) == 2


def test_manifests_are_committed_for_all_registered_programs():
    d = mc.default_manifest_dir(_REPO_ROOT)
    for nm in sc.REGISTRY:
        assert os.path.exists(manifest_path(nm, d)), (
            f"missing committed memcheck manifest for {nm}; run "
            f"'python tools/memcheck.py --update --program {nm}'")


# ---------------------------------------------------------------------------
# The tier-1 gate: committed manifests match what the tree compiles
# ---------------------------------------------------------------------------


def test_repo_manifests_clean_tier1():
    """The memcheck analogue of ``test_repo_lints_clean``: compiling the
    REAL tier-1 programs and diffing their memory reports against the
    committed ``runs/memcheck/`` manifests must come back clean.  Any
    peak/temp/donation/recompute drift is either a fix or a reviewed
    ``--update`` re-pin.  (The builds come from shardcheck's in-process
    report cache, so this shares one lower+compile with the shardcheck
    gate.)"""
    d = mc.default_manifest_dir(_REPO_ROOT)
    findings = mc.check_programs(list(sc.TIER1_PROGRAMS), d)
    live = _live(findings)
    assert not live, "\n".join(f.render() for f in live)


def test_repo_manifest_pins_exact_tier1():
    """observed == recomputed, not merely observed <= budget: the MC4xx
    ceilings only catch drift UP, so a footprint that silently shrinks
    (or an accounting change like the per-device donation fix) would
    leave committed manifests stale while the gate stays green.  Exact
    equality makes every drift a visible diff that either re-pins via
    ``memcheck --update`` or reverts."""
    d = mc.default_manifest_dir(_REPO_ROOT)
    for nm in sc.TIER1_PROGRAMS:
        committed = load_manifest(manifest_path(nm, d)).observed
        recomputed = mc.memory_report_for(nm).to_json()
        stale = {k for k in set(committed) | set(recomputed)
                 if committed.get(k) != recomputed.get(k)}
        assert not stale, (
            f"{nm}: committed manifest is stale on {sorted(stale)} — "
            f"run 'python tools/memcheck.py --update' and review the "
            f"diff")


def test_tier1_step_many_pins_nonzero_hoistable_conditioning():
    """ROADMAP item 2a as a pinned number: the committed step_many
    manifest must carry a NONZERO hoistable-FLOPs ceiling — the sampler
    still recomputes loop-invariant conditioning work every denoise
    step, and the manifest is the machine-checked record.  When
    conditioning reuse lands, this ceiling is tightened, not deleted.

    (The earlier ~1.8 GFLOP/step figure was a parser artifact: the
    quoted generic-syntax ops in the denoiser callee truncated the
    callee parse, making the whole denoiser look like an invariant
    passthrough.  With anonymous regions parsed correctly the true
    invariant portion is ~154 kFLOP/step — equivcheck pins the same
    number independently, see test_equivcheck's cross-pillar gate.)"""
    d = mc.default_manifest_dir(_REPO_ROOT)
    m = load_manifest(manifest_path("step_many", d))
    assert m.budgets.hoistable_flops_per_step > 0
    obs = m.observed
    assert obs["hoistable_flops_per_step"] > 0
    (loop,) = [l for l in obs["scan_loops"]]
    assert loop["invariant_flops"] > 0
    assert loop["invariant_flops"] <= loop["total_flops"]
    # The record_imgs donation must stay effective — pinned by index.
    assert m.budgets.effective_donations


@pytest.mark.slow
def test_repo_manifests_clean_full_sweep():
    """All five registered programs (adds distill, DDIM, serving
    warmup) — the full manifest sweep the CLI runs."""
    d = mc.default_manifest_dir(_REPO_ROOT)
    findings = mc.check_programs(sorted(sc.REGISTRY), d)
    live = _live(findings)
    assert not live, "\n".join(f.render() for f in live)
