"""shardcheck (the IR-level sharding/comms analyzer), tested from both
sides like graftlint: for every detector a fixture that must FIRE and a
fixture that must stay SILENT — on synthetic HLO/StableHLO text for the
parsers (including the f64 case, which a live CPU trace without
``jax_enable_x64`` cannot produce) and on real lowered pjit programs
over the 8-virtual-device mesh for the end-to-end path.  Then the two
seeded regressions the issue demands (a replicated fsdp param, an
injected resharding site), the manifest round-trip + suppression
grammar, the ``comms_budget`` marker (incl. vacuous-pass protection,
via an in-process sub-pytest), and the repo-clean gate: the committed
manifests for the tier-1 programs must match what the current tree
lowers.
"""

import dataclasses
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from diff3d_tpu.analysis import budgets as budgets_lib
from diff3d_tpu.analysis import ir
from diff3d_tpu.analysis.budgets import (Suppression, check_report,
                                         check_report_against_dir,
                                         load_manifest,
                                         manifest_from_report,
                                         manifest_path, write_manifest)
from diff3d_tpu.analysis.lint import (Finding, apply_baseline,
                                      load_baseline, write_baseline)
from diff3d_tpu.analysis.pytest_plugin import CommsCheck
from diff3d_tpu.analysis import shardcheck as sc

pytest_plugins = ["pytester"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fsdp_env():
    return sc._fsdp_mesh()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _report(**kw):
    base = dict(name="prog", mesh_shape={"data": 8}, collectives={},
                resharding_sites=[], dtype_upcasts={}, host_callbacks=[],
                param_table=[])
    base.update(kw)
    return ir.ProgramReport(**base)


def _live(findings, rule=None):
    out = [f for f in findings if not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ---------------------------------------------------------------------------
# Text parsers on synthetic HLO / StableHLO
# ---------------------------------------------------------------------------

_HLO = textwrap.dedent("""\
    HloModule fixture

    ENTRY %main (p0: f32[2,8]) -> f32[16,8] {
      %ag = f32[16,8]{1,0} all-gather(f32[2,8]{1,0} %p0), dimensions={0}
      %ars = f32[4,4]{1,0} all-reduce-start(f32[4,4]{1,0} %x), to_apply=%add
      %ard = f32[4,4]{1,0} all-reduce-done(f32[4,4]{1,0} %ars)
      %rs = f32[2,8]{1,0} reduce-scatter(f32[16,8]{1,0} %ag), dimensions={0}
      %cp = f32[4]{0} collective-permute(f32[4]{0} %y)
      %up = f32[4,4]{1,0} convert(bf16[4,4]{1,0} %z)
      %down = bf16[4,4]{1,0} convert(f32[4,4]{1,0} %up)
      %wide = f64[2]{0} convert(f32[2]{0} %v)
      ROOT %cb = f32[1]{0} custom-call(f32[1]{0} %w), custom_call_target="xla_python_cpu_callback"
    }
""")


def test_parse_compiled_collectives_counts_and_bytes():
    stats = ir.parse_compiled_collectives(_HLO)
    assert stats["all-gather"].count == 1
    assert stats["all-gather"].bytes == 16 * 8 * 4
    # async pair: -start counts once, -done is skipped
    assert stats["all-reduce"].count == 1
    assert stats["all-reduce"].bytes == 4 * 4 * 4
    assert stats["reduce-scatter"].count == 1
    assert stats["reduce-scatter"].bytes == 2 * 8 * 4
    assert stats["collective-permute"].count == 1
    assert "all-to-all" not in stats


def test_parse_compiled_collectives_silent_on_local_ops():
    clean = textwrap.dedent("""\
        ENTRY %main {
          %a = f32[8,8]{1,0} add(f32[8,8]{1,0} %x, f32[8,8]{1,0} %y)
          ROOT %d = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %y)
        }
    """)
    assert ir.parse_compiled_collectives(clean) == {}


def test_parse_compiled_upcasts_fires_on_widening_only():
    up = ir.parse_compiled_upcasts(_HLO)
    # bf16->f32 widening and the f64 landing fire; f32->bf16 is silent.
    assert up == {"bf16->f32": 1, "f32->f64": 1}


def test_is_upcast_f64_rule_and_same_width():
    assert ir._is_upcast("s32", "f64")       # anything -> f64
    assert ir._is_upcast("bf16", "f32")
    assert not ir._is_upcast("f64", "f64")
    assert not ir._is_upcast("f16", "bf16")  # same width, not wider
    assert not ir._is_upcast("f32", "bf16")


_SHLO = textwrap.dedent("""\
    module @fixture {
      func.func public @main(%arg0: tensor<16x8xbf16>) -> tensor<16x8xf32> {
        %0 = stablehlo.convert %arg0 : (tensor<16x8xbf16>) -> tensor<16x8xf32>
        %1 = sdy.sharding_constraint %0 <@mesh, [{"data"}, {}]> : tensor<16x8xf32>
        %2 = stablehlo.convert %1 : (tensor<16x8xf32>) -> tensor<16x8xbf16>
        %3 = stablehlo.custom_call @xla_python_cpu_callback(%2) {api_version = 2 : i32} : (tensor<16x8xbf16>) -> tensor<16x8xf32>
        return %3 : tensor<16x8xf32>
      }
    }
""")


def test_parse_stablehlo_extracts_all_three_facts():
    facts = ir.parse_stablehlo(_SHLO)
    assert facts["dtype_upcasts"] == {"bf16->f32": 1}
    (site,) = facts["resharding_sites"]
    assert site.sharding == '<@mesh, [{"data"}, {}]>'
    assert facts["host_callbacks"] == ["xla_python_cpu_callback"]


def test_parse_stablehlo_silent_on_clean_module():
    clean = ("module @m {\n  func.func public @main(%a: tensor<4xf32>)"
             " -> tensor<4xf32> {\n    return %a : tensor<4xf32>\n  }\n}\n")
    facts = ir.parse_stablehlo(clean)
    assert facts == {"dtype_upcasts": {}, "resharding_sites": [],
                     "host_callbacks": []}


# ---------------------------------------------------------------------------
# Live lowered programs on the 8-device mesh: fire + silent per detector
# ---------------------------------------------------------------------------


def test_live_collectives_fire_on_cross_device_reduction():
    env = _fsdp_env()
    xsh = NamedSharding(env.mesh, P("data"))
    rep = NamedSharding(env.mesh, P())
    f = jax.jit(lambda x: x.sum(), in_shardings=(xsh,), out_shardings=rep)
    report = ir.analyze_lowered("sum_fixture", f.lower(_sds((16, 4))))
    assert report.total_collective_count >= 1
    assert report.total_collective_bytes > 0
    assert report.mesh_shape == {"data": 8, "model": 1}


def test_live_collectives_silent_on_elementwise():
    env = _fsdp_env()
    xsh = NamedSharding(env.mesh, P("data"))
    g = jax.jit(lambda x: x * 2.0, in_shardings=(xsh,),
                out_shardings=xsh)
    report = ir.analyze_lowered("elem_fixture", g.lower(_sds((16, 4))))
    assert report.total_collective_count == 0
    assert report.total_collective_bytes == 0


def test_live_resharding_sites_counted():
    env = _fsdp_env()
    xsh = NamedSharding(env.mesh, P("data"))

    def with_constraint(x):
        return jax.lax.with_sharding_constraint(x + 1.0, xsh) * 2.0

    def without(x):
        return (x + 1.0) * 2.0

    fire = ir.analyze_lowered(
        "resh_fire", jax.jit(with_constraint, in_shardings=(xsh,),
                             out_shardings=xsh).lower(_sds((16, 4))))
    silent = ir.analyze_lowered(
        "resh_silent", jax.jit(without, in_shardings=(xsh,),
                               out_shardings=xsh).lower(_sds((16, 4))))
    assert len(fire.resharding_sites) == len(silent.resharding_sites) + 1


def test_live_dtype_upcast_detected():
    fire = ir.analyze_lowered(
        "upcast_fire",
        jax.jit(lambda x: x.astype(jnp.float32) * 2.0).lower(
            _sds((8,), jnp.bfloat16)))
    assert fire.dtype_upcasts.get("bf16->f32", 0) >= 1
    silent = ir.analyze_lowered(
        "upcast_silent",
        jax.jit(lambda x: x * 2.0).lower(_sds((8,), jnp.float32)))
    assert silent.dtype_upcasts == {}


def test_live_host_callback_detected():
    def with_cb(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    fire = ir.analyze_lowered(
        "cb_fire", jax.jit(with_cb).lower(_sds((4,))))
    assert fire.host_callbacks, "pure_callback not detected"
    assert any("callback" in t for t in fire.host_callbacks)
    silent = ir.analyze_lowered(
        "cb_silent", jax.jit(lambda x: x + 1.0).lower(_sds((4,))))
    assert silent.host_callbacks == []


# ---------------------------------------------------------------------------
# Param-sharding table + seeded regression 1: replicated fsdp param
# ---------------------------------------------------------------------------

#: (32, 32) f32 = 1024 elements — exactly at the fsdp policy's sharding
#: threshold (8 devices x 128), so the policy wants it sharded.
_PARAMS = {"dense": {"kernel": jax.ShapeDtypeStruct((32, 32),
                                                    jnp.float32)}}


def _matmul_program(env, param_shardings):
    rep = NamedSharding(env.mesh, P())
    f = jax.jit(lambda p, x: x @ p["dense"]["kernel"],
                in_shardings=(param_shardings, rep), out_shardings=rep)
    return f.lower(_PARAMS, _sds((8, 32)))


def test_param_table_flags_replicated_policy_param():
    env = _fsdp_env()
    expected = env.params(_PARAMS)
    # Policy sanity: fsdp DOES want this leaf sharded.
    assert not ir._is_replicated(expected["dense"]["kernel"])
    rep = NamedSharding(env.mesh, P())
    bad = ir.analyze_lowered(
        "sc201_fire",
        _matmul_program(env, jax.tree.map(lambda _: rep, _PARAMS)),
        params_template=_PARAMS, params_argnum=0,
        expected_param_shardings=expected)
    (flagged,) = bad.replicated_policy_params
    assert "kernel" in flagged
    good = ir.analyze_lowered(
        "sc201_silent", _matmul_program(env, expected),
        params_template=_PARAMS, params_argnum=0,
        expected_param_shardings=expected)
    assert good.replicated_policy_params == []
    assert len(good.param_table) == 1


def test_sc201_seeded_regression_fires_through_manifest_check():
    """The issue's seeded regression: pin a manifest from the healthy
    fsdp lowering, then force the param replicated — SC201 must fire."""
    env = _fsdp_env()
    expected = env.params(_PARAMS)
    rep = NamedSharding(env.mesh, P())
    good = ir.analyze_lowered(
        "sc201_seed", _matmul_program(env, expected),
        params_template=_PARAMS, params_argnum=0,
        expected_param_shardings=expected)
    manifest = manifest_from_report(good)
    assert not _live(check_report(good, manifest, "m.json"))
    bad = ir.analyze_lowered(
        "sc201_seed", _matmul_program(env, jax.tree.map(lambda _: rep,
                                                        _PARAMS)),
        params_template=_PARAMS, params_argnum=0,
        expected_param_shardings=expected)
    hits = _live(check_report(bad, manifest, "m.json"), "SC201")
    assert hits and "replicated" in hits[0].message


def test_param_table_arity_mismatch_raises():
    with pytest.raises(ValueError, match="arity"):
        ir.param_sharding_table(_PARAMS, [])


def test_mesh_param_spec_table_is_readable():
    env = _fsdp_env()
    table = env.param_spec_table(_PARAMS)
    (path,) = table
    assert "kernel" in path and "data" in table[path]


# ---------------------------------------------------------------------------
# Seeded regression 2: injected resharding site over a pinned manifest
# ---------------------------------------------------------------------------


def test_sc206_injected_resharding_flagged_and_suppressible():
    env = _fsdp_env()
    xsh = NamedSharding(env.mesh, P("data"))

    def base(x):
        return (x + 1.0) * 2.0

    def injected(x):
        return jax.lax.with_sharding_constraint(x + 1.0, xsh) * 2.0

    good = ir.analyze_lowered(
        "resh_seed", jax.jit(base, in_shardings=(xsh,),
                             out_shardings=xsh).lower(_sds((16, 4))))
    manifest = manifest_from_report(good)
    assert not _live(check_report(good, manifest, "m.json"))
    bad = ir.analyze_lowered(
        "resh_seed", jax.jit(injected, in_shardings=(xsh,),
                             out_shardings=xsh).lower(_sds((16, 4))))
    hits = _live(check_report(bad, manifest, "m.json"), "SC206")
    assert hits and "resharding" in hits[0].message
    # A reviewed manifest suppression silences it (reason mandatory).
    manifest.suppressions.append(
        Suppression("SC206", "*", "constraint added intentionally"))
    findings = check_report(bad, manifest, "m.json")
    assert not _live(findings, "SC206")
    assert any(f.rule == "SC206" and f.suppressed
               and f.suppress_reason for f in findings)


# ---------------------------------------------------------------------------
# Budget checking on synthetic reports (each SC rule, fire + silent)
# ---------------------------------------------------------------------------


def test_sc202_unbudgeted_and_over_count():
    r = _report(collectives={"all-gather": ir.CollectiveStat(
        "all-gather", count=3, bytes=512)})
    m = manifest_from_report(_report())          # empty budgets
    (f,) = _live(check_report(r, m, "m.json"), "SC202")
    assert "unbudgeted" in f.message
    m2 = manifest_from_report(r)
    assert not _live(check_report(r, m2, "m.json"))
    worse = _report(collectives={"all-gather": ir.CollectiveStat(
        "all-gather", count=4, bytes=512)})
    (f2,) = _live(check_report(worse, m2, "m.json"), "SC202")
    assert "exceeds budget 3" in f2.message


def test_sc203_bytes_over_budget():
    r = _report(collectives={"all-reduce": ir.CollectiveStat(
        "all-reduce", count=1, bytes=100)})
    m = manifest_from_report(r)
    fatter = _report(collectives={"all-reduce": ir.CollectiveStat(
        "all-reduce", count=1, bytes=200)})
    (f,) = _live(check_report(fatter, m, "m.json"), "SC203")
    assert "exceed budget 100" in f.message


def test_sc204_upcast_unbudgeted_over_and_pinned():
    m = manifest_from_report(_report(dtype_upcasts={"bf16->f32": 2}))
    ok = _report(dtype_upcasts={"bf16->f32": 2})
    assert not _live(check_report(ok, m, "m.json"))
    extra = _report(dtype_upcasts={"bf16->f32": 3})
    (f,) = _live(check_report(extra, m, "m.json"), "SC204")
    assert "exceed budget 2" in f.message
    novel = _report(dtype_upcasts={"f32->f64": 1})
    (f2,) = _live(check_report(novel, m, "m.json"), "SC204")
    assert "unbudgeted" in f2.message and "f32->f64" in f2.message


def test_sc205_callback_allowlist():
    m = manifest_from_report(_report(host_callbacks=["known_callback"]))
    ok = _report(host_callbacks=["known_callback"])
    assert not _live(check_report(ok, m, "m.json"))
    rogue = _report(host_callbacks=["rogue_callback"])
    (f,) = _live(check_report(rogue, m, "m.json"), "SC205")
    assert "rogue_callback" in f.message


def test_sc002_reasonless_manifest_suppression_warns():
    m = manifest_from_report(_report())
    m.suppressions.append(Suppression("SC204", "bf16->f32", reason=None))
    (f,) = _live(check_report(_report(), m, "m.json"), "SC002")
    assert f.severity == "warning" and "no reason" in f.message


def test_suppression_key_scoping():
    supp = Suppression("SC202", "all-gather", "pinned elsewhere")
    assert supp.covers("SC202", "all-gather")
    assert not supp.covers("SC202", "all-reduce")
    assert not supp.covers("SC203", "all-gather")
    assert Suppression("SC202", "*", "r").covers("SC202", "anything")


# ---------------------------------------------------------------------------
# Manifest round-trip, SC207, and the shared fingerprint-baseline format
# ---------------------------------------------------------------------------


def test_manifest_round_trip(tmp_path):
    r = _report(
        name="rt_prog",
        collectives={"all-gather": ir.CollectiveStat("all-gather", 2, 64)},
        dtype_upcasts={"bf16->f32": 1},
        host_callbacks=["cb"],
        resharding_sites=[ir.ReshardingSite("{devices=[8]<=[8]}")])
    m = manifest_from_report(
        r, [Suppression("SC205", "cb", "metrics tap, reviewed")])
    path = manifest_path("rt_prog", str(tmp_path))
    write_manifest(path, m)
    loaded = load_manifest(path)
    assert loaded.program == "rt_prog"
    assert loaded.budgets.collectives == {
        "all-gather": {"count": 2, "bytes": 64}}
    assert loaded.budgets.dtype_upcasts == {"bf16->f32": 1}
    assert loaded.budgets.resharding_sites == 1
    assert loaded.suppressions[0].reason == "metrics tap, reviewed"
    assert not _live(check_report_against_dir(r, str(tmp_path)))


def test_sc207_missing_and_unreadable_manifest(tmp_path):
    r = _report(name="ghost")
    (f,) = check_report_against_dir(r, str(tmp_path))
    assert f.rule == "SC207" and "--update" in f.message
    with open(manifest_path("ghost", str(tmp_path)), "w") as fh:
        fh.write("{not json")
    (f2,) = check_report_against_dir(r, str(tmp_path))
    assert f2.rule == "SC207" and "unreadable" in f2.message
    with open(manifest_path("ghost", str(tmp_path)), "w") as fh:
        json.dump({"version": 99, "tool": "other"}, fh)
    (f3,) = check_report_against_dir(r, str(tmp_path))
    assert f3.rule == "SC207"


def test_ir_findings_share_the_baseline_format(tmp_path):
    mf = str(tmp_path / "m.json")
    f = Finding(path=mf, rule="SC202", line=1, col=0, severity="error",
                message="a", fingerprint_data="p\x00SC202\x00all-gather")
    same_key = dataclasses.replace(f, message="different text")
    other_key = dataclasses.replace(
        f, fingerprint_data="p\x00SC202\x00all-reduce")
    root = str(tmp_path)
    # identity is (path, rule, key) — message and line text irrelevant
    assert f.fingerprint(root) == same_key.fingerprint(root)
    assert f.fingerprint(root) != other_key.fingerprint(root)
    bl = str(tmp_path / "baseline.json")
    assert write_baseline(bl, [f], root) == 1
    out = apply_baseline([same_key, other_key], load_baseline(bl), root)
    assert out[0].suppressed and out[0].suppress_reason == "baseline"
    assert not out[1].suppressed


# ---------------------------------------------------------------------------
# The comms_budget marker
# ---------------------------------------------------------------------------


def test_comms_check_violations_aggregate():
    check = CommsCheck()
    check.add(_report(collectives={"all-gather": ir.CollectiveStat(
        "all-gather", count=2, bytes=300)}))
    check.add(_report(
        collectives={"all-gather": ir.CollectiveStat(
            "all-gather", count=1, bytes=100)},
        resharding_sites=[ir.ReshardingSite("s")],
        host_callbacks=["cb"]))
    assert check.violations({"all_gather": 3, "total_bytes": 400,
                             "resharding_sites": 1,
                             "host_callbacks": 1}) == []
    v = check.violations({"all_gather": 2, "total_bytes": 399,
                          "resharding_sites": 0, "host_callbacks": 0})
    assert len(v) == 4
    assert any("all-gather: 3" in s for s in v)
    assert any("total_bytes: 400" in s for s in v)


@pytest.mark.comms_budget(all_reduce=4, total_bytes=1 << 20,
                          resharding_sites=0, dtype_upcasts=0,
                          host_callbacks=0)
def test_comms_budget_marker_e2e(comms_check):
    env = _fsdp_env()
    xsh = NamedSharding(env.mesh, P("data"))
    rep = NamedSharding(env.mesh, P())
    f = jax.jit(lambda x: x.sum(), in_shardings=(xsh,),
                out_shardings=rep)
    r = comms_check.analyze("marker_fixture", f.lower(_sds((16, 4))))
    assert r.total_collective_count >= 1     # the budget is non-vacuous


def test_comms_budget_vacuous_pass_protection(pytester):
    """A marked test that never registers a report must FAIL, not pass
    vacuously — run an in-process sub-pytest to observe the teardown."""
    pytester.makepyfile(textwrap.dedent("""\
        import pytest

        @pytest.mark.comms_budget(all_gather=1)
        def test_never_registers(comms_check):
            pass
    """))
    result = pytester.runpytest_inprocess(
        "-p", "diff3d_tpu.analysis.pytest_plugin",
        "-p", "no:cacheprovider", "-p", "no:randomly")
    assert result.ret != 0
    result.stdout.fnmatch_lines(["*vacuously*"])


def test_comms_budget_marker_rejects_bad_usage(pytester):
    pytester.makepyfile(textwrap.dedent("""\
        import pytest

        @pytest.mark.comms_budget(warp_drive=1)
        def test_unknown_key(comms_check):
            pass

        @pytest.mark.comms_budget(all_gather=1)
        def test_no_fixture():
            pass

        @pytest.mark.comms_budget()
        def test_no_limits(comms_check):
            pass
    """))
    result = pytester.runpytest_inprocess(
        "-p", "diff3d_tpu.analysis.pytest_plugin",
        "-p", "no:cacheprovider", "-p", "no:randomly")
    assert result.ret != 0
    result.stdout.fnmatch_lines(["*unknown keys warp_drive*"])
    result.stdout.fnmatch_lines(["*requires the comms_check fixture*"])
    result.stdout.fnmatch_lines(["*no limits*"])


# ---------------------------------------------------------------------------
# CLI + registry plumbing
# ---------------------------------------------------------------------------


def test_cli_list_and_bad_invocation(capsys):
    assert sc.main(["--list"]) == 0
    out = capsys.readouterr().out
    for nm in sc.REGISTRY:
        assert nm in out
    assert sc.main(["--program", "train_step", "--programs-tier1"]) == 2


def test_registry_names_and_tier1():
    assert set(sc.TIER1_PROGRAMS) == {"train_step", "step_many",
                                      "step_many_cascade_draft",
                                      "step_many_cascade_refine"}
    assert set(sc.TIER1_PROGRAMS) <= set(sc.REGISTRY)


def test_tier1_manifests_are_committed():
    d = sc.default_manifest_dir(_REPO_ROOT)
    for nm in sc.REGISTRY:
        assert os.path.exists(manifest_path(nm, d)), (
            f"missing committed manifest for {nm}; run "
            f"'python tools/shardcheck.py --update --program {nm}'")


def test_update_preserves_suppressions(tmp_path, monkeypatch):
    """--update re-pins observations but keeps reviewed suppressions."""
    d = str(tmp_path)
    supp = Suppression("SC204", "bf16->f32", "mixed-precision by design")
    r = _report(name="train_step")
    write_manifest(manifest_path("train_step", d),
                   manifest_from_report(r, [supp]))
    monkeypatch.setitem(
        sc.REGISTRY, "train_step",
        dataclasses.replace(sc.REGISTRY["train_step"],
                            build=lambda: _report(name="train_step")))
    sc.update_manifests(["train_step"], d)
    loaded = load_manifest(manifest_path("train_step", d))
    assert loaded.suppressions == [supp]


# ---------------------------------------------------------------------------
# The tier-1 gate: committed manifests match what the tree lowers today
# ---------------------------------------------------------------------------


def test_repo_manifests_clean_tier1():
    """The shardcheck analogue of ``test_repo_lints_clean``: building
    the REAL tier-1 programs (sharded train step, sharded ``step_many``)
    and diffing against the committed manifests must come back clean.
    Any collective/param/upcast drift is either a fix or a reviewed
    ``--update`` re-pin."""
    d = sc.default_manifest_dir(_REPO_ROOT)
    findings = sc.check_programs(list(sc.TIER1_PROGRAMS), d)
    live = _live(findings)
    assert not live, "\n".join(f.render() for f in live)


@pytest.mark.slow
def test_repo_manifests_clean_full_sweep():
    """All five registered programs (adds distill, DDIM, serving
    warmup) — the full manifest sweep the CLI runs."""
    d = sc.default_manifest_dir(_REPO_ROOT)
    findings = sc.check_programs(sorted(sc.REGISTRY), d)
    live = _live(findings)
    assert not live, "\n".join(f.render() for f in live)
