"""shardcheck: the program registry + CLI over the IR analyzer.

``analysis/ir.py`` knows how to turn one lowered pjit program into a
:class:`~diff3d_tpu.analysis.ir.ProgramReport`; ``analysis/budgets.py``
knows how to diff a report against a committed manifest.  This module
knows WHICH programs the repo ships: every registered entry builds the
real production program — the mesh-sharded train step, the distill
step, the sampler's ``step_many`` per schedule, a serving-warmup
program routed through :class:`~diff3d_tpu.serving.cache.ProgramCache`
— on tiny test-config shapes over an 8-virtual-CPU-device fsdp mesh,
lowers it on ABSTRACT args (nothing executes; XLA still runs the full
GSPMD partitioner, so the collectives are the real ones), and analyzes.

CLI (also installed as the ``shardcheck`` console script)::

    shardcheck                       # check every program vs manifests
    shardcheck --program train_step  # one program
    shardcheck --update              # re-pin manifests from observed
    shardcheck --list                # registry contents

Exit codes match graftlint: 0 clean, 1 unsuppressed findings, 2 bad
invocation.  ``tools/lint.py`` runs this as the second half of the
tier-1 static-analysis gate (``--programs-tier1`` keeps that fast).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Callable, Dict, List, Optional, Sequence

from diff3d_tpu.analysis import budgets as budgets_lib
from diff3d_tpu.analysis import ir
from diff3d_tpu.analysis.lint import Finding

#: Virtual device count the registry's mesh expects (matches the test
#: suite's conftest).
MESH_DEVICES = 8


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One registered pjit program."""

    name: str
    description: str
    build: Callable[[], "ir.ProgramReport"]
    #: tier-1 programs are cheap enough for the always-on gate (the
    #: repo-clean test and ``tools/lint.py``); the rest ride the
    #: ``slow``-marked full sweep and the standalone CLI.
    tier1: bool = False


def ensure_cpu_mesh_devices(n: int = MESH_DEVICES) -> None:
    """Force ``n`` virtual CPU devices, tolerating an already-imported
    jax: ``XLA_FLAGS`` is read at backend *initialisation* (lazy), so
    setting it plus ``jax_platforms`` works as long as no backend has
    been created yet.  Under pytest the conftest has already done the
    same thing; a backend initialised with fewer devices is an error."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"shardcheck needs {n} CPU devices, backend has {have} — "
            "jax was initialised before shardcheck could set "
            "--xla_force_host_platform_device_count")


def _fsdp_mesh():
    import jax

    from diff3d_tpu.config import MeshConfig
    from diff3d_tpu.parallel import make_mesh

    return make_mesh(
        MeshConfig(data_parallel=MESH_DEVICES, model_parallel=1,
                   param_sharding="fsdp"),
        devices=jax.devices()[:MESH_DEVICES])


def _abstract_state(model, cfg):
    """Abstract TrainState template (shapes via ``eval_shape`` — no
    param buffers are ever materialised)."""
    import jax

    from diff3d_tpu.train import create_train_state
    from diff3d_tpu.train.trainer import init_params

    def build(rng):
        return create_train_state(init_params(model, cfg, rng), cfg.train)

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def _abstract_batch(cfg):
    import jax
    import jax.numpy as jnp

    B = cfg.train.global_batch
    H = cfg.model.H
    sds = jax.ShapeDtypeStruct
    return {"imgs": sds((B, 2, H, H, 3), jnp.uint8),
            "R": sds((B, 2, 3, 3), jnp.float32),
            "T": sds((B, 2, 3), jnp.float32),
            "K": sds((B, 3, 3), jnp.float32)}


def _train_cfg():
    from diff3d_tpu.config import test_config

    return test_config(imgsize=16, ch=8, shallow=True)


def build_train_step_report(name: str = "train_step") -> "ir.ProgramReport":
    import jax
    import jax.numpy as jnp

    from diff3d_tpu.models import build_model
    from diff3d_tpu.train import make_train_step

    cfg = _train_cfg()
    env = _fsdp_mesh()
    model = build_model(cfg)
    state = _abstract_state(model, cfg)
    batch = _abstract_batch(cfg)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = make_train_step(model, cfg, env, donate=False)
    lowered = step.lower(state, batch, rng)
    return ir.analyze_lowered(
        name, lowered, params_template=state.params,
        params_argnum=lambda sh: sh[0].params,
        expected_param_shardings=env.params(state.params))


def build_distill_step_report(
        name: str = "distill_step") -> "ir.ProgramReport":
    import jax
    import jax.numpy as jnp

    from diff3d_tpu.models import build_model
    from diff3d_tpu.train.distill import make_distill_step

    cfg = _train_cfg()
    env = _fsdp_mesh()
    model = build_model(cfg)
    state = _abstract_state(model, cfg)
    batch = _abstract_batch(cfg)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    k = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_distill_step(model, cfg, env, donate=False)
    lowered = step.lower(state, state.params, batch, rng, k)
    return ir.analyze_lowered(
        name, lowered, params_template=state.params,
        params_argnum=lambda sh: sh[0].params,
        expected_param_shardings=env.params(state.params))


def _sampler(sampler_kind: str = "ancestral",
             steps: Optional[int] = None,
             kernels: Optional[str] = None):
    import dataclasses

    import jax

    from diff3d_tpu.config import test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = test_config(imgsize=8, ch=8)
    if kernels is not None:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, kernels=kernels))
    env = _fsdp_mesh()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    return Sampler(model, params, cfg, mesh=env,
                   sampler_kind=sampler_kind, steps=steps), env


def build_step_many_report(name: str = "step_many") -> "ir.ProgramReport":
    sampler, env = _sampler()
    lowered = sampler.lower_step_many(lanes=MESH_DEVICES, capacity=4)
    return ir.analyze_lowered(
        name, lowered, params_template=sampler.params,
        params_argnum=0,
        expected_param_shardings=env.params(sampler.params))


def build_step_many_pallas_report(
        name: str = "step_many_pallas") -> "ir.ProgramReport":
    """step_many with the fused GroupNorm->FiLM/SiLU Pallas kernels
    (interpret-mode lowering on the CPU mesh).  Not tier-1 — the
    interpret-mode pallas_call lowering is several times slower to trace
    than the XLA path, so the lint gate pins it out-of-band."""
    sampler, env = _sampler(kernels="pallas")
    lowered = sampler.lower_step_many(lanes=MESH_DEVICES, capacity=4)
    return ir.analyze_lowered(
        name, lowered, params_template=sampler.params,
        params_argnum=0,
        expected_param_shardings=env.params(sampler.params))


def build_step_many_ddim_report(
        name: str = "step_many_ddim") -> "ir.ProgramReport":
    sampler, env = _sampler(sampler_kind="ddim", steps=2)
    lowered = sampler.lower_step_many(lanes=MESH_DEVICES, capacity=4)
    return ir.analyze_lowered(
        name, lowered, params_template=sampler.params,
        params_argnum=0,
        expected_param_shardings=env.params(sampler.params))


def build_serving_warmup_report(
        name: str = "serving_warmup") -> "ir.ProgramReport":
    from diff3d_tpu.serving.cache import ProgramCache

    sampler, env = _sampler()
    cache = ProgramCache(sampler)
    H = sampler.cfg.model.H
    lowered = cache.lower((H, H, 4), lanes=MESH_DEVICES)
    return ir.analyze_lowered(
        name, lowered, params_template=sampler.params,
        params_argnum=0,
        expected_param_shardings=env.params(sampler.params))


def _cascade():
    """The cascade pair at analysis scale: a tiny 16² refine model whose
    draft phase is the resolution-adapted 8² student — the same
    construction serving uses, so the lowered programs carry the real
    extra ``draft`` operand and truncated grid."""
    import jax

    from diff3d_tpu.cascade import CascadePlan, CascadeSampler
    from diff3d_tpu.config import test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.train.trainer import init_params

    cfg = test_config(imgsize=16, ch=8)
    env = _fsdp_mesh()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    plan = CascadePlan.parse("draft=8:ddim:2,refine=16:ancestral:2@t0.5")
    return CascadeSampler(model, params, cfg, plan, mesh=env), env


def build_step_many_cascade_draft_report(
        name: str = "step_many_cascade_draft") -> "ir.ProgramReport":
    cascade, env = _cascade()
    s = cascade.draft
    lowered = s.lower_step_many(lanes=MESH_DEVICES, capacity=4)
    return ir.analyze_lowered(
        name, lowered, params_template=s.params,
        params_argnum=0,
        expected_param_shardings=env.params(s.params))


def build_step_many_cascade_refine_report(
        name: str = "step_many_cascade_refine") -> "ir.ProgramReport":
    cascade, env = _cascade()
    s = cascade.refine
    lowered = s.lower_step_many(lanes=MESH_DEVICES, capacity=4)
    return ir.analyze_lowered(
        name, lowered, params_template=s.params,
        params_argnum=0,
        expected_param_shardings=env.params(s.params))


REGISTRY: Dict[str, ProgramSpec] = {
    spec.name: spec for spec in (
        ProgramSpec(
            "train_step",
            "mesh-sharded train step (tiny shallow config, fsdp x8)",
            build_train_step_report, tier1=True),
        ProgramSpec(
            "step_many",
            "sharded sampler step_many, ancestral full grid "
            "(8 lanes, capacity 4)",
            build_step_many_report, tier1=True),
        ProgramSpec(
            "step_many_pallas",
            "sharded sampler step_many with fused GroupNorm Pallas "
            "kernels (interpret-mode lowering)",
            build_step_many_pallas_report),
        ProgramSpec(
            "distill_step",
            "mesh-sharded progressive-distillation step",
            build_distill_step_report),
        ProgramSpec(
            "step_many_ddim",
            "sharded sampler step_many, deterministic DDIM few-step",
            build_step_many_ddim_report),
        ProgramSpec(
            "serving_warmup",
            "serving-warmup view-step program routed via ProgramCache",
            build_serving_warmup_report),
        ProgramSpec(
            "step_many_cascade_draft",
            "cascade draft phase: resolution-adapted student, few-step "
            "DDIM at the draft resolution",
            build_step_many_cascade_draft_report, tier1=True),
        ProgramSpec(
            "step_many_cascade_refine",
            "cascade refine phase: start_t-truncated scan with the "
            "upsampled-draft operand",
            build_step_many_cascade_refine_report, tier1=True),
    )
}

TIER1_PROGRAMS = tuple(s.name for s in REGISTRY.values() if s.tier1)


def default_manifest_dir(root: Optional[str] = None) -> str:
    if root is None:
        root = _find_root()
    return os.path.join(root, budgets_lib.DEFAULT_MANIFEST_DIR)


def _find_root() -> str:
    cur = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return cur


#: In-process report cache.  Lowering is deterministic for a fixed tree
#: (the whole premise of the manifest gates), and the shardcheck and
#: memcheck pillars analyze the SAME programs — one build feeds both
#: when they run in one process (tools/lint.py, the tier-1 pytest run).
#: Keyed by (name, builder) so a test that monkeypatches a REGISTRY
#: entry's ``build`` never sees a stale cached report.
_REPORT_CACHE: Dict[tuple, "ir.ProgramReport"] = {}


def build_report(name: str) -> "ir.ProgramReport":
    """Build (or fetch the cached) :class:`ir.ProgramReport` for a
    registered program."""
    spec = REGISTRY[name]
    key = (name, spec.build)
    report = _REPORT_CACHE.get(key)
    if report is None:
        report = _REPORT_CACHE[key] = spec.build()
    return report


def check_programs(names: Sequence[str], manifest_dir: str,
                   reports_out: Optional[list] = None) -> List[Finding]:
    """Build + analyze each named program and diff against its manifest.
    Returns ALL findings (suppressed marked), ``lint_source``-style."""
    findings: List[Finding] = []
    for nm in names:
        report = build_report(nm)
        if reports_out is not None:
            reports_out.append(report)
        findings.extend(
            budgets_lib.check_report_against_dir(report, manifest_dir))
    return findings


def update_manifests(names: Sequence[str], manifest_dir: str) -> List[str]:
    """Re-pin each named program's manifest from its current report,
    PRESERVING any suppressions the committed manifest carries (they are
    reviewed policy, not observations)."""
    from diff3d_tpu.analysis import manifests as manifests_lib
    written = []
    for nm in names:
        report = build_report(nm)
        path = budgets_lib.manifest_path(nm, manifest_dir)
        supps = manifests_lib.carry_suppressions(
            path, budgets_lib.load_manifest)
        budgets_lib.write_manifest(
            path, budgets_lib.manifest_from_report(report, supps))
        written.append(path)
    return written


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="shardcheck",
        description="IR-level sharding/communication analyzer over the "
                    "repo's pjit programs (rules SC2xx; see "
                    "docs/DESIGN.md §10)")
    p.add_argument("--program", action="append", default=None,
                   choices=sorted(REGISTRY), dest="programs",
                   help="check one program (repeatable; default: all)")
    p.add_argument("--programs-tier1", action="store_true",
                   help=f"check only the tier-1 set {TIER1_PROGRAMS}")
    p.add_argument("--manifest-dir", default=None,
                   help="manifest directory (default <root>/"
                        f"{budgets_lib.DEFAULT_MANIFEST_DIR})")
    p.add_argument("--update", action="store_true",
                   help="write manifests pinned to the current reports "
                        "(keeps existing suppressions) and exit 0")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--show-suppressed", action="store_true")
    p.add_argument("--list", action="store_true", dest="list_programs",
                   help="list registered programs")
    args = p.parse_args(argv)

    if args.list_programs:
        for spec in REGISTRY.values():
            tag = " [tier1]" if spec.tier1 else ""
            print(f"{spec.name:18s} {spec.description}{tag}")
        return 0

    if args.programs and args.programs_tier1:
        print("shardcheck: --program and --programs-tier1 are exclusive",
              file=sys.stderr)
        return 2
    names = (args.programs or
             (list(TIER1_PROGRAMS) if args.programs_tier1
              else sorted(REGISTRY)))
    manifest_dir = args.manifest_dir or default_manifest_dir()

    ensure_cpu_mesh_devices()

    if args.update:
        for path in update_manifests(names, manifest_dir):
            print(f"shardcheck: wrote {path}")
        return 0

    reports: list = []
    findings = check_programs(names, manifest_dir, reports_out=reports)
    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]
    if args.format == "json":
        print(json.dumps({
            "reports": [r.to_json() for r in reports],
            "findings": [dataclasses.asdict(f) for f in findings],
            "unsuppressed": len(live),
            "suppressed": len(suppressed),
        }, indent=1))
    else:
        shown = findings if args.show_suppressed else live
        for f in shown:
            print(f.render())
        print(f"shardcheck: {len(live)} finding(s), "
              f"{len(suppressed)} suppressed, "
              f"{len(names)} program(s)")
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
