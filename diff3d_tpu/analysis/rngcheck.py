"""rngcheck: interprocedural RNG-lineage & precision-flow analyzer.

The fifth analysis pillar.  Every load-bearing correctness contract in
this repo is a *determinism* contract — the ancestral-256 bit-parity
oracle, the chunked carried-RNG schedule independence, the elastic
bit-identical consumed-batch stream, stochastic conditioning itself —
and all of them sit on disciplined key derivation.  graftlint GL101
catches literal same-function key reuse; this tool extends the same
linear-resource model (``analysis/rngflow.py``) across the call graph,
adds seed-hygiene and precision-flow rules, and pins each production
program's ordered key-derivation stream as a committed manifest under
``runs/rngcheck/`` — so a change that perturbs any RNG stream fails
tier-1 by manifest diff, not by a 900-second parity test.

Static rules (suppress inline with
``# rngcheck: disable=<rule>(reason)``):

  RC001  file does not parse                                  (error)
  RC002  suppression without a reason                       (warning)
  RC003  malformed ``# rng-lineage:`` annotation              (error)
  RC501  key double-consumption across call sites             (error)
  RC502  key reused after being split, across call sites      (error)
  RC503  derived key never consumed (dead stream branch)    (warning)
  RC504  host-level random / np.random inside a traced body   (error)
  RC505  PRNGKey built from non-static traced data            (error)
  RC506  seed derived from host time / pid / urandom          (error)
  RC507  fold_in with loop-invariant key AND index in a loop  (error)
  RC508  sharded-vs-replicated exact-equality comparison with
         no threefry_partitionable guard                      (error)
  RC509  f32→bf16 downcast on a loss/accumulation path        (error)

Stream-manifest rules (suppress in the manifest's
``suppressions`` list, key-scoped, reason mandatory):

  RC510  observed stream digest differs from the manifest     (error)
  RC511  program has no committed stream manifest             (error)
  RC512  runtime witness recorded a key consumed twice        (error)

GL101 and RC501/RC502 share one scanner (:func:`rngflow.
linear_violations`) and partition cleanly: GL101 owns violations whose
both sides are local ``jax.random`` events; rngcheck owns the ones
involving a resolved call edge.  They cannot disagree.

CLI (also the ``rngcheck`` console script)::

    rngcheck                       # static pass + all stream manifests
    rngcheck --ast-only            # static rules only (no jax import)
    rngcheck --streams-tier1       # static + tier-1 streams (the gate)
    rngcheck --update              # re-pin stream manifests
    rngcheck --list-streams        # registry contents

Exit codes match graftlint: 0 clean, 1 unsuppressed findings, 2 bad
invocation.  ``tools/lint.py`` runs this as the fifth gate.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Set

from diff3d_tpu.analysis import manifests as manifests_lib
from diff3d_tpu.analysis import rngflow
from diff3d_tpu.analysis.lint import (DEFAULT_TARGETS, Finding,
                                      SEVERITY_ERROR, SEVERITY_WARNING,
                                      _find_root, apply_baseline,
                                      iter_python_files, lint_source,
                                      load_baseline, write_baseline)
from diff3d_tpu.analysis.rules.base import Rule
from diff3d_tpu.analysis.rules.context import (ModuleContext, dotted_name,
                                               param_names)

TOOL = "rngcheck"
PARSE_RULE = "RC001"
REASONLESS_RULE = "RC002"
DEFAULT_BASELINE = ".rngcheck-baseline.json"

#: Default stream-manifest directory, relative to the repo root.
DEFAULT_MANIFEST_DIR = os.path.join("runs", "rngcheck")
MANIFEST_VERSION = 1


# ---------------------------------------------------------------------
# static rules
# ---------------------------------------------------------------------


class RcAnnotationRule(Rule):
    id = "RC003"
    name = "rng-lineage-annotation"
    severity = SEVERITY_ERROR
    description = ("a # rng-lineage: annotation does not parse "
                   "(unknown directive or bad argument list)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            ann = rngflow.parse_lineage_annotations(ctx, node)
            for lineno, msg in ann.errors:
                yield Finding(path=ctx.path, rule=self.id, line=lineno,
                              col=0, severity=self.severity,
                              message=msg)


class RcLinearRule(Rule):
    """RC501/RC502: the interprocedural half of the linear-key scan.

    GL101 owns violations where both consumptions are local
    ``jax.random`` events; this rule emits only when a resolved call
    edge is involved — the cross-function cases a single-scope pass
    cannot see.  One shared scanner, disjoint jurisdictions."""

    id = "RC501"
    name = "rng-key-cross-call-reuse"
    severity = SEVERITY_ERROR
    description = ("a PRNG key is consumed twice, at least once by "
                   "passing it to a function that draws from it")

    def __init__(self, graph: Optional[rngflow.ProgramGraph] = None):
        self.graph = graph

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if self.graph is None:
            return
        for v in rngflow.linear_violations(ctx, self.graph):
            if v.kind != "call" and v.prev_kind != "call":
                continue  # GL101's jurisdiction
            rule = "RC502" if v.prev_kind == "split" else "RC501"
            prev = {"split": "split", "draw": "drawn from",
                    "call": "consumed by a callee"}[v.prev_kind]
            if v.kind == "call":
                how = (f"passing it to '{v.detail}()' (which draws "
                       f"from its key parameter) consumes it again")
            else:
                how = "this draw consumes it again"
            yield Finding(
                path=ctx.path, rule=rule, line=v.node.lineno,
                col=v.node.col_offset + 1, severity=self.severity,
                message=(f"PRNG key '{v.name}' was already "
                         f"{prev} on line {v.prev_line} — {how}; "
                         "split it (or reassign the carry) first"))


class RcDeadKeyRule(Rule):
    id = "RC503"
    name = "rng-dead-derived-key"
    severity = SEVERITY_WARNING
    description = ("a key derived via split/fold_in/PRNGKey is never "
                   "used — a dead stream branch (or a stream-schema "
                   "drift waiting to happen)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node, name in rngflow.dead_derived_keys(ctx):
            yield self.finding(
                ctx, node,
                f"derived key '{name}' is never consumed — prefix "
                f"with _ if the discard is intentional (it still "
                f"shapes the split schema), else delete the branch")


class RcHostRandomRule(Rule):
    id = "RC504"
    name = "host-rng-in-traced-body"
    severity = SEVERITY_ERROR
    description = ("Python random / np.random called inside a traced "
                   "body — it runs once at trace time, baking one "
                   "sample into the compiled program")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        random_roots: Set[str] = set()
        random_names: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "random":
                        random_roots.add(a.asname or "random")
                    elif a.name in ("numpy", "numpy.random"):
                        pass  # covered by the np-root check below
            elif isinstance(node, ast.ImportFrom):
                if node.module in ("random", "numpy.random"):
                    for a in node.names:
                        random_names.add(a.asname or a.name)
        # `from jax import random` shadows the stdlib name.
        random_roots -= ctx.random_aliases
        if not ctx.traced_functions:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = ctx.enclosing_function(node)
            if fn is None or id(fn) not in ctx.traced_functions:
                continue
            dotted = dotted_name(node.func)
            if dotted is None:
                continue
            root = dotted.split(".")[0]
            hit = (root in random_roots
                   or dotted in random_names
                   or (root in ("np", "numpy")
                       and dotted.split(".")[1:2] == ["random"]))
            if hit:
                yield self.finding(
                    ctx, node,
                    f"'{dotted}' inside a traced body runs ONCE at "
                    "trace time — the compiled program replays that "
                    "single sample forever; thread a jax.random key "
                    "instead")


class RcTracedSeedRule(Rule):
    id = "RC505"
    name = "key-from-traced-data"
    severity = SEVERITY_ERROR
    description = ("PRNGKey/key constructed from a non-static traced "
                   "value — the stream becomes data-dependent")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        seen: Set[int] = set()
        for fn in ctx.traced_nodes():
            dyn = set(param_names(fn)) - ctx.static_params_of(fn)
            if not dyn:
                continue
            for node in ast.walk(fn):
                if (not isinstance(node, ast.Call)
                        or id(node) in seen
                        or not isinstance(node.func, ast.Attribute)):
                    continue
                if (dotted_name(node.func.value)
                        not in ctx.random_aliases
                        or node.func.attr not in ("PRNGKey", "key")):
                    continue
                if ctx.enclosing_function(node) is not fn:
                    continue
                names = {n.id for a in node.args
                         for n in ast.walk(a)
                         if isinstance(n, ast.Name)
                         and isinstance(n.ctx, ast.Load)}
                bad = sorted(names & dyn)
                if bad:
                    seen.add(id(node))
                    yield self.finding(
                        ctx, node,
                        f"PRNGKey built from traced value(s) "
                        f"{', '.join(bad)} — the seed is data-"
                        "dependent; derive via fold_in on a threaded "
                        "key instead")


#: Host entropy sources that make a seed unreproducible.
_TIME_SOURCES = ("time.time", "time.time_ns", "time.monotonic",
                 "time.monotonic_ns", "time.perf_counter",
                 "datetime.now", "datetime.utcnow", "os.urandom",
                 "os.getpid", "uuid.uuid4", "uuid.uuid1")

_NP_SEED_SUFFIXES = (".random.seed", ".random.default_rng",
                     ".random.RandomState")


class RcHostTimeSeedRule(Rule):
    id = "RC506"
    name = "host-time-seed"
    severity = SEVERITY_ERROR
    description = ("a PRNG seed derived from wall clock / pid / "
                   "urandom — the run is unreproducible by "
                   "construction")

    def _is_seed_ctor(self, ctx: ModuleContext, node: ast.Call) -> bool:
        if isinstance(node.func, ast.Attribute):
            if (dotted_name(node.func.value) in ctx.random_aliases
                    and node.func.attr in ("PRNGKey", "key")):
                return True
        dotted = dotted_name(node.func)
        if dotted is None:
            return False
        if any(dotted.endswith(s) for s in _NP_SEED_SUFFIXES):
            return True
        return dotted.split(".")[-1] == "SeedSequence"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and self._is_seed_ctor(ctx, node)):
                continue
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                for inner in ast.walk(arg):
                    if not isinstance(inner, ast.Call):
                        continue
                    d = dotted_name(inner.func)
                    if d and any(d == s or d.endswith("." + s)
                                 for s in _TIME_SOURCES):
                        yield self.finding(
                            ctx, node,
                            f"seed derived from '{d}()' — every run "
                            "gets a different stream; take the seed "
                            "from config and log it instead")
                        break


class RcFoldInLoopRule(Rule):
    id = "RC507"
    name = "fold-in-loop-invariant"
    severity = SEVERITY_ERROR
    description = ("fold_in inside a Python loop with BOTH key and "
                   "index loop-invariant — every iteration derives "
                   "the same key")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        flagged: Set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            stored = {n.id for n in ast.walk(loop)
                      if isinstance(n, ast.Name)
                      and isinstance(n.ctx, (ast.Store, ast.Del))}
            for node in ast.walk(loop):
                if (not isinstance(node, ast.Call)
                        or id(node) in flagged
                        or not isinstance(node.func, ast.Attribute)
                        or node.func.attr != "fold_in"
                        or dotted_name(node.func.value)
                        not in ctx.random_aliases
                        or len(node.args) < 2):
                    continue
                key_a, data_a = node.args[0], node.args[1]
                # A Call in either slot derives fresh state per
                # iteration as far as this syntactic pass can tell.
                if any(isinstance(n, ast.Call)
                       for a in (key_a, data_a) for n in ast.walk(a)):
                    continue
                names = {n.id for a in (key_a, data_a)
                         for n in ast.walk(a)
                         if isinstance(n, ast.Name)
                         and isinstance(n.ctx, ast.Load)}
                if names & stored:
                    continue
                flagged.add(id(node))
                yield self.finding(
                    ctx, node,
                    "fold_in with loop-invariant key AND index — "
                    "every iteration of this loop derives the same "
                    "key; fold in the loop counter")


_EXACT_EQ_TAILS = ("assert_array_equal", "array_equal",
                   "assert_trees_all_equal")
_GUARD_TOKENS = ("threefry_partitionable", "partitionable_rng",
                 "jax_threefry_partitionable")


class RcThreefryGuardRule(Rule):
    id = "RC508"
    name = "unguarded-sharded-parity"
    severity = SEVERITY_ERROR
    description = ("sharded-vs-replicated exact-equality comparison "
                   "with no threefry_partitionable guard — legacy "
                   "threefry produces different bits under "
                   "partitioning (the PR 8 tier-1 failures)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        uses_random = any(
            isinstance(n, ast.Attribute)
            and dotted_name(n.value) in ctx.random_aliases
            for n in ast.walk(ctx.tree))
        if not uses_random:
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            text = ast.get_source_segment(ctx.source, fn) or ""
            if any(tok in text for tok in _GUARD_TOKENS):
                continue
            if fn.args and any(a.arg in _GUARD_TOKENS
                               for a in fn.args.args):
                continue
            exact_eq = False
            callee_modes: Dict[str, Set[str]] = {}
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted_name(node.func)
                if d and any(d.endswith(t) for t in _EXACT_EQ_TAILS):
                    exact_eq = True
                name = d or (node.func.attr if isinstance(
                    node.func, ast.Attribute) else None)
                if name is None:
                    continue
                mode = "nomesh"
                for kw in node.keywords:
                    if kw.arg == "mesh":
                        mode = ("nomesh" if isinstance(kw.value,
                                                       ast.Constant)
                                and kw.value.value is None else "mesh")
                callee_modes.setdefault(name, set()).add(mode)
            both = sorted(n for n, modes in callee_modes.items()
                          if {"mesh", "nomesh"} <= modes)
            if exact_eq and both:
                yield self.finding(
                    ctx, fn,
                    f"'{fn.name}' compares {both[0]}(mesh=...) against "
                    "an unsharded run with exact equality and no "
                    "threefry_partitionable guard — wrap the test in "
                    "`with jax.threefry_partitionable(True):` (or the "
                    "partitionable_rng fixture)")


_ACC_NAME_RE = re.compile(
    r"(loss|grad|acc|accum|sum|mean|total|metric|avg|norm|err)",
    re.IGNORECASE)
_REDUCTIONS = ("mean", "sum", "prod", "average", "var", "std")


def _is_bf16(node: ast.AST) -> bool:
    d = dotted_name(node)
    if d is not None and d.split(".")[-1] == "bfloat16":
        return True
    return (isinstance(node, ast.Constant)
            and node.value == "bfloat16")


class RcPrecisionFlowRule(Rule):
    id = "RC509"
    name = "bf16-on-accumulation-path"
    severity = SEVERITY_ERROR
    description = ("f32→bf16 downcast on a loss/accumulation/"
                   "reduction path inside a traced body — bf16 "
                   "accumulation loses ~8 bits of mantissa per "
                   "reduce")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        seen: Set[int] = set()
        for fn in ctx.traced_nodes():
            for node in ast.walk(fn):
                if (not isinstance(node, ast.Call)
                        or id(node) in seen):
                    continue
                seen.add(id(node))
                # pattern A: <acc>.astype(bfloat16) / casting into an
                # accumulator-named target.
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "astype"
                        and node.args and _is_bf16(node.args[0])):
                    recv = dotted_name(node.func.value) or ""
                    target = ""
                    parent = ctx.parent.get(id(node))
                    if isinstance(parent, ast.Assign):
                        target = " ".join(
                            t.id for t in parent.targets
                            if isinstance(t, ast.Name))
                    subject = " ".join(dict.fromkeys(
                        s for s in (recv, target) if s))
                    if _ACC_NAME_RE.search(subject):
                        yield self.finding(
                            ctx, node,
                            f"'{subject or 'value'}' downcast to "
                            "bfloat16 on an accumulation path — keep "
                            "the reduce in f32 and cast afterwards")
                    continue
                # pattern B: a reduction told to accumulate in bf16.
                d = dotted_name(node.func)
                if d and d.split(".")[-1] in _REDUCTIONS:
                    for kw in node.keywords:
                        if kw.arg == "dtype" and _is_bf16(kw.value):
                            yield self.finding(
                                ctx, node,
                                f"'{d}(dtype=bfloat16)' accumulates "
                                "the reduction in bf16 — reduce in "
                                "f32, cast the result")


def make_rc_rules(
        graph: Optional[rngflow.ProgramGraph] = None) -> tuple:
    """The full RC rule pack (graph-bound linear rule included)."""
    return (RcAnnotationRule(), RcLinearRule(graph), RcDeadKeyRule(),
            RcHostRandomRule(), RcTracedSeedRule(),
            RcHostTimeSeedRule(), RcFoldInLoopRule(),
            RcThreefryGuardRule(), RcPrecisionFlowRule())


#: Ids listed by --list-rules (RC510+ are manifest-side, not AST).
_RULE_DOCS = (
    ("RC001", "file does not parse"),
    ("RC002", "suppression without a reason"),
    ("RC003", "malformed # rng-lineage: annotation"),
    ("RC510", "stream digest differs from the committed manifest"),
    ("RC511", "program has no committed stream manifest"),
    ("RC512", "runtime witness recorded a key consumed twice"),
)


# ---------------------------------------------------------------------
# static pass
# ---------------------------------------------------------------------


def _read_sources(targets: Sequence[str]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for path in iter_python_files(targets):
        try:
            with open(path, encoding="utf-8") as f:
                out[path] = f.read()
        except OSError:
            out[path] = ""
    return out


def rngcheck_paths(targets: Sequence[str],
                   tests: Optional[Sequence[str]] = None
                   ) -> List[Finding]:
    """Static pass: full RC rule pack over ``targets`` (one program
    graph spanning all of them), plus the RC508 guard rule over
    ``tests`` (test files get only the rules that are *about* tests —
    running the linear pack there would police fixture code that
    intentionally abuses keys)."""
    sources = _read_sources(targets)
    graph = rngflow.build_program_graph(sources)
    rules = make_rc_rules(graph)
    findings: List[Finding] = []
    for path in sorted(sources):
        findings.extend(lint_source(
            path, sources[path], rules, tool=TOOL,
            parse_rule=PARSE_RULE, reasonless_rule=REASONLESS_RULE))
    if tests:
        test_rules = (RcThreefryGuardRule(),)
        for path, source in sorted(_read_sources(tests).items()):
            findings.extend(lint_source(
                path, source, test_rules, tool=TOOL,
                parse_rule=PARSE_RULE,
                reasonless_rule=REASONLESS_RULE))
    return findings


# ---------------------------------------------------------------------
# stream registry + manifests
# ---------------------------------------------------------------------


# The shared manifest contract (envelope validation, key-scoped
# reason-mandatory suppressions, suppression-preserving --update) lives
# in analysis/manifests.py; the dataclass is re-exported so callers
# keep constructing ``rngcheck.Suppression``.
Suppression = manifests_lib.Suppression


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """One registered RNG stream: a builder that traces (or runs) the
    real program under the witness and returns the ordered events."""

    name: str
    description: str
    build: Callable[[], List[str]]
    tier1: bool = False


def _witnessed_lower(lower: Callable[[], object]) -> List[str]:
    """Install the witness, trace, uninstall, return the events.  A
    key consumed twice during the trace raises — a linearity bug in a
    *production* program must never be pinned into a manifest."""
    w, uninstall = rngflow.install_rng_witness()
    try:
        lower()
    finally:
        uninstall()
    w.check()
    return list(w.events)


def build_train_step_events() -> List[str]:
    import jax
    import jax.numpy as jnp

    from diff3d_tpu.analysis import shardcheck
    from diff3d_tpu.models import build_model
    from diff3d_tpu.train import make_train_step

    cfg = shardcheck._train_cfg()
    env = shardcheck._fsdp_mesh()
    model = build_model(cfg)
    state = shardcheck._abstract_state(model, cfg)
    batch = shardcheck._abstract_batch(cfg)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = make_train_step(model, cfg, env, donate=False)
    return _witnessed_lower(lambda: step.lower(state, batch, rng))


def build_distill_step_events() -> List[str]:
    import jax
    import jax.numpy as jnp

    from diff3d_tpu.analysis import shardcheck
    from diff3d_tpu.models import build_model
    from diff3d_tpu.train.distill import make_distill_step

    cfg = shardcheck._train_cfg()
    env = shardcheck._fsdp_mesh()
    model = build_model(cfg)
    state = shardcheck._abstract_state(model, cfg)
    batch = shardcheck._abstract_batch(cfg)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    k = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_distill_step(model, cfg, env, donate=False)
    return _witnessed_lower(
        lambda: step.lower(state, state.params, batch, rng, k))


def build_step_many_events() -> List[str]:
    from diff3d_tpu.analysis import shardcheck

    sampler, _env = shardcheck._sampler()
    return _witnessed_lower(
        lambda: sampler.lower_step_many(lanes=shardcheck.MESH_DEVICES,
                                        capacity=4))


def build_step_many_pallas_events() -> List[str]:
    from diff3d_tpu.analysis import shardcheck

    sampler, _env = shardcheck._sampler(kernels="pallas")
    return _witnessed_lower(
        lambda: sampler.lower_step_many(lanes=shardcheck.MESH_DEVICES,
                                        capacity=4))


def build_step_many_ddim_events() -> List[str]:
    from diff3d_tpu.analysis import shardcheck

    sampler, _env = shardcheck._sampler(sampler_kind="ddim", steps=2)
    return _witnessed_lower(
        lambda: sampler.lower_step_many(lanes=shardcheck.MESH_DEVICES,
                                        capacity=4))


def build_step_many_cascade_draft_events() -> List[str]:
    from diff3d_tpu.analysis import shardcheck

    cascade, _env = shardcheck._cascade()
    return _witnessed_lower(
        lambda: cascade.draft.lower_step_many(
            lanes=shardcheck.MESH_DEVICES, capacity=4))


def build_step_many_cascade_refine_events() -> List[str]:
    from diff3d_tpu.analysis import shardcheck

    cascade, _env = shardcheck._cascade()
    return _witnessed_lower(
        lambda: cascade.refine.lower_step_many(
            lanes=shardcheck.MESH_DEVICES, capacity=4))


def build_loader_events() -> List[str]:
    return rngflow.loader_stream_events()


STREAM_REGISTRY: Dict[str, StreamSpec] = {
    spec.name: spec for spec in (
        StreamSpec(
            "train_step",
            "key-derivation stream of the mesh-sharded train step "
            "(fold_in(step) -> dropout/p_losses splits)",
            build_train_step_events, tier1=True),
        StreamSpec(
            "step_many",
            "sampler step_many ancestral stream (per-view split "
            "schedule through the scan)",
            build_step_many_events, tier1=True),
        StreamSpec(
            "loader",
            "InfiniteLoader SeedSequence spawn tree: global batch as "
            "a pure function of (seed, step, slot), both sample modes",
            build_loader_events, tier1=True),
        StreamSpec(
            "step_many_pallas",
            "sampler step_many with fused GroupNorm Pallas kernels — "
            "the kernels consume no keys, so this stream must be "
            "byte-identical to step_many's",
            build_step_many_pallas_events),
        StreamSpec(
            "distill_step",
            "progressive-distillation step: teacher/student stream "
            "split off one folded key",
            build_distill_step_events),
        StreamSpec(
            "step_many_ddim",
            "sampler step_many deterministic-DDIM stream (noise keys "
            "derived but unconsumed by design)",
            build_step_many_ddim_events),
        StreamSpec(
            "step_many_cascade_draft",
            "cascade draft phase stream: the few-step student at the "
            "draft resolution (its own split of the parent key)",
            build_step_many_cascade_draft_events, tier1=True),
        StreamSpec(
            "step_many_cascade_refine",
            "cascade refine phase stream: start_t-truncated scan — the "
            "init-noise key is always drawn (renoising the draft), so "
            "the stream matches the untruncated sampler's exactly",
            build_step_many_cascade_refine_events, tier1=True),
    )
}

TIER1_STREAMS = tuple(s.name for s in STREAM_REGISTRY.values()
                      if s.tier1)

#: In-process events cache, keyed by (name, builder) so a test that
#: monkeypatches a STREAM_REGISTRY entry's ``build`` never sees a
#: stale cached stream (same convention as shardcheck's report cache).
_EVENTS_CACHE: Dict[tuple, List[str]] = {}


def build_events(name: str) -> List[str]:
    spec = STREAM_REGISTRY[name]
    key = (name, spec.build)
    events = _EVENTS_CACHE.get(key)
    if events is None:
        events = _EVENTS_CACHE[key] = spec.build()
    return list(events)


def manifest_path(program: str, manifest_dir: str) -> str:
    return os.path.join(manifest_dir, f"{program}.json")


def stream_manifest(program: str, events: Sequence[str],
                    suppressions: Sequence[Suppression] = ()) -> dict:
    digest = rngflow.stream_digest(events)
    return {
        "version": MANIFEST_VERSION,
        "tool": TOOL,
        "program": program,
        "budgets": {"digest": digest, "n_events": len(events)},
        "observed": {"digest": digest, "events": list(events)},
        "suppressions": [dataclasses.asdict(s) for s in suppressions],
    }


def load_stream_manifest(path: str) -> dict:
    return manifests_lib.load_manifest_data(
        path, TOOL, MANIFEST_VERSION, "rngcheck stream manifest")


def write_stream_manifest(path: str, manifest: dict) -> None:
    manifests_lib.write_manifest_data(path, manifest)


def _manifest_suppressions(data: dict) -> List[Suppression]:
    return manifests_lib.parse_suppressions(data.get("suppressions", []))


def _stream_finding(program: str, rule: str, key: str,
                    message: str, path: str = "",
                    severity: str = SEVERITY_ERROR) -> Finding:
    return Finding(
        path=path or f"<{TOOL}:{program}>", rule=rule, line=0, col=0,
        severity=severity, message=message,
        fingerprint_data=f"{program}\x00{rule}\x00{key}")


def _apply_stream_suppressions(
        findings: List[Finding], supps: Sequence[Suppression],
        program: str, path: str) -> List[Finding]:
    return manifests_lib.apply_suppressions(
        findings, supps,
        lambda s: _stream_finding(
            program, REASONLESS_RULE, f"{s.rule}:{s.key}",
            f"manifest suppression of {s.rule} (key "
            f"'{s.key}') has no reason — suppressions are "
            "reviewed policy, write why it is safe",
            path=path, severity=SEVERITY_WARNING))


def _first_divergence(committed: Sequence[str],
                      observed: Sequence[str]) -> str:
    for i, (a, b) in enumerate(zip(committed, observed)):
        if a != b:
            return (f"first divergence at event {i}: committed "
                    f"{a!r}, observed {b!r}")
    n, m = len(committed), len(observed)
    if n == m:
        return "event lists equal but digests differ (corrupt manifest?)"
    short, longer = (committed, observed) if n < m else (observed,
                                                         committed)
    extra = longer[len(short)]
    side = "observed" if m > n else "committed"
    return (f"streams agree for {len(short)} event(s), then the "
            f"{side} side continues with {extra!r}")


def check_streams(names: Sequence[str],
                  manifest_dir: str) -> List[Finding]:
    """Build each named stream and diff it against the committed
    manifest.  Returns ALL findings (suppressed marked)."""
    findings: List[Finding] = []
    for nm in names:
        path = manifest_path(nm, manifest_dir)
        try:
            events = build_events(nm)
            witness_violations: List[str] = []
        except rngflow.RngWitnessViolation as e:
            events = None
            witness_violations = [str(e)]
        per: List[Finding] = []
        supps: List[Suppression] = []
        for v in witness_violations:
            per.append(_stream_finding(
                nm, "RC512", "witness",
                f"program '{nm}': {v}", path=path))
        if not os.path.exists(path):
            per.append(_stream_finding(
                nm, "RC511", "manifest",
                f"program '{nm}' has no committed stream manifest — "
                f"run `rngcheck --update --program {nm}` and commit "
                f"{path}", path=path))
            findings.extend(per)
            continue
        try:
            data = load_stream_manifest(path)
            supps = _manifest_suppressions(data)
        except (ValueError, json.JSONDecodeError) as e:
            per.append(_stream_finding(
                nm, "RC511", "manifest",
                f"unreadable stream manifest: {e}", path=path))
            findings.extend(
                _apply_stream_suppressions(per, supps, nm, path))
            continue
        if events is not None:
            committed = data.get("budgets", {}).get("digest")
            committed_events = data.get("observed", {}).get(
                "events", [])
            observed = rngflow.stream_digest(events)
            if observed != committed:
                per.append(_stream_finding(
                    nm, "RC510", "stream",
                    f"program '{nm}' RNG stream drifted: committed "
                    f"digest {str(committed)[:12]}…, observed "
                    f"{observed[:12]}… over {len(events)} event(s) "
                    f"({_first_divergence(committed_events, events)})"
                    f" — if intentional, re-pin with `rngcheck "
                    f"--update --program {nm}`", path=path))
        findings.extend(
            _apply_stream_suppressions(per, supps, nm, path))
    return findings


def update_stream_manifests(names: Sequence[str],
                            manifest_dir: str) -> List[str]:
    """Re-pin each named stream manifest, PRESERVING committed
    suppressions (they are reviewed policy, not observations)."""
    written = []
    for nm in names:
        path = manifest_path(nm, manifest_dir)
        supps = manifests_lib.carry_suppressions(
            path, load_stream_manifest)
        write_stream_manifest(
            path, stream_manifest(nm, build_events(nm), supps))
        written.append(path)
    return written


def default_manifest_dir(root: Optional[str] = None) -> str:
    if root is None:
        root = _find_root(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    return os.path.join(root, DEFAULT_MANIFEST_DIR)


# ---------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="rngcheck",
        description="interprocedural RNG-lineage & precision-flow "
                    "analyzer (rules RC5xx + stream manifests; see "
                    "docs/DESIGN.md §17)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs for the static pass (default: "
                        "diff3d_tpu, tools under the repo "
                        "root, plus tests/ for the RC508 guard rule)")
    p.add_argument("--ast-only", action="store_true",
                   help="static rules only (no stream builds, no jax)")
    p.add_argument("--streams-only", action="store_true",
                   help="stream-manifest checks only")
    p.add_argument("--program", action="append", default=None,
                   choices=sorted(STREAM_REGISTRY), dest="programs",
                   help="check one stream (repeatable; default: all)")
    p.add_argument("--streams-tier1", action="store_true",
                   help=f"limit streams to the tier-1 set "
                        f"{TIER1_STREAMS}")
    p.add_argument("--manifest-dir", default=None,
                   help="stream-manifest directory (default <root>/"
                        f"{DEFAULT_MANIFEST_DIR})")
    p.add_argument("--update", action="store_true",
                   help="re-pin stream manifests from the observed "
                        "streams (keeps suppressions) and exit 0")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default <root>/"
                        f"{DEFAULT_BASELINE} when present)")
    p.add_argument("--update-baseline", action="store_true")
    p.add_argument("--format", choices=("text", "json"),
                   default="text")
    p.add_argument("--show-suppressed", action="store_true")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--list-streams", action="store_true")
    args = p.parse_args(argv)

    if args.list_rules:
        for rule in make_rc_rules():
            print(f"{rule.id}  {rule.name:28s} [{rule.severity}] "
                  f"{rule.description}")
        for rid, desc in _RULE_DOCS:
            print(f"{rid}  {'(engine/manifest)':28s} [-] {desc}")
        return 0
    if args.list_streams:
        for spec in STREAM_REGISTRY.values():
            tag = " [tier1]" if spec.tier1 else ""
            print(f"{spec.name:16s} {spec.description}{tag}")
        return 0
    if args.ast_only and (args.streams_only or args.update):
        print("rngcheck: --ast-only excludes --streams-only/--update",
              file=sys.stderr)
        return 2
    if args.programs and args.streams_tier1:
        print("rngcheck: --program and --streams-tier1 are exclusive",
              file=sys.stderr)
        return 2

    root = _find_root(os.getcwd())
    manifest_dir = args.manifest_dir or default_manifest_dir(root)
    stream_names = (args.programs
                    or (list(TIER1_STREAMS) if args.streams_tier1
                        else sorted(STREAM_REGISTRY)))

    findings: List[Finding] = []
    if not args.streams_only and not args.update:
        if args.paths:
            targets, tests = list(args.paths), []
        else:
            targets = [os.path.join(root, t) for t in DEFAULT_TARGETS]
            targets = [t for t in targets if os.path.exists(t)]
            tests_dir = os.path.join(root, "tests")
            tests = [tests_dir] if os.path.isdir(tests_dir) else []
            if not targets:
                print(f"rngcheck: no default targets under {root}",
                      file=sys.stderr)
                return 2
        findings.extend(rngcheck_paths(targets, tests))

    if not args.ast_only:
        # Stream builds trace real programs over the 8-device CPU mesh.
        from diff3d_tpu.analysis.shardcheck import ensure_cpu_mesh_devices

        if any(nm != "loader" for nm in stream_names):
            ensure_cpu_mesh_devices()
        if args.update:
            for path in update_stream_manifests(stream_names,
                                                manifest_dir):
                print(f"rngcheck: wrote {path}")
            return 0
        findings.extend(check_streams(stream_names, manifest_dir))

    baseline_path = args.baseline or os.path.join(root,
                                                  DEFAULT_BASELINE)
    if args.update_baseline:
        n = write_baseline(baseline_path, findings, root, tool=TOOL)
        print(f"rngcheck: baseline written to {baseline_path} "
              f"({n} entries)")
        return 0
    try:
        baseline = load_baseline(baseline_path)
    except (ValueError, json.JSONDecodeError) as e:
        print(f"rngcheck: {e}", file=sys.stderr)
        return 2
    findings = apply_baseline(findings, baseline, root)

    live = [f for f in findings if not f.suppressed]
    suppressed = [f for f in findings if f.suppressed]
    if args.format == "json":
        print(json.dumps({
            "findings": [dataclasses.asdict(f) for f in findings],
            "unsuppressed": len(live),
            "suppressed": len(suppressed),
        }, indent=1))
    else:
        shown = findings if args.show_suppressed else live
        for f in shown:
            print(f.render())
        print(f"rngcheck: {len(live)} finding(s), "
              f"{len(suppressed)} suppressed")
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
