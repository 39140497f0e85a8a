"""equiv — StableHLO canonicalizer & semantic-equivalence engine.

The sixth analysis pillar's core (docs/DESIGN.md §18).  The five
existing pillars pin *resources* (AST idioms, collectives, locks,
bytes, RNG streams); none can answer the question a deep refactor
raises: **is this compiled program still the same computation?**  This
module answers it structurally, to the extent a text-level analyzer
can, and backs the structural answer with a concrete one:

  * :func:`canonicalize` rewrites a pretty-printed StableHLO module
    into a **canonical form** that is invariant under the transforms a
    semantics-preserving refactor is allowed to make:

      - alpha-renaming — SSA names never appear in the output; values
        are numbered by first definition in a deterministic walk;
      - commutative-operand order — ``add``/``mul``/``min``/``max``/
        bitwise operands are sorted by value hash;
      - identity movement — no-op ``reshape``/``convert``/
        ``broadcast_in_dim`` (operand type == result type) fold away;
      - outlining — ``func.call`` callees are inlined (the same model
        jitted with or without an outlined helper canonicalizes
        identically), reusing :mod:`diff3d_tpu.analysis.mem`'s parser;
      - duplicate subcomputations — value numbering is Merkle-style
        (an op's hash covers its operands' hashes), so a recomputed
        value collapses onto its first definition.

    The sha256 of the canonical lines is the program's **semantic
    fingerprint** — equal fingerprints mean structurally-equal
    computations; a changed fingerprint is a *reviewable diff*, not
    just a hash flip, because the lines are kept.

  * :func:`structural_diff` names the first divergent canonical op
    between two programs, with surrounding context from both sides —
    the EQ601 message body.

  * :func:`verify_hoist` certifies a scan-hoist refactor: every
    non-trivial computation the hoisted program performs outside the
    loop must match (by canonical value hash) an *in-loop ancestor*
    of the original — loop-invariant values hash identically whether
    computed inside or outside the loop, because invariant iterArgs
    resolve to their init hashes — and both callables must agree on
    randomized tiny-shape concrete inputs.  A hoist that reorders
    non-commutative operands loses its ancestor (structural EQ602); a
    hoist that drops a dependency diverges numerically (concrete
    EQ602).

The canonicalizer is an *equivalence estimator*, not a theorem prover:
it never claims two different-looking programs are equal beyond the
rewrites above, and the concrete cross-check is randomized testing,
not exhaustive.  Its job is the contract in ROADMAP item 1: a
conditioning-branch hoist merges EQ-certified or not at all.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

from diff3d_tpu.analysis.lint import Finding, SEVERITY_ERROR
from diff3d_tpu.analysis.mem import (_MOVEMENT_OPS, _TENSOR_RE, _Func,
                                     _Stmt, _stmt_flops, _trip_count,
                                     parse_functions)

#: Elementwise/bitwise ops whose two operands commute — sorted by value
#: hash so ``a*b`` and ``b*a`` canonicalize identically.
_COMMUTATIVE = frozenset({"add", "multiply", "maximum", "minimum",
                          "and", "or", "xor"})
#: Single-operand movement ops folded away when operand type == result
#: type (and, for broadcast_in_dim, the dims are the identity map).
_FOLDABLE = frozenset({"reshape", "convert", "broadcast_in_dim"})

_TOK_RE = re.compile(r"%[\w.]+(?:#\d+)?")
_LHS_RE = re.compile(r"^\s*%[\w.]+(?::\d+)?\s*=\s*")
_NRES_RE = re.compile(r"^\s*%[\w.]+:(\d+)\s*=")
_DIMS_RE = re.compile(r"dims\s*=\s*\[([0-9, ]*)\]")
_WS_RE = re.compile(r"\s+")

#: func.call inlining recursion cap — past this the call stays opaque.
_INLINE_DEPTH = 8


def _h(*parts) -> str:
    return hashlib.sha256(
        "\x1f".join(str(p) for p in parts).encode()).hexdigest()[:16]


def _attr_text(line: str) -> str:
    """A statement line with the lhs assignment removed and every SSA
    token replaced by ``_`` — the name-free attribute/type payload that
    goes into the value hash (literals, dims, enums, signatures)."""
    s = _LHS_RE.sub("", line.strip())
    s = _TOK_RE.sub("_", s)
    return _WS_RE.sub(" ", s).strip()


def _rhs_tokens(line: str) -> List[str]:
    """Operand tokens of a statement line, ``#k`` suffixes intact."""
    return _TOK_RE.findall(_LHS_RE.sub("", line))


def _sig_types(line: str) -> Tuple[List[str], List[str]]:
    """``(operand_types, result_types)`` from the trailing signature;
    the single-type shorthand (``: tensor<f32>``) yields both equal."""
    if "->" in line:
        head, tail = line.rsplit("->", 1)
        ins = (_TENSOR_RE.findall(head.rsplit(":", 1)[-1])
               if ":" in head else [])
        return ins, _TENSOR_RE.findall(tail)
    if ":" in line:
        t = _TENSOR_RE.findall(line.rsplit(":", 1)[-1])
        return t, t
    return [], []


def _is_identity(st: _Stmt) -> bool:
    if st.op not in _FOLDABLE:
        return False
    ins, outs = _sig_types(st.line)
    if not (len(ins) == 1 and ins == outs):
        return False
    if st.op == "broadcast_in_dim":
        m = _DIMS_RE.search(st.line)
        if not m:
            return False
        dims = [int(x) for x in m.group(1).replace(" ", "").split(",")
                if x]
        rank = len(ins[0].replace(" ", "").split("x")) - 1
        return dims == list(range(rank))
    return True


# -- report dataclasses ------------------------------------------------


@dataclasses.dataclass
class WhileLoopInfo:
    """One ``stablehlo.while`` in the canonical walk (depth 0 = a
    direct loop of ``@main``, i.e. a ``lax.scan``)."""

    index: int
    depth: int
    trip_count: Optional[int]
    body_ops: int                  # statements processed (calls inlined)
    invariant_ops: int
    invariant_flops: float         # per iteration — the hoistable number
    total_flops: float

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DeadOp:
    """A computed (non-movement, flops>0) value unreachable from the
    program's outputs — compute XLA will DCE but the traced program
    asked for (EQ603)."""

    op: str
    canonical: str
    flops: float

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DuplicateGroup:
    """One value computed by more than one statement (same canonical
    value hash) — the static CSE-duplicate precursor of memcheck's
    MC404 recompute rule (EQ604)."""

    op: str
    count: int
    flops_each: float
    redundant_flops: float         # (count - 1) * flops_each
    canonical: str                 # the canonical line of the value

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class SemanticReport:
    """Everything equivcheck knows about one lowered program."""

    name: str
    available: bool = True
    digest: str = ""
    n_ops: int = 0                 # emitted canonical ops
    lines: List[str] = dataclasses.field(default_factory=list)
    while_loops: List[WhileLoopInfo] = dataclasses.field(
        default_factory=list)
    dead_ops: List[DeadOp] = dataclasses.field(default_factory=list)
    duplicates: List[DuplicateGroup] = dataclasses.field(
        default_factory=list)
    error: Optional[str] = None
    #: value hash -> canonical line, for ops a hoist may legally move
    #: out of a loop: everything already outside plus loop-invariant
    #: body ops (hashed loop-insensitively).  Verifier-facing; not
    #: serialized.
    ancestor_hashes: Dict[str, str] = dataclasses.field(
        default_factory=dict, repr=False)
    #: value hash -> canonical line of non-movement ops outside every
    #: loop (the hoisted side's obligation list).  Not serialized.
    outside_hashes: Dict[str, str] = dataclasses.field(
        default_factory=dict, repr=False)

    @property
    def cse_duplicate_flops(self) -> float:
        return sum(g.redundant_flops for g in self.duplicates)

    @property
    def hoistable_flops_per_step(self) -> float:
        """Loop-invariant FLOPs re-executed per scan iteration, summed
        over ``@main``'s direct loops — the number that must agree
        (within estimator slack) with memcheck's MC404 pin."""
        return sum(w.invariant_flops for w in self.while_loops
                   if w.depth == 0)

    @property
    def duplicate_flops(self) -> float:
        """Total statically-detectable redundant compute: CSE
        duplicates plus loop-invariant recompute across iterations
        (``invariant_flops * (trip - 1)`` per loop)."""
        loop = sum(w.invariant_flops * (max(w.trip_count or 1, 1) - 1)
                   for w in self.while_loops if w.depth == 0)
        return self.cse_duplicate_flops + loop

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "available": self.available,
            "digest": self.digest,
            "n_ops": self.n_ops,
            "n_lines": len(self.lines),
            "lines": list(self.lines),
            "while_loops": [w.to_json() for w in self.while_loops],
            "dead_ops": [d.to_json() for d in self.dead_ops],
            "duplicates": [g.to_json() for g in self.duplicates],
            "cse_duplicate_flops": self.cse_duplicate_flops,
            "hoistable_flops_per_step": self.hoistable_flops_per_step,
            "duplicate_flops": self.duplicate_flops,
            "error": self.error,
        }


# -- the canonicalizer -------------------------------------------------


class _Canonicalizer:
    """One canonicalization pass over a parsed module.  Hashing and
    emission are a single recursive walk; while-loop invariance is a
    small fixpoint of hash-only walks before the body's emit walk."""

    def __init__(self, functions: Dict[str, _Func]):
        self.functions = functions
        self.lines: List[str] = []
        self.ids: Dict[str, str] = {}        # value hash -> canonical id
        self.records: List[dict] = []        # emit-walk op records
        self.opaque: Dict[str, str] = {}     # unresolved token -> hash
        self.while_infos: List[WhileLoopInfo] = []
        self.n_ops = 0
        self._next_id = 0

    # - small helpers -

    def _define(self, h: str) -> str:
        cid = self.ids.get(h)
        if cid is None:
            cid = f"%v{self._next_id}"
            self._next_id += 1
            self.ids[h] = cid
        return cid

    def _show(self, h: str) -> str:
        return self.ids.get(h, f"%?{h[:8]}")

    def _resolve(self, tok: str, env: Dict[str, str]) -> str:
        got = env.get(tok)
        if got is None and "#" in tok:
            got = env.get(tok.split("#")[0])
        if got is None:
            # Parser gap (an op form we never emit in practice): a
            # stable opaque value, keyed by first-encounter order so
            # renaming alone cannot change it.
            got = self.opaque.get(tok)
            if got is None:
                got = self.opaque[tok] = _h("opaque", len(self.opaque))
        return got

    def _emit(self, text: str, indent: int) -> None:
        self.lines.append("  " * indent + text)

    # - the walk -

    def walk(self, stmts: List[_Stmt], env: Dict[str, str],
             variant: set, emit: bool, indent: int, depth: int,
             call_depth: int, records: Optional[List[dict]],
             flops_out: Optional[dict]) -> None:
        """Process a statement region.  ``env`` maps raw SSA tokens to
        value hashes (mutated); ``variant`` is the set of loop-variant
        hashes (mutated); ``flops_out`` accumulates the enclosing while
        body's totals; ``records`` collects liveness/duplicate records
        when emitting."""
        for st in stmts:
            if st.op == "while":
                self._while(st, env, variant, emit, indent, depth,
                            call_depth, records, flops_out)
                continue
            if st.op in ("func.call", "call") and st.callee:
                fn = self.functions.get(st.callee)
                if fn is not None and call_depth < _INLINE_DEPTH:
                    self._inline(st, fn, env, variant, emit, indent,
                                 depth, call_depth, records, flops_out)
                    continue
            operands = _rhs_tokens(st.line)
            attr = _attr_text(st.line)
            # Anonymous-region ops (scatter/sort reducers): the block
            # body is part of the op's semantics — fold it into the
            # attribute text so reducer edits move the fingerprint.
            region = getattr(st, "region_lines", None)
            if region:
                attr += " region=" + _h(*[_attr_text(l) for l in region])
            opnd_h = [self._resolve(t, env) for t in operands]
            if _is_identity(st) and opnd_h:
                # Fold: the statement defines nothing new.
                if st.lhs:
                    env[st.lhs] = opnd_h[0]
                continue
            if st.op in _COMMUTATIVE and len(opnd_h) == 2:
                opnd_h = sorted(opnd_h)
            # Multi-result assignments print as ``%N:k = ...`` in MLIR;
            # a bare lhs is single-result regardless of how many types
            # the shorthand signature lists (e.g. select's pred type).
            m = _NRES_RE.match(st.line)
            n_res = int(m.group(1)) if m else 1
            res_h = [_h(st.op, attr, *opnd_h) if n_res == 1
                     else _h(st.op, attr, j, *opnd_h)
                     for j in range(n_res)]
            is_variant = any(h in variant for h in opnd_h)
            if st.lhs:
                for j, h in enumerate(res_h):
                    env[f"{st.lhs}#{j}"] = h
                if res_h:
                    env[st.lhs] = res_h[0]
            if is_variant:
                variant.update(res_h)
            flops = _stmt_flops(st)
            movement = st.op in _MOVEMENT_OPS
            if flops_out is not None:
                flops_out["body_ops"] += 1
                flops_out["total_flops"] += flops
                if not is_variant:
                    flops_out["invariant_ops"] += 1
                    flops_out["invariant_flops"] += flops
            if not emit:
                continue
            known = all(h in self.ids for h in res_h)
            line_text = None
            if not known:
                ids = [self._define(h) for h in res_h]
                shown = [self._show(h) for h in opnd_h]
                line_text = (f"{', '.join(ids)} = {st.op}"
                             f"{' ' + ', '.join(shown) if shown else ''}"
                             f" ; {attr}")
                self._emit(line_text, indent)
                self.n_ops += 1
            if records is not None:
                records.append({
                    "op": st.op, "results": res_h, "operands": opnd_h,
                    "flops": flops, "movement": movement,
                    "outside": depth == 0,
                    "invariant": not is_variant,
                    "canonical": line_text, "body": None})

    def _while(self, st: _Stmt, env, variant, emit, indent, depth,
               call_depth, records, flops_out) -> None:
        iter_args = list(getattr(st, "iter_args", []))
        body_ret = list(getattr(st, "body_ret_full",
                                getattr(st, "body_ret", [])))
        body = st.body or []
        attr = _attr_text(st.line)
        inits = [t for t in _rhs_tokens(st.line)
                 if not t.startswith("%iterArg")]
        k = min(len(iter_args), len(inits))
        init_h = [self._resolve(inits[j], env) for j in range(k)]
        trip = _trip_count(st)
        cond_digest = _h(*[_attr_text(l) for l in
                           getattr(st, "cond_lines", [])])

        # Fixpoint: optimistically bind every iterArg to its init hash
        # (invariant); demote any carry position whose body return
        # does not hash back to its binding.  Demotion is monotone, so
        # this converges in <= k+1 hash-only walks.
        invariant = [True] * k
        ret_h: List[str] = []
        for _ in range(k + 1):
            benv = dict(env)
            bvar = set(variant)
            for j in range(k):
                if invariant[j]:
                    benv[iter_args[j]] = init_h[j]
                else:
                    ih = _h("iterarg", j, attr, cond_digest, *init_h)
                    benv[iter_args[j]] = ih
                    bvar.add(ih)
            self.walk(body, benv, bvar, emit=False, indent=0,
                      depth=depth + 1, call_depth=call_depth,
                      records=None, flops_out=None)
            ret_h = [self._resolve(t, benv)
                     for t in body_ret[:k]] + [""] * (k - len(body_ret))
            new_inv = [invariant[j] and ret_h[j] == benv[iter_args[j]]
                       for j in range(k)]
            if new_inv == invariant:
                break
            invariant = new_inv

        # Result hashes: an invariant carry's result IS its init value;
        # a variant result hashes the loop structure.
        res_h = [init_h[j] if invariant[j]
                 else _h("while", j, attr, trip, cond_digest,
                         *(init_h + ret_h))
                 for j in range(k)]
        if st.lhs:
            for j, h in enumerate(res_h):
                env[f"{st.lhs}#{j}"] = h
            if res_h:
                env[st.lhs] = res_h[0]
        if any(h in variant for h in init_h):
            variant.update(res_h)

        if not emit:
            return

        # Final walk, emitting the body region.
        stats = {"body_ops": 0, "invariant_ops": 0,
                 "invariant_flops": 0.0, "total_flops": 0.0}
        res_ids = [self._define(h) for h in res_h]
        self._emit(f"{', '.join(res_ids)} = while "
                   f"{', '.join(self._show(h) for h in init_h)} ; "
                   f"trip={trip} cond={cond_digest[:8]}", indent)
        self.n_ops += 1
        benv = dict(env)
        bvar = set(variant)
        body_records: List[dict] = []
        for j in range(k):
            if invariant[j]:
                benv[iter_args[j]] = init_h[j]
            else:
                ih = _h("iterarg", j, attr, cond_digest, *init_h)
                benv[iter_args[j]] = ih
                bvar.add(ih)
                self._emit(f"{self._define(ih)} = iterarg {j}",
                           indent + 1)
        self.walk(body, benv, bvar, emit=True, indent=indent + 1,
                  depth=depth + 1, call_depth=call_depth,
                  records=body_records, flops_out=stats)
        final_ret = [self._resolve(t, benv) for t in body_ret[:k]]
        self._emit("yield " + ", ".join(self._show(h)
                                        for h in final_ret), indent + 1)
        self.while_infos.append(WhileLoopInfo(
            index=len(self.while_infos), depth=depth, trip_count=trip,
            body_ops=stats["body_ops"],
            invariant_ops=stats["invariant_ops"],
            invariant_flops=stats["invariant_flops"],
            total_flops=stats["total_flops"]))
        if records is not None:
            records.append({
                "op": "while", "results": res_h, "operands": init_h,
                "flops": 0.0, "movement": False, "outside": depth == 0,
                "invariant": not any(h in variant for h in init_h),
                "canonical": None, "body": body_records,
                "body_roots": final_ret})

    def _inline(self, st: _Stmt, fn: _Func, env, variant, emit, indent,
                depth, call_depth, records, flops_out) -> None:
        operands = [t for t in _rhs_tokens(st.line)]
        fenv: Dict[str, str] = {}
        for j, a in enumerate(fn.args):
            fenv[a] = (self._resolve(operands[j], env)
                       if j < len(operands)
                       else _h("missing-arg", fn.name, j))
        self.walk(fn.stmts, fenv, variant, emit=emit, indent=indent,
                  depth=depth, call_depth=call_depth + 1,
                  records=records, flops_out=flops_out)
        rets = fn.ret_full or fn.ret
        res_h = [self._resolve(t, fenv) for t in rets]
        if st.lhs:
            for j, h in enumerate(res_h):
                env[f"{st.lhs}#{j}"] = h
            if res_h:
                env[st.lhs] = res_h[0]


def _collect_live(records: List[dict], roots: set) -> set:
    """Backward liveness over emit-walk records (regions recursed at
    their position in the reversed scan)."""
    live = set(roots)
    for rec in reversed(records):
        if any(h in live for h in rec["results"]):
            live.update(rec["operands"])
            if rec["body"] is not None:
                live.update(rec.get("body_roots", []))
                live |= _collect_live(rec["body"],
                                      set(rec.get("body_roots", []))
                                      | live)
    return live


def _iter_records(records: List[dict]):
    for rec in records:
        yield rec
        if rec["body"] is not None:
            yield from _iter_records(rec["body"])


def canonicalize(name: str, stablehlo_text: str,
                 entry: str = "main") -> SemanticReport:
    """Canonicalize one pretty-printed StableHLO module (see module
    docstring for the invariances) and derive the semantic report."""
    functions = parse_functions(stablehlo_text)
    fn = functions.get(entry)
    if fn is None and functions:
        fn = next(iter(functions.values()))
    if fn is None:
        raise ValueError(f"{name}: no parseable func.func in module")

    canon = _Canonicalizer(functions)
    env: Dict[str, str] = {}
    # Argument types from the signature line make signature changes
    # part of the fingerprint.
    sig_line = next((l for l in stablehlo_text.splitlines()
                     if f"@{fn.name}(" in l or f'@"{fn.name}"(' in l),
                    "")
    arg_types = _TENSOR_RE.findall(sig_line)
    for j, a in enumerate(fn.args):
        h = _h("arg", j)
        env[a] = h
        t = f" ; tensor<{arg_types[j]}>" if j < len(arg_types) else ""
        canon.ids[h] = f"%a{j}"
        canon.lines.append(f"%a{j} = arg {j}{t}")
    records: List[dict] = []
    canon.walk(fn.stmts, env, variant=set(), emit=True, indent=0,
               depth=0, call_depth=0, records=records, flops_out=None)
    rets = fn.ret_full or fn.ret
    ret_h = [canon._resolve(t, env) for t in rets]
    canon.lines.append("return " + ", ".join(canon._show(h)
                                             for h in ret_h))

    live = _collect_live(records, set(ret_h))
    dead: List[DeadOp] = []
    groups: Dict[str, List[dict]] = {}
    for rec in _iter_records(records):
        if rec["movement"] or rec["op"] == "while" or rec["flops"] <= 0:
            continue
        if not any(h in live for h in rec["results"]):
            dead.append(DeadOp(
                op=rec["op"], flops=rec["flops"],
                canonical=(rec["canonical"] or rec["op"])[:200]))
        groups.setdefault(rec["results"][0], []).append(rec)
    dups = [DuplicateGroup(
                op=recs[0]["op"], count=len(recs),
                flops_each=recs[0]["flops"],
                redundant_flops=(len(recs) - 1) * recs[0]["flops"],
                canonical=next((r["canonical"] for r in recs
                                if r["canonical"]), recs[0]["op"])[:200])
            for recs in groups.values() if len(recs) > 1]
    dups.sort(key=lambda g: -g.redundant_flops)

    outside: Dict[str, str] = {}
    ancestors: Dict[str, str] = {}
    for rec in _iter_records(records):
        if rec["movement"] or rec["op"] == "while" or rec["flops"] <= 0:
            continue
        line = (rec["canonical"]
                or canon.ids.get(rec["results"][0], rec["op"]))
        if rec["outside"]:
            outside[rec["results"][0]] = line
            ancestors[rec["results"][0]] = line
        elif rec["invariant"]:
            ancestors[rec["results"][0]] = line

    digest = hashlib.sha256(
        "\n".join(canon.lines).encode()).hexdigest()
    return SemanticReport(
        name=name, available=True, digest=digest, n_ops=canon.n_ops,
        lines=list(canon.lines), while_loops=canon.while_infos,
        dead_ops=dead, duplicates=dups,
        ancestor_hashes=ancestors, outside_hashes=outside)


def build_semantic_report(name: str,
                          stablehlo_text: str) -> SemanticReport:
    """Tolerant entry point: an analyzer failure yields an
    ``available=False`` report, never an exception (this rides every
    ``ir.analyze_lowered`` pass)."""
    try:
        return canonicalize(name, stablehlo_text)
    except Exception as e:  # estimator, not a verifier
        return SemanticReport(name=name, available=False,
                              error=f"{type(e).__name__}: {e}")


# -- the structural differ ---------------------------------------------


def structural_diff(committed: Sequence[str], observed: Sequence[str],
                    context: int = 2) -> Optional[str]:
    """Name the first divergent canonical op between two programs,
    with each side's surrounding lines — the EQ601 message body.
    Returns None when the line lists are identical."""
    committed = list(committed)
    observed = list(observed)
    if committed == observed:
        return None

    def window(lines: Sequence[str], i: int) -> str:
        lo, hi = max(0, i - context), min(len(lines), i + context + 1)
        return " | ".join(f"{k}: {lines[k].strip()}"
                          for k in range(lo, hi))

    n = min(len(committed), len(observed))
    for i in range(n):
        if committed[i] != observed[i]:
            return (f"first divergent op at canonical line {i}: "
                    f"committed {committed[i].strip()!r} vs observed "
                    f"{observed[i].strip()!r} — committed context "
                    f"[{window(committed, i)}]; observed context "
                    f"[{window(observed, i)}]")
    longer = "observed" if len(observed) > len(committed) else "committed"
    extra = (observed if len(observed) > len(committed)
             else committed)[n]
    return (f"programs agree for {n} canonical line(s), then the "
            f"{longer} side continues with {extra.strip()!r}")


# -- the scan-hoist verifier -------------------------------------------


@dataclasses.dataclass
class HoistVerdict:
    """Result of :func:`verify_hoist`.  ``equivalent`` means every
    hoisted computation matched an in-loop ancestor AND the concrete
    cross-check agreed on every trial."""

    equivalent: bool
    findings: List[Finding]
    matched: int                   # hoisted ops with an ancestor
    unmatched: List[str]           # canonical lines without one
    trials: int
    max_abs_diff: float


def _hoist_finding(name: str, key: str, message: str) -> Finding:
    return Finding(
        path=f"<equivcheck:{name}>", rule="EQ602", line=0, col=0,
        severity=SEVERITY_ERROR, message=message,
        fingerprint_data=f"{name}\x00EQ602\x00{key}")


def _randomized_args(example_args, rng):
    """Fresh concrete inputs with the example's shapes/dtypes: floats
    and complex are redrawn, integers/bools keep the example values
    (they are schedule indices/counters — randomizing them changes
    which program runs, not whether two programs agree)."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree.flatten(example_args)
    out = []
    for leaf in leaves:
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating):
            out.append(rng.standard_normal(a.shape).astype(a.dtype))
        elif np.issubdtype(a.dtype, np.complexfloating):
            out.append((rng.standard_normal(a.shape)
                        + 1j * rng.standard_normal(a.shape)
                        ).astype(a.dtype))
        else:
            out.append(a)
    return jax.tree.unflatten(treedef, out)


def verify_hoist(original, hoisted, example_args, *, name: str = "hoist",
                 seed: int = 0, trials: int = 2, rtol: float = 1e-4,
                 atol: float = 1e-5) -> HoistVerdict:
    """Certify that ``hoisted`` is a semantics-preserving scan-hoist of
    ``original`` (EQ602 on every way it can fail).

    Structural half: lower both on the example shapes; every
    non-trivial computation the hoisted program performs outside its
    loops must hash-match an ancestor in the original (an op already
    outside, or a loop-invariant body op — invariant values hash the
    same in both positions).  Wrong operand order or changed inputs
    lose the ancestor.

    Concrete half: run both callables on ``trials`` randomized
    tiny-shape inputs derived from ``example_args`` and require
    allclose agreement — catches dropped dependencies and anything the
    text-level matcher cannot see.
    """
    import jax
    import numpy as np

    from diff3d_tpu.analysis import ir as ir_lib

    jo = original if hasattr(original, "lower") else jax.jit(original)
    jh = hoisted if hasattr(hoisted, "lower") else jax.jit(hoisted)
    example_args = tuple(example_args)
    abstract = ir_lib.abstractify(example_args)

    findings: List[Finding] = []
    orig = build_semantic_report(
        f"{name}:original", jo.lower(*abstract).as_text())
    hois = build_semantic_report(
        f"{name}:hoisted", jh.lower(*abstract).as_text())
    matched = 0
    unmatched: List[str] = []
    if not (orig.available and hois.available):
        bad = orig if not orig.available else hois
        findings.append(_hoist_finding(
            name, "unanalyzable",
            f"hoist of '{name}' is unverifiable: canonicalization "
            f"failed for {bad.name} ({bad.error})"))
    else:
        for h, line in hois.outside_hashes.items():
            if h in orig.ancestor_hashes:
                matched += 1
            else:
                unmatched.append(line)
                findings.append(_hoist_finding(
                    name, f"ancestor:{h[:12]}",
                    f"hoisted computation `{line.strip()}` has no "
                    f"ancestor in the original program — no op outside "
                    f"the loop and no loop-invariant body op computes "
                    f"this value (operand order or inputs changed)"))

    max_diff = 0.0
    for t in range(trials):
        rng = np.random.default_rng(seed * 1000003 + t)
        args = _randomized_args(example_args, rng)
        out_o = jo(*args)
        out_h = jh(*args)
        lo, to = jax.tree.flatten(out_o)
        lh, th = jax.tree.flatten(out_h)
        if to != th:
            findings.append(_hoist_finding(
                name, f"structure:{t}",
                f"trial {t}: output trees differ ({to} vs {th})"))
            continue
        for i, (a, b) in enumerate(zip(lo, lh)):
            a = np.asarray(a)
            b = np.asarray(b)
            if a.shape != b.shape or a.dtype != b.dtype:
                findings.append(_hoist_finding(
                    name, f"output:{i}",
                    f"trial {t}: output {i} shape/dtype differs "
                    f"({a.shape}/{a.dtype} vs {b.shape}/{b.dtype})"))
                continue
            if np.issubdtype(a.dtype, np.inexact):
                diff = float(np.max(np.abs(
                    a.astype(np.float64) - b.astype(np.float64)))) \
                    if a.size else 0.0
                max_diff = max(max_diff, diff)
                ok = np.allclose(a, b, rtol=rtol, atol=atol)
            else:
                ok = bool(np.array_equal(a, b))
            if not ok:
                findings.append(_hoist_finding(
                    name, f"output:{i}",
                    f"trial {t}: concrete cross-check diverged at "
                    f"output {i} (max |delta| = {max_diff:.3g}, rtol="
                    f"{rtol}, atol={atol}) — the hoisted program is "
                    f"NOT the same computation"))

    return HoistVerdict(
        equivalent=not findings, findings=findings, matched=matched,
        unmatched=unmatched, trials=trials, max_abs_diff=max_diff)


def semantic_summary(report: SemanticReport) -> dict:
    """The compact block ``equivcheck --format json`` prints per
    program."""
    return {
        "available": report.available,
        "digest": report.digest or None,
        "n_ops": report.n_ops,
        "hoistable_flops_per_step": report.hoistable_flops_per_step,
        "duplicate_flops": report.duplicate_flops,
        "dead_ops": len(report.dead_ops),
    }
