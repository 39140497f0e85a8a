"""HLO-level memory & recompute analyzer (memcheck's engine).

shardcheck (``analysis/ir.py``) pins what XLA lowered on the *comms*
axis; this module pins the *memory* axis of the same compiled programs —
the three regressions that silently eat HBM or per-step FLOPs:

  * **peak footprint drift** — the compiled executable's memory analysis
    (argument / output / temp / generated-code bytes, aliased bytes
    counted once) moves because an optimisation boundary shifted, and a
    program that used to fit a replica slice no longer does.  The
    multi-replica router's admission control needs these numbers to be
    *pinned*, not re-measured per deploy.
  * **ineffective donation** — the Python layer requested
    ``donate_argnums`` but the donated buffer was never aliased to an
    output: either jax could not pair it at lowering time (no
    shape/dtype-matching output — the classic silent copy) or XLA
    declined the alias at compile time.  The buffer then lives twice.
  * **scan-invariant recompute** — ops inside a ``lax.scan`` /
    ``stablehlo.while`` body whose inputs never change across
    iterations: they re-run every step for the same answer.  The 3DiM
    sampler's conditioning branch (clean frame + pose rays, constant
    across all 256 denoise steps of a view) is the repo's canonical
    case — this pass turns "we recompute the conditioning" from a hunch
    into a pinned FLOPs/bytes number (hoist-vs-remat tradeoffs in the
    spirit of Chen et al., sublinear-memory training).

Extraction sources, mirroring ir.py's philosophy (parse what the
compiler actually said, not what the Python source hoped):

  * ``lowered.args_info`` — per-flattened-argument *requested* donation
    flags (survives even when lowering dropped the pairing);
  * the lowered StableHLO text — ``tf.aliasing_output`` /
    ``jax.buffer_donor`` arg attributes (what jax established) and the
    ``stablehlo.while`` regions for the loop-invariance dataflow pass;
  * ``compiled.memory_analysis()`` — the executable's byte accounting;
  * the compiled HLO module header's ``input_output_alias`` table —
    what XLA actually aliased.

``analysis/membudgets.py`` diffs :class:`MemoryReport`s against
committed manifests under ``runs/memcheck/`` (rules MC4xx);
``analysis/memcheck.py`` is the CLI over the shardcheck program
registry and prints :func:`memory_summary` blocks under
``--format json``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from diff3d_tpu.analysis.ir import _DTYPE_BYTES

#: Ops that move/reshape bytes without arithmetic — 0 FLOPs.
_MOVEMENT_OPS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "broadcast", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "convert", "iota", "constant", "reverse", "gather", "scatter",
    "bitcast_convert", "get_tuple_element", "tuple", "copy",
    "optimization_barrier", "return", "custom_call", "after_all",
})

_TENSOR_RE = re.compile(r"tensor<([^>]*)>")
_VAR_RE = re.compile(r"%[\w.#]+")
# `%4:3 = stablehlo.while(` / `%8 = stablehlo.add` / `stablehlo.return`
# / generic-syntax region ops like `%88 = "stablehlo.scatter"(...) ({`
_STMT_RE = re.compile(
    r"^\s*(?:(%[\w.]+)(?::(\d+))?\s*=\s*)?"
    r"((?:\"stablehlo\.\w+\")"
    r"|(?:stablehlo\.\w+|func\.call|call|chlo\.\w+|return)\b)(.*)$")
_CALLEE_RE = re.compile(r"@([\w.\"]+)")
_FUNC_RE = re.compile(r"^\s*func\.func\s+(?:public|private)?\s*@([\w.\"]+)"
                      r"\((.*)$")
_CONTRACT_RE = re.compile(r"contracting_dims\s*=\s*\[([0-9, ]*)\]")
_KERNEL_O_RE = re.compile(r"x\[([^\]]*)\]->")
_ALIAS_RE = re.compile(
    r"\{([0-9, ]*)\}:\s*\((\d+),\s*\{[0-9, ]*\},\s*(may-alias|must-alias)\)")
_ALIAS_HEADER_RE = re.compile(r"input_output_alias=\{(.*?)\}(?:, |\n)",
                              re.DOTALL)
_ARG_ATTR_RE = re.compile(
    r"%arg(\d+):\s*tensor<([^>]*)>((?:\s*\{)?)")
_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_SHARDING_DEVICES_RE = re.compile(r"devices=\[([0-9,\s]+)\]")
_LAST_TILE_DIMS_RE = re.compile(r"last_tile_dims=\{([^}]*)\}")


def _tensor_numel_dtype(t: str) -> Tuple[int, str]:
    """``"8x4x8xf32"`` -> (256, "f32"); ``"i32"`` -> (1, "i32")."""
    parts = t.replace(" ", "").split("x")
    dims, dtype = parts[:-1], parts[-1]
    n = 1
    for d in dims:
        if d.isdigit():
            n *= int(d)
    return n, dtype


def _tensor_bytes(t: str) -> int:
    n, dtype = _tensor_numel_dtype(t)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _shard_count(sharding: Optional[str]) -> int:
    """How many shards an ``mhlo.sharding`` annotation splits a tensor
    into — the divisor that turns the global StableHLO tensor size into
    the per-device bytes ``memory_analysis()`` accounts in.

    ``{replicated}`` / ``{maximal device=k}`` / absent -> 1;
    ``{devices=[8,1,1]<=[8]}`` -> 8;
    ``{devices=[2,1,4]<=[8] last_tile_dim_replicate}`` -> 2 (the last
    tile dim replicates across 4 devices, it does not tile);
    ``last_tile_dims={...}`` subgroup dims likewise do not tile.
    """
    if not sharding:
        return 1
    m = _SHARDING_DEVICES_RE.search(sharding)
    if not m:
        return 1
    dims = [int(d) for d in m.group(1).replace(" ", "").split(",") if d]
    lm = _LAST_TILE_DIMS_RE.search(sharding)
    if lm:
        drop = len([e for e in lm.group(1).split(",") if e.strip()])
    elif "last_tile_dim_replicate" in sharding:
        drop = 1
    else:
        drop = 0
    tiles = 1
    for d in (dims[:len(dims) - drop] if drop else dims):
        tiles *= d
    return max(1, tiles)


# -- donation tables ---------------------------------------------------


@dataclasses.dataclass
class DonationEntry:
    """One flattened entry argument's donation story, end to end."""

    arg_index: int
    type: str                 # GLOBAL tensor type text, e.g. "8x4x8x8x3xf32"
    bytes: int                # PER-DEVICE bytes (global size / shard_count)
    #                           — the unit memory_analysis() accounts in
    requested: bool           # Python layer asked (donate_argnums/donor)
    lowered: bool             # jax established an alias / donor mark
    effective: bool           # XLA's compiled module aliases this param
    output_index: Optional[int] = None   # aliased output, when effective
    shard_count: int = 1      # from the arg's mhlo.sharding annotation

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def parse_arg_donations(stablehlo_text: str) -> Dict[int, dict]:
    """Per-arg donation attributes of ``@main``: ``tf.aliasing_output``
    (jax paired the donated arg with an output), ``jax.buffer_donor``
    (donated, pairing left to XLA), and the ``mhlo.sharding`` annotation
    (the tensor type is the GLOBAL shape; the sharding says how many
    devices split it)."""
    m = re.search(r"func\.func\s+public\s+@main\((.*)$",
                  stablehlo_text, re.MULTILINE)
    if not m:
        return {}
    sig = m.group(1)
    out: Dict[int, dict] = {}
    # Split the signature on argument starts; each chunk carries that
    # arg's type and (possibly) attribute dict.
    chunks = re.split(r"%arg(\d+):", sig)[1:]
    for idx_s, body in zip(chunks[0::2], chunks[1::2]):
        idx = int(idx_s)
        tm = _TENSOR_RE.search(body)
        ttype = tm.group(1) if tm else ""
        am = re.search(r"tf\.aliasing_output\s*=\s*(\d+)", body)
        donor = "jax.buffer_donor" in body
        sm = _SHARDING_ATTR_RE.search(body)
        out[idx] = {
            "type": ttype,
            "aliasing_output": int(am.group(1)) if am else None,
            "buffer_donor": donor,
            "sharding": sm.group(1) if sm else None,
        }
    return out


def parse_input_output_aliases(hlo_text: str) -> List[dict]:
    """The compiled module header's ``input_output_alias`` table —
    what XLA *actually* aliased, post-optimisation."""
    header = hlo_text.split("\n\n", 1)[0]
    pos = header.find("input_output_alias=")
    if pos < 0:
        return []
    out = []
    # The alias-entry shape `{o}: (p, {}, may-alias)` is distinctive
    # enough to findall directly; non-greedy brace matching trips over
    # the nested `{}` index field.
    for outidx, param, kind in _ALIAS_RE.findall(header[pos:]):
        first = outidx.split(",")[0].strip()
        out.append({"output_index": int(first) if first else 0,
                    "param": int(param), "kind": kind})
    return out


def donation_table(requested: Sequence[bool],
                   lowered_attrs: Dict[int, dict],
                   aliases: Sequence[dict]) -> List[DonationEntry]:
    """Join the three donation sources into one per-arg table.  Only args
    that were requested OR marked at lowering OR aliased appear."""
    aliased_params = {a["param"]: a for a in aliases}
    indices = sorted(
        set(i for i, r in enumerate(requested) if r)
        | set(i for i, a in lowered_attrs.items()
              if a["aliasing_output"] is not None or a["buffer_donor"])
        | set(aliased_params))
    table = []
    for i in indices:
        attrs = lowered_attrs.get(i, {})
        ttype = attrs.get("type", "")
        alias = aliased_params.get(i)
        # The StableHLO type is the GLOBAL shape; memory_analysis()
        # accounts per-device bytes.  Divide by the arg's shard count so
        # the two live in the same unit (MC402 messages, alias
        # discount) — on the 8-way fsdp mesh the difference is 8x.
        shards = _shard_count(attrs.get("sharding"))
        table.append(DonationEntry(
            arg_index=i,
            type=ttype,
            bytes=_tensor_bytes(ttype) // shards if ttype else 0,
            requested=bool(i < len(requested) and requested[i]),
            lowered=bool(attrs.get("aliasing_output") is not None
                         or attrs.get("buffer_donor")),
            effective=alias is not None,
            output_index=(alias["output_index"]
                          if alias is not None else None),
            shard_count=shards))
    return table


# -- StableHLO statement / function parsing ----------------------------


@dataclasses.dataclass
class _Stmt:
    lhs: Optional[str]            # "%8" (base name, no "#k" suffix)
    op: str                       # "stablehlo.add", "func.call", ...
    operands: List[str]           # RHS %-tokens, "#k" suffixes stripped
    result_types: List[str]       # tensor type texts
    callee: Optional[str]         # for func.call
    line: str
    body: Optional[List["_Stmt"]] = None   # while: the `do` region


@dataclasses.dataclass
class _Func:
    name: str
    args: List[str]               # "%arg0", ...
    stmts: List[_Stmt]
    ret: List[str]                # returned value tokens (base names)
    #: returned tokens with "#k" tuple suffixes intact — the invariance
    #: pass compares base names, but equiv's value-numbering needs the
    #: exact element (``%4#1`` vs ``%4#0`` are different values).
    ret_full: List[str] = dataclasses.field(default_factory=list)


def _base(tok: str) -> str:
    return tok.split("#")[0]


def _line_types(line: str) -> List[str]:
    """Result tensor types of an op line: after the LAST ``->`` if any,
    else after the final ``:``."""
    if "->" in line:
        seg = line.rsplit("->", 1)[1]
    elif ":" in line:
        seg = line.rsplit(":", 1)[1]
    else:
        return []
    return _TENSOR_RE.findall(seg)


def parse_functions(txt: str) -> Dict[str, _Func]:
    """Parse the pretty-printed StableHLO module into per-function
    statement lists; ``stablehlo.while`` statements carry their ``do``
    region as children (the ``cond`` region is parsed for trip counts
    separately).  Line-oriented and tolerant: anything unrecognised is
    skipped — this is an estimator, not a verifier."""
    funcs: Dict[str, _Func] = {}
    lines = txt.splitlines()
    i = 0
    n = len(lines)
    while i < n:
        m = _FUNC_RE.match(lines[i])
        if not m:
            i += 1
            continue
        fname = m.group(1).strip('"')
        args = [f"%arg{k}" for k in
                range(len(re.findall(r"%arg\d+:", lines[i])))]
        stmts, ret, ret_full, i = _parse_region(lines, i + 1,
                                                base_indent=None)
        funcs[fname] = _Func(fname, args, stmts, ret, ret_full)
    return funcs


def _parse_region(lines: List[str], i: int, base_indent) -> tuple:
    """Parse statements until the region's closing ``}``.  Returns
    ``(stmts, return_tokens, full_return_tokens, next_line_index)``."""
    stmts: List[_Stmt] = []
    ret: List[str] = []
    ret_full: List[str] = []
    n = len(lines)
    while i < n:
        raw = lines[i]
        s = raw.strip()
        if s == "}" or s.startswith("}"):
            return stmts, ret, ret_full, i + 1
        m = _STMT_RE.match(raw)
        if not m:
            i += 1
            continue
        lhs, _nres, op, rest = m.groups()
        op = op.strip('"')
        opname = op.split(".")[-1] if op.startswith("stablehlo.") else op
        if opname == "while":
            # operands: the iterArg bindings' RHS values.
            inits = [_base(t) for t in _VAR_RE.findall(rest)
                     if not t.startswith("%iterArg")]
            iter_args = [t for t in _VAR_RE.findall(rest)
                         if t.startswith("%iterArg")]
            types = _TENSOR_RE.findall(rest)
            # skip the cond region (capture for trip count), then body
            cond_lines: List[str] = []
            i += 1
            while i < n and "cond" not in lines[i]:
                i += 1
            i += 1
            while i < n and not lines[i].strip().startswith("} do"):
                cond_lines.append(lines[i])
                i += 1
            body, bret, bret_full, i = _parse_region(lines, i + 1, None)
            st = _Stmt(lhs=lhs, op="while", operands=inits,
                       result_types=types, callee=None, line=raw,
                       body=body)
            st.iter_args = iter_args            # type: ignore[attr-defined]
            st.body_ret = bret                  # type: ignore[attr-defined]
            st.body_ret_full = bret_full        # type: ignore[attr-defined]
            st.cond_lines = cond_lines          # type: ignore[attr-defined]
            stmts.append(st)
            continue
        if opname in ("return",):
            ret_full = list(_VAR_RE.findall(rest))
            ret = [_base(t) for t in ret_full]
            i += 1
            continue
        callee = None
        if opname in ("func.call", "call"):
            cm = _CALLEE_RE.search(rest)
            callee = cm.group(1).strip('"') if cm else None
        st = _Stmt(
            lhs=lhs, op=opname,
            operands=[_base(t) for t in _VAR_RE.findall(rest)],
            result_types=_line_types(raw), callee=callee, line=raw)
        stmts.append(st)
        i += 1
        # Generic-syntax region ops (`"stablehlo.scatter"(...) ({ ... })`)
        # carry an anonymous block whose `stablehlo.return` belongs to the
        # reducer/comparator, not to this region — consume through the
        # matching `})` so neither the block body nor its closer is taken
        # for region-level syntax.  The skipped lines ride on the stmt so
        # downstream analyzers can still fingerprint the block.
        if "({" in raw and raw.count("({") > raw.count("})"):
            depth_r = raw.count("({") - raw.count("})")
            region: List[str] = []
            while i < n and depth_r > 0:
                depth_r += lines[i].count("({") - lines[i].count("})")
                if depth_r > 0:
                    region.append(lines[i])
                i += 1
            st.region_lines = region            # type: ignore[attr-defined]
    return stmts, ret, ret_full, i


# -- FLOP estimation ---------------------------------------------------


def _stmt_flops(st: _Stmt) -> float:
    """Estimated FLOPs of one statement (dot/conv exact up to 2x
    convention, elementwise = numel, movement = 0)."""
    if not st.result_types:
        return 0.0
    out_numel = sum(_tensor_numel_dtype(t)[0] for t in st.result_types)
    if st.op in _MOVEMENT_OPS or st.op in ("while", "func.call", "call"):
        return 0.0
    operand_types = []
    if ":" in st.line and "(" in st.line.rsplit(":", 1)[-1]:
        sig = st.line.rsplit(":", 1)[-1].split("->")[0]
        operand_types = _TENSOR_RE.findall(sig)
    if st.op == "dot_general":
        contract = 1
        cm = _CONTRACT_RE.search(st.line)
        if cm and operand_types:
            lhs_dims = [d for d in
                        operand_types[0].replace(" ", "").split("x")[:-1]]
            for idx in cm.group(1).split(","):
                idx = idx.strip()
                if idx and int(idx) < len(lhs_dims):
                    contract *= int(lhs_dims[int(idx)])
        return 2.0 * out_numel * contract
    if st.op == "convolution":
        if len(operand_types) >= 2:
            k_numel, _ = _tensor_numel_dtype(operand_types[1])
            o_size = 1
            km = _KERNEL_O_RE.search(st.line)
            if km:
                spec = [x.strip() for x in km.group(1).split(",")]
                kdims = operand_types[1].replace(" ", "").split("x")[:-1]
                if "o" in spec and len(kdims) == len(spec):
                    o_size = int(kdims[spec.index("o")])
            return 2.0 * out_numel * (k_numel / max(1, o_size))
        return 2.0 * out_numel
    if st.op in ("reduce", "reduce_window"):
        in_numel = sum(_tensor_numel_dtype(t)[0] for t in operand_types)
        return float(max(in_numel, out_numel))
    return float(out_numel)


def _trip_count(st: _Stmt) -> Optional[int]:
    """Best-effort trip count from the canonical jax loop condition
    ``compare LT, %counter, constant`` (assumes a zero start)."""
    lines = getattr(st, "cond_lines", [])
    consts = {}
    for ln in lines:
        cm = re.match(r"\s*(%[\w.]+)\s*=\s*stablehlo\.constant\s+"
                      r"dense<(-?\d+)>", ln)
        if cm:
            consts[cm.group(1)] = int(cm.group(2))
    for ln in lines:
        if "compare" in ln and " LT," in ln:
            toks = _VAR_RE.findall(ln.split("=", 1)[-1])
            for t in toks:
                if _base(t) in consts:
                    return consts[_base(t)]
    return None


# -- the loop-invariance dataflow pass ---------------------------------


@dataclasses.dataclass
class ScanLoopReport:
    """One ``stablehlo.while``'s variant/invariant partition."""

    index: int                     # document order within @main
    trip_count: Optional[int]
    body_ops: int                  # statements analyzed (incl. callees)
    invariant_ops: int
    invariant_flops: float         # per iteration — the hoistable number
    invariant_bytes: int           # frontier bytes: invariant values
    #                                consumed by variant ops (what a
    #                                hoisted carry would have to hold)
    total_flops: float             # per iteration, whole body
    top_invariant: List[dict] = dataclasses.field(default_factory=list)

    @property
    def hoistable_flops_total(self) -> float:
        return self.invariant_flops * (self.trip_count or 1)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["hoistable_flops_total"] = self.hoistable_flops_total
        return d


class _InvarianceAnalyzer:
    """Partitions while-body ops into loop-variant / loop-invariant and
    sums hoistable FLOPs/bytes, recursing through ``func.call``s with
    per-call-site operand variance masks (memoized)."""

    def __init__(self, functions: Dict[str, _Func]):
        self.functions = functions
        self._memo: Dict[tuple, tuple] = {}

    def analyze_while(self, st: _Stmt, variant_inits: set) -> dict:
        """``variant_inits``: indices of while operands whose *initial*
        values are already variant in the enclosing scope (rare — the
        dominant variance source is the loop itself)."""
        iter_args = list(getattr(st, "iter_args", []))
        body = st.body or []
        body_ret = list(getattr(st, "body_ret", []))
        # An iterArg is loop-variant unless the body returns it
        # unchanged (same SSA token at the same carry position).
        variant: set = set()
        for pos, ia in enumerate(iter_args):
            returned = body_ret[pos] if pos < len(body_ret) else None
            if returned != ia or pos in variant_inits:
                variant.add(ia)
        stats = self._walk(body, variant, depth=0)
        return stats

    def _walk(self, stmts: List[_Stmt], variant: set, depth: int) -> dict:
        inv_flops = 0.0
        inv_bytes = 0
        inv_ops = 0
        total_flops = 0.0
        n_ops = 0
        top: List[dict] = []
        inv_values: Dict[str, int] = {}     # invariant value -> bytes
        for st in stmts:
            n_ops += 1
            op_variant = any(o in variant for o in st.operands
                             if o.startswith("%"))
            if st.op in ("func.call", "call") and st.callee:
                sub = self._call(st, variant, depth)
                total_flops += sub["total_flops"]
                n_ops += sub["body_ops"]
                if not op_variant:
                    # Whole call is invariant: all its flops hoist.
                    inv_flops += sub["total_flops"]
                    inv_ops += sub["body_ops"]
                else:
                    inv_flops += sub["invariant_flops"]
                    inv_bytes += sub["invariant_bytes"]
                    inv_ops += sub["invariant_ops"]
                    top.extend(sub["top"])
                if sub["variant_out"] or op_variant:
                    if st.lhs:
                        variant.add(st.lhs)
                continue
            if st.op == "while":
                # Nested loop: opaque. Variant if any operand variant.
                if op_variant and st.lhs:
                    variant.add(st.lhs)
                continue
            f = _stmt_flops(st)
            total_flops += f
            if op_variant:
                if st.lhs:
                    variant.add(st.lhs)
                # Frontier: invariant operands feeding a variant op.
                for o in st.operands:
                    if o in inv_values:
                        inv_bytes += inv_values.pop(o)
            else:
                inv_ops += 1
                inv_flops += f
                if st.lhs:
                    b = sum(_tensor_bytes(t) for t in st.result_types)
                    inv_values[st.lhs] = b
                if f > 0:
                    top.append({"op": st.op, "flops": f,
                                "line": st.line.strip()[:160]})
        top.sort(key=lambda d: -d["flops"])
        return {"invariant_flops": inv_flops, "invariant_bytes": inv_bytes,
                "invariant_ops": inv_ops, "total_flops": total_flops,
                "body_ops": n_ops, "top": top[:5],
                "variant_out": True}

    def _call(self, st: _Stmt, variant: set, depth: int) -> dict:
        fn = self.functions.get(st.callee or "")
        operand_vals = [o for o in st.operands if o.startswith("%")]
        if fn is None or depth > 6:
            return {"invariant_flops": 0.0, "invariant_bytes": 0,
                    "invariant_ops": 0, "total_flops": 0.0,
                    "body_ops": 0, "top": [],
                    "variant_out": any(o in variant for o in operand_vals)}
        mask = tuple(
            (operand_vals[k] in variant) if k < len(operand_vals) else False
            for k in range(len(fn.args)))
        key = (fn.name, mask)
        if key in self._memo:
            return dict(self._memo[key])
        callee_variant = {a for a, v in zip(fn.args, mask) if v}
        sub = self._walk(list(fn.stmts), callee_variant, depth + 1)
        sub["variant_out"] = any(r in callee_variant for r in fn.ret) or \
            any(m for m in mask)
        # Conservative: if any arg is variant, outputs are variant unless
        # the return is a passthrough of invariant args only (checked
        # above via fn.ret membership — keep the stronger condition).
        sub["variant_out"] = any(r in callee_variant for r in fn.ret) \
            if fn.ret else any(mask)
        self._memo[key] = dict(sub)
        return sub


def analyze_scan_invariants(stablehlo_text: str) -> List[ScanLoopReport]:
    """The StableHLO ``while``-loop dataflow pass: for each while in
    ``@main``'s body (document order — jax lowers each ``lax.scan`` to
    one), partition the body into loop-variant vs loop-invariant
    subgraphs and quantify the recompute: FLOPs per step that a
    hoisted-carry restructuring would save, and the frontier bytes such
    a carry would have to hold."""
    functions = parse_functions(stablehlo_text)
    main = functions.get("main")
    if main is None:
        return []
    analyzer = _InvarianceAnalyzer(functions)
    out: List[ScanLoopReport] = []
    idx = 0
    for st in main.stmts:
        if st.op != "while":
            continue
        stats = analyzer.analyze_while(st, variant_inits=set())
        out.append(ScanLoopReport(
            index=idx,
            trip_count=_trip_count(st),
            body_ops=stats["body_ops"],
            invariant_ops=stats["invariant_ops"],
            invariant_flops=stats["invariant_flops"],
            invariant_bytes=stats["invariant_bytes"],
            total_flops=stats["total_flops"],
            top_invariant=stats["top"]))
        idx += 1
    return out


# -- report assembly ---------------------------------------------------


@dataclasses.dataclass
class MemoryReport:
    """Everything memcheck knows about one compiled program."""

    name: str
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    alias_bytes: int = 0
    available: bool = True          # memory_analysis() present
    donations: List[DonationEntry] = dataclasses.field(
        default_factory=list)
    scan_loops: List[ScanLoopReport] = dataclasses.field(
        default_factory=list)

    @property
    def peak_bytes(self) -> int:
        """Executable-footprint upper bound: arguments + outputs + temps
        + generated code, aliased bytes counted once (the donation
        discount).  The number the router's admission control budgets
        against."""
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                + self.generated_code_bytes - self.alias_bytes)

    @property
    def ineffective_donations(self) -> List[int]:
        """Arg indices whose donation was requested but never aliased —
        each one is a full silent buffer copy."""
        return [d.arg_index for d in self.donations
                if d.requested and not d.effective]

    @property
    def hoistable_flops_per_step(self) -> float:
        """Loop-invariant FLOPs re-executed per scan iteration, summed
        over ``@main``'s scan loops."""
        return sum(l.invariant_flops for l in self.scan_loops)

    @property
    def hoistable_flops_total(self) -> float:
        return sum(l.hoistable_flops_total for l in self.scan_loops)

    @property
    def hoistable_bytes(self) -> int:
        return sum(l.invariant_bytes for l in self.scan_loops)

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "available": self.available,
            "peak_bytes": self.peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "alias_bytes": self.alias_bytes,
            "donations": [d.to_json() for d in self.donations],
            "ineffective_donations": self.ineffective_donations,
            "scan_loops": [l.to_json() for l in self.scan_loops],
            "hoistable_flops_per_step": self.hoistable_flops_per_step,
            "hoistable_flops_total": self.hoistable_flops_total,
            "hoistable_bytes": self.hoistable_bytes,
        }


def requested_donations(lowered) -> List[bool]:
    """Flattened per-argument donation flags the Python layer requested,
    from ``lowered.args_info`` (set even when lowering could not pair
    the donated buffer with an output — exactly the case MC402 hunts)."""
    import jax

    info = getattr(lowered, "args_info", None)
    if info is None:
        return []
    leaves = jax.tree_util.tree_leaves(
        info, is_leaf=lambda x: hasattr(x, "donated"))
    return [bool(getattr(l, "donated", False)) for l in leaves]


def compiled_memory_stats(compiled) -> Optional[dict]:
    """``compiled.memory_analysis()`` as a plain dict (None when the
    backend does not expose it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    return {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "generated_code_bytes": int(
            getattr(ma, "generated_code_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }


def build_memory_report(name: str, stablehlo_text: str, compiled,
                        requested: Sequence[bool] = ()) -> MemoryReport:
    """Assemble a :class:`MemoryReport` from the lowered StableHLO text,
    the compiled executable, and the requested-donation flags."""
    stats = compiled_memory_stats(compiled)
    try:
        hlo_text = compiled.as_text()
    except Exception:
        hlo_text = ""
    report = MemoryReport(
        name=name,
        available=stats is not None,
        donations=donation_table(
            list(requested), parse_arg_donations(stablehlo_text),
            parse_input_output_aliases(hlo_text)),
        scan_loops=analyze_scan_invariants(stablehlo_text))
    if stats is not None:
        report.argument_bytes = stats["argument_bytes"]
        report.output_bytes = stats["output_bytes"]
        report.temp_bytes = stats["temp_bytes"]
        report.generated_code_bytes = stats["generated_code_bytes"]
        # memory_analysis() reports alias bytes only for freshly-compiled
        # executables — a persistent-compilation-cache hit deserializes
        # with the field zeroed, which would flap the peak pin by the
        # donation discount depending on cache state.  The compiled
        # header's alias table is cache-stable, so derive the discount
        # from the (already parsed) donation table when it is larger.
        # Both sides are per-device: donation bytes are the global
        # StableHLO size divided by the arg's shard count.
        report.alias_bytes = max(
            stats["alias_bytes"],
            sum(d.bytes for d in report.donations if d.effective))
    return report


def analyze_lowered_memory(name: str, lowered) -> MemoryReport:
    """Standalone entry point: lower -> compile -> memory report (the
    jit-cache makes re-compiling an already-built program cheap)."""
    return build_memory_report(
        name, lowered.as_text(), lowered.compile(),
        requested=requested_donations(lowered))


def memory_summary(report: MemoryReport) -> dict:
    """The compact block ``memcheck --format json`` prints per
    program."""
    return {
        "peak_bytes": report.peak_bytes,
        "argument_bytes": report.argument_bytes,
        "output_bytes": report.output_bytes,
        "temp_bytes": report.temp_bytes,
        "donations": [d.to_json() for d in report.donations],
        "ineffective_donations": report.ineffective_donations,
        "hoistable_flops_per_step": report.hoistable_flops_per_step,
        "hoistable_flops_total": report.hoistable_flops_total,
        "hoistable_bytes": report.hoistable_bytes,
        "scan_loops": len(report.scan_loops),
    }
