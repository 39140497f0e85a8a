"""IR-level sharding & communication analyzer (shardcheck's engine).

graftlint (``analysis/lint.py``) audits the Python AST; this module
audits what XLA actually *lowered* — the layer where the regressions
that cost chips live.  An fsdp param that silently compiled as fully
replicated, an implicit resharding all-gather inside the 256-step scan,
a bf16 model upcasting to f32 mid-graph: none of these are visible in
source, all of them are visible in the StableHLO / compiled-HLO text of
a pjit program (GSPMD propagates sharding decisions at the IR level, so
that is where they must be checked).

One :class:`ProgramReport` per compiled program, extracted from three
places:

  * the **lowered StableHLO** (``lowered.as_text()``) — source-level
    facts that survive verbatim: explicit resharding sites
    (``sdy.sharding_constraint`` from ``with_sharding_constraint``),
    dtype upcasts (``stablehlo.convert`` widening a float or landing in
    f64), and host callbacks (``@xla_python_cpu_callback`` and
    friends) inside the traced body;
  * the **compiled (post-SPMD-partitioning) HLO**
    (``compiled.as_text()``) — the collectives GSPMD inserted:
    all-gather / all-reduce / reduce-scatter / collective-permute /
    all-to-all, with instruction counts and per-device result bytes;
  * the **compiled input shardings** — the parameter-sharding table,
    diffed against the mesh policy's intent
    (:meth:`~diff3d_tpu.parallel.MeshEnv.params`) so an fsdp-policy
    param that lowered replicated is flagged by name.

``analysis/budgets.py`` checks reports against committed per-program
budget manifests; ``analysis/shardcheck.py`` is the program registry +
CLI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: Collective opcodes tracked in compiled HLO (async ``-start`` forms
#: are folded into the base opcode; ``-done`` halves are skipped).
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_FLOAT_BYTES = {"f8e4m3fn": 1, "f8e5m2": 1, "f16": 2, "bf16": 2,
                "f32": 4, "f64": 8}

# ``f32[16,8]{1,0}`` / ``pred[]`` tokens inside an HLO result type.
_HLO_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")
# ``%name = <result-type> <opcode>(`` — the instruction head.
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(?P<result>.*?)\s+"
    r"(?P<op>[a-z][a-z0-9\-]*)\(")
_HLO_CONVERT_RE = re.compile(
    r"=\s*([a-z]\d*[a-z0-9]*)\[[0-9,]*\][^ ]*\s+convert\("
    r"\s*([a-z]\d*[a-z0-9]*)\[")
# stablehlo.convert %x : (tensor<16x8xbf16>) -> tensor<16x8xf32>
_SHLO_CONVERT_RE = re.compile(
    r"stablehlo\.convert\s+%\S+\s*:\s*\(tensor<([^>]*)>\)\s*->\s*"
    r"tensor<([^>]*)>")
# %2 = sdy.sharding_constraint %1 <@mesh, [{"data"}, {}]> : tensor<..>
_SHLO_SHARDING_RE = re.compile(
    r"sdy\.sharding_constraint\s+%\S+\s+(<@[^\n]*?\]>)\s*:")
_SHLO_CALLBACK_RE = re.compile(
    r"stablehlo\.custom_call\s+@([\w.]*callback[\w.]*)")
_HLO_CALLBACK_RE = re.compile(
    r"custom_call_target=\"([^\"]*callback[^\"]*)\"")


def _tensor_dtype(tensor_type: str) -> str:
    """``"16x8xbf16"`` / ``"f32"`` -> element dtype."""
    return tensor_type.split("x")[-1].strip()


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _is_upcast(src: str, dst: str) -> bool:
    """Widening float conversion, or anything landing in f64."""
    if dst == "f64" and src != "f64":
        return True
    if src in _FLOAT_BYTES and dst in _FLOAT_BYTES:
        return _FLOAT_BYTES[dst] > _FLOAT_BYTES[src]
    return False


@dataclasses.dataclass
class CollectiveStat:
    """One collective opcode's footprint in a compiled program."""

    op: str
    count: int = 0
    bytes: int = 0     # per-device result bytes, summed over instructions

    def to_json(self) -> dict:
        return {"count": self.count, "bytes": self.bytes}


@dataclasses.dataclass
class ReshardingSite:
    """One explicit sharding constraint in the lowered program."""

    sharding: str      # the sdy sharding text, ``<@mesh, [{"data"}, {}]>``

    def to_json(self) -> dict:
        return {"sharding": self.sharding}


@dataclasses.dataclass
class ParamShardingEntry:
    """One parameter leaf: lowered spec vs the policy's intended spec."""

    path: str
    shape: Tuple[int, ...]
    dtype: str
    actual: str        # str(PartitionSpec) as lowered
    expected: Optional[str]   # policy intent; None when no mesh/policy
    flagged: bool = False     # expected sharded, lowered replicated

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramReport:
    """Everything shardcheck knows about one lowered pjit program."""

    name: str
    mesh_shape: Dict[str, int]
    collectives: Dict[str, CollectiveStat]
    resharding_sites: List[ReshardingSite]
    dtype_upcasts: Dict[str, int]         # "bf16->f32" -> count
    host_callbacks: List[str]             # custom-call target names
    param_table: List[ParamShardingEntry]
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    #: memcheck's :class:`~diff3d_tpu.analysis.mem.MemoryReport` for the
    #: same compiled program (None when analysis was skipped).
    memory: Optional[object] = None
    #: equivcheck's :class:`~diff3d_tpu.analysis.equiv.SemanticReport`
    #: for the same lowering (None when analysis was skipped).  Kept out
    #: of :meth:`to_json` — equivcheck pins its own manifests under
    #: ``runs/equivcheck/``; shardcheck manifests stay unchanged.
    semantic: Optional[object] = None

    @property
    def total_collective_bytes(self) -> int:
        return sum(c.bytes for c in self.collectives.values())

    @property
    def total_collective_count(self) -> int:
        return sum(c.count for c in self.collectives.values())

    @property
    def replicated_policy_params(self) -> List[str]:
        """Paths of params the policy wanted sharded but lowered
        replicated — the silent-replication regression."""
        return [e.path for e in self.param_table if e.flagged]

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "mesh": dict(self.mesh_shape),
            "collectives": {op: c.to_json()
                            for op, c in sorted(self.collectives.items())},
            "total_collective_bytes": self.total_collective_bytes,
            "resharding_sites": [s.to_json()
                                 for s in self.resharding_sites],
            "dtype_upcasts": dict(sorted(self.dtype_upcasts.items())),
            "host_callbacks": list(self.host_callbacks),
            "replicated_policy_params": self.replicated_policy_params,
            "num_params": len(self.param_table),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "memory": (self.memory.to_json()
                       if self.memory is not None else None),
        }


# -- text parsers ------------------------------------------------------


def parse_compiled_collectives(hlo_text: str) -> Dict[str, CollectiveStat]:
    """Collective instructions of a compiled (partitioned) HLO module.

    ``bytes`` is the instruction's *result* size as printed — the
    per-device buffer the collective materialises (tuple results, e.g.
    variadic all-reduce, sum their elements).  Async pairs count once:
    ``-start`` carries the stats, ``-done`` is skipped.
    """
    out: Dict[str, CollectiveStat] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.match(line)
        if not m:
            continue
        op = m.group("op")
        if op.endswith("-done"):
            continue
        base = op[:-6] if op.endswith("-start") else op
        if base not in COLLECTIVE_OPS:
            continue
        stat = out.setdefault(base, CollectiveStat(op=base))
        stat.count += 1
        stat.bytes += sum(_shape_bytes(d, dims) for d, dims
                          in _HLO_SHAPE_RE.findall(m.group("result")))
    return out


def parse_compiled_upcasts(hlo_text: str) -> Dict[str, int]:
    """``convert`` instructions that widen a float (or land in f64) in
    the compiled module — includes converts XLA itself introduced."""
    out: Dict[str, int] = {}
    for dst, src in _HLO_CONVERT_RE.findall(hlo_text):
        if _is_upcast(src, dst):
            key = f"{src}->{dst}"
            out[key] = out.get(key, 0) + 1
    return out


def parse_stablehlo(txt: str) -> dict:
    """Source-level facts from the lowered (pre-partitioning) StableHLO:
    upcasts the *program asked for*, explicit sharding-constraint sites,
    and host callbacks in the traced body."""
    upcasts: Dict[str, int] = {}
    for src_t, dst_t in _SHLO_CONVERT_RE.findall(txt):
        src, dst = _tensor_dtype(src_t), _tensor_dtype(dst_t)
        if _is_upcast(src, dst):
            key = f"{src}->{dst}"
            upcasts[key] = upcasts.get(key, 0) + 1
    sites = [ReshardingSite(sharding=s)
             for s in _SHLO_SHARDING_RE.findall(txt)]
    callbacks = sorted(set(_SHLO_CALLBACK_RE.findall(txt)))
    return {"dtype_upcasts": upcasts, "resharding_sites": sites,
            "host_callbacks": callbacks}


# -- param-sharding table ----------------------------------------------


def _spec_str(sharding) -> str:
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return str(sharding)
    return str(tuple(spec))


def _is_replicated(sharding) -> bool:
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return False
    return all(axis is None for axis in tuple(spec))


def param_sharding_table(params_template, actual_shardings,
                         expected_shardings=None
                         ) -> List[ParamShardingEntry]:
    """Per-leaf table of lowered vs intended placement.

    ``params_template`` is the params pytree (arrays or
    ``ShapeDtypeStruct``s), ``actual_shardings`` the matching pytree of
    lowered shardings (``compiled.input_shardings`` for the params
    argument), ``expected_shardings`` the policy pytree
    (``MeshEnv.params(template)``).  A leaf is *flagged* when the policy
    wanted it sharded but it lowered fully replicated.
    """
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(params_template)[0]
    actual = jax.tree_util.tree_leaves(
        actual_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    expected = (jax.tree_util.tree_leaves(
        expected_shardings, is_leaf=lambda x: hasattr(x, "spec"))
        if expected_shardings is not None else [None] * len(leaves))
    if not (len(leaves) == len(actual) == len(expected)):
        raise ValueError(
            f"param table arity mismatch: {len(leaves)} leaves, "
            f"{len(actual)} actual shardings, {len(expected)} expected")
    table = []
    for (path, leaf), act, exp in zip(leaves, actual, expected):
        flagged = (exp is not None
                   and not _is_replicated(exp)
                   and _is_replicated(act))
        table.append(ParamShardingEntry(
            path=jax.tree_util.keystr(path),
            shape=tuple(getattr(leaf, "shape", ())),
            dtype=str(getattr(leaf, "dtype", "?")),
            actual=_spec_str(act),
            expected=None if exp is None else _spec_str(exp),
            flagged=flagged))
    return table


# -- report assembly ---------------------------------------------------


def cost_summary(compiled) -> Dict[str, Optional[float]]:
    """``{"flops", "bytes_accessed"}`` from XLA cost analysis, as the
    manifests record them (:func:`analyze_lowered`)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {"flops": None, "bytes_accessed": None}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not ca:
        return {"flops": None, "bytes_accessed": None}
    return {"flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed")}


def _mesh_shape_of(shardings) -> Dict[str, int]:
    import jax

    for sh in jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "mesh")):
        mesh = getattr(sh, "mesh", None)
        if mesh is not None:
            return {str(k): int(v) for k, v in mesh.shape.items()}
    return {}


def analyze_lowered(name: str, lowered, *, params_template=None,
                    params_argnum: int = 0,
                    expected_param_shardings=None) -> ProgramReport:
    """Build a :class:`ProgramReport` from a ``jax.stages.Lowered``.

    Compiles the lowered program (the persistent compilation cache makes
    re-analysis of an already-built program cheap) and merges the
    StableHLO-level facts with the partitioned-HLO collectives and the
    input-sharding table.  ``params_template``/``params_argnum`` locate
    the parameter pytree among the program's positional arguments;
    ``expected_param_shardings`` is the policy pytree to diff against
    (both optional — without them the param table is empty).
    """
    stablehlo_text = lowered.as_text()
    shlo = parse_stablehlo(stablehlo_text)
    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    collectives = parse_compiled_collectives(hlo_text)
    for target in _HLO_CALLBACK_RE.findall(hlo_text):
        if target not in shlo["host_callbacks"]:
            shlo["host_callbacks"].append(target)

    table: List[ParamShardingEntry] = []
    mesh_shape: Dict[str, int] = {}
    try:
        in_shardings = compiled.input_shardings[0]
        mesh_shape = _mesh_shape_of(in_shardings)
        if params_template is not None:
            # params_argnum: positional index of the params pytree, or a
            # callable extracting it (e.g. the train step's params live
            # inside the state at argnum 0: ``lambda sh: sh[0].params``).
            actual = (params_argnum(in_shardings)
                      if callable(params_argnum)
                      else in_shardings[params_argnum])
            table = param_sharding_table(params_template, actual,
                                         expected_param_shardings)
    except Exception:
        # Shardings are advisory for the report: a backend that does not
        # expose them still yields the comms/dtype/callback sections.
        table = table or []

    cost = cost_summary(compiled)
    # memcheck rides the same lower+compile pass (lazy import: mem
    # depends on this module for the dtype table).
    from diff3d_tpu.analysis import mem as _mem

    memory = _mem.build_memory_report(
        name, stablehlo_text, compiled,
        requested=_mem.requested_donations(lowered))
    # equivcheck rides it too: the canonical semantic fingerprint is a
    # pure function of the StableHLO text already in hand.
    from diff3d_tpu.analysis import equiv as _equiv

    semantic = _equiv.build_semantic_report(name, stablehlo_text)
    return ProgramReport(
        name=name, mesh_shape=mesh_shape, collectives=collectives,
        resharding_sites=shlo["resharding_sites"],
        dtype_upcasts=shlo["dtype_upcasts"],
        host_callbacks=sorted(shlo["host_callbacks"]),
        param_table=table, flops=cost["flops"],
        bytes_accessed=cost["bytes_accessed"], memory=memory,
        semantic=semantic)


def abstractify(tree):
    """Pytree of arrays -> matching ``ShapeDtypeStruct`` pytree (lower
    programs without staging real buffers on a device)."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype), tree)
