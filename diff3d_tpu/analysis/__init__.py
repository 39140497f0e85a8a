"""Static analysis + runtime invariants for the TPU hot paths.

Four layers, one contract (DESIGN.md §9–12):

  * ``analysis.lint`` — graftlint, the AST tracer-hygiene linter
    (``python -m diff3d_tpu.analysis`` walks diff3d_tpu/ and tools/
    and exits nonzero on unsuppressed findings; tier 1 runs it as a
    gate);
  * ``analysis.ir`` / ``analysis.budgets`` / ``analysis.shardcheck`` —
    the IR-level sharding & communication analyzer: per-program
    collective/dtype/param-placement reports over lowered StableHLO and
    compiled HLO, diffed against committed budget manifests under
    ``runs/shardcheck/`` (``shardcheck`` console script; tools/lint.py
    runs both passes as one gate);
  * ``analysis.lockcheck`` / ``analysis.rules.concurrency`` — lockcheck,
    the concurrency linter for the threaded serving/checkpoint runtime:
    per-class lock-order graphs, ``# guarded-by:`` discipline, blocking
    calls and callback invocation under locks (rules LC3xx; ``lockcheck``
    console script, third leg of the tools/lint.py gate);
  * ``analysis.runtime`` / ``analysis.witness`` — the recompilation
    sentinel, transfer/donation guards and the runtime lock-order
    witness, surfaced as the ``compile_budget``/``comms_budget``/
    ``lock_witness`` pytest markers that enforce the same invariants on
    running code.
"""

from diff3d_tpu.analysis.ir import (ProgramReport, analyze_lowered,
                                    cost_summary)
from diff3d_tpu.analysis.lint import (Finding, lint_paths, lint_source,
                                      main)
from diff3d_tpu.analysis.lockcheck import lockcheck_paths, lockcheck_source
from diff3d_tpu.analysis.runtime import (CompileBudgetExceeded,
                                         RecompilationSentinel,
                                         assert_consumed, assert_live,
                                         compile_budget,
                                         no_host_transfers, owned)
from diff3d_tpu.analysis.witness import (LockWitness, WitnessViolation,
                                         install_witness)

__all__ = [
    "Finding", "lint_paths", "lint_source", "main",
    "lockcheck_paths", "lockcheck_source",
    "ProgramReport", "analyze_lowered", "cost_summary",
    "RecompilationSentinel", "CompileBudgetExceeded", "compile_budget",
    "no_host_transfers", "assert_consumed", "assert_live", "owned",
    "LockWitness", "WitnessViolation", "install_witness",
]
