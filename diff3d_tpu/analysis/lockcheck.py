"""lockcheck: concurrency static analysis for the threaded runtime.

The third analysis pillar (DESIGN.md §12), next to graftlint (AST
tracer hygiene) and shardcheck (IR sharding/communication): an AST
analyzer for the *threaded* parts of the codebase — the serving engine,
the async checkpointer, the prefetch loader and the native-library
loader.  It shares graftlint's engine wholesale (`analysis/lint.py`):
the same Finding type, fingerprints, JSON baseline format and
inline-suppression grammar, namespaced under its own tool tag so the
two analyzers never shadow each other on a shared line:

    # lockcheck: disable=LC303(queue is unbounded; put never blocks)

Rules (docs/DESIGN.md §12 for the full contract):

  LC001  parse-error              file does not parse (engine-emitted)
  LC002  reasonless-suppression   suppression without a (reason)
  LC301  lock-order-cycle         A->B and B->A acquisition orders
  LC302  unguarded-access         '# guarded-by:' state touched unlocked
  LC303  blocking-under-lock      wait/get/put/sleep/sync under a lock
  LC304  wait-without-predicate   Condition.wait outside a while loop
  LC305  thread-leak              Thread neither daemon nor joined
  LC306  callback-under-lock      user callback invoked under the lock
  LC307  double-acquire           non-reentrant Lock re-acquired
  LC308  unguarded-global-mutation thread target writes a bare global

The static half is deliberately conservative (unknown receivers stay
silent); its blind spots — cross-class orders, locks passed by
argument — are covered at runtime by ``analysis/witness.py`` and the
``@pytest.mark.lock_witness`` marker.

Exit codes: 0 clean, 1 unsuppressed findings, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import List, Optional, Sequence

from diff3d_tpu.analysis.lint import (DEFAULT_TARGETS, Finding,
                                      _find_root, apply_baseline,
                                      iter_python_files, lint_paths,
                                      lint_source, load_baseline,
                                      write_baseline)
from diff3d_tpu.analysis.rules.concurrency import LC_RULES

DEFAULT_BASELINE = ".lockcheck-baseline.json"

TOOL = "lockcheck"
PARSE_RULE = "LC001"
REASONLESS_RULE = "LC002"


def lockcheck_source(path: str, source: str,
                     rules: Optional[Sequence] = None) -> List[Finding]:
    """Lint one file's source with the LC rule pack."""
    return lint_source(path, source, LC_RULES if rules is None else rules,
                       tool=TOOL, parse_rule=PARSE_RULE,
                       reasonless_rule=REASONLESS_RULE)


def lockcheck_paths(targets: Sequence[str],
                    rules: Optional[Sequence] = None) -> List[Finding]:
    return lint_paths(targets, LC_RULES if rules is None else rules,
                      tool=TOOL, parse_rule=PARSE_RULE,
                      reasonless_rule=REASONLESS_RULE)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="lockcheck",
        description="concurrency static analyzer (rules LC3xx; see "
                    "docs/DESIGN.md §12)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to check (default: diff3d_tpu, "
                        "tools under the repo root)")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default <root>/"
                        f"{DEFAULT_BASELINE} when present)")
    p.add_argument("--update-baseline", action="store_true",
                   help="write current unsuppressed findings to the "
                        "baseline and exit 0")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--show-suppressed", action="store_true",
                   help="also print suppressed findings")
    p.add_argument("--list-rules", action="store_true")
    args = p.parse_args(argv)

    if args.list_rules:
        for rule in LC_RULES:
            print(f"{rule.id}  {rule.name:28s} [{rule.severity}] "
                  f"{rule.description}")
        return 0

    root = _find_root(os.getcwd())
    if args.paths:
        targets = list(args.paths)
    else:
        targets = [os.path.join(root, t) for t in DEFAULT_TARGETS]
        targets = [t for t in targets if os.path.exists(t)]
        if not targets:
            print("lockcheck: no default targets found under "
                  f"{root}", file=sys.stderr)
            return 2

    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)
    findings = lockcheck_paths(targets)

    if args.update_baseline:
        n = write_baseline(baseline_path, findings, root, tool=TOOL)
        print(f"lockcheck: baseline written to {baseline_path} "
              f"({n} entries)")
        return 0

    try:
        baseline = load_baseline(baseline_path)
    except (ValueError, json.JSONDecodeError) as e:
        print(f"lockcheck: {e}", file=sys.stderr)
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
        print(f"lockcheck: {len(live)} finding(s), "
              f"{len(suppressed)} suppressed, "
              f"{len(iter_python_files(targets))} file(s)")
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
