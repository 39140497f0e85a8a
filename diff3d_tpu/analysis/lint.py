"""graftlint: AST-based TPU/JAX tracer-hygiene linter.

The worst bugs this codebase has shipped were *silent JAX-semantics
violations* — a donated buffer read after donation (latent heap
corruption), a hidden host sync inside a jit body, a shape-like argument
left traced (recompilation storm).  None of them fail loudly at the call
site; all of them are visible in the AST.  This module is the engine:
rule discovery, per-file analysis, inline suppressions, a repo baseline,
and the CLI that tier 1 runs as a gate.

Vocabulary:

  * A **finding** is one (rule, file, line) violation with a severity.
  * An inline comment ``# graftlint: disable=GL104(reason)`` suppresses
    that rule on its line; ``disable-next-line=`` suppresses on the line
    below; ``disable-file=`` at any point suppresses for the whole file.
    Reasons are part of the contract — a suppression without one is
    itself reported (severity warning, rule GL002).
  * The **baseline** (``--baseline``/``--update-baseline``) is a JSON
    set of finding fingerprints that are tolerated — the adoption path
    for a legacy tree.  This repo's baseline is EMPTY by policy: every
    finding is either fixed or carries an inline reason.

Exit codes: 0 clean, 1 unsuppressed findings, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import hashlib
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from diff3d_tpu.analysis.rules import ALL_RULES
from diff3d_tpu.analysis.rules.context import ModuleContext

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

#: Default lint targets, relative to the repo root (ISSUE 8 gate scope).
DEFAULT_TARGETS = ("diff3d_tpu", "tools")
DEFAULT_BASELINE = ".graftlint-baseline.json"

_RULE_HEAD_RE = re.compile(r"\s*,?\s*([A-Za-z]+\d+|all)")


def _suppress_re(tool: str) -> "re.Pattern[str]":
    """The inline-suppression comment grammar, parameterised on the tool
    tag so sibling analyzers (lockcheck) reuse the exact grammar under
    their own namespace: ``# <tool>: disable[-next-line|-file]=RULE(r)``."""
    return re.compile(
        rf"#\s*{re.escape(tool)}:\s*(disable|disable-next-line|disable-file)"
        r"\s*=\s*(.*)$")


_SUPPRESS_RE = _suppress_re("graftlint")


def _parse_rule_tokens(spec: str):
    """``GL104(reason),GL106`` -> [(rule, reason|None), ...].

    Reasons are free-form text in balanced parens (nested parens fine);
    parsing consumes rule tokens from the start and stops at the first
    thing that is not one — so prose in a reason can never be mistaken
    for another rule id.
    """
    out = []
    pos = 0
    while pos < len(spec):
        m = _RULE_HEAD_RE.match(spec, pos)
        if not m:
            break
        rule = m.group(1)
        pos = m.end()
        reason = None
        if pos < len(spec) and spec[pos] == "(":
            depth, start = 0, pos + 1
            for i in range(pos, len(spec)):
                if spec[i] == "(":
                    depth += 1
                elif spec[i] == ")":
                    depth -= 1
                    if depth == 0:
                        reason = spec[start:i].strip() or None
                        pos = i + 1
                        break
            else:
                reason = spec[start:].strip() or None
                pos = len(spec)
        out.append((rule, reason))
    return out


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation."""

    path: str
    rule: str
    line: int
    col: int
    severity: str
    message: str
    suppressed: bool = False
    suppress_reason: Optional[str] = None
    #: IR-level findings (shardcheck SC2xx) have no source line to hash;
    #: they set this to a stable key (program + rule + subject) instead,
    #: so AST and IR findings share one fingerprint-baseline format.
    fingerprint_data: Optional[str] = None

    def fingerprint(self, root: str) -> str:
        """Location-independent identity for baseline matching: file +
        rule + the violating source line's text (so pure line-number
        drift does not invalidate a baseline entry).  IR findings hash
        their ``fingerprint_data`` key instead of a source line."""
        rel = os.path.relpath(self.path, root)
        if self.fingerprint_data is not None:
            text = self.fingerprint_data
        else:
            try:
                with open(self.path, encoding="utf-8") as f:
                    lines = f.read().splitlines()
                text = lines[self.line - 1].strip() if self.line <= len(
                    lines) else ""
            except OSError:
                text = ""
        h = hashlib.sha256(
            f"{rel}\x00{self.rule}\x00{text}".encode()).hexdigest()
        return h[:20]

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.severity}] {self.message}{tag}")


@dataclasses.dataclass
class Suppression:
    line: int          # the line the suppression applies to
    rules: Set[str]    # rule ids, or {"all"}
    reasons: Dict[str, str]
    declared_line: int

    def covers(self, rule: str) -> bool:
        return "all" in self.rules or rule in self.rules


def _parse_suppressions(
        lines: Sequence[str],
        suppress_re: "re.Pattern[str]" = _SUPPRESS_RE,
) -> Tuple[List[Suppression], List[Suppression], List[Tuple[int, str]]]:
    """-> (line-scoped, file-scoped, reasonless (line, rule) pairs)."""
    line_scoped: List[Suppression] = []
    file_scoped: List[Suppression] = []
    missing_reason: List[Tuple[int, str]] = []
    for i, text in enumerate(lines, start=1):
        m = suppress_re.search(text)
        if not m:
            continue
        kind, spec = m.group(1), m.group(2)
        rules: Set[str] = set()
        reasons: Dict[str, str] = {}
        for rule, reason in _parse_rule_tokens(spec):
            rules.add(rule)
            if reason:
                reasons[rule] = reason
            else:
                missing_reason.append((i, rule))
        if not rules:
            continue
        target = i + 1 if kind == "disable-next-line" else i
        supp = Suppression(line=target, rules=rules, reasons=reasons,
                           declared_line=i)
        (file_scoped if kind == "disable-file" else line_scoped).append(
            supp)
    return line_scoped, file_scoped, missing_reason


def lint_source(path: str, source: str,
                rules: Optional[Sequence] = None, *,
                tool: str = "graftlint",
                parse_rule: str = "GL001",
                reasonless_rule: str = "GL002") -> List[Finding]:
    """Lint one file's source text.  Returns ALL findings, suppressed
    ones included (marked), so callers can report both sides.

    ``tool`` selects the suppression-comment namespace (and the ids the
    engine-emitted parse/reasonless findings carry) — graftlint by
    default; lockcheck passes its own so the two analyzers' suppressions
    never shadow each other on a shared line."""
    rules = ALL_RULES if rules is None else rules
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path=path, rule=parse_rule, line=e.lineno or 1,
                        col=e.offset or 0, severity=SEVERITY_ERROR,
                        message=f"file does not parse: {e.msg}")]
    ctx = ModuleContext(path, source, tree)
    raw: List[Finding] = []
    for rule in rules:
        for f in rule.check(ctx):
            raw.append(f)

    line_scoped, file_scoped, missing_reason = _parse_suppressions(
        ctx.lines, _suppress_re(tool) if tool != "graftlint"
        else _SUPPRESS_RE)
    out: List[Finding] = []
    for f in sorted(raw, key=lambda f: (f.line, f.col, f.rule)):
        reason = None
        suppressed = False
        for supp in file_scoped:
            if supp.covers(f.rule):
                suppressed = True
                reason = supp.reasons.get(f.rule) or supp.reasons.get(
                    "all")
        if not suppressed:
            for supp in line_scoped:
                if supp.line == f.line and supp.covers(f.rule):
                    suppressed = True
                    reason = supp.reasons.get(f.rule) or supp.reasons.get(
                        "all")
        out.append(dataclasses.replace(f, suppressed=suppressed,
                                       suppress_reason=reason))
    # A suppression without a reason is a policy violation of its own —
    # the inline comment is the audit trail.
    for line, rule in missing_reason:
        out.append(Finding(
            path=path, rule=reasonless_rule, line=line, col=0,
            severity=SEVERITY_WARNING,
            message=f"suppression of {rule} has no (reason) — write "
                    f"'# {tool}: disable={rule}(why it is safe)'"))
    return out


def iter_python_files(targets: Iterable[str]) -> List[str]:
    files: List[str] = []
    for target in targets:
        if os.path.isfile(target):
            files.append(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__", ".git"))
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    return files


def lint_paths(targets: Sequence[str],
               rules: Optional[Sequence] = None, *,
               tool: str = "graftlint",
               parse_rule: str = "GL001",
               reasonless_rule: str = "GL002") -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_python_files(targets):
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except OSError as e:
            findings.append(Finding(
                path=path, rule=parse_rule, line=1, col=0,
                severity=SEVERITY_ERROR,
                message=f"unreadable: {e}"))
            continue
        findings.extend(lint_source(path, source, rules, tool=tool,
                                    parse_rule=parse_rule,
                                    reasonless_rule=reasonless_rule))
    return findings


# -- baseline ----------------------------------------------------------


def load_baseline(path: str) -> Set[str]:
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("version") != 1:
        raise ValueError(f"{path}: not a graftlint baseline (version 1)")
    return set(data.get("entries", []))


def write_baseline(path: str, findings: Sequence[Finding],
                   root: str, tool: str = "graftlint") -> int:
    entries = sorted({f.fingerprint(root) for f in findings
                      if not f.suppressed})
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1,
                   "tool": tool,
                   "entries": entries}, f, indent=1, sort_keys=True)
        f.write("\n")
    return len(entries)


def apply_baseline(findings: Sequence[Finding], baseline: Set[str],
                   root: str) -> List[Finding]:
    """Mark baseline-matched findings as suppressed (reason=baseline)."""
    if not baseline:
        return list(findings)
    out = []
    for f in findings:
        if not f.suppressed and f.fingerprint(root) in baseline:
            f = dataclasses.replace(f, suppressed=True,
                                    suppress_reason="baseline")
        out.append(f)
    return out


# -- CLI ---------------------------------------------------------------


def _find_root(start: str) -> str:
    cur = os.path.abspath(start)
    while True:
        if os.path.exists(os.path.join(cur, "pyproject.toml")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start)
        cur = parent


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="graftlint",
        description="TPU tracer-hygiene linter (rules GL1xx; see "
                    "docs/DESIGN.md §9)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to lint (default: diff3d_tpu, "
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
        for rule in ALL_RULES:
            print(f"{rule.id}  {rule.name:24s} [{rule.severity}] "
                  f"{rule.description}")
        return 0

    root = _find_root(os.getcwd())
    if args.paths:
        targets = list(args.paths)
    else:
        targets = [os.path.join(root, t) for t in DEFAULT_TARGETS]
        targets = [t for t in targets if os.path.exists(t)]
        if not targets:
            print("graftlint: no default targets found under "
                  f"{root}", file=sys.stderr)
            return 2

    baseline_path = args.baseline or os.path.join(root, DEFAULT_BASELINE)
    findings = lint_paths(targets)

    if args.update_baseline:
        n = write_baseline(baseline_path, findings, root)
        print(f"graftlint: baseline written to {baseline_path} "
              f"({n} entries)")
        return 0

    try:
        baseline = load_baseline(baseline_path)
    except (ValueError, json.JSONDecodeError) as e:
        print(f"graftlint: {e}", file=sys.stderr)
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
        print(f"graftlint: {len(live)} finding(s), "
              f"{len(suppressed)} suppressed, "
              f"{len(iter_python_files(targets))} file(s)")
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
