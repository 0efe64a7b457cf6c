"""nerrflint: the repo's rule-based static analyzer over its own ASTs.

`scripts/check_metrics.py` proved the pattern — a repo-specific lint wired
into tier-1 catches whole regression classes for free.  This engine
generalizes it: every invariant the codebase enforces only by convention
(traced functions stay host-pure, the serve path never recompiles after
warmup, threaded code touches shared state under its locks, metric names
follow the contract) becomes a Rule producing structured Findings, and the
full ruleset runs on every test invocation and as a chip-queue pre-flight.

Surfaces:

    python scripts/nerrflint.py              # full ruleset over nerrf_tpu/
    python scripts/nerrflint.py --deep       # + jaxpr-level contracts
    python -m nerrf_tpu.cli lint [--json]    # same, as a CLI subcommand
    tests/test_analysis.py                   # the tier-1 gate (AST tier)
    tests/test_programs.py                   # the tier-1 gate (deep tier)

Suppression, two flavors (both REQUIRE a justification):

  * inline — append ``# nerrflint: ok[rule-id] why`` to the flagged line
    (or the line above).  Lives next to the code; survives refactors.
  * baseline — one line per accepted finding in ``.nerrflint-baseline``
    at the repo root: ``<rule> <path> <anchor>  # why``.  Anchors are
    content-derived (never line numbers), so baselines survive unrelated
    edits; stale entries are reported so the file stays honest.

Exit codes: 0 clean (or fully suppressed), 1 unbaselined findings,
2 usage/baseline-format errors.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from nerrf_tpu.analysis.astutil import Project, collect_files

REPO = Path(__file__).resolve().parents[2]
BASELINE_NAME = ".nerrflint-baseline"
DEFAULT_PATHS = ("nerrf_tpu",)

# schema version of the --json document (tests pin the key set).
# 1 → "1.1": each `rules` entry gained `elapsed_sec` (per-rule wall time,
# so the queue pre-flights can log which rule eats the budget).
JSON_SCHEMA_VERSION = "1.1"

_SUPPRESS = re.compile(r"#\s*nerrflint:\s*ok\[([a-z0-9-]+)\]\s*(\S.*)?")


@dataclasses.dataclass
class Finding:
    """One rule violation at one site.

    ``anchor`` is the stable identity used for baseline matching and
    dedup: rules derive it from names (function qualnames, attribute
    names, effect kinds) — never from line numbers, which churn."""

    rule: str
    path: str
    line: int
    message: str
    hint: str = ""
    anchor: str = ""

    @property
    def key(self) -> str:
        return f"{self.rule} {self.path} {self.anchor}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message, "hint": self.hint,
                "anchor": self.anchor}

    def render(self) -> str:
        out = f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


class Rule:
    """Base class: subclasses set ``id``/``description`` and implement
    ``run(project) -> list[Finding]``.  ``deep`` marks the jaxpr-level
    tier (`nerrf_tpu/analysis/programs/`): those rules import jax at run
    time and only load under ``--deep`` — the base engine stays
    stdlib-only."""

    id: str = ""
    description: str = ""
    deep: bool = False

    def run(self, project: Project) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError


def default_rules() -> List[Rule]:
    """The full shipped ruleset (import here, not at module top, so the
    engine itself stays importable from rule modules)."""
    from nerrf_tpu.analysis.concurrency import (
        AtomicityViolation,
        BlockingUnderLock,
        CallbackUnderLock,
        ThreadLifecycle,
    )
    from nerrf_tpu.analysis.locks import LockDiscipline
    from nerrf_tpu.analysis.metrics_contract import MetricsContract
    from nerrf_tpu.analysis.operability import (
        AtomicWrite,
        BoundedGrowth,
        FailurePolicy,
        JournalContract,
    )
    from nerrf_tpu.analysis.purity import JaxPurity
    from nerrf_tpu.analysis.recompile import RecompileHazard
    from nerrf_tpu.analysis.syncs import SyncInHotLoop

    return [JaxPurity(), RecompileHazard(), SyncInHotLoop(),
            LockDiscipline(), AtomicityViolation(), CallbackUnderLock(),
            BlockingUnderLock(), ThreadLifecycle(), MetricsContract(),
            AtomicWrite(), JournalContract(), FailurePolicy(),
            BoundedGrowth()]


# -- baseline -----------------------------------------------------------------


@dataclasses.dataclass
class Baseline:
    entries: Dict[str, str]            # finding.key → justification
    errors: List[str]

    @classmethod
    def load(cls, path: Optional[Path]) -> "Baseline":
        entries: Dict[str, str] = {}
        errors: List[str] = []
        if path is None or not path.exists():
            return cls(entries, errors)
        for i, raw in enumerate(path.read_text().splitlines(), 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            body, _, why = line.partition("#")
            parts = body.split()
            if len(parts) != 3:
                errors.append(
                    f"{path.name}:{i}: expected '<rule> <path> <anchor>"
                    f"  # justification', got {raw!r}")
                continue
            if not why.strip():
                errors.append(
                    f"{path.name}:{i}: baseline entry for {parts[0]!r} has "
                    f"no justification — every suppression must say why")
                continue
            entries[" ".join(parts)] = why.strip()
        return cls(entries, errors)


def _inline_suppressed(project: Project, f: Finding) -> Optional[str]:
    """The justification text when the finding's line (or the line above)
    carries a ``# nerrflint: ok[rule]`` marker for this rule.  Files the
    AST scan never parsed (metrics-contract reaches bench.py/benchmarks/)
    are read from disk so inline markers work everywhere findings do."""
    mod = next((m for m in project.modules.values() if m.path == f.path),
               None)
    if mod is not None:
        lines = mod.lines
    else:
        try:
            lines = (project.root / f.path).read_text().splitlines()
        except OSError:
            return None
    for n in (f.line, f.line - 1):
        src = lines[n - 1] if 0 < n <= len(lines) else ""
        m = _SUPPRESS.search(src)
        if m and m.group(1) == f.rule:
            return (m.group(2) or "").strip() or "(no reason given)"
    return None


# -- runner -------------------------------------------------------------------


@dataclasses.dataclass
class Report:
    findings: List[Finding]            # unsuppressed, the failures
    suppressed: List[Finding]          # inline- or baseline-accepted
    stale: List[str]                   # baseline keys that matched nothing
    errors: List[str]                  # parse/baseline-format problems
    files: int
    elapsed: float
    rules: List[Rule]
    rule_elapsed: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.errors

    def to_json(self) -> dict:
        return {
            "schema": JSON_SCHEMA_VERSION,
            "ok": self.ok,
            "files": self.files,
            "elapsed_sec": round(self.elapsed, 3),
            "rules": [{"id": r.id, "description": r.description,
                       "elapsed_sec": round(
                           self.rule_elapsed.get(r.id, 0.0), 4)}
                      for r in self.rules],
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            "stale_baseline": list(self.stale),
            "errors": list(self.errors),
        }


def analyze(root: Path = REPO, paths: Sequence[str] = DEFAULT_PATHS,
            rules: Optional[List[Rule]] = None,
            baseline_path: Optional[Path] = None) -> Report:
    """Run ``rules`` over ``paths`` under ``root`` and fold in baseline +
    inline suppressions.  ``baseline_path=None`` means the repo default
    (pass a nonexistent path to run baseline-free)."""
    t0 = time.perf_counter()
    root = Path(root)
    if baseline_path is None:
        baseline_path = root / BASELINE_NAME
    rules = default_rules() if rules is None else rules
    project = Project(root, collect_files(root, paths))
    baseline = Baseline.load(baseline_path)
    errors = list(project.errors) + list(baseline.errors)

    raw: List[Finding] = []
    rule_elapsed: Dict[str, float] = {}
    for rule in rules:
        r0 = time.perf_counter()
        try:
            raw.extend(rule.run(project))
        except Exception as e:  # noqa: BLE001 — a crashed rule is exit 2,
            # not a traceback: the pre-flights must distinguish "the
            # analyzer broke" from "the code has findings"
            errors.append(
                f"rule {rule.id or type(rule).__name__} crashed: "
                f"{type(e).__name__}: {e}")
        rule_elapsed[rule.id] = (rule_elapsed.get(rule.id, 0.0)
                                 + time.perf_counter() - r0)
    raw.sort(key=lambda f: (f.path, f.line, f.rule))

    seen_keys = set()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    matched = set()
    for f in raw:
        if f.key in seen_keys:       # same anchor twice: report once
            continue
        seen_keys.add(f.key)
        if _inline_suppressed(project, f) is not None:
            suppressed.append(f)
        elif f.key in baseline.entries:
            matched.add(f.key)
            suppressed.append(f)
        else:
            findings.append(f)
    stale = sorted(set(baseline.entries) - matched)
    return Report(findings, suppressed, stale, errors,
                  len(project.modules), time.perf_counter() - t0, rules,
                  rule_elapsed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="nerrflint",
        description="rule-based static analysis over the nerrf_tpu ASTs")
    ap.add_argument("--root", default=str(REPO),
                    help="repo root to analyze (default: this checkout)")
    ap.add_argument("--rule", action="append", default=None, metavar="ID",
                    help="run only this rule (repeatable)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help=f"suppression file (default: <root>/{BASELINE_NAME})")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--deep", action="store_true",
                    help="also run the jaxpr-level program-contract rules "
                         "(signature closure, donation, collectives, "
                         "cache-key coverage) — imports "
                         "jax and forces a virtual multi-device CPU "
                         "backend; ~20 s instead of ~2 s")
    args = ap.parse_args(argv)

    rules = default_rules()
    if args.deep:
        # rule construction is jax-free; the backend setup (jax import,
        # XLA_FLAGS) waits until rules actually run, so --list-rules
        # stays instant even with --deep
        from nerrf_tpu.analysis.programs import deep_rules

        rules += deep_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.id:<20} {r.description}")
        return 0
    if args.rule:
        known = {r.id: r for r in rules}
        unknown = [rid for rid in args.rule if rid not in known]
        if unknown:
            print(f"nerrflint: unknown rule(s): {', '.join(unknown)} "
                  f"(--list-rules shows the catalog)", file=sys.stderr)
            return 2
        rules = [known[rid] for rid in args.rule]

    if any(getattr(r, "deep", False) for r in rules):
        from nerrf_tpu.analysis.programs import prepare_backend

        prepare_backend()
    report = analyze(
        Path(args.root), DEFAULT_PATHS, rules,
        Path(args.baseline) if args.baseline else None)

    if args.json:
        print(json.dumps(report.to_json(), indent=2))
    else:
        for e in report.errors:
            print(f"nerrflint: error: {e}", file=sys.stderr)
        for f in report.findings:
            print(f.render(), file=sys.stderr)
        for key in report.stale:
            print(f"nerrflint: stale baseline entry (no longer matches; "
                  f"delete it): {key}", file=sys.stderr)
        status = "clean" if report.ok else \
            f"{len(report.findings)} finding(s)"
        print(f"nerrflint: {report.files} files, {len(rules)} rules, "
              f"{len(report.suppressed)} suppressed, {status} "
              f"in {report.elapsed:.2f}s")
    if report.errors:
        return 2
    return 1 if report.findings else 0


if __name__ == "__main__":
    sys.exit(main())
