"""nerrflint — rule-based static analysis over the package's own ASTs.

The invariants this repo enforces only by convention (traced functions
stay host-pure, the serve path never recompiles after warmup, threaded
code touches shared state under its locks, metric names follow the
Prometheus contract) each became a bug once; every rule here is the
generalized regression test for one of those bug classes, wired into
tier-1 so every future PR is analyzed on every test run.

Three tiers: the AST rules here (purity / recompile / sync / lock
discipline / metrics), the concurrency tier
(``nerrf_tpu/analysis/concurrency.py`` — atomicity, callbacks and
blocking work under locks, thread lifecycle — built on the shared lock
model in ``locks.py``), and the deep (jaxpr-level) program contracts in
``nerrf_tpu/analysis/programs/`` — abstract tracing of the real
serve/train/parallel entry points behind ``nerrf lint --deep``
(signature closure, donation discipline, collective/sharding
consistency, cache-key coverage).

Entry points: ``python scripts/nerrflint.py [--deep]``, ``nerrf lint``
(CLI), ``tests/test_analysis.py`` / ``tests/test_programs.py`` (the
tier-1 gates).  See docs/static-analysis.md for the rule catalog and how
to suppress or add a rule.

Stdlib-only: importing this package must never initialize jax (the deep
tier imports jax only inside rule execution, and only under --deep).
"""

from nerrf_tpu.analysis.engine import (  # noqa: F401
    Baseline,
    Finding,
    Report,
    Rule,
    analyze,
    default_rules,
    main,
)
