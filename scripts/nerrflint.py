#!/usr/bin/env python3
"""Standalone nerrflint entry point (the pre-flight surface).

Thin shim over ``nerrf_tpu.analysis.engine`` — same flags, same exit
codes (0 clean, 1 unbaselined findings, 2 usage/baseline errors):

    python scripts/nerrflint.py [--json] [--list-rules] [--rule ID]
    python scripts/nerrflint.py --deep      # + jaxpr-level contracts

Runs the full AST ruleset over ``nerrf_tpu/`` in seconds on CPU (no jax
import), so ``scripts/e2e.sh`` fails fast on analysis errors instead of
burning chip time.  ``--deep`` adds the
program-contract tier (``nerrf_tpu/analysis/programs/``): abstract
tracing of the real serve/train/parallel entry points on a virtual CPU
backend — signature closure, donation, collectives, cache-key
coverage — in under 30 s, still with no accelerator.  Rule
catalog and suppression workflow: docs/static-analysis.md.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from nerrf_tpu.analysis.engine import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
