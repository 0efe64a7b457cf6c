"""The deep nerrflint tier: jaxpr-level program-contract verification.

Where the base rules (`nerrf_tpu/analysis/*.py`) read source ASTs, these
rules abstractly trace the *real entry points* — the serve bucket ladder,
the flat train-step boundary, the shard_map/pjit shims — via `jax.eval_shape`/`jax.make_jaxpr`/`jit.lower` over
`ShapeDtypeStruct` avals (no devices, no data, no compiles) and verify
four contracts:

  ============================  ============================================
  program-closure               warmup-compiled set == admission-reachable
                                signature set (the zero-recompile proof)
  donation-discipline           donated-then-read, un-donated train state,
                                wasted/forbidden/double donation
  collective-consistency        collective axis names vs the mesh spec,
                                PartitionSpec rank-match
  cache-key-coverage            jaxpr dependencies the CompileCache
                                fingerprint cannot see
  ============================  ============================================

Surfaces: ``nerrf lint --deep`` / ``python scripts/nerrflint.py --deep``
(both force a virtual multi-device CPU backend first), the tier-1 gate
``tests/test_programs.py`` (which also asserts the <30 s CPU budget), and
the chip-queue pre-flights in scripts/.  Findings flow through the same
engine schema, suppressions and baseline as every other rule.

Import discipline: this package imports jax only inside rule execution —
the base engine (and plain ``nerrf lint``) must stay importable with no
jax on the path.
"""

from nerrf_tpu.analysis.programs.abstract import prepare_backend
from nerrf_tpu.analysis.programs.cachekey import CacheKeyCoverage
from nerrf_tpu.analysis.programs.closure import SignatureClosure
from nerrf_tpu.analysis.programs.collectives import CollectiveConsistency
from nerrf_tpu.analysis.programs.donation import DonationDiscipline

DEEP_RULE_IDS = ("program-closure", "donation-discipline",
                 "collective-consistency", "cache-key-coverage")


def deep_rules():
    """The deep ruleset, in contract order (engine.main --deep appends
    these to the base rules)."""
    return [SignatureClosure(), DonationDiscipline(),
            CollectiveConsistency(), CacheKeyCoverage()]


__all__ = [
    "CacheKeyCoverage",
    "CollectiveConsistency",
    "DEEP_RULE_IDS",
    "DonationDiscipline",
    "SignatureClosure",
    "deep_rules",
    "prepare_backend",
]
