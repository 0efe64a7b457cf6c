"""nerrf_tpu — a TPU-native undo-computing framework.

A ground-up JAX/XLA implementation of the capability set specified by the
NERRF reference (Itz-Agasta/nerrf): streaming syscall-event ingest, a temporal
dependency graph, GraphSAGE-T + BiLSTM attack detection, an MCTS rollback
planner with batched value-net rollouts on TPU, and a verified file-level
rollback executor.

Design stance (see SURVEY.md §7): array-first event pipeline (structure-of-
arrays from the ingest bridge onward), fixed-capacity padded graph state that
is XLA-jit friendly, models as pure jitted functions, distributed execution via
`jax.sharding.Mesh` + XLA collectives over ICI/DCN rather than any NCCL-style
backend.
"""

# first, and jax-free: the tracer's epoch IS the program's first import, so
# the set-up timeline (docs/operations.md, "Time to first step") has one
# origin whatever module of the package a caller reaches for first
from nerrf_tpu import tracing as _tracing  # noqa: F401

__version__ = "0.1.0"

# Chip-side entry points (bench.py, train.run, the offline benchmarks)
# opt into the persistent XLA compilation cache explicitly via
# nerrf_tpu.utils.enable_compilation_cache() — NOT here: importing jax at
# package import would defeat the CLI's deliberate lazy-import startup.
