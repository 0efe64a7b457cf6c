from nerrf_tpu.planner.device_mcts import DeviceMCTS
from nerrf_tpu.planner.domain import UndoAction, UndoDomain, UndoPlan, ActionKind
from nerrf_tpu.planner.mcts import MCTSConfig, MCTSPlanner


def make_planner(domain, value, cfg: MCTSConfig, kind: str = "auto"):
    """One constructor for both planner families.

    ``kind='host'`` → batched-leaf :class:`MCTSPlanner` (``value`` used as
    the batch evaluator); ``kind='device'`` → single-program
    :class:`DeviceMCTS`, handed the value net as the pure
    ``(value.apply_fn, value.params)`` pair so the weights ride the
    compiled search's runtime arguments — embedding a params-closed
    callable would recompile per incident and forfeit the program cache.
    ``value=None`` falls back to the heuristic either way.

    ``kind='auto'`` (default) picks ``device`` on EVERY backend, CPU
    included: MTTR is planner-bound (m1 recovery artifact: plan time
    dominates), and on the CPU backend the single-XLA-program search
    beats the Python host loop.  Whether it also beats it on a TPU is not
    measured (ROADMAP Queue 1 item 5).  The host planner remains for
    explicit comparison runs and is what auto uses — saying so on stderr —
    when the device program cannot be built: jax compiles lazily, so auto
    forces the compile via ``warmup()`` INSIDE the guard; construction
    alone succeeding proves nothing."""
    if kind == "auto":
        try:
            planner = DeviceMCTS(
                domain, cfg,
                value_apply=value.apply_fn if value else None,
                value_params=value.params if value else None)
            planner.warmup()  # the real compile — the failure we guard
            return planner
        except Exception as e:  # noqa: BLE001 — planning must degrade, not die
            import sys

            print(f"[planner] device planner unavailable "
                  f"({type(e).__name__}: {e}); using host search",
                  file=sys.stderr, flush=True)
            kind = "host"
    if kind == "device":
        return DeviceMCTS(
            domain, cfg,
            value_apply=value.apply_fn if value else None,
            value_params=value.params if value else None)
    if kind != "host":
        raise ValueError(f"unknown planner kind {kind!r}")
    return MCTSPlanner(domain, value, cfg)


__all__ = [
    "make_planner",
    "UndoAction",
    "UndoDomain",
    "UndoPlan",
    "ActionKind",
    "MCTSConfig",
    "MCTSPlanner",
    "DeviceMCTS",
]
