"""The multi-token-prediction module's share of the step: device time under
``mtp_embed_proj`` (the two norms and the joining projection), ``mtp_block``
(its expert layer, whole) and ``mtp_head_loss`` (its pass through the main
model's head) over the device time of all the step's leaf operations, in
percent.  None where the trace has none of these scopes."""

from chipbench.work.glm47flash import MTP_GROUPS as GROUPS


def read(run):
    trace = run.get("trace") or {}
    scope_s = trace.get("scope_s") or {}
    seconds = sum(scope_s.get(name, 0.0) for name in GROUPS)
    total = trace.get("leaf_op_s")
    if not seconds or not total:
        return None
    return 100.0 * seconds / total
