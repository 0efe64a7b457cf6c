"""The held experts' load imbalance: the largest expert's assignments over
the mean, averaged over the layers, as the program's own gauge
``moe_expert_load_max_over_mean`` last read it (the step waits for its
fullest expert's tiles).  None where the program has no such gauge."""


def read(run):
    value = run["counters"].get("moe_expert_load_max_over_mean")
    return float(value) if value else None
