"""Mean host time for one ``train_step(state, rng)`` call to return, on the
benchmark's clock, over every call of the window (ms)."""


def read(run):
    calls = run["counters"].get("dispatch_s") or []
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
