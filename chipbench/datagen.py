"""Corpus generation for the training cells: seeded traces -> window arrays.

The calls are the program's own: `nerrf_tpu.data.make_corpus` and the
per-trace lowering `train.build_dataset` runs (`windows_of_trace`).
"""

from __future__ import annotations


def corpus_base_seed(seed: int) -> int:
    """A seed of any size -> the simulator's base seed.  The simulator
    stamps events at ``1.7e18 + seed * 1e13`` ns, which leaves int64 near
    seed 7.5e5: so the seed is hashed into [0, 500000)."""
    import numpy as np

    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               % 500_000)


def trace_windows(config: dict, base: int, index: int) -> dict:
    """Trace ``index`` of the configuration's recipe -> its stacked window
    arrays.  Odd traces carry the attack: the alternation `make_corpus`
    gives ``attack_fraction`` 0.5."""
    import numpy as np

    from nerrf_tpu.config import from_dict
    from nerrf_tpu.data import make_corpus
    from nerrf_tpu.train.data import DatasetConfig, windows_of_trace

    c = config["corpus"]
    attack = round((index + 1) * c["attack_fraction"]) - round(
        index * c["attack_fraction"]) == 1
    (trace,) = make_corpus(
        1, attack_fraction=1.0 if attack else 0.0,
        base_seed=base + index, duration_sec=c["duration_sec"],
        num_target_files=c["num_target_files"],
        benign_rate_hz=c["benign_rate_hz"])
    samples = windows_of_trace(trace, from_dict(DatasetConfig,
                                                config["dataset"]))
    if not samples:
        return {}
    return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


def make_windows(config: dict, seed: int, num_traces: int,
                 num_windows: int) -> dict:
    """Exactly ``num_windows`` windows drawn (seeded, without replacement)
    from the windows of ``num_traces`` seeded traces, benign and attack
    alternating; the same seed gives the same arrays.
    Fewer windows than asked is an error: the resident dataset's size is
    part of the compiled step, and a short one would train a smaller batch
    in silence."""
    import numpy as np

    base = corpus_base_seed(seed)
    parts = [trace_windows(config, base, i) for i in range(num_traces)]
    parts = [p for p in parts if p]
    got = sum(len(p["node_feat"]) for p in parts)
    if got < num_windows:
        raise RuntimeError(
            f"dataset smaller than the batch: {num_traces} traces gave "
            f"{got} windows, the cell needs {num_windows}; raise the cell's "
            "`traces`")
    pick = np.sort(np.random.default_rng([int(seed), 0xda7a]).choice(
        got, size=num_windows, replace=False))
    return {k: np.concatenate([p[k] for p in parts])[pick]
            for k in parts[0]}
