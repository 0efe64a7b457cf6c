"""Toy sizes for the CPU tests.  A rehearsal is reachable only from Python
(`chipbench.run.main(argv, rehearsal=...)`), never from the command line,
and its result carries ``"rehearsal": true``: a number from such a run is
never a device number."""

from __future__ import annotations

import copy


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def apply(cell: dict, config: dict, rehearsal: dict):
    return (merge(cell, rehearsal.get("cell", {})),
            merge(config, rehearsal.get("config", {})))


# one toy the tests share: a 3-layer, 32-wide GNN over 128-node windows, a
# 16-wide BiLSTM over 8 sequences of 20 events, float32 so that the
# comparison with the reference is tight
TOY = {
    "config": {
        "corpus": {"duration_sec": 120.0, "num_target_files": 10,
                   "benign_rate_hz": 20.0},
        "dataset": {"graph": {"max_nodes": 128, "max_edges": 256},
                    "seq_len": 20, "max_seqs": 8},
        "train": {"model": {
            "gnn": {"hidden": 32, "num_layers": 3, "dtype": "float32"},
            "lstm": {"hidden": 16, "num_layers": 2, "dtype": "float32"}}},
    },
    "cell": {"batch": 4, "windows": 8, "traces": 2, "corpus_seed": 11,
             "table_rows": 4,
             "in_flight": 2,
             "trace_seconds": 1.0, "reference_block": 2,
             "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                        "grad_gap_mean": 1e-3, "update_gap": 1e-2,
                        "update_gap_mean": 1e-2}},
    "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1 << 34},
}
