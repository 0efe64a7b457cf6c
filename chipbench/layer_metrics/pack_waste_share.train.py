"""Share of the resident packed sequences' positions that are padding, in
percent: the program's own gauge ``stream_pack_waste_fraction``
(`nerrf_tpu/data/stream.py::pack_documents`).  Every padded position is
computed and trains nothing.  None where the program has no such gauge."""


def read(run):
    waste = run["counters"].get("stream_pack_waste_fraction")
    return None if waste is None else 100.0 * float(waste)
