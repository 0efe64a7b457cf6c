"""Seconds of set-up the program spent making its data (s): the union of its
`corpus_simulate`, `trace_lower`, `graph_lower`, `stream_tokenize`,
`stream_pack` and `dataset_upload` spans before the window.
(`chipbench/setup_timeline.py`)"""

from chipbench import setup_timeline


def read(run):
    return setup_timeline.read(run, "data")
