"""Seconds of set-up the program spent making its data (s): the union of
its `corpus_simulate`, `graph_lower` and `dataset_upload` spans before the
window (`chipbench/program_spans.py`)."""

from chipbench import program_spans


def read(run):
    return program_spans.setup_data_s(run)
