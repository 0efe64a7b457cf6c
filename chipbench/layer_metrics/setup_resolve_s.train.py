"""Summed duration of the program's `compile_resolve` spans in set-up (s):
fingerprint, cache read, deserialize or compile, persist, for every
executable the program obtained before the window
(`chipbench/program_spans.py`)."""

from chipbench import program_spans


def read(run):
    return program_spans.setup_resolve_s(run)
