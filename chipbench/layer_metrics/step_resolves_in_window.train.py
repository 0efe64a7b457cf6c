"""`compile_resolve` spans of the program that started at or after the
window's first step call: executables obtained (cache read or compile)
while the window ran.  Expected: 0 (`chipbench/program_spans.py`)."""

from chipbench import program_spans


def read(run):
    return program_spans.resolves_in_window(run)
