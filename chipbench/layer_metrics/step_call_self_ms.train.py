"""Mean self time of the window's `train_step_call` spans (ms): the call
less its children (`train_step_execute`, the resolved executable's call):
the Python the program adds to each step, signature, lookup and re-wrap
(`chipbench/program_spans.py`)."""

from chipbench import program_spans


def read(run):
    return program_spans.mean_call_ms(run, self_only=True)
