"""Mean duration of the window's `train_step_call` spans (ms): one call of
the program's train step as the program itself times it, its Python and the
runtime's call, never a wait for the device (`chipbench/program_spans.py`).
`host_dispatch_ms.train` times the same calls from outside."""

from chipbench import program_spans


def read(run):
    return program_spans.mean_call_ms(run)
