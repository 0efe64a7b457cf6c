"""Seconds from the process's start to the program's first import (the tracer's
epoch): interpreter, the caller's imports, `import jax`, the backend's start
where it precedes the program.  None in a process that ran a cell before.
(`chipbench/setup_timeline.py`)"""

from chipbench import setup_timeline


def read(run):
    return setup_timeline.read(run, "preprogram")
