"""Seconds of set-up inside the program's `compile_resolve` spans (their
union): every executable it obtained through its AOT cache, stage by stage.
(`chipbench/setup_timeline.py`)"""

from chipbench import setup_timeline


def read(run):
    return setup_timeline.read(run, "resolve")
