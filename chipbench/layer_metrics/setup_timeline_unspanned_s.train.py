"""Seconds of set-up that neither the program nor its tracer can name: the
extent (process start to the window's first call) less `preprogram` less the
union of every program span in set-up.  None in a process that ran a cell
before.
(`chipbench/setup_timeline.py`)"""

from chipbench import setup_timeline


def read(run):
    return setup_timeline.read(run, "unspanned")
