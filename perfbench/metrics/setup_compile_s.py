"""Seconds set-up spends tracing, lowering and compiling the program's own
programs: the ``compile`` records, all three stages, opened under a program
span and ended before the window (``perfbench/setup_records.py``)."""
from perfbench import setup_records as sr


def read(ctx):
    compiles = sr.program_compiles(ctx)
    return None if compiles is None else sr.covered_seconds(compiles)
