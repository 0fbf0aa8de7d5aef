"""Programs traced, lowered or compiled inside the window (``compile``
records, one per ``jax.monitoring`` event): 0 where the probe compiled every
shape."""
from perfbench import program_spans as ps


def read(ctx):
    compiles = ps.in_window(ctx, "compile")
    return None if compiles is None else float(len(compiles))
