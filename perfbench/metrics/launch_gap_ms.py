"""Idle time on the device between one program's end and the next one's
start, median over consecutive programs of the window."""
from perfbench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window_ns
    mods = trace_reduce.modules_in(ctx.trace, lo, hi)
    if len(mods) < 2:
        return None
    gaps = [max(b[1] - (a[1] + a[2]), 0.0) for a, b in zip(mods[:-1], mods[1:])]
    return trace_reduce.median(gaps) / 1e6
