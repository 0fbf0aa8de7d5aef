"""Device time of one launch-sized step program (``jit_step_fn``), median
over the window's launches."""
from perfbench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window_ns
    steps = trace_reduce.launch_sized(trace_reduce.modules_in(ctx.trace, lo, hi, "jit_step_fn"))
    if not steps:
        return None
    return trace_reduce.median([e[2] for e in steps]) / 1e6
