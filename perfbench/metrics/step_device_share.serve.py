"""Share of the traced window the device spends in step programs (launches
and padded tail steps together): what is left for forecasts."""
from perfbench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window_ns
    steps = trace_reduce.modules_in(ctx.trace, lo, hi, "jit_step_fn")
    if not steps:
        return None
    return 100.0 * sum(e[2] for e in steps) / (hi - lo)
