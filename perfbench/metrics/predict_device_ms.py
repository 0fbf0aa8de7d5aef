"""Device time of the predict program, median over the window's forecasts."""
from perfbench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.window_ns
    preds = trace_reduce.modules_in(ctx.trace, lo, hi, "jit_predict_fn")
    if not preds:
        return None
    return trace_reduce.median([e[2] for e in preds]) / 1e6
