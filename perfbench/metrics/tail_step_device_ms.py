"""Device time of the padded tail step: median over the executions of the
step program that the ordinal join pairs with ``fit`` spans marked ``tail``."""
from perfbench import program_spans as ps
from perfbench import trace_reduce


def read(ctx):
    pairs = ps.joined_fits(ctx)
    if pairs is None:
        return None
    tails = [dur / 1e6 for fit, (_, _, dur) in pairs if (fit.attrs or {}).get("tail")]
    return trace_reduce.median(tails)
