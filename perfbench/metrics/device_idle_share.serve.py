"""Share of the traced window in which no operation ran on the device."""
from perfbench import trace_reduce


def read(ctx):
    return trace_reduce.idle_share_percent(ctx.trace, *ctx.window_ns)
