"""The wait for the dispatch queue to drain before a forecast may touch the
trainer: median ``quiesce`` span under a ``forecast`` span."""
from perfbench import program_spans as ps
from perfbench import trace_reduce


def read(ctx):
    waits = ps.in_window(ctx, "quiesce")
    if waits is None:
        return None
    return trace_reduce.median(ps.durations_ms([r for r in waits if r.parent_name == "forecast"]))
