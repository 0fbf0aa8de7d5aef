"""The part of a forecast's latency that is the program's: the end of its
``emit`` span minus the start of the ``ingest_file`` span of its hand-over,
median over the window's forecasts."""
from perfbench import program_spans as ps
from perfbench import trace_reduce


def read(ctx):
    parts = [ps.in_window(ctx, name) for name in ("forecast", "emit", "ingest_file")]
    if any(p is None for p in parts):
        return None
    forecasts, emits, files = parts
    emitted = {r.parent: r.end for r in emits}
    began = {r.id: r.start for r in files}
    return trace_reduce.median([
        (emitted[f.id] - began[f.parent]) * 1e3
        for f in forecasts if f.id in emitted and f.parent in began
    ])
