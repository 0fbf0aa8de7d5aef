"""The forecast's call into the trainer, median ``serve`` span: the padded
batch up, the wait behind every step still queued on the device, the predict
program (``predict_device_ms``), the answer down."""
from perfbench import program_spans as ps
from perfbench import trace_reduce


def read(ctx):
    calls = ps.in_window(ctx, "serve")
    if calls is None:
        return None
    return trace_reduce.median(ps.durations_ms(calls))
