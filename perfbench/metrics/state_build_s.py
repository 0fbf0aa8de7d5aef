"""Seconds the trainer takes to build its state: the ``build_state`` span
(template, leaves built on the host, ``device_put``), which lies before the
window."""
from perfbench import program_spans as ps


def read(ctx):
    rec = ps.recorder()
    if rec is None or getattr(ctx, "t0", None) is None:
        return None
    built = [r for r in rec.records("build_state") if r.end <= ctx.t0]
    return built[-1].end - built[-1].start if built else None
