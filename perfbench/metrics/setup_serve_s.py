"""Seconds the probe's forecasts spend in the trainer, on either route: the
summed ``serve`` spans before the window (the padded batch up, the predict
program, the answer down) less the compiles under them, which
``setup_compile_s`` counts. 0 where the probe asks no forecast."""
from perfbench import setup_records as sr


def read(ctx):
    serves = sr.in_setup(ctx, "serve")
    compiles = sr.in_setup(ctx, "compile")
    if serves is None or compiles is None:
        return None
    if not serves:  # none asked, or a program that does not time them
        return 0.0 if getattr(ctx, "n_probe_forecasts", None) == 0 else None
    ids = {r.id for r in serves}
    compiling = sr.covered_seconds([r for r in compiles if r.parent in ids])
    return sum(r.end - r.start for r in serves) - compiling
