"""Device time of one launch (one SGD step on one row: the program that
``step_many_dense`` runs), median over the window's launches."""
from perfbench import trace_reduce


def read(ctx):
    launches = ctx.kind.launches(ctx)
    if not launches:
        return None
    return trace_reduce.median([e[2] for e in launches]) / 1e6
