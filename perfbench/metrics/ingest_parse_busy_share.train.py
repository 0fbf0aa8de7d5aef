"""Share of the window the producer thread spends reading, parsing and
staging: self time of the ``read``, ``parse`` and ``stage`` spans (the
``pool_wait`` inside a ``stage`` is not its own time) over the window."""
from perfbench import program_spans as ps


def read(ctx):
    parts = [ps.in_window(ctx, name) for name in ("read", "parse", "stage")]
    if any(p is None for p in parts) or not parts[1]:
        return None
    return 100.0 * sum(r.self_s for p in parts for r in p) / ps.window_s(ctx)
