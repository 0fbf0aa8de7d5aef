"""Share of the window in which the program's host side runs at all: the
``ingest_file`` spans of the window over its length. The rest is the harness
waiting on the device."""
from perfbench import program_spans as ps


def read(ctx):
    files = ps.in_window(ctx, "ingest_file")
    if not files:
        return None
    return 100.0 * sum(r.end - r.start for r in files) / ps.window_s(ctx)
