"""The program's records of set-up, for the readers of ``setup_s``'s parts.

Set-up is what the recorder holds that ENDS before the window opens
(``ctx.t0``), as ``state_build_s`` reads it, and starts with the run's
``deploy``: no program span of a run opens before its job deploys, so what
began earlier is another run's (the tests and the self-check run several in
a process; the driver runs one). Where the ring of a name has dropped
records the run may have held, the readers return None. A ``compile``
record counts where a program span was open (``parent`` != 0): what the
harness compiles with none open (its marker, the traced run's
``launch_scopes``, ``host_params``) is the harness's and not the program's.
"""

from __future__ import annotations

from typing import Optional

from perfbench import program_spans as ps

BACKEND_COMPILE = "backend_compile_duration"


def run_start(ctx) -> float:
    """The start of the newest ``deploy`` before the window; the process's
    start in a program without the span."""
    deploys = [r for r in ps.recorder().records("deploy") if r.end <= ctx.t0]
    return deploys[-1].start if deploys else float("-inf")


def in_setup(ctx, name: str) -> Optional[list]:
    """The records of ``name`` of the run's set-up, oldest first (as
    ``program_spans.in_window`` gives the window's)."""
    rec = ps.recorder()
    if rec is None or getattr(ctx, "t0", None) is None:
        return None
    start, records = run_start(ctx), rec.records(name)
    if rec.dropped(name) and (not records or records[0].start >= start):
        return None  # the ring wrapped inside the run
    return [r for r in records if r.start >= start and r.end <= ctx.t0]


def program_compiles(ctx) -> Optional[list]:
    """Set-up's ``compile`` records opened under a program span."""
    compiles = in_setup(ctx, "compile")
    return None if compiles is None else [r for r in compiles if r.parent != 0]


def covered_seconds(records: list) -> float:
    """Seconds the records cover, per thread: a program traced inside
    another's trace (jax reports both) counts once."""
    total = 0.0
    for thread in {r.thread for r in records}:
        end = float("-inf")
        for r in sorted((r for r in records if r.thread == thread), key=lambda r: r.start):
            if r.end > end:
                total += r.end - max(r.start, end)
                end = r.end
    return total
