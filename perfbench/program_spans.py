"""The program's own spans, for the per-layer readers that read them.

Since PR 25 the program notes every timed block of its fused SPMD route as a
record (name, start, end, thread, parent, key, self time, attributes) in a
process-wide recorder, ``omldm_tpu.utils.tracing.RECORDER``, on
``time.perf_counter``: the clock of the harness's own window (``ctx.t0``,
``ctx.t1``). The recorder lives in the harness's process and outlives the job,
so the readers find it after the run. The records carry what the profiler
trace's ``omldm.*`` spans do not (parent, key, counters, self time), so the
readers here take the program's spans from the recorder and not from the
trace (``trace_reduce.load`` keeps those only to name ``breakdown.idle_gaps``);
where a reader needs the device trace beside them, the two ends of the window
(the start of ``perfbench.window`` against ``ctx.t0``, the end of
``perfbench.drain`` against ``ctx.t1``) map one clock onto the other.

Everything returns None where there is nothing sound to read, and the reader
then returns None: a program without the recorder (the parent of PR 25), a
ring that has dropped records the window may have held, the two ends of the
window disagreeing by more than ``MAX_CLOCK_DISAGREEMENT_NS``, or another
number of ``fit`` spans than of step programs on the device.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce

STEP_PROGRAM = "jit_step_fn"
HANDOVER_SPAN = "perfbench.handover"
DRAIN_SPAN = "perfbench.drain"
MAX_CLOCK_DISAGREEMENT_NS = 0.5e6

# spans in which the producer thread only waits for the dispatch thread: a
# device gap under one of them is attributed to what that thread was doing
PRODUCER_WAITS = ("quiesce", "pool_wait", "dispatcher_close")
# records that are not blocks of the route's two threads
NOT_ON_TIMELINE = ("compile",)


def recorder():
    """The program's recorder, or None in a program that has none."""
    try:
        from omldm_tpu.utils import tracing
    except ImportError:
        return None
    return getattr(tracing, "RECORDER", None)


def in_window(ctx, name: str) -> Optional[list]:
    """The records of ``name`` whose start lies in the window, by start."""
    rec = recorder()
    if rec is None or getattr(ctx, "t0", None) is None:
        return None
    records = rec.records(name)
    if rec.dropped(name) and (not records or records[0].start >= ctx.t0):
        return None  # the ring wrapped inside the window
    return sorted((r for r in records if ctx.t0 <= r.start <= ctx.t1),
                  key=lambda r: r.start)


def durations_ms(records: list) -> List[float]:
    return [(r.end - r.start) * 1e3 for r in records]


def window_s(ctx) -> float:
    return ctx.t1 - ctx.t0


class ClockMap:
    """Host clock (seconds) to trace clock (nanoseconds) and back, straight
    through two points read on both."""

    def __init__(self, t0: float, t1: float, lo_ns: float, hi_ns: float):
        self.t0, self.lo = t0, lo_ns
        self.ns_per_s = (hi_ns - lo_ns) / (t1 - t0)

    def to_ns(self, t: float) -> float:
        return self.lo + (t - self.t0) * self.ns_per_s

    def to_s(self, ns: float) -> float:
        return self.t0 + (ns - self.lo) / self.ns_per_s


def _clock_points(ctx) -> Optional[Tuple[float, float]]:
    """Two points the harness reads on both clocks a few microseconds apart,
    on the trace's clock: ``perfbench.window`` opens just before ``t0`` is
    read, and ``t1`` is read just after ``perfbench.drain`` closes. (The window
    span itself closes later: the open loop sums its counters between ``t1``
    and the span's end, 0.8 ms on the chip at PR 25.) A trace with no drain
    span has the window's end for the second point."""
    lo, hi = ctx.window_ns
    if ctx.trace is None or hi <= lo or getattr(ctx, "t0", None) is None or ctx.t1 <= ctx.t0:
        return None
    drains = [s + d for name, s, d in ctx.trace.spans
              if name == DRAIN_SPAN and lo <= s and s + d <= hi]
    return lo, max(drains) if drains else hi


def clock_disagreement_ns(ctx) -> Optional[float]:
    """The offset between the clocks at the second point minus the first."""
    points = _clock_points(ctx)
    if points is None:
        return None
    return (points[1] - ctx.t1 * 1e9) - (points[0] - ctx.t0 * 1e9)


def clock_map(ctx) -> Optional[ClockMap]:
    off = clock_disagreement_ns(ctx)
    if off is None or abs(off) > MAX_CLOCK_DISAGREEMENT_NS:
        return None
    return ClockMap(ctx.t0, ctx.t1, *_clock_points(ctx))


def joined_fits(ctx) -> Optional[List[tuple]]:
    """``(fit record, step execution)`` pairs: the k-th ``fit`` span of the
    window, by the trainer's step ordinal, with the k-th execution of the
    step program that starts in it on the device."""
    fits = in_window(ctx, "fit")
    if not fits or clock_map(ctx) is None:
        return None
    lo, hi = ctx.window_ns
    steps = trace_reduce.modules_in(ctx.trace, lo, hi, STEP_PROGRAM)
    if len(steps) != len(fits):
        return None
    return list(zip(sorted(fits, key=lambda r: r.key), steps))


def completion_lags_ms(ctx) -> Optional[List[float]]:
    """Device end of each step minus the host start of its ``fit``."""
    pairs = joined_fits(ctx)
    if pairs is None:
        return None
    cm = clock_map(ctx)
    return [(cm.to_s(start + dur) - fit.start) * 1e3 for fit, (_, start, dur) in pairs]


class Timeline:
    """The innermost span covering a time, among records whose spans nest
    (one thread, or threads that never run at once)."""

    def __init__(self, records: list):
        self.records = sorted(records, key=lambda r: (r.start, -r.end))
        self.starts = [r.start for r in self.records]
        self.by_id = {r.id: r for r in self.records}

    def at(self, t: float):
        i = bisect.bisect_right(self.starts, t) - 1
        r = self.records[i] if i >= 0 else None
        while r is not None and r.end < t:
            r = self.by_id.get(r.parent)
        return r


def handover_idle(ctx) -> Optional[List[Tuple[float, str, bool]]]:
    """``(seconds, label, attributed)`` for every device-idle gap of the
    window whose middle lies in a ``perfbench.handover`` span (the rule of
    ``trace_reduce.breakdown``). The label is the innermost program span
    covering that middle on the producer thread (the caller of
    ``run_file_fused``); where the producer only waits for the dispatch
    thread, ``<wait>><span>`` adds what that thread was in. Attributed: that
    span has no child span, so it names one thing."""
    cm = clock_map(ctx)
    files = in_window(ctx, "ingest_file")
    if cm is None or files is None:
        return None
    rec = recorder()
    records = []
    for name in rec.names():
        if name in NOT_ON_TIMELINE:
            continue
        got = in_window(ctx, name)
        if got is None:
            return None
        records += got
    producer_threads = {r.thread for r in files}
    producer = Timeline([r for r in records if r.thread in producer_threads])
    dispatch = Timeline([r for r in records if r.thread not in producer_threads])
    has_child = {r.parent for r in records}
    lo, hi = ctx.window_ns
    handovers = sorted((s, s + d) for name, s, d in ctx.trace.spans if name == HANDOVER_SPAN)
    starts = [a for a, _ in handovers]
    out = []
    for a, b in trace_reduce.idle_gaps(ctx.trace, lo, hi):
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or mid >= handovers[i][1]:
            continue
        t = cm.to_s(mid)
        span = producer.at(t)
        label = span.name if span is not None else "no_program_span"
        if span is not None and span.name in PRODUCER_WAITS:
            behind = dispatch.at(t)
            if behind is not None:
                span, label = behind, label + ">" + behind.name
        out.append(((b - a) / 1e9, label, span is not None and span.id not in has_child))
    return out


def by_label(gaps: List[Tuple[float, str, bool]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for seconds, label, _ in gaps:
        out[label] = out.get(label, 0.0) + seconds
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
