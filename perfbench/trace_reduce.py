"""From a profiler trace (``.xplane.pb``) to what the per-layer readers need.

One place, so every PR computes a device number the same way. The layout was
read off a TPU v5e trace by hand (``testdata/scout_v5e.xplane.pb.gz`` is that
trace, and the self-check reduces it to numbers worked out beside it):

- plane ``/device:TPU:<n>``: line ``XLA Modules`` has one event per program
  execution, named ``jit_<function>(<fingerprint>)``; line ``XLA Ops`` has the
  operations inside them (nested ones overlap their parents, so busy time is
  the UNION of intervals, never a sum);
- plane ``/host:CPU``: ``jax.profiler.TraceAnnotation`` spans appear under
  their own names, on the same clock: the harness's (``perfbench.*``) and,
  since PR 25, the program's (``omldm.<span>``, ``omldm_tpu/utils/tracing``).

Times are nanoseconds from the start of the trace.
"""

from __future__ import annotations

import gzip
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

WINDOW_SPAN = "perfbench.window"
SPAN_PREFIX = "perfbench."  # the harness's own spans
PROGRAM_PREFIX = "omldm."  # the program's, kept beside them to name idle gaps


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)  # device plane -> XLA Ops
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # -> XLA Modules
    spans: List[Event] = field(default_factory=list)  # perfbench.* and omldm.* host spans

    @property
    def devices(self) -> List[str]:
        return sorted(self.modules)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = trace.ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dest = trace.modules.setdefault(plane.name, [])
                else:
                    continue
                dest.extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        trace.spans.append((e.name, e.start_ns, e.duration_ns))
    for evs in list(trace.ops.values()) + list(trace.modules.values()):
        evs.sort(key=lambda e: e[1])
    trace.spans.sort(key=lambda e: e[1])
    return trace


def window_of(trace: Trace) -> Tuple[float, float]:
    """The measured window inside the trace: the ``perfbench.window`` span,
    or, in a trace without one, the extent of all harness spans."""
    for name, start, dur in trace.spans:
        if name == WINDOW_SPAN:
            return start, start + dur
    own = [(s, s + d) for name, s, d in trace.spans if name.startswith(SPAN_PREFIX)]
    if not own:
        raise ValueError("the trace holds no perfbench span to take the window from")
    return min(a for a, _ in own), max(b for _, b in own)


def _clip(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    devices in the trace."""
    if not trace.ops:
        return 0.0
    total = 0.0
    for evs in trace.ops.values():
        total += sum(b - a for a, b in merge(_clip(evs, lo, hi)))
    return total / len(trace.ops) / 1e9


def idle_share_percent(trace: Optional[Trace], lo: float, hi: float) -> Optional[float]:
    """Share of the window in which no operation ran on the device; nothing
    where the trace holds no device operation."""
    if trace is None or not trace.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - busy_seconds(trace, lo, hi) / ((hi - lo) / 1e9))


def program_name(module_event_name: str) -> str:
    """``jit_step_fn(1721779...)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def modules_in(trace: Trace, lo: float, hi: float, program: Optional[str] = None,
               device: Optional[str] = None) -> List[Event]:
    """Program executions that START inside the window, on one device (the
    first by default), optionally of one program name."""
    if not trace.modules:
        return []
    evs = trace.modules[device or trace.devices[0]]
    return [e for e in evs if lo <= e[1] < hi
            and (program is None or program_name(e[0]) == program)]


def by_fingerprint(events: Sequence[Event]) -> Dict[str, List[Event]]:
    groups: Dict[str, List[Event]] = {}
    for e in events:
        groups.setdefault(e[0], []).append(e)
    return groups


def median(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    m = len(v) // 2
    return v[m] if len(v) % 2 else 0.5 * (v[m - 1] + v[m])


def idle_gaps(trace: Trace, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of the window in which nothing ran on the first device."""
    if not trace.ops:
        return [(lo, hi)]
    busy = merge(_clip(trace.ops[sorted(trace.ops)[0]], lo, hi))
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def host_activities(trace: Trace, times: Sequence[float]) -> List[str]:
    """What the host was doing at each of ``times``: the innermost span that
    covers it, the program's where one does and the harness's otherwise (never
    the window span itself). Innermost is shortest: spans of one thread nest,
    and of two threads' spans the shorter one names the narrower thing. (A
    saturated window has some 10^4 gaps and, with the program's, as many
    spans: one vector pass a gap, not a Python loop over the spans.)"""
    import numpy as np

    spans = [e for e in trace.spans if e[0] != WINDOW_SPAN]
    starts = np.array([e[1] for e in spans], np.float64)
    durs = np.array([e[2] for e in spans], np.float64)
    out = []
    for t in times:
        covering = np.nonzero((starts <= t) & (t < starts + durs))[0]
        if len(covering):
            out.append(spans[covering[np.argmin(durs[covering])]][0])
        else:
            out.append("host.outside_harness_spans")
    return out


def host_activity(trace: Trace, t: float) -> str:
    return host_activities(trace, [t])[0]


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """``device_ops``: the operations that took most device time (seconds,
    summed by name; a parent such as a while loop counts its children's time
    too). ``idle_gaps``: idle seconds of the device by what the host was
    doing at the middle of each gap."""
    ops: Dict[str, float] = {}
    if trace.ops:
        for name, start, dur in trace.ops[sorted(trace.ops)[0]]:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                key = name.split(" = ")[0][:48] + " " + _opcode(name)
                ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
    gaps: Dict[str, float] = {}
    idle = idle_gaps(trace, lo, hi)
    for (a, b), key in zip(idle, host_activities(trace, [0.5 * (a + b) for a, b in idle])):
        gaps[key] = gaps.get(key, 0.0) + (b - a) / 1e9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}


def _opcode(hlo_line: str) -> str:
    """The operation of an HLO text line: ``%x = f32[..]{..} reduce(...)`` ->
    ``reduce``."""
    m = re.search(r"\}\s*\)?\s*([a-z][a-z0-9\-_.]*)\(", hlo_line)
    return m.group(1) if m else ""


def launch_sized(events: Sequence[Event]) -> List[Event]:
    """Of the executions of one program name, those of the fingerprint that
    runs longest. A step program has one fingerprint per batch shape; the
    launch-sized one (16 times the rows of the padded tail step, every other
    cost shared) is the longest. With one shape there is nothing to choose."""
    groups = by_fingerprint(events)
    if not groups:
        return []
    return max(groups.values(), key=lambda evs: median([e[2] for e in evs]))
