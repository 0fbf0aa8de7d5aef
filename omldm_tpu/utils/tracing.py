"""Tracing: the program's one span API, the profiler hook, and StepTimer.

The reference has no tracing at all (SURVEY.md section 5: its only cost
observability is CountableSerial byte accounting). Four things live here:

- :func:`span` -- THE timed block of the package. A context manager that
  enters ``jax.profiler.TraceAnnotation("omldm.<name>")`` (so whenever a
  profiler session is on -- ``--profileDir``, a benchmark's traced run --
  the block lies in the trace's ``/host:CPU`` plane on the same clock as
  the device's ``XLA Ops``; with no session on that is the profiler's own
  inactive check) and notes one :class:`Record` into a :class:`Recorder`:
  name, start and end on :data:`omldm_tpu.utils.clock.PERF`, the thread,
  the enclosing span (or, on a thread that :func:`adopt`-ed one, the span
  that caused it), a ``key`` shared with the other layers' records (file,
  launch and step ordinals, a forecast's id) and its self time. Per name
  the recorder keeps exact ``count``/``total`` and a bounded ring of the
  newest records; ``**counts`` add to the name's counters at the same
  boundary (``rows``, ``rows_padded``: the phase table prints them beside
  the seconds); :meth:`Recorder.add_counts` adds what is known only later
  (a launch's device counters). :data:`RECORDER` is the process-wide recorder ``span``
  writes to; it is always on (two clock reads, one inactive annotation and
  one ring slot a span -- measured in ``PERF.md``).
  ``runtime.telemetry.PhaseProfile`` is the table over a recorder, so the
  host plane's phases are these spans.
- a ``jax.monitoring`` listener, registered on import, that notes every
  program jax traces, lowers or compiles as a ``compile`` record under the
  span that called it, so a measured window that compiled says so.
- :func:`trace` -- context manager around ``jax.profiler`` writing a
  TensorBoard-loadable trace directory (op/fusion timeline, HBM usage).
- :class:`StepTimer` -- host-side wall-clock accounting for the host
  plane's flush/serve launches: per-step ms percentiles and steps/sec,
  emitted alongside the Statistics plane's bytesShipped counters
  (FlinkHub.scala:118-127). ROADMAP C6 folds it into :func:`span`.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation

from omldm_tpu.utils import clock

# bounded per-name sample window (percentiles and records cover the most
# recent window; counts and totals stay exact)
RING_CAP = 4096

ANNOTATION_PREFIX = "omldm."

# the duration events the installed jax (0.9) emits around a program's
# trace, lowering and backend compile (a persistent-cache hit still emits
# the last one, timing the retrieval)
COMPILE_EVENT_PREFIX = "/jax/core/compile/"


class Record(NamedTuple):
    """One finished span. Times are ``clock.PERF`` seconds."""

    id: int            # process-wide ordinal, in order of entry
    name: str
    start: float
    end: float
    self_s: float      # duration minus its children on the same thread
    thread: int        # threading.get_ident() of the thread that ran it
    parent: int        # id of the enclosing (or adopted) span; 0 = none
    parent_name: str
    key: Any
    attrs: Optional[Dict[str, Any]]


class Ring:
    """Bounded float sample ring (the ServeStats layout) with an EXACT
    running total -- percentiles summarize the retained window, sums and
    counts stay true for the whole stream. ``records=True`` keeps one
    object per sample beside it (a span's :class:`Record`)."""

    __slots__ = ("count", "total", "_cap", "_ring", "_records", "_n", "_i")

    def __init__(self, cap: int = RING_CAP, records: bool = False):
        self.count = 0
        self.total = 0.0
        self._cap = cap
        self._ring: List[float] = [0.0] * cap
        self._records: Optional[list] = [None] * cap if records else None
        self._n = 0
        self._i = 0

    def note(self, value: float, record=None) -> None:
        self.count += 1
        self.total += value
        i = self._i
        self._ring[i] = value
        if self._records is not None:
            self._records[i] = record
        self._i = i + 1 if i + 1 < self._cap else 0
        if self._n < self._cap:
            self._n += 1

    def percentiles(self, qs=(50.0, 99.0)) -> Tuple[float, ...]:
        if self._n == 0:
            return tuple(0.0 for _ in qs)
        p = np.percentile(self._ring[: self._n], qs)
        return tuple(float(v) for v in np.atleast_1d(p))

    @property
    def dropped(self) -> int:
        """Samples the ring no longer holds."""
        return self.count - self._n

    def _order(self) -> List[int]:
        """Retained slots, oldest first."""
        first = self._i if self._n == self._cap else 0
        return [(first + k) % self._cap for k in range(self._n)]

    def records(self) -> list:
        """The retained records, oldest first (samples noted without one
        are skipped)."""
        if self._records is None:
            return []
        return [r for r in (self._records[k] for k in self._order())
                if r is not None]

    def merge(self, other: "Ring") -> None:
        count, total = self.count + other.count, self.total + other.total
        records = other._records
        for k in other._order():
            self.note(
                other._ring[k], records[k] if records is not None else None
            )
        # the other ring's whole stream, not only its retained window
        self.count, self.total = count, total


def _parent(outer: Optional["Span"]) -> Tuple[int, str]:
    """Id and name a record keeps of the span it was opened under."""
    return (outer.id, outer.name) if outer is not None else (0, "")


class Span:
    """One timed block, as :meth:`Recorder.span` hands it out. Use as a
    context manager; :meth:`add` and :meth:`set` may be called until it
    exits (a file's row count is known only at its end)."""

    __slots__ = (
        "_recorder", "_annotation", "_counts", "_attrs", "_children_s",
        "name", "key", "id", "start", "parent", "parent_name",
    )

    def __init__(self, recorder: "Recorder", name: str, key, counts):
        self._recorder = recorder
        self._counts = counts or None
        self._attrs: Optional[Dict[str, Any]] = None
        self._children_s = 0.0
        self.name = name
        self.key = key
        self.id = 0

    def add(self, **counts) -> None:
        """Add to the counters of the span's name."""
        if self._counts is None:
            self._counts = counts
        else:
            for k, v in counts.items():
                self._counts[k] = self._counts.get(k, 0) + v

    def set(self, **attrs) -> None:
        """Attributes kept on the record (``tail=True``)."""
        if self._attrs is None:
            self._attrs = attrs
        else:
            self._attrs.update(attrs)

    def __enter__(self) -> "Span":
        if self.key is None:
            self._annotation = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        else:
            self._annotation = TraceAnnotation(
                ANNOTATION_PREFIX + self.name, key=self.key
            )
        self._annotation.__enter__()
        local = self._recorder._thread_state()
        self.parent, self.parent_name = _parent(
            local.stack[-1] if local.stack else local.cause
        )
        self.id = next(self._recorder._ids)
        local.stack.append(self)
        self.start = clock.PERF()
        return self

    def __exit__(self, *exc):
        end = clock.PERF()
        local = self._recorder._thread_state()
        local.stack.pop()
        seconds = end - self.start
        if local.stack:
            local.stack[-1]._children_s += seconds
        self._recorder._note(
            Record(
                self.id, self.name, self.start, end,
                max(seconds - self._children_s, 0.0), local.ident,
                self.parent, self.parent_name, self.key, self._attrs,
            ),
            self._counts,
        )
        self._annotation.__exit__(*exc)
        return False


class _SpanStats:
    """Per-name accounting: the ring holds SELF seconds and the records;
    ``total`` is the exact sum of whole durations; ``counts`` the name's
    counters."""

    __slots__ = ("ring", "total", "counts")

    def __init__(self, cap: int):
        self.ring = Ring(cap, records=True)
        self.total = 0.0
        self.counts: Dict[str, float] = {}


class Mark(NamedTuple):
    """A recorder's exact per-name totals at one moment: what
    :meth:`Recorder.summary` takes off to cover only what came after."""

    id: int  # records entered later have a larger id
    totals: Dict[str, Tuple[int, float, Dict[str, float]]]


class Recorder:
    """Where spans land: per name an exact count, exact total and self
    seconds, its counters, and a bounded ring of the newest records. Safe
    to note into from several threads."""

    def __init__(self, cap: int = RING_CAP):
        self.cap = cap
        self._stats: Dict[str, _SpanStats] = {}
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._lock = threading.Lock()
        self._local = threading.local()

    # --- writes ----------------------------------------------------------

    def span(self, name: str, key=None, **counts) -> Span:
        return Span(self, name, key, counts)

    def note_seconds(self, name: str, seconds: float) -> None:
        """Seconds clocked elsewhere (shard processes' parse clocks) under
        ``name``: counted and summed, no record."""
        with self._lock:
            stats = self._stats_for(name)
            stats.ring.note(seconds)
            stats.total += seconds

    def add_counts(self, name: str, **counts) -> None:
        """Add to the counters of ``name`` outside any span of it: what a
        launch counted on the device is known only where its outputs are
        read (``slots``, ``slots_distinct``, ``overflow_launches`` of
        ``fit``)."""
        with self._lock:
            into = self._stats_for(name).counts
            for k, v in counts.items():
                into[k] = into.get(k, 0) + v

    def _stats_for(self, name: str) -> _SpanStats:
        stats = self._stats.get(name)
        if stats is None:
            stats = self._stats[name] = _SpanStats(self.cap)
        return stats

    def _thread_state(self):
        local = self._local
        if not hasattr(local, "stack"):
            local.stack = []
            local.cause = None
            local.ident = threading.get_ident()
        return local

    def _note(self, record: Record, counts: Optional[dict]) -> None:
        with self._lock:
            stats = self._stats_for(record.name)
            stats.ring.note(record.self_s, record)
            stats.total += record.end - record.start
            if counts:
                for k, v in counts.items():
                    stats.counts[k] = stats.counts.get(k, 0) + v

    # --- cause across threads --------------------------------------------

    def current(self) -> Optional[Span]:
        """The innermost open span of the calling thread (or the cause it
        adopted): what a thread about to be started is working for."""
        local = self._thread_state()
        return local.stack[-1] if local.stack else local.cause

    def adopt(self, cause: Optional[Span]) -> None:
        """Name ``cause`` (another thread's open span) as the parent of the
        spans the calling thread opens at its top level. Self time stays
        per thread: an adopted parent's is not reduced."""
        self._thread_state().cause = cause

    # --- reads -----------------------------------------------------------

    def names(self) -> List[str]:
        return list(self._stats)

    def count(self, name: str) -> int:
        stats = self._stats.get(name)
        return stats.ring.count if stats is not None else 0

    def total_seconds(self, name: str) -> float:
        """Exact sum of the whole durations noted under ``name``."""
        stats = self._stats.get(name)
        return stats.total if stats is not None else 0.0

    def records(self, name: str) -> List[Record]:
        """The retained records of ``name``, oldest first."""
        stats = self._stats.get(name)
        if stats is None:
            return []
        with self._lock:
            return stats.ring.records()

    def dropped(self, name: str) -> int:
        """Records of ``name`` the ring no longer holds."""
        stats = self._stats.get(name)
        return stats.ring.dropped if stats is not None else 0

    def counts(self, name: str) -> Dict[str, float]:
        """The counters of ``name`` (``rows``, ``rows_padded``)."""
        stats = self._stats.get(name)
        if stats is None:
            return {}
        with self._lock:
            return dict(stats.counts)

    def mark(self) -> Mark:
        """The totals as of now, for :meth:`summary` to count from."""
        with self._lock:
            return Mark(next(self._ids), {
                name: (s.ring.count, s.ring.total, dict(s.counts))
                for name, s in self._stats.items()
            })

    def summary(self, name: str, since: Optional[Mark] = None):
        """``(count, self seconds, counters, retained self-second samples)``
        of ``name``: exact for the whole stream, or for what came after
        ``since`` (the samples then those of the records entered after
        it)."""
        stats = self._stats.get(name)
        if stats is None:
            return 0, 0.0, {}, []
        with self._lock:
            ring = stats.ring
            count, seconds, counts = ring.count, ring.total, dict(stats.counts)
            if since is None:
                return count, seconds, counts, ring._ring[: ring._n]
            samples = [r.self_s for r in ring.records() if r.id > since.id]
        count0, seconds0, counts0 = since.totals.get(name, (0, 0.0, {}))
        for k, v in counts0.items():
            counts[k] -= v
        return count - count0, seconds - seconds0, counts, samples

    def merge(self, other: "Recorder") -> None:
        """Fold another recorder in (rings concatenate, bounded)."""
        first, second = sorted((self._lock, other._lock), key=id)
        with first, second:
            for name, theirs in other._stats.items():
                mine = self._stats_for(name)
                mine.ring.merge(theirs.ring)
                mine.total += theirs.total
                for k, v in theirs.counts.items():
                    mine.counts[k] = mine.counts.get(k, 0) + v


# the process-wide recorder: always on, outlives any job
RECORDER = Recorder()
span = RECORDER.span
current = RECORDER.current
adopt = RECORDER.adopt


def _on_duration_event(event: str, seconds: float, **kwargs) -> None:
    """A ``compile`` record per trace, lowering or backend compile, named
    by the program and the stage, under the span that called it (whose
    self time holds these seconds too). jax reports an event when it
    ends."""
    if not event.startswith(COMPILE_EVENT_PREFIX):
        return
    local = RECORDER._thread_state()
    end = clock.PERF()
    RECORDER._note(
        Record(
            next(RECORDER._ids), "compile", end - seconds, end, seconds,
            local.ident,
            *_parent(local.stack[-1] if local.stack else local.cause),
            kwargs.get("fun_name"),
            {"stage": event[len(COMPILE_EVENT_PREFIX):]},
        ),
        None,
    )


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Profile the enclosed block with jax.profiler when ``log_dir`` is
    set; no-op otherwise (so call sites can pass the flag through
    unconditionally)."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


class StepTimer:
    """Record per-step wall-clock durations and summarize percentiles.

    ``cap`` bounds the retained sample window (a ring of the most recent
    ``cap`` durations, like ServeStats' latency ring): a timer on a
    per-record hot path of a long-lived streaming job must not grow host
    memory with the stream. ``count`` stays the TOTAL recorded;
    percentiles summarize the retained window. ``cap=None`` (default)
    keeps every sample — the pre-existing behavior for short-lived
    profiling timers."""

    def __init__(self, name: str = "step", cap: Optional[int] = None):
        self.name = name
        self.cap = cap
        self._durations_ms: List[float] = []
        self._total = 0
        # exact cumulative wall (ms) across ALL recorded steps — the ring
        # bounds the percentile window, not the total; the telemetry
        # plane's phase table reads this for fit/serve attribution
        self.total_ms = 0.0
        # a stack: one shared timer may wrap NESTED steps (a flush whose
        # protocol reply synchronously drains another pipeline's flush)
        self._starts: List[float] = []

    def __enter__(self):
        self._starts.append(time.perf_counter())
        return self

    def __exit__(self, *exc):
        self.record((time.perf_counter() - self._starts.pop()) * 1000.0)
        return False

    def record(self, duration_ms: float) -> None:
        if self.cap is not None and len(self._durations_ms) >= self.cap:
            self._durations_ms[self._total % self.cap] = float(duration_ms)
        else:
            self._durations_ms.append(float(duration_ms))
        self._total += 1
        self.total_ms += float(duration_ms)

    @property
    def count(self) -> int:
        return self._total

    def summary(self) -> Dict[str, float]:
        """{count, mean_ms, p50_ms, p99_ms, steps_per_sec}; zeros if empty."""
        import numpy as np

        if not self._durations_ms:
            return {"count": 0, "mean_ms": 0.0, "p50_ms": 0.0, "p99_ms": 0.0,
                    "steps_per_sec": 0.0}
        d = np.asarray(self._durations_ms)
        mean = float(d.mean())
        return {
            "count": self._total,
            "mean_ms": mean,
            "p50_ms": float(np.percentile(d, 50)),
            "p99_ms": float(np.percentile(d, 99)),
            "steps_per_sec": 1000.0 / mean if mean > 0 else 0.0,
        }

    def recent_p99(self, window: int = 256) -> float:
        """p99 ms over (approximately) the most recent ``window`` samples
        — the overload controller's cheap latency signal. Reads the tail
        of the sample ring without sorting the whole retained window;
        ring order scrambles sample recency slightly past one wrap, which
        is fine for a pressure signal. 0.0 when empty."""
        import numpy as np

        if not self._durations_ms:
            return 0.0
        tail = self._durations_ms[-min(window, len(self._durations_ms)):]
        return float(np.percentile(np.asarray(tail), 99))

    def reset(self) -> None:
        self._durations_ms = []
        self._total = 0
        self.total_ms = 0.0
