"""Telemetry plane: unified metrics registry, continuous performance
heartbeats, phase-attributed hot-loop profiling, and sampled cross-process
round tracing.

The reference's ONLY observability is the terminate-time ``JobStatistics``
report on the Kafka ``performance`` stream (StatisticsOperator.scala:21-150,
SURVEY §3.5): the job is a black box until the silence timer kills it. This
runtime had accumulated accounting all over the place — ``Statistics``
counters on the hubs, ``StepTimer`` launch rings on the spokes,
``ServeStats`` latency rings per net, ``TransportCodec.encode_seconds``,
overload pressure, guard/lifecycle counters — with exactly one pull point:
the terminate fold. This module is the missing plane:

- :class:`MetricsRegistry` — counters (additive), gauges (last-write, with
  a max-combining variant), and bounded-ring histograms, with
  ``snapshot()``/``merge()`` as the single pull point. The existing
  accounting publishes INTO it (probes — zero-cost callables read at
  snapshot time — avoid double bookkeeping on the hot paths).
- :class:`TelemetryPlane` — armed per job by ``JobConfig.telemetry`` (or
  lazily by the first pipeline whose ``trainingConfiguration.telemetry``
  table arms it). UNSET (the default) = no telemetry objects anywhere and
  every route is the exact pre-plane code path, pinned like every prior
  plane. Armed, the plane clocks CONTINUOUS heartbeats: every
  ``statsEvery`` records (count-clocked — deterministic under replay) the
  job emits an incremental ``JobStatistics`` snapshot through the existing
  ``on_performance`` sink (the Kafka ``performance`` topic), plus a
  wall-clock idle tick (``idleMs``) so a stalled stream still reports.
  Heartbeats carry counters and latency percentiles, never holdout scores
  — scoring mid-stream would dispatch evaluation programs into the hot
  loop and break the unarmed bit-identity contract.
- :class:`PhaseProfile` — per-phase wall-clock accounting (bounded sample
  rings + EXACT total seconds) for the hot-loop phases ``read``/``parse``/
  ``stage``/``holdout``/``fit``/``device_wait``/``serve``/``ship``, wired
  through the spoke/ingest/serving paths and surfaced as the job's
  ``phase_table()`` — so ingest-wall work starts from measured
  attribution instead of guesses.
- :class:`SpanLog` — sampled (``traceSample`` = 1/N) span events for
  protocol rounds, keyed by the reliable transport's existing
  (networkId, seq) stamps (falling back to a local per-stream counter when
  the channel is unarmed), giving hub<->spoke round-trip latency as
  compact JSONL records (``spanPath``) plus a bounded in-memory ring.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from omldm_tpu.utils.tracing import Mark, Recorder, Ring, Span

# canonical hot-loop phase names (the rows of ``phase_table()``);
# PhaseProfile accepts any name — these are the ones the runtime wires
PHASES = (
    "read",        # source I/O: kafka poll / file block read
    "parse",       # bytes -> rows (JSON parse, C block parse)
    "stage",       # rows -> fixed-shape micro-batches (vectorize + batcher)
    "holdout",     # 8-of-10 test-set split bookkeeping
    "fit",         # training program dispatch (the StepTimer flush path)
    "device_wait", # blocking on device results (SPMD drain; 0 on host CPU)
    "serve",       # forecast predict dispatch (the serve StepTimer path)
    "ship",        # transport codec encode+decode (wire prep)
    # the fused SPMD route (runtime/spmd_bridge.py; spans of the
    # process-wide utils.tracing.RECORDER). Its read/parse/stage/fit/serve
    # are the rows above; holdout runs inside the C stager, under "stage"
    "ingest_file",       # one file through SPMDBridge.ingest_file
    "dispatcher_open",   # stage sets allocated, dispatch thread started
    "dispatcher_close",  # join on everything still queued
    "pool_wait",         # producer blocked: every stage set queued/in flight
    "split_lines",       # a block with a special line: copied and split
    "launch",            # dispatch thread: one stage set (copy + its fits)
    "copy_stage",        # ... its rows copied off the reused stage set
    "forecast",          # one forecast inside a file (children below; both routes)
    "fallback",          # a line left to the Python codec (same children)
    "decode",            # forecast line -> DataInstance -> COO row
    "quiesce",           # wait for the dispatch queue to drain
    "emit",              # the prediction sink
    "deploy",            # StreamJob._deploy: learner, bridge and trainer built
    "build_state",       # SPMDTrainer state: template, host build, placement
    "init_state_host",   # ... the leaves built on the host
    "place_state",       # ... device_put of every leaf
    "compile",           # a program traced, lowered or compiled (jax.monitoring)
)

SPAN_RING_CAP = 4096

DEFAULT_STATS_EVERY = 10_000
DEFAULT_IDLE_MS = 2_000.0


@dataclasses.dataclass
class TelemetryConfig:
    """Parsed ``JobConfig.telemetry`` / ``trainingConfiguration.telemetry``
    knobs."""

    # heartbeat cadence in RECORDS (count-clocked: the emission schedule
    # is a pure function of the record sequence, deterministic under
    # replay); <= 0 disables count-clocked heartbeats
    stats_every: int = DEFAULT_STATS_EVERY
    # wall-clock idle heartbeat: with activity pending since the last
    # beat, an idle stream still reports after this many ms (0 = off —
    # the one wall-clock knob, so replay determinism is opt-out only for
    # the idle tick, never for the count-clocked cadence)
    idle_ms: float = DEFAULT_IDLE_MS
    # span sampling rate 1/N on protocol sends (0 = spans off)
    trace_sample: int = 0
    # JSONL file for completed spans ("" = in-memory ring only)
    span_path: str = ""
    # in-memory completed-span ring cap
    span_cap: int = SPAN_RING_CAP
    # phase-attributed profiling on the hot paths (on by default when the
    # plane is armed; the hooks cost two perf_counter reads per block)
    phases: bool = True


_KNOBS = {
    "statsEvery": ("stats_every", int),
    "idleMs": ("idle_ms", float),
    "traceSample": ("trace_sample", int),
    "spanPath": ("span_path", str),
    "spanCap": ("span_cap", int),
    "phases": ("phases", None),  # bool-ish
}


def _parse_bool(v) -> bool:
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes", "on")
    return bool(v)


def parse_telemetry_spec(spec) -> Optional[TelemetryConfig]:
    """dict / spec-string / True -> TelemetryConfig; None / False / "" ->
    None (unarmed). Raises ValueError on unknown knobs or nonsense values
    — the control gate turns that into a request drop, the job
    constructor into a fail-fast (the serving/overload/lifecycle
    pattern)."""
    if spec is None or spec is False or spec == "":
        return None
    if spec is True:
        spec = {}
    if isinstance(spec, str):
        s = spec.strip()
        if s.lower() == "on":
            spec = {}
        else:
            out: dict = {}
            for part in s.split(","):
                part = part.strip()
                if not part:
                    continue
                if "=" not in part:
                    raise ValueError(
                        f"bad telemetry spec entry {part!r} (want k=v)"
                    )
                k, v = part.split("=", 1)
                out[k.strip()] = v.strip()
            spec = out
    if not isinstance(spec, dict):
        raise ValueError(
            f"telemetry spec must be a table, got {type(spec).__name__}"
        )
    unknown = set(spec) - set(_KNOBS)
    if unknown:
        raise ValueError(f"unknown telemetry knob(s): {sorted(unknown)}")
    cfg = TelemetryConfig()
    for key, raw in spec.items():
        field, conv = _KNOBS[key]
        if conv is None:
            value: Any = _parse_bool(raw)
        elif conv is str:
            value = str(raw)
        else:
            value = conv(float(raw))
        setattr(cfg, field, value)
    if cfg.stats_every < 0:
        raise ValueError("telemetry.statsEvery must be >= 0")
    if cfg.idle_ms < 0:
        raise ValueError("telemetry.idleMs must be >= 0")
    if cfg.trace_sample < 0:
        raise ValueError("telemetry.traceSample must be >= 0")
    if cfg.span_cap < 1:
        raise ValueError("telemetry.spanCap must be >= 1")
    if cfg.stats_every == 0 and cfg.idle_ms == 0 and cfg.trace_sample == 0:
        raise ValueError(
            "telemetry spec arms nothing (statsEvery, idleMs and "
            "traceSample all 0); unset it instead"
        )
    return cfg


def telemetry_config(tc, job_spec: str = "") -> Optional[TelemetryConfig]:
    """The pipeline's telemetry config: ``trainingConfiguration.telemetry``
    wins (including an explicit False = opt this pipeline out of span
    sampling under a job default); otherwise the job-wide
    ``JobConfig.telemetry`` spec applies. None = unarmed."""
    extra = getattr(tc, "extra", None) or {}
    if "telemetry" in extra:
        return parse_telemetry_spec(extra["telemetry"])
    return parse_telemetry_spec(job_spec or "")


def validate_telemetry(tc) -> Optional[str]:
    """Control-gate twin of :func:`telemetry_config`: the error string for
    an undeployable telemetry table, or None (a bad request drops at
    admission instead of killing the job)."""
    try:
        telemetry_config(tc)
    except (ValueError, TypeError) as exc:
        return str(exc)
    return None


class MetricsRegistry:
    """The unified pull point: counters, gauges, histograms, probes.

    - ``counter(name, n)`` — additive; snapshots sum, merges sum.
    - ``gauge(name, v)`` — last-write wins (an operator rollback really
      moves the value back down); ``gauge_max(name, v)`` — peak-combining
      (pressure levels, mesh widths).
    - ``observe(name, v)`` — bounded-ring histogram sample (exact
      count/total, windowed percentiles).
    - ``probe(name, fn)`` — a zero-argument callable read at snapshot
      time: existing accounting (StepTimer rings, queue depths, overload
      signals) publishes into the registry WITHOUT double bookkeeping on
      its hot path. Probe errors degrade to absence, never crash a
      heartbeat.
    """

    def __init__(self):
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self._max_gauges: set = set()
        self.histograms: Dict[str, Ring] = {}
        self._probes: Dict[str, Callable[[], float]] = {}

    # --- writes ----------------------------------------------------------

    def counter(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def gauge_max(self, name: str, value: float) -> None:
        self._max_gauges.add(name)
        if value > self.gauges.get(name, float("-inf")):
            self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        ring = self.histograms.get(name)
        if ring is None:
            ring = self.histograms[name] = Ring()
        ring.note(value)

    def probe(self, name: str, fn: Callable[[], float]) -> None:
        self._probes[name] = fn

    def read_probe(self, name: str, default: float = 0.0) -> float:
        fn = self._probes.get(name)
        if fn is None:
            return default
        try:
            return float(fn())
        except Exception:
            return default

    # --- the pull point --------------------------------------------------

    def snapshot(self) -> dict:
        """One JSON-shaped view of everything registered: counters,
        gauges, histogram summaries ({count, total, p50, p99}), and the
        probes' current values (under ``gauges``, read now)."""
        gauges = dict(self.gauges)
        for name, fn in self._probes.items():
            try:
                gauges[name] = float(fn())
            except Exception:
                pass  # a dead probe must not kill a heartbeat
        hists = {}
        for name, ring in self.histograms.items():
            p50, p99 = ring.percentiles()
            hists[name] = {
                "count": ring.count,
                "total": round(ring.total, 6),
                "p50": round(p50, 4),
                "p99": round(p99, 4),
            }
        return {"counters": dict(self.counters), "gauges": gauges,
                "histograms": hists}

    def merge(self, other: "MetricsRegistry") -> None:
        """Fold another registry in: counters sum, max-gauges peak, plain
        gauges last-write (other wins), histogram rings concatenate
        (bounded)."""
        for k, v in other.counters.items():
            self.counters[k] = self.counters.get(k, 0) + v
        for k, v in other.gauges.items():
            if k in other._max_gauges or k in self._max_gauges:
                self.gauge_max(k, v)
            else:
                self.gauges[k] = v
        for k, ring in other.histograms.items():
            mine = self.histograms.get(k)
            if mine is None:
                mine = self.histograms[k] = Ring()
            mine.merge(ring)


class PhaseProfile:
    """The phase table over a span recorder: per-phase SELF seconds (a
    span's duration minus what its children on the same thread cover,
    exact), counts, counters and windowed percentiles. ``phase(name)`` is
    ``utils.tracing``'s span -- the package's one timed block -- so every
    phase also lies in a profiler trace as ``omldm.<name>``.
    ``table(e2e_s)`` is the breakdown the benchmarks print; ``share`` sums
    to the measured attribution fraction. A profile notes into a recorder
    of its own unless handed one: ``tracing.RECORDER`` for the fused SPMD
    route's process-wide spans, with ``since`` (a ``Recorder.mark()``) to
    cover only what came after it."""

    def __init__(self, recorder: Optional[Recorder] = None,
                 since: Optional[Mark] = None):
        self.recorder = recorder if recorder is not None else Recorder()
        self.since = since

    def note(self, name: str, seconds: float) -> None:
        self.recorder.note_seconds(name, seconds)

    def phase(self, name: str) -> Span:
        return self.recorder.span(name)

    def seconds(self, name: str) -> float:
        return self.recorder.summary(name, self.since)[1]

    def total_seconds(self) -> float:
        return sum(self.seconds(n) for n in self.recorder.names())

    def table(self, e2e_s: Optional[float] = None,
              extra: Optional[Dict[str, float]] = None,
              also: Optional["PhaseProfile"] = None) -> dict:
        """{phase: {seconds, count, p50_ms, p99_ms, share}} + a
        ``_coverage`` row when ``e2e_s`` is given: the fraction of the
        measured end-to-end wall the attributed phases account for.
        Seconds are self time, so nested phases count once (``compile``
        is no span: its seconds are also in the self time of the span that
        called the program). A phase with counters carries them in its
        row (``rows``, ``rows_padded``; ``fit`` also ``overflow_launches``
        and ``distinct_share`` where launches ran the sparse index plan).
        Phases noted from more than one thread (the fused route's producer and dispatch threads) overlap
        in wall time. ``also`` adds another profile's phases, row by row;
        ``extra`` folds in phase totals tracked elsewhere (StepTimer
        total_ms, codec seconds) as {phase: seconds} without sample
        rings."""
        phases: Dict[str, list] = {}
        for profile in (self, also) if also is not None else (self,):
            for name in profile.recorder.names():
                count, seconds, counts, samples = profile.recorder.summary(
                    name, profile.since
                )
                if not count:
                    continue
                into = phases.setdefault(name, [0, 0.0, {}, []])
                into[0] += count
                into[1] += seconds
                for k, v in counts.items():
                    into[2][k] = into[2].get(k, 0) + v
                into[3] += samples
        out: dict = {}
        total = 0.0
        for name, (count, seconds, counts, samples) in phases.items():
            p50, p99 = (
                np.percentile(samples, (50.0, 99.0)) if samples else (0.0, 0.0)
            )
            out[name] = {
                "seconds": round(seconds, 4),
                "count": count,
                "p50_ms": round(float(p50) * 1000.0, 4),
                "p99_ms": round(float(p99) * 1000.0, 4),
                **counts,
            }
            if counts.get("slots"):
                # the sparse index plan's launches: distinct addresses over
                # slots (ops.sparse; 1.0 = nothing to combine)
                out[name]["distinct_share"] = round(
                    counts["slots_distinct"] / counts["slots"], 4
                )
            total += seconds
        for name, secs in (extra or {}).items():
            row = out.setdefault(
                name, {"seconds": 0.0, "count": 0, "p50_ms": 0.0,
                       "p99_ms": 0.0}
            )
            row["seconds"] = round(row["seconds"] + secs, 4)
            total += secs
        if e2e_s and e2e_s > 0:
            for row in out.values():
                row["share"] = round(row["seconds"] / e2e_s, 4)
            out["_coverage"] = round(total / e2e_s, 4)
        return out

    def merge(self, other: "PhaseProfile") -> None:
        self.recorder.merge(other.recorder)


class SpanLog:
    """Sampled protocol-round spans: 1/N of worker->hub sends open a span
    keyed by the transport's (networkId, seq) stamp (a local per-stream
    counter stands in when the reliable channel is unarmed); the next
    hub->worker delivery on that stream closes it with the round-trip
    latency. Completed spans land in a bounded ring and (optionally) a
    JSONL file — compact records an operator can join across processes.

    One outstanding span per (networkId, hubId, workerId) stream: protocol
    rounds on one stream are serial (the worker blocks or proceeds, but
    reply k answers send k), so a second sampled send before the reply
    would measure queueing noise — the sampler skips it instead."""

    def __init__(self, sample: int, path: str = "", cap: int = SPAN_RING_CAP,
                 clock: Callable[[], float] = time.perf_counter):
        self.sample = int(sample)
        self.path = path
        self.cap = int(cap)
        self._clock = clock
        self._file = None
        self._sends: Dict[Tuple[int, int, int], int] = {}
        self._open: Dict[Tuple[int, int, int], Tuple[int, str, float]] = {}
        self.spans: List[dict] = []
        self.opened = 0
        self.completed = 0

    @property
    def active(self) -> bool:
        return self.sample > 0

    def maybe_open(
        self, network_id: int, hub_id: int, worker_id: int, op: str,
        seq: Optional[int],
    ) -> None:
        key = (network_id, hub_id, worker_id)
        n = self._sends.get(key, 0)
        self._sends[key] = n + 1
        if n % self.sample != 0 or key in self._open:
            return
        self._open[key] = (n if seq is None else int(seq), op, self._clock())
        self.opened += 1

    def maybe_close(
        self, network_id: int, hub_id: int, worker_id: int, reply_op: str
    ) -> None:
        key = (network_id, hub_id, worker_id)
        entry = self._open.pop(key, None)
        if entry is None:
            return
        seq, op, t0 = entry
        span = {
            "networkId": network_id,
            "hubId": hub_id,
            "workerId": worker_id,
            "seq": seq,
            "op": op,
            "replyOp": reply_op,
            "rttMs": round((self._clock() - t0) * 1000.0, 4),
        }
        self.completed += 1
        self.spans.append(span)
        if len(self.spans) > self.cap:
            del self.spans[: len(self.spans) - self.cap]
        if self.path:
            try:
                if self._file is None:
                    self._file = open(self.path, "a")
                self._file.write(json.dumps(span) + "\n")
                self._file.flush()
            except OSError:
                self.path = ""  # a full disk must not kill the job

    def close(self) -> None:
        if self._file is not None:
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None


class TelemetryPlane:
    """Job-level telemetry state: the registry, the phase profile, the
    span log, and the heartbeat clock. One instance per StreamJob when
    armed; None (the default) everywhere else."""

    def __init__(
        self,
        cfg: TelemetryConfig,
        wall: Callable[[], float] = time.time,
    ):
        self.cfg = cfg
        self.registry = MetricsRegistry()
        self.phases = PhaseProfile() if cfg.phases else None
        self.spans = SpanLog(cfg.trace_sample, cfg.span_path, cfg.span_cap)
        self._wall = wall
        self.heartbeats_emitted = 0
        # records since the last heartbeat (the count clock)
        self._records_since = 0
        self._last_beat_wall: Optional[float] = None

    # --- the heartbeat clock --------------------------------------------

    def note_records(self, n: int) -> bool:
        """Advance the count clock by ``n`` records; True when the
        count-clocked cadence says a heartbeat is due."""
        self._records_since += n
        self.registry.counter("records", n)
        return (
            self.cfg.stats_every > 0
            and self._records_since >= self.cfg.stats_every
        )

    def idle_due(self, now: Optional[float] = None) -> bool:
        """Wall-clock idle tick: a beat is due when activity is pending
        since the last one and ``idleMs`` elapsed — an idle/paused stream
        still reports what it has instead of going dark until terminate."""
        if self.cfg.idle_ms <= 0 or self._records_since == 0:
            return False
        now = self._wall() if now is None else now
        if self._last_beat_wall is None:
            # records flowed but no beat yet (statsEvery not reached):
            # the idle clock starts at the first pending check — stamped
            # from the CALLER's clock so an injected-now driver
            # (check_silence's pattern) never mixes clock domains
            self._last_beat_wall = now
            return False
        return (now - self._last_beat_wall) * 1000.0 >= self.cfg.idle_ms

    def mark_beat(self, now: Optional[float] = None) -> int:
        """Reset the clocks after an emission; returns the beat seq."""
        self._records_since = 0
        self._last_beat_wall = self._wall() if now is None else now
        self.heartbeats_emitted += 1
        self.registry.counter("heartbeats")
        return self.heartbeats_emitted

    def close(self) -> None:
        self.spans.close()


__all__ = [
    "DEFAULT_IDLE_MS",
    "DEFAULT_STATS_EVERY",
    "MetricsRegistry",
    "PHASES",
    "PhaseProfile",
    "SpanLog",
    "TelemetryConfig",
    "TelemetryPlane",
    "parse_telemetry_spec",
    "telemetry_config",
    "validate_telemetry",
]
