"""Job-level configuration.

Mirrors the reference's flag system: Flink ``ParameterTool`` CLI flags with code
defaults (reference: src/main/scala/omldm/utils/DefaultJobParameters.scala:3-12,
src/main/scala/omldm/Job.scala:113-120, README.md:28-41). Per-pipeline
configuration arrives at runtime inside ``Request.training_configuration``
(see omldm_tpu.api.requests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping


@dataclasses.dataclass
class JobConfig:
    """Global job configuration.

    Defaults replicate the reference's ``DefaultJobParameters``
    (DefaultJobParameters.scala:4-11): parallelism 16, maxMsgParams 2000,
    timeout 30_000 ms, testSetSize 256, test mode on.

    TPU-specific knobs (micro-batching, dtype, mesh shape) have no reference
    counterpart: the reference fits one record at a time on the JVM
    (hs_err_pid77107.log:110-111); on TPU the unit of work is a fixed-shape
    micro-batch so XLA compiles the training step once.
    """

    job_name: str = "OMLDM"
    # Number of logical workers (spokes). Reference default 16
    # (DefaultJobParameters.scala:5).
    parallelism: int = 16
    # Message-size cap in #parameters for protocol messages
    # (DefaultJobParameters.scala:6, FlinkSpoke.scala:30).
    max_msg_params: int = 2_000
    # Silence timeout (ms) after which the statistics operator fires the
    # termination probe (DefaultJobParameters.scala:10,
    # StatisticsOperator.scala:91).
    timeout_ms: int = 30_000
    # Per-worker holdout test-set size (DefaultJobParameters.scala:11).
    test_set_size: int = 256
    # Test mode: holdout sampling, poll markers, stats harness, timer-driven
    # self-termination (DefaultJobParameters.scala:9, FlinkLearning.scala:43).
    test: bool = True
    # Checkpointing (opt-in in the reference: Job.scala:120,
    # Checkpointing.scala:9-25; 5000 ms default interval).
    checkpointing: bool = False
    check_interval_ms: int = 5_000
    checkpoint_dir: str = "/tmp/omldm_tpu_checkpoints"
    # snapshots retained on disk (oldest pruned after each save); <= 0
    # keeps everything. Recovery only ever restores the latest, but a
    # couple of spares survive a torn write of the newest file.
    checkpoint_keep: int = 3

    # --- capacity limits (host-side buffering) ---
    # Spoke training-record buffer cap (SpokeLogic.scala:32).
    record_buffer_cap: int = 100_000
    # Spoke request buffer cap (SpokeLogic.scala:34).
    request_buffer_cap: int = 10_000
    # Hub pre-creation message cache cap (StateAccumulators.scala:128-146).
    hub_cache_cap: int = 20_000
    # PS model-state bucket size in #parameters (FlinkNetwork.scala:50).
    max_param_bucket_size: int = 10_000
    # Poll/progress marker cadence in #training records (FlinkSpoke.scala:83-89).
    poll_every: int = 100

    # --- lossy-channel hardening (no reference counterpart: the reference
    # rides Kafka at-least-once and hopes) ---
    # Deterministic chaos spec for the in-process hub<->spoke bridge, e.g.
    # "seed=7,drop=0.05,dup=0.05,reorder=0.1,window=4" (per-direction
    # overrides: "up.drop=...", "down.dup=..."). Corruption classes
    # "nan"/"explode" plant seeded NaNs / 1e12 norm explosions in shipped
    # parameter vectors ("poison" mutates source records, Kafka route) —
    # the model-integrity guard's fault drivers. Empty (default) = fault
    # free; the OMLDM_CHAOS env var arms it too (reaches worker
    # subprocesses). When armed, the reliable channel (sequence numbers,
    # receive windows, NACK/resync) arms itself per pipeline.
    chaos: str = ""

    # --- model integrity (omldm_tpu.guard / runtime.deadletter; no
    # reference counterpart: the reference silently drops records its
    # parsers reject, DataPointParser.scala:13-21) ---
    # Dead-letter JSONL file for malformed / validation-rejected records
    # and requests ("" = bounded in-memory quarantine only). Every entry
    # carries a reason code; the per-pipeline guard itself is armed via
    # trainingConfiguration.guard, not here.
    dead_letter_path: str = ""
    # In-memory quarantine ring size (oldest entries evict).
    dead_letter_cap: int = 10_000

    # --- multi-tenant cohort execution (runtime.cohort; no reference
    # counterpart: the reference steps every pipeline's PipelineMap entry
    # serially per record, FlinkSpoke.scala:92-107) ---
    # "off": every pipeline dispatches its own XLA programs (the exact
    # pre-cohort code path). "auto" (default): same-spec pipelines gang
    # into one stacked launch once `cohort_min` of them are live on a
    # spoke. "on": every eligible pipeline cohorts immediately.
    cohort: str = "auto"
    # homogeneous-pipeline count above which "auto" forms a cohort.
    cohort_min: int = 8
    # gang member iteration: "map" (lax.map — bit-identical to
    # per-pipeline execution, the CPU default), "vmap" (batched — faster
    # on parallel backends, ~1e-9 batched-reduction drift), or "auto"
    # (map on CPU, vmap elsewhere).
    cohort_impl: str = "auto"
    # device sharding of the cohort tenant axis (runtime.cohort): "off"
    # (default — every gang launch runs on one device, the exact
    # pre-sharding path), "auto" (lay the cohort's leading pipeline axis
    # across the largest power-of-two slice of the local mesh), or an
    # integer shard count (clamped to the local device count, floored to
    # a power of two). With S > 1 shards, members balance across shards,
    # capacity buckets are per-shard, and fit / gang predict / flat
    # params / guard health all run as ONE shard_map launch over a
    # "tenants" mesh axis with per-shard lax.map member iteration.
    cohort_shards: str = "off"
    # Hub liveness walk stride on the record path: with quorum/timeout
    # armed, the per-record check_liveness walk runs every N events (or on
    # a deadline), not per record (runtime/hub.py).
    liveness_stride: int = 16

    # --- adaptive-batching forecast serving (runtime/serving.py; no
    # reference counterpart: the reference answers every forecasting
    # record inline, FlinkSpoke.scala:92-107) ---
    # Job-wide DEFAULT serving spec applied to pipelines whose
    # trainingConfiguration carries no "serving" table of their own, e.g.
    # "maxBatch=64,maxDelayMs=5" or "relaxed" or "on". Empty (default):
    # nothing is armed and every forecast takes the exact pre-plane
    # immediate per-record predict path. Per-pipeline
    # trainingConfiguration.serving always wins (an explicit false opts a
    # pipeline out of this default).
    serving: str = ""

    # --- model lifecycle (runtime/lifecycle.py; no reference counterpart:
    # the reference's only rollout primitive is the destructive Update
    # that tears the live model down, PipelineMap.scala:43-47) ---
    # Job-wide DEFAULT lifecycle spec applied to pipelines whose
    # trainingConfiguration carries no "lifecycle" table of their own,
    # e.g. "rampTo=0.5,rampEvery=64,seed=7" or "on". Empty (default):
    # nothing is armed — zero lifecycle objects exist and every route is
    # the exact pre-plane code path. Armed, each pipeline gains a model-
    # version registry: Shadow requests register candidate configurations
    # that train + holdout-score on the live stream without serving,
    # Promote starts a deterministic hash-routed canary traffic ramp, and
    # the guard fence (candidate normLimit/non-finite trip) or a shadow-
    # score regression past scoreEnvelope auto-rolls the candidate back.
    # Per-pipeline trainingConfiguration.lifecycle always wins (an
    # explicit false opts a pipeline out).
    lifecycle: str = ""

    # --- overload control (runtime/overload.py; the reference delegates
    # overload entirely to Flink's credit-based network backpressure,
    # SURVEY §5 — the job itself has no admission control) ---
    # Job-wide DEFAULT overload spec applied to pipelines whose
    # trainingConfiguration carries no "overload" table of their own,
    # e.g. "window=64,share=2,hotHigh=48,hotCritical=160" or "on".
    # Empty (default): nothing is armed — no controller objects exist and
    # every route is the exact pre-plane code path. Armed, each spoke
    # derives a pressure level (OK/ELEVATED/CRITICAL) from its queues and
    # per-tenant admission imbalance, rate-limits tenants with
    # count-clocked token buckets, climbs a degradation ladder (widen
    # serving batching, relax staleness, defer over-limit tenants'
    # training) and finally SHEDS over-limit forecasts with reason-coded
    # dead-letter entries; the Kafka drive loops pause consumption while
    # any spoke is CRITICAL. Per-pipeline trainingConfiguration.overload
    # always wins (an explicit false opts a pipeline out).
    overload: str = ""

    # --- telemetry plane (runtime/telemetry.py; the reference's only
    # observability is the terminate-time JobStatistics report on the
    # performance stream, StatisticsOperator.scala:21-150) ---
    # Job-wide DEFAULT telemetry spec applied to pipelines whose
    # trainingConfiguration carries no "telemetry" table of their own,
    # e.g. "statsEvery=10000,idleMs=2000,traceSample=64" or "on". Empty
    # (default): nothing is armed — zero telemetry objects exist and
    # every route is the exact pre-plane code path. Armed, the job emits
    # continuous performance HEARTBEATS (incremental JobStatistics
    # snapshots through the on_performance sink, count-clocked every
    # statsEvery records plus a wall-clock idle tick), attributes
    # hot-loop wall time to phases (read/parse/stage/holdout/fit/serve/
    # ship), and samples 1/traceSample protocol rounds into JSONL span
    # events keyed by the transport's (networkId, seq) stamps.
    # Per-pipeline trainingConfiguration.telemetry always wins (an
    # explicit false opts a pipeline out of span sampling).
    telemetry: str = ""

    # --- flight recorder (runtime/events.py; the reference's failure
    # story is a black box: JobTerminator.scala:6-10 kills the job by
    # throwing on the first performance record, leaving no record of
    # what went wrong) ---
    # Job-wide DEFAULT events spec applied to pipelines whose
    # trainingConfiguration carries no "events" table of their own, e.g.
    # "cap=4096,watchdogEvery=10000,shedHigh=1" or "on". Empty (default):
    # nothing is armed — zero recorder objects exist and every route is
    # the exact pre-plane code path. Armed, every plane's decision sites
    # (guard trip/rollback/eviction, delta rejection + strike, quorum
    # release, resync, shed/throttle + pressure transitions, canary
    # transitions, rescale decisions, supervisor restarts) record typed
    # events into a bounded per-process journal; on guard trip, worker
    # death, rescale, or terminate the ring dumps to JSONL under
    # ``blackbox_path``; and the watchdog rule knobs (collapseFrac /
    # p99BudgetMs / shedHigh / curveSlope / silenceMs) emit ``alert``
    # events through the journal AND onto the performance sink as
    # kind="alert" records. Per-pipeline trainingConfiguration.events
    # always wins (an explicit false opts a pipeline out). NOTE: on the
    # CLI this spec rides the --flightRecorder flag — the bare --events
    # flag already names the combined replay FILE (__main__.py) and is
    # excluded from config mapping in from_args.
    events: str = ""
    # Directory for flight-recorder ring dumps (blackbox-proc<N>.jsonl)
    # and supervisor incident bundles (incident-*.json). "" (default) =
    # in-memory ring only. The events spec's own blackboxPath knob wins
    # when set; this is the job-wide CLI-friendly default
    # (--blackboxPath).
    blackbox_path: str = ""
    # In-memory prediction/response mirror cap: StreamJob keeps every
    # emitted prediction/response in a list for callers WITHOUT sink
    # callbacks; with a sink attached the list is just a mirror, so it is
    # trimmed (oldest first) beyond this many entries — a stalled/slow
    # sink consumer can no longer grow host memory with the stream.
    # <= 0 disables trimming.
    emission_buffer_cap: int = 100_000

    # --- TPU-native knobs (no reference counterpart) ---
    # Micro-batch size per training step; records are padded + masked to this
    # fixed shape so the jitted step never recompiles.
    batch_size: int = 256
    # Compute dtype for learner math. bfloat16 keeps matmuls on the MXU at
    # full rate; params are kept in float32.
    compute_dtype: str = "float32"
    # Mesh axis sizes: data-parallel spokes ("dp") and sharded parameter
    # server ("hub", the reference's HubParallelism).
    mesh_shape: Mapping[str, int] = dataclasses.field(
        default_factory=lambda: {"dp": 1, "hub": 1}
    )

    # Aliases mapping the reference's exact CLI flag names to our fields
    # (FlinkLearning.scala:43-48, Job.scala:120, Checkpointing.scala:15-22).
    _FLAG_ALIASES = {
        "timeout": "timeout_ms",
        "checkInterval": "check_interval_ms",
        "stateBackend": "checkpoint_dir",
        "jobName": "job_name",
    }

    @classmethod
    def from_args(cls, args: Mapping[str, Any]) -> "JobConfig":
        """Build a config from a flat string map (CLI-style), mirroring
        ``ParameterTool.fromArgs`` (Job.scala:114). Accepts snake_case,
        camelCase, and the reference's own flag names (e.g. ``timeout``)."""
        cfg = cls()
        args = dict(args)
        # the bare --events CLI flag names the combined replay FILE
        # (__main__.py), not the flight-recorder spec: drop it from
        # config mapping and accept the spec as --flightRecorder instead
        # (programmatic JobConfig(events=...) is unaffected)
        args.pop("events", None)
        if "flightRecorder" in args:
            args["events"] = args.pop("flightRecorder")
        for alias, field_name in cls._FLAG_ALIASES.items():
            if alias in args and field_name not in args:
                args[field_name] = args.pop(alias)
        for field in dataclasses.fields(cls):
            for key in (field.name, _camel(field.name)):
                if key in args:
                    raw = args[key]
                    current = getattr(cfg, field.name)
                    if isinstance(current, bool):
                        value = str(raw).lower() in ("1", "true", "yes", "on")
                    elif isinstance(current, int):
                        value = int(raw)
                    elif isinstance(current, str):
                        value = str(raw)
                    elif field.name == "mesh_shape" and isinstance(raw, str):
                        # "dp=8,hub=2" -> {"dp": 8, "hub": 2}
                        value = {
                            k.strip(): int(v)
                            for k, v in (p.split("=") for p in raw.split(",") if p)
                        }
                    else:
                        value = raw
                    setattr(cfg, field.name, value)
        return cfg


def _camel(snake: str) -> str:
    head, *tail = snake.split("_")
    return head + "".join(t.capitalize() for t in tail)
