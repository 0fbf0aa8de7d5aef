"""CLI entry point: ``python -m omldm_tpu [--flag value ...]``.

Reference counterpart: ``Job.main(args)`` (reference:
src/main/scala/omldm/Job.scala:110-171) — parse ``--key value`` CLI flags
with ``ParameterTool.fromArgs`` semantics (Job.scala:114), build the sources
and sinks, assemble the job graph, and run it. The reference's flag surface
(README.md:28-41) is per-topic Kafka name+broker pairs plus the job knobs
(``parallelism``, ``test``, ``maxMsgParams``, ``jobName``, ``timeout``,
``testSetSize``, ``checkpointing``, ``checkInterval``, ``stateBackend``);
all job knobs are accepted here with the same names (JobConfig.from_args).

Sources (choose one style):

- ``--trainingData path.jsonl`` / ``--forecastingData path.jsonl`` /
  ``--requests path.jsonl`` — JSON-lines file replay, round-robin
  interleaved (the deterministic stand-in for stream union, Job.scala:70);
  ``EOS`` marker lines are dropped and replay continues, matching the
  reference parser (DataInstanceParser.scala:13-21).
- ``--events combined.jsonl`` — one fully-ordered file of
  ``{"stream": "trainingData"|"forecastingData"|"requests", "data": {...}}``
  lines, when the exact arrival order matters (e.g. Query after training).
- ``--kafkaBrokers host:port`` — live Kafka consumer/producer via
  omldm_tpu.runtime.kafka_io (requires kafka-python; silence-timer
  termination as in StatisticsOperator.scala:135-142).

Sinks: ``--predictionsOut`` / ``--responsesOut`` / ``--performanceOut``
write JSON lines to files (default: performance to stdout, mirroring the
reference's PerformanceWriter -> performance topic, FlinkLearning.scala:137-144).
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

from omldm_tpu.config import JobConfig
from omldm_tpu.runtime.ingest import file_events, interleave
from omldm_tpu.runtime.job import (
    FORECASTING_STREAM,
    PACKED_STREAM,
    REQUEST_STREAM,
    TRAINING_STREAM,
    StreamJob,
)

_STREAMS = (TRAINING_STREAM, FORECASTING_STREAM, REQUEST_STREAM)


def parse_flags(argv: List[str]) -> Dict[str, str]:
    """``--key value`` pairs -> dict (ParameterTool.fromArgs, Job.scala:114).
    A flag without a value is treated as boolean true."""
    flags: Dict[str, str] = {}
    i = 0
    while i < len(argv):
        arg = argv[i]
        if not arg.startswith("--"):
            raise SystemExit(f"expected --flag, got {arg!r}")
        key = arg[2:]
        if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
            flags[key] = argv[i + 1]
            i += 2
        else:
            flags[key] = "true"
            i += 1
    return flags


def combined_events(path: str) -> Iterator[Tuple[str, str]]:
    """Replay a fully-ordered combined event file: each line is
    ``{"stream": <topic>, "data": <record object or JSON string>}``."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            stream = obj.get("stream")
            if stream not in _STREAMS:
                continue
            data = obj.get("data")
            yield (stream, data if isinstance(data, str) else json.dumps(data))


class _FileSink:
    def __init__(self, path: Optional[str], default=None):
        self._f = open(path, "w") if path else default

    def __call__(self, obj: Any) -> None:
        if self._f is None:
            return
        payload = obj.to_json() if hasattr(obj, "to_json") else json.dumps(obj)
        self._f.write(payload + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._f is not None and self._f not in (sys.stdout, sys.stderr):
            self._f.close()


def build_job(flags: Dict[str, str]) -> Tuple[StreamJob, List[_FileSink]]:
    config = JobConfig.from_args(flags)
    pred_sink = _FileSink(flags.get("predictionsOut"))
    resp_sink = _FileSink(flags.get("responsesOut"))
    perf_sink = _FileSink(flags.get("performanceOut"), default=sys.stdout)
    job = StreamJob(
        config,
        on_prediction=pred_sink,
        on_response=resp_sink,
        on_performance=perf_sink,
    )
    return job, [pred_sink, resp_sink, perf_sink]


def announce_device() -> None:
    """One stderr line naming the device the job runs on, so a run that
    lost its accelerator cannot pass for one that had it."""
    import jax

    devices = jax.devices()
    print(
        f"omldm_tpu: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} devices={len(devices)}",
        file=sys.stderr,
    )


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    flags = parse_flags(argv)
    if any(
        k in flags
        for k in ("processes", "processId", "coordinator", "supervise")
    ):
        # multi-process deployment: one entry point for both shapes
        # (Job.scala:110-120 — the reference has exactly one main); each
        # process runs the same command with its own --processId
        from omldm_tpu.runtime.distributed_job import run_distributed

        return run_distributed(argv)
    from omldm_tpu.utils import trace
    from omldm_tpu.utils.compile_cache import enable_compile_cache

    # --compileCache off disables the persistent XLA compilation cache;
    # its directory is placed from outside (utils/compile_cache.py)
    enable_compile_cache(flags.get("compileCache", "on"))
    announce_device()
    job, sinks = build_job(flags)

    try:
        if "kafkaBrokers" in flags:
            # unbounded stream: the kafka loop bounds its own profile window
            # (--profileSteps events) instead of tracing the job lifetime
            return _run(job, flags)
        with trace(flags.get("profileDir")):
            return _run(job, flags)
    finally:
        for sink in sinks:
            sink.close()


def _run(job: StreamJob, flags: Dict[str, str]) -> int:
    if "kafkaBrokers" in flags:
        return _run_kafka(job, flags)
    elif "events" in flags:
        _run_replay(job, flags, lambda: combined_events(flags["events"]))
    else:
        if _try_fused_run(job, flags):
            return 0

        def make_events():
            packed = None
            if (
                TRAINING_STREAM in flags
                and flags.get("fastIngest", "auto") != "false"
            ):
                packed = _packed_training_source(flags)
            sources = []
            for topic in _STREAMS:
                if topic not in flags:
                    continue
                if topic == TRAINING_STREAM and packed is not None:
                    sources.append(packed)
                else:
                    sources.append(file_events(flags[topic], topic))
            if not sources:
                raise SystemExit(
                    "no sources: pass --trainingData/--forecastingData/"
                    "--requests <path.jsonl>, --events <combined.jsonl>, "
                    "or --kafkaBrokers <host:port>"
                )
            return interleave(*sources)

        _run_replay(job, flags, make_events)
    return 0


def _apply_kafka_sinks(job: StreamJob, flags: Dict[str, str], producer_sinks) -> None:
    """Kafka producers are the default egress; an explicitly-passed file
    sink keeps precedence over the producer for its stream."""
    job.set_sinks(
        on_prediction=(
            None if "predictionsOut" in flags else producer_sinks.on_prediction
        ),
        on_response=(
            None if "responsesOut" in flags else producer_sinks.on_response
        ),
        on_performance=(
            None if "performanceOut" in flags else producer_sinks.on_performance
        ),
    )
    # quarantined records/requests publish to the deadLetters topic in
    # addition to the job's in-memory ring / --deadLetterPath file
    job.dead_letter.publish = producer_sinks.on_dead_letter


def _kafka_loop(job: StreamJob, events, flags: Dict[str, str], profile: Dict) -> None:
    """One supervised attempt at the live polling loop. ``profile`` carries
    the bounded trace-window state across restart attempts (the window
    counts TOTAL events, and tracing stops exactly once)."""
    # start the silence clock at loop entry so a broker that never
    # delivers anything still terminates after the timeout
    job.stats.mark_activity()
    for event in events:  # yields None on each idle poll window
        if event is not None:
            job.process_event(*event)
            if job.checkpoint_manager is not None:
                job.checkpoint_manager.maybe_save(job)
            profile["n_events"] += 1
            if profile["tracing"] and profile["n_events"] >= profile["steps"]:
                import jax

                jax.profiler.stop_trace()
                profile["tracing"] = False
        else:
            # idle / backpressure-paused poll window: idle capacity decays
            # the overload counters so a CRITICAL pause can clear (no-op
            # when the plane is unarmed)
            job.overload_idle_tick()
        job.check_silence()
        if job.stats.terminated:
            break


def _kafka_retry_policies(flags: Dict[str, str]):
    """(connect/metadata policy, producer-send policy) from the CLI knobs
    ``--retry{Attempts,BaseDelayMs,Growth,JitterMs,TimeoutMs}`` and
    ``--sendRetry{...}`` (defaults in kafka_io)."""
    import dataclasses

    from omldm_tpu.runtime.kafka_io import CONNECT_RETRY, SEND_RETRY
    from omldm_tpu.utils.backoff import BackoffPolicy

    connect = BackoffPolicy.from_flags(
        flags, "retry", **dataclasses.asdict(CONNECT_RETRY)
    )
    send = BackoffPolicy.from_flags(
        flags, "sendRetry", **dataclasses.asdict(SEND_RETRY)
    )
    return connect, send


def _run_kafka(job: StreamJob, flags: Dict[str, str]) -> int:
    """The live Kafka job, optionally supervised (--restartAttempts N):
    on failure, restore the latest checkpoint taken during this run and
    seek the rebuilt consumer to the snapshot's (topic, partition) offsets
    — Flink's restore-from-checkpoint with Kafka source offsets. Without a
    usable snapshot the incarnation restarts fresh from the live position
    (no replay), Flink's uncheckpointed behavior on a live source. The
    restart loop itself runs under the shared backoff helper (fixed delay,
    bounded attempts — RestartStrategies.fixedDelayRestart)."""
    from omldm_tpu.runtime.kafka_io import connect_kafka
    from omldm_tpu.utils.backoff import with_backoff

    attempts = int(flags.get("restartAttempts", "0"))
    delay_s = float(flags.get("restartDelayMs", "0")) / 1000.0
    connect_retry, send_retry = _kafka_retry_policies(flags)
    # bounded profile window for the unbounded stream: trace only the
    # first --profileSteps events (default 1000)
    profile = {
        "tracing": False,
        "n_events": 0,
        "steps": int(flags.get("profileSteps", "1000")),
    }
    if flags.get("profileDir"):
        import jax

        jax.profiler.start_trace(flags["profileDir"])
        profile["tracing"] = True

    manager = job.checkpoint_manager
    ckpt_floor = manager.latest_path() if manager is not None else None
    tracker: Dict = {}
    # upstream backpressure (runtime/overload.py): while any spoke's
    # overload controller reports CRITICAL, the polling loop stops
    # consuming — offsets stay uncommitted, so paused traffic replays
    # instead of buffering. The indirection survives restarts (recovery
    # swaps the job object).
    pause_ref = {"job": job}
    _pause_when = lambda: pause_ref["job"].overload_level() >= 2  # noqa: E731
    events, producer_sinks = connect_kafka(
        flags["kafkaBrokers"], tracker=tracker,
        retry=connect_retry, send_retry=send_retry,
        pause_when=_pause_when,
    )
    # mutable attempt state: each restart swaps in the recovered job and
    # the reconnected clients for the next with_backoff attempt
    state = {"job": job, "events": events, "sinks": producer_sinks,
             "tracker": tracker}

    def _attempt() -> int:
        j = state["job"]
        j.source_position = state["tracker"]
        _apply_kafka_sinks(j, flags, state["sinks"])
        _kafka_loop(j, state["events"], flags, profile)
        return 0

    def _on_restart(exc: Exception, next_attempt: int) -> None:
        print(
            f"job failure ({type(exc).__name__}: {exc}); "
            f"restart {next_attempt - 1}/{attempts}",
            file=sys.stderr,
        )
        from omldm_tpu.runtime.recovery import recover_job

        new_job, _restored_from = recover_job(state["job"], ckpt_floor)
        if new_job.source_position is None:
            # fresh incarnation: data streams continue from the
            # live position (no replay on a live source), but the
            # CONTROL stream rewinds to the beginning — a
            # fresh-state job must re-consume Create/Update/Delete
            # requests to rebuild its topology (the reference's
            # topology is part of the submitted job graph; here it
            # is request-driven). Dropping the key makes the
            # reconnect seek those partitions to the beginning.
            position = dict(state["tracker"])
            from omldm_tpu.runtime.kafka_io import DEFAULT_TOPICS

            for key in list(position):
                if DEFAULT_TOPICS.get(key[0]) == REQUEST_STREAM:
                    del position[key]
            new_job.source_position = position
        tracker = dict(new_job.source_position)
        # close the abandoned clients: restarts must not leak
        # broker connections / fetcher threads
        state["sinks"].close()
        new_events, new_sinks = connect_kafka(
            flags["kafkaBrokers"],
            position=tracker,
            tracker=tracker,
            retry=connect_retry,
            send_retry=send_retry,
            pause_when=_pause_when,
        )
        state.update(
            job=new_job, events=new_events, sinks=new_sinks, tracker=tracker
        )
        pause_ref["job"] = new_job

    try:
        # fixed-delay restart strategy over the whole live loop —
        # RestartStrategies.fixedDelayRestart(attempts, delay) semantics
        return with_backoff(
            _attempt,
            attempts=attempts + 1,
            base_delay=delay_s,
            growth=1.0,
            retry_on=(Exception,),
            on_retry=_on_restart,
        )
    finally:
        if profile["tracing"]:
            import jax

            jax.profiler.stop_trace()


def _run_replay(job: StreamJob, flags: Dict[str, str], make_events) -> None:
    """Replay a deterministic source; ``--restartAttempts N`` opts into
    supervised recovery (Flink's fixed-delay restart strategy: restore the
    latest checkpoint — pass ``--checkpointing`` for stateful recovery —
    and resume the replay at the snapshot's event offset)."""
    attempts = int(flags.get("restartAttempts", "0"))
    if attempts > 0:
        from omldm_tpu.runtime.recovery import JobSupervisor, replayable

        JobSupervisor(
            job,
            replayable(make_events),
            max_restarts=attempts,
            restart_delay_s=float(flags.get("restartDelayMs", "0")) / 1000.0,
        ).run()
    else:
        job.run(make_events())


def _try_fused_run(job: StreamJob, flags: Dict[str, str]) -> bool:
    """The fastest file route: requests replayed up front, then the training
    file consumed by the fused C parse->holdout->stage loop
    (StreamJob.run_file_fused). Taken only when the per-event loop would
    have nothing else to schedule — a single SPMD-plane pipeline, a
    training file as the only data source, no checkpointing (the event loop
    owns maybe_save), no forecasting/file sinks racing the stream. Falls
    back to the packed event route otherwise; requests stay processed (the
    packed route coarsens request/data interleaving the same way)."""
    if TRAINING_STREAM not in flags:
        return False
    if flags.get("fastIngest", "auto") == "false":
        return False
    if flags.get("fusedIngest", "auto") == "false":
        return False
    if job.checkpoint_manager is not None:
        return False
    if int(flags.get("restartAttempts", "0")) > 0:
        return False  # supervised recovery wraps the event loop, not this
    if any(
        t in flags for t in _STREAMS if t not in (TRAINING_STREAM, REQUEST_STREAM)
    ):
        return False
    spec = _stream_spec(flags)
    sparse = False
    if spec is None:
        # sparse pipelines can't use the dense packed batcher, but they DO
        # have a fused route (SparseSPMDBridge.ingest_file): resolve the
        # width from a sparse Create instead
        spec = _sparse_stream_spec(flags)
        sparse = spec is not None
    if spec is None:
        return False
    if REQUEST_STREAM in flags:
        for stream, line in file_events(flags[REQUEST_STREAM], REQUEST_STREAM):
            job.process_event(stream, line)
        # consumed here either way: the fallback event route must not
        # replay them a second time. The packed fallback still needs the
        # width the requests pinned, so stash the resolved spec — except
        # for sparse jobs, whose fallback is the per-record route (the
        # dense packed batcher cannot feed them).
        del flags[REQUEST_STREAM]
        if sparse:
            # the dense packed batcher must NOT pick these jobs up on
            # fallback (it would infer a dense width from the data);
            # the marker sends them down the per-record route
            flags["__sparseStream__"] = "1"
        else:
            flags["__streamSpec__"] = f"{spec[0]},{spec[1]}"
    job.ensure_deployed(spec[0])
    if job.fused_file_bridge() is None:
        return False  # requests stay processed; packed route resumes
    job.run_file_fused(flags[TRAINING_STREAM])
    job.terminate()
    return True


def _sparse_stream_spec(flags: Dict[str, str]) -> Optional[Tuple[int, int]]:
    """(total feature dim, 0) from the first SPARSE Create/Update — the
    fused sparse route needs the width up front like the packed one."""
    from omldm_tpu.api.requests import Request, RequestType

    if REQUEST_STREAM not in flags:
        return None
    try:
        for _, line in file_events(flags[REQUEST_STREAM], REQUEST_STREAM):
            req = Request.from_json(line)
            if req is None or req.request not in (
                RequestType.CREATE, RequestType.UPDATE
            ):
                continue
            ds = req.learner.data_structure if req.learner else None
            if ds and ds.get("sparse") and "nFeatures" in ds:
                return int(ds["nFeatures"]), 0
            return None
    except OSError:
        return None
    return None


def _stream_spec(flags: Dict[str, str]) -> Optional[Tuple[int, int]]:
    """(total feature dim, hash_dims) for the packed ingest path: from the
    first Create/Update request carrying nFeatures, else inferred from the
    first training record (the reference sizes models lazily on the first
    record; here the packed batcher needs the width up front)."""
    from omldm_tpu.api.data import DataInstance
    from omldm_tpu.api.requests import Request, RequestType
    from omldm_tpu.runtime.vectorizer import Vectorizer

    if "__sparseStream__" in flags:
        return None  # sparse pipelines featurize per record (see below)
    if "__streamSpec__" in flags:  # resolved earlier by the fused route
        dim, hash_dims = flags["__streamSpec__"].split(",")
        return int(dim), int(hash_dims)
    if REQUEST_STREAM in flags:
        try:
            for _, line in file_events(flags[REQUEST_STREAM], REQUEST_STREAM):
                req = Request.from_json(line)
                if req is None or req.request not in (
                    RequestType.CREATE, RequestType.UPDATE
                ):
                    continue
                hash_dims = int(
                    req.training_configuration.extra.get("hashDims", 0)
                )
                ds = req.learner.data_structure if req.learner else None
                if ds and ds.get("sparse"):
                    # sparse pipelines featurize per record into padded COO
                    # (SparseVectorizer); the dense C++ block parser cannot
                    # feed them a wide hashed index space
                    return None
                if ds and "nFeatures" in ds:
                    return int(ds["nFeatures"]) + hash_dims, hash_dims
                # first Create without an explicit width: infer from data
                for _, dline in file_events(
                    flags[TRAINING_STREAM], TRAINING_STREAM
                ):
                    inst = DataInstance.from_json(dline)
                    if inst is not None:
                        return Vectorizer.infer_dim(inst, hash_dims), hash_dims
                return None
        except OSError:
            return None
    try:
        for _, dline in file_events(flags[TRAINING_STREAM], TRAINING_STREAM):
            inst = DataInstance.from_json(dline)
            if inst is not None:
                return Vectorizer.infer_dim(inst, 0), 0
    except OSError:
        return None
    return None


def _packed_training_source(flags: Dict[str, str]):
    """The training file as PACKED_STREAM events: C++ bulk parse ->
    (x, y, op) blocks, prefetched one block ahead of the device feed.
    Returns None when the width can't be pinned or (in auto mode) the
    native parser is unavailable — callers fall back to per-record JSON."""
    from omldm_tpu.ops.native import fast_parser_available
    from omldm_tpu.runtime.fast_ingest import iter_file_batches
    from omldm_tpu.runtime.prefetch import prefetch

    spec = _stream_spec(flags)
    if spec is None:
        return None
    if flags.get("fastIngest", "auto") != "true" and not fast_parser_available():
        return None
    dim, hash_dims = spec
    batches = iter_file_batches(
        flags[TRAINING_STREAM],
        dim,
        int(flags.get("ingestBatch", "8192")),
        hash_dims,
    )
    depth = int(flags.get("prefetchDepth", "2"))
    return ((PACKED_STREAM, b) for b in prefetch(batches, depth))


if __name__ == "__main__":
    sys.exit(main())
