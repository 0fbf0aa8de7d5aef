"""Protocol comparison at the reference's own operating point.

The reference project's core experiment is comparing distributed online
learning protocols (its 8 worker/PS pairs, MLNodeGenerator.scala:20-76) on
throughput, communication traffic, and accuracy at job parallelism 16 (its
default, DefaultJobParameters.scala:5, observed live in
hs_err_pid77107.log:21). This harness reproduces that comparison on the
host plane of the streaming runtime: one identical synthetic stream
(BASELINE config-1 shape: 28 numeric features, linearly separable), one
StreamJob per protocol, measuring end-to-end examples/sec, final holdout
score, and the hub-side communication accounting (bytesShipped /
modelsShipped / numOfBlocks, FlinkHub.scala:118-127).

Runs on the CPU backend, by itself (no other benchmark starts it): the
host plane's per-batch dispatch is what is being compared (protocol logic +
message traffic), on an 8-device virtual mesh. Its timings are CPU timings,
never device figures.

The same comparison also runs on the SPMD COLLECTIVE engine (the 6
protocols with device-plane equivalents, `{"engine": "spmd"}` on an
8-worker virtual mesh): examples/sec, score, logical bytesShipped vs
physical collective bytes, and host-vs-SPMD score parity per protocol.

`--codec` adds the TRANSPORT CODEC comparison (runtime.codec): the same
protocols on a params-dominated 256-feature stream, swept over the
requested codec(s), reporting bytes-on-wire, the reduction vs the
uncompressed baseline, codec encode+decode seconds, and final score —
plus the multi-process model-exchange route (the SPMDTrainer collective
the distributed job's psMessages-equivalent traffic rides) measured the
same way. `--smoke` is the CI mode: a small stream, the codec sections
only, and a NONZERO EXIT if an int8 run fails the >= 3.5x bytes-on-wire
reduction bar or drifts past the convergence envelope.

Usage: python benchmarks/protocol_comparison.py [--records N]
           [--codec none|fp16|int8|topk|sweep] [--smoke]
Prints ONE JSON line: {"config": "protocol_comparison", ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


PROTOCOLS = (
    "Asynchronous",
    "Synchronous",
    "SSP",
    "EASGD",
    "GM",
    "FGM",
    "CentralizedTraining",
    "SingleLearner",
)


SPMD_PROTOCOLS = (
    "Asynchronous",
    "Synchronous",
    "SSP",
    "EASGD",
    "GM",
    "FGM",
)


def _codec_seconds(job) -> float:
    """Total transport-codec encode+decode time across every node."""
    total = 0.0
    for hub in job.hub_manager.hubs.values():
        c = getattr(hub.node, "codec", None)
        if c is not None:
            total += c.encode_seconds + c.decode_seconds
    for spoke in job.spokes:
        for net in spoke.nets.values():
            c = getattr(net.node, "codec", None)
            if c is not None:
                total += c.encode_seconds + c.decode_seconds
    return total


def run_one(protocol: str, x, y, parallelism: int, batch: int,
            engine: str = "host", codec: str = "none", chaos: str = "",
            sync_every: int = 4, guard: bool = False, telemetry: str = "",
            events: str = ""):
    import numpy as np

    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    n = x.shape[0]
    job = StreamJob(
        JobConfig(
            parallelism=parallelism, batch_size=batch, test_set_size=64,
            chaos=chaos, telemetry=telemetry, events=events,
        )
    )
    create = {
        "id": 0,
        "request": "Create",
        "learner": {
            "name": "PA",
            "hyperParameters": {"C": 1.0},
            "dataStructure": {"nFeatures": int(x.shape[1])},
        },
        "trainingConfiguration": {"protocol": protocol, "syncEvery": sync_every},
    }
    if codec != "none":
        create["trainingConfiguration"]["comm"] = {"codec": codec}
    if guard:
        create["trainingConfiguration"]["guard"] = True
    if engine == "spmd":
        create["trainingConfiguration"]["engine"] = "spmd"
        create["trainingConfiguration"]["stageChain"] = 4
    job.process_event(REQUEST_STREAM, json.dumps(create))
    op = np.zeros((n,), np.uint8)
    chunk = 8192
    t0 = time.perf_counter()
    for i in range(0, n, chunk):
        job.process_packed_batch(
            x[i : i + chunk], y[i : i + chunk], op[i : i + chunk]
        )
    report = job.terminate()
    elapsed = time.perf_counter() - t0
    timing = job.launch_timing()
    [stats] = report.statistics
    out = {
        "examples_per_sec": round(n / elapsed, 1),
        "score": round(stats.score, 4),
        "fitted": stats.fitted,
        "bytes_shipped": stats.bytes_shipped,
        "bytes_on_wire": stats.bytes_on_wire,
        "models_shipped": stats.models_shipped,
        "num_of_blocks": stats.num_of_blocks,
        # resilience counters (runtime/messages receive windows + hub
        # liveness): zero on fault-free runs, nonzero under chaos — BENCH
        # rounds track chaos overhead through these
        "duplicates_dropped": stats.duplicates_dropped,
        "gaps_resynced": stats.gaps_resynced,
        "quorum_releases": stats.quorum_releases,
        # model-integrity guard counters (trainingConfiguration.guard):
        # zero on guard-off and clean guarded runs, nonzero when the
        # admission / rollback / quarantine / eviction paths engage
        "deltas_rejected": stats.deltas_rejected,
        "rollbacks_performed": stats.rollbacks_performed,
        "records_quarantined": stats.records_quarantined,
        "members_evicted": stats.members_evicted,
        # forecast serving telemetry (runtime/serving.py): served count +
        # enqueue->emit latency percentiles, populated by the per-record
        # path and the adaptive-batching plane alike (zero on the
        # all-training streams of the protocol section)
        "forecasts_served": stats.forecasts_served,
        "serve_latency_p50_ms": round(stats.serve_latency_p50_ms, 3),
        "serve_latency_p99_ms": round(stats.serve_latency_p99_ms, 3),
        "serve_latency_p999_ms": round(stats.serve_latency_p999_ms, 3),
        # model-lifecycle counters (runtime/lifecycle.py): zero with the
        # plane unarmed (the default here); shadow/canary activity and
        # the live version gauge engage under --lifecycle-smoke
        "shadow_scored": stats.shadow_scored,
        "canary_promotions": stats.canary_promotions,
        "canary_rollbacks": stats.canary_rollbacks,
        "active_version": stats.active_version,
        # overload-control counters (runtime/overload.py): zero with the
        # plane unarmed; under pressure the shed/throttle/pressure gauges
        # engage (--overload-smoke gates them)
        "forecasts_shed": stats.forecasts_shed,
        "records_throttled": stats.records_throttled,
        "pressure_level": stats.pressure_level,
        "shed_latency_ms": round(stats.shed_latency_ms, 3),
        # end-of-run queue-depth snapshot (uniform accessors: serving
        # rows, batcher backlog, throttled rows, paused rows) — nonzero
        # values at terminate mean stranded work
        "queue_depths": job.queue_depths(),
        # serving-LAUNCH percentiles (Spoke.serve_timer): per predict
        # dispatch ms on the immediate, batched-plane and gang serve
        # paths — the launch-cost twin of the enqueue->emit latencies
        "serve_launch_p50_ms": round(timing["serve_p50_ms"], 4),
        "serve_launch_p99_ms": round(timing["serve_p99_ms"], 4),
        # transport-codec wall time, surfaced from the Statistics report
        # itself (ISSUE 13 satellite: previously visible only on the
        # codec objects) — zero with codec none
        "codec_encode_seconds": round(stats.codec_encode_seconds, 4),
        "codec_decode_seconds": round(stats.codec_decode_seconds, 4),
        # launch-dispatch percentile gauges from the report (folded only
        # with the telemetry plane armed — they are wall-clock values,
        # and unarmed reports stay reproducible)
        "launch_p50_ms": round(stats.launch_p50_ms, 4),
        "launch_p99_ms": round(stats.launch_p99_ms, 4),
        # flight-recorder counters (runtime/events.py): zero with the
        # plane unarmed; decision events + watchdog alerts engage under
        # --incident-smoke
        "events_recorded": stats.events_recorded,
        "alerts_raised": stats.alerts_raised,
    }
    if telemetry:
        tel = job.telemetry
        out["heartbeats"] = tel.heartbeats_emitted
        out["spans_completed"] = tel.spans.completed
        out["phase_table"] = job.phase_table(elapsed)
    if codec != "none":
        out["codec_seconds"] = round(_codec_seconds(job), 4)
    if job.spmd_bridges:
        [bridge] = job.spmd_bridges.values()
        out["bytes_physical"] = bridge.trainer.collective_bytes_physical()
    return out


def run_multi_tenant_one(n_pipe, x, y, batch, cohort, test=False,
                         sync_every=4, protocol="Asynchronous",
                         shards="off"):
    """One multi-tenant job: N same-spec pipelines on one stream through
    the packed route (parallelism 1 — the co-hosted serving plane),
    cohort gang dispatch on or off, the tenant axis optionally laid
    across the device mesh (``shards``: off / auto / N)."""
    import numpy as np

    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    records = x.shape[0]
    job = StreamJob(
        JobConfig(
            parallelism=1, batch_size=batch, test_set_size=64,
            cohort=cohort, cohort_min=2, test=test, cohort_shards=shards,
        )
    )
    for pid in range(n_pipe):
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": pid,
            "request": "Create",
            "learner": {
                "name": "PA",
                "hyperParameters": {"C": 1.0},
                "dataStructure": {"nFeatures": int(x.shape[1])},
            },
            "trainingConfiguration": {
                "protocol": protocol, "syncEvery": sync_every,
            },
        }))
    op = np.zeros((records,), np.uint8)
    # untimed warmup chunk compiles the (shared) programs; clamped so
    # short streams keep a timed region instead of reporting negative
    # throughput
    chunk = min(8192, max(records // 2, 1))
    job.process_packed_batch(x[:chunk], y[:chunk], op[:chunk])
    t0 = time.perf_counter()
    for i in range(chunk, records, chunk):
        job.process_packed_batch(x[i:i+chunk], y[i:i+chunk], op[i:i+chunk])
    elapsed = time.perf_counter() - t0
    report = job.terminate()
    timing = job.launch_timing()
    # mesh-width attribution (ISSUE 9): the device count, the engaged
    # tenant shard count and the per-shard member placement ride every
    # sweep row so BENCH rounds can attribute throughput to mesh width
    topo = job.tenant_topology()
    timed = records - chunk
    return {
        "pipelines": n_pipe,
        "per_tenant_examples_per_sec": round(timed / elapsed, 1),
        "aggregate_examples_per_sec": round(timed * n_pipe / elapsed, 1),
        "program_launches": sum(
            s.program_launches for s in report.statistics
        ),
        "score": round(report.statistics[0].score, 4),
        "launch_p50_ms": round(timing["p50_ms"], 4),
        "launch_p99_ms": round(timing["p99_ms"], 4),
        "serve_launch_p50_ms": round(timing["serve_p50_ms"], 4),
        "serve_launch_p99_ms": round(timing["serve_p99_ms"], 4),
        "devices": topo["devices"],
        "cohort_shards": topo["cohort_shards"],
        "tenant_placement": topo["placement"],
        "queue_depths": topo["queues"],
    }


def _mt_stream(records, dim=28):
    """The multi-tenant synthetic stream (one definition for the sweep AND
    the CI gate, so they always measure the same task)."""
    import numpy as np

    rng = np.random.RandomState(0)
    w = np.random.RandomState(42).randn(dim)
    x = rng.randn(records, dim).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    return x, y


# records for the holdout-scored parity legs: throughput runs use
# test=False (production serving mode, where every score is trivially 0),
# so score parity is checked on separate SHORT test=True runs
MT_PARITY_RECORDS = 16_384


def run_multi_tenant(pipeline_counts, records, batch, test=False):
    """Multi-tenant sweep: per-tenant and aggregate ex/s for N co-hosted
    same-spec pipelines, per-pipeline dispatch (cohort off) vs cohort gang
    dispatch (cohort auto) vs DEVICE-SHARDED cohort dispatch (cohort auto
    + cohort_shards auto — the tenant axis laid across the local mesh),
    with programLaunches, spoke-flush launch percentiles, and the device
    count / tenant placement per run — plus a holdout-scored (test=True)
    parity pair per point, whose scores must match bitwise."""
    import jax

    x, y = _mt_stream(records)
    px, py = _mt_stream(MT_PARITY_RECORDS)

    out = {}
    for n in pipeline_counts:
        per = run_multi_tenant_one(n, x, y, batch, "off", test=test)
        coh = run_multi_tenant_one(n, x, y, batch, "auto", test=test)
        coh["aggregate_speedup_vs_per_pipeline"] = round(
            coh["aggregate_examples_per_sec"]
            / max(per["aggregate_examples_per_sec"], 1e-9), 2
        )
        pp = run_multi_tenant_one(n, px, py, batch, "off", test=True)
        pc = run_multi_tenant_one(n, px, py, batch, "auto", test=True)
        coh["holdout_score"] = pc["score"]
        coh["holdout_score_parity"] = pc["score"] == pp["score"]
        row = {"per_pipeline": per, "cohort": coh}
        if jax.local_device_count() > 1:
            shd = run_multi_tenant_one(
                n, x, y, batch, "auto", test=test, shards="auto"
            )
            shd["aggregate_speedup_vs_per_pipeline"] = round(
                shd["aggregate_examples_per_sec"]
                / max(per["aggregate_examples_per_sec"], 1e-9), 2
            )
            shd["aggregate_speedup_vs_single_device_cohort"] = round(
                shd["aggregate_examples_per_sec"]
                / max(coh["aggregate_examples_per_sec"], 1e-9), 2
            )
            ps = run_multi_tenant_one(
                n, px, py, batch, "auto", test=True, shards="auto"
            )
            shd["holdout_score"] = ps["score"]
            shd["holdout_score_parity"] = ps["score"] == pp["score"]
            row["cohort_sharded"] = shd
        out[str(n)] = row
    return out


def run_shard_protocol_one(protocol, x, y, batch, shards, parallelism=2,
                           n_pipe=3, sync_every=4):
    """One multi-tenant multi-worker job for the shard-smoke protocol
    envelope: N same-spec pipelines under ``protocol`` at parallelism 2,
    cohort gang dispatch with the tenant axis on ``shards`` device
    shards. Returns {pipeline: holdout score}."""
    import numpy as np

    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    job = StreamJob(
        JobConfig(
            parallelism=parallelism, batch_size=batch, test_set_size=64,
            cohort="auto", cohort_min=2, cohort_shards=shards,
        )
    )
    for pid in range(n_pipe):
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": pid,
            "request": "Create",
            "learner": {
                "name": "PA",
                "hyperParameters": {"C": 1.0},
                "dataStructure": {"nFeatures": int(x.shape[1])},
            },
            "trainingConfiguration": {
                "protocol": protocol, "syncEvery": sync_every,
            },
        }))
    op = np.zeros((x.shape[0],), np.uint8)
    for i in range(0, x.shape[0], 2048):
        job.process_packed_batch(x[i:i+2048], y[i:i+2048], op[i:i+2048])
    report = job.terminate()
    return {s.pipeline: round(s.score, 4) for s in report.statistics}


def run_serving_one(n_pipe, x, y, op, batch, serving, cohort="off",
                    test=False, collect_preds=False,
                    protocol="Asynchronous", shards="off"):
    """One forecast-mix job: N same-spec pipelines on one mixed
    train/forecast stream through the packed route (parallelism 1 — the
    co-hosted serving plane), with the adaptive-batching serving config
    ``serving`` (None = the per-record reference path). Reports forecast
    throughput and the serving-latency percentiles from the pipeline
    statistics."""
    import numpy as np

    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    records = x.shape[0]
    job = StreamJob(
        JobConfig(
            parallelism=1, batch_size=batch, test_set_size=64,
            cohort=cohort, cohort_min=2, test=test, cohort_shards=shards,
        )
    )
    for pid in range(n_pipe):
        tc = {"protocol": protocol, "syncEvery": 4}
        if serving is not None:
            tc["serving"] = serving
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": pid,
            "request": "Create",
            "learner": {
                "name": "PA",
                "hyperParameters": {"C": 1.0},
                "dataStructure": {"nFeatures": int(x.shape[1])},
            },
            "trainingConfiguration": tc,
        }))
    # untimed warmup chunk compiles the fit AND the padded predict
    # programs (per pow2 queue bucket), so the timed region measures
    # dispatch, not compilation; clamped so short streams still leave a
    # timed region instead of reporting negative throughput
    chunk = min(4096, max(records // 2, 1))
    job.process_packed_batch(x[:chunk], y[:chunk], op[:chunk])
    t0 = time.perf_counter()
    for i in range(chunk, records, chunk):
        job.process_packed_batch(x[i:i+chunk], y[i:i+chunk], op[i:i+chunk])
    elapsed = time.perf_counter() - t0
    report = job.terminate()
    timing = job.launch_timing()
    n_forecast_timed = int((op[chunk:] != 0).sum())
    stats = report.statistics[0]
    out = {
        "pipelines": n_pipe,
        "records": records,
        "forecast_rows": int((op != 0).sum()),
        "examples_per_sec": round((records - chunk) / elapsed, 1),
        "forecasts_per_sec_per_tenant": round(n_forecast_timed / elapsed, 1),
        "aggregate_forecasts_per_sec": round(
            n_forecast_timed * n_pipe / elapsed, 1
        ),
        "forecasts_served": sum(
            s.forecasts_served for s in report.statistics
        ),
        "serve_latency_p50_ms": round(
            max(s.serve_latency_p50_ms for s in report.statistics), 3
        ),
        "serve_latency_p99_ms": round(
            max(s.serve_latency_p99_ms for s in report.statistics), 3
        ),
        "serve_latency_p999_ms": round(
            max(s.serve_latency_p999_ms for s in report.statistics), 3
        ),
        "serve_launch_p50_ms": round(timing["serve_p50_ms"], 4),
        "serve_launch_p99_ms": round(timing["serve_p99_ms"], 4),
        "program_launches": sum(
            s.program_launches for s in report.statistics
        ),
        "score": round(stats.score, 4),
        "queue_depths": job.queue_depths(),
    }
    if collect_preds:
        preds = {}
        for p in job.predictions:
            preds.setdefault(p.mlp_id, []).append(p.value)
        out["_preds"] = preds
        out["_scores"] = {
            s.pipeline: s.score for s in report.statistics
        }
    return out


# the serve-smoke latency budget: generous enough for a throttled CI box,
# tight enough that a deadline/flush regression (stranded queues) fails
SERVE_SMOKE_DELAY_MS = 250.0
SERVE_SMOKE_BATCH = 128


def run_serving_comparison(mix, records, batch, pipeline_counts=(64,)):
    """The forecast-mix serving sweep: per-record serving vs the adaptive-
    batching plane (exact and relaxed staleness) at each tenant count, on
    one shared forecast-heavy stream (benchmarks/streams.py) — measured on
    BOTH serving topologies: solo per-tenant dispatch (cohort off, the
    reference's serving semantics) and cohort gang dispatch (cohort auto,
    where PR6's cross-tenant gang already amortizes launches and the
    plane's remaining win is batching across stream positions)."""
    from benchmarks.streams import forecast_stream

    x, y, op = forecast_stream(records, mix=mix)
    serving_exact = {"maxBatch": SERVE_SMOKE_BATCH,
                     "maxDelayMs": SERVE_SMOKE_DELAY_MS,
                     "staleness": "exact"}
    serving_relaxed = {**serving_exact, "staleness": "relaxed",
                       "staleChunks": 4}
    out = {"forecast_mix": mix}
    for n in pipeline_counts:
        rows = {}
        for label, cohort in (("solo", "off"), ("cohort", "auto")):
            per = run_serving_one(n, x, y, op, batch, None, cohort=cohort)
            exact = run_serving_one(
                n, x, y, op, batch, serving_exact, cohort=cohort
            )
            relaxed = run_serving_one(
                n, x, y, op, batch, serving_relaxed, cohort=cohort
            )
            for row in (exact, relaxed):
                row["forecast_speedup_vs_per_record"] = round(
                    row["aggregate_forecasts_per_sec"]
                    / max(per["aggregate_forecasts_per_sec"], 1e-9), 2
                )
            rows[label] = {
                "per_record": per,
                "serving_exact": exact,
                "serving_relaxed": relaxed,
            }
        out[str(n)] = rows
    return out


# the overload-smoke operating point (ISSUE 10): 64 co-hosted tenants on
# a 50/50 train/forecast per-record stream, a 10x forecast burst flooding
# tenant 0 through the middle half of the stream, serving armed with a
# 500 ms delay budget (a fan-out forecast fills all 64 solo queues, so a
# fill cycle dispatches 64 predict launches back to back — a single-core
# CI box needs the headroom; tight enough that stranded queues or a
# burst-induced latency collapse still fails), and the controller tuned
# so the burst traverses the WHOLE ladder (ELEVATED throttling ->
# CRITICAL shedding) and decays back to OK inside the post-burst tail
OVERLOAD_SPEC = "window=32,share=2,hotHigh=24,hotCritical=48,cool=24"
OVERLOAD_SERVING = {"maxBatch": 64, "maxDelayMs": 500.0}
OVERLOAD_BURST = 10


def _overload_chaos(records: int) -> str:
    # burst window in FORECAST records (mix 0.5 => records/2 forecasts):
    # the middle half floods, leaving a clean ramp and a decay tail
    n_fore = records // 2
    return (
        f"seed=7,burst={OVERLOAD_BURST},burstFrom={n_fore // 4},"
        f"burstLen={n_fore // 2},hotTenant=0"
    )


def run_overload_one(n_pipe, x, y, burst, records=None, batch=256,
                     overload=OVERLOAD_SPEC, serving=OVERLOAD_SERVING):
    """One overload job: N same-spec pipelines fed the PER-RECORD route
    (tenant-addressed burst clones need record-level routing) with a
    50/50 train/forecast mix; ``burst`` arms the seeded hot-tenant
    injector. Reports hot/healthy split of the serving + shed counters."""
    import numpy as np

    from omldm_tpu.api.data import DataInstance, FORECASTING
    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import (
        FORECASTING_STREAM,
        REQUEST_STREAM,
        TRAINING_STREAM,
    )

    records = records or x.shape[0]
    # cohort off: the smoke measures the overload plane on SOLO per-tenant
    # dispatch (the reference's serving semantics; the cohort axis has its
    # own gates), and the per-event gang bookkeeping would otherwise tax
    # every injected burst clone
    job = StreamJob(JobConfig(
        parallelism=1, batch_size=batch, test_set_size=64, test=False,
        cohort="off", overload=overload, serving="",
        chaos=_overload_chaos(records) if burst else "",
    ))
    for pid in range(n_pipe):
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": pid, "request": "Create",
            "learner": {
                "name": "PA", "hyperParameters": {"C": 1.0},
                "dataStructure": {"nFeatures": int(x.shape[1])},
            },
            "trainingConfiguration": {
                "protocol": "Asynchronous", "syncEvery": 4,
                "serving": serving,
            },
        }))
    # untimed warmup (compiles fit + padded predict programs)
    warm = min(512, records // 4)
    for i in range(warm):
        if i % 2 == 0:
            job.process_event(FORECASTING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), operation=FORECASTING))
        else:
            job.process_event(TRAINING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), target=float(y[i])))
    t0 = time.perf_counter()
    for i in range(warm, records):
        if i % 2 == 0:
            job.process_event(FORECASTING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), operation=FORECASTING))
        else:
            job.process_event(TRAINING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), target=float(y[i])))
    elapsed = time.perf_counter() - t0
    level_after_feed = job.overload_level()
    report = job.terminate()
    by_pipe = {s.pipeline: s for s in report.statistics}
    hot = by_pipe[0]
    healthy = [s for p, s in by_pipe.items() if p != 0]
    healthy_served = sum(s.forecasts_served for s in healthy)
    return {
        "pipelines": n_pipe,
        "records": records,
        "burst": bool(burst),
        "elapsed_s": round(elapsed, 3),
        "healthy_forecasts_served": healthy_served,
        "healthy_forecasts_per_sec": round(healthy_served / elapsed, 1),
        "healthy_serve_p99_ms": round(
            max((s.serve_latency_p99_ms for s in healthy), default=0.0), 3
        ),
        "healthy_shed": sum(s.forecasts_shed for s in healthy),
        "hot_served": hot.forecasts_served,
        "hot_shed": hot.forecasts_shed,
        "hot_throttled": hot.records_throttled,
        "pressure_peak": max(s.pressure_level for s in by_pipe.values()),
        "level_after_feed": level_after_feed,
        "shed_latency_ms": round(
            max(s.shed_latency_ms for s in by_pipe.values()), 3
        ),
        "dead_letter_reasons": dict(job.dead_letter.by_reason),
        "queue_depths": job.queue_depths(),
    }


# the lifecycle-smoke operating point (ISSUE 11): one lifecycle-armed
# pipeline on a 50/50 per-record train/forecast stream; the canary ramps
# 0 -> 50% (step 0.125 every 64 canary-era forecasts), auto-promotion
# needs 128 canary serves at the full ramp + 2 healthy shadow evals
LIFECYCLE_SPEC = {
    "rampFrom": 0.0, "rampTo": 0.5, "rampEvery": 64, "rampStep": 0.125,
    "promoteAfter": 128, "shadowEvery": 8, "minShadowEvals": 2,
    "scoreEnvelope": 0.05, "seed": 7,
}


def run_lifecycle_one(x, y, mode, lifecycle=None, poison_at=1024):
    """One lifecycle job on a 50/50 per-record stream. ``mode``:

    - ``"off"``: lifecycle unarmed — the pre-plane reference leg;
    - ``"healthy"``: Shadow + Promote a healthy candidate (same learner,
      softer C) and let the ramp auto-promote it;
    - ``"hold"``: same canary but promoteAfter beyond the stream — the
      ramp serves the whole run, pinning baseline bitwise identity;
    - ``"poison"``: Shadow + Promote, then seed the candidate's params
      with an exploding vector at event ``poison_at`` — the candidate's
      guard must trip and auto-roll the canary back.

    Returns emitted predictions (value, version) in stream order plus the
    registry view and folded statistics."""
    import numpy as np

    from omldm_tpu.api.data import DataInstance, FORECASTING
    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import (
        FORECASTING_STREAM,
        REQUEST_STREAM,
        TRAINING_STREAM,
    )

    records = x.shape[0]
    spec = dict(lifecycle or LIFECYCLE_SPEC)
    if mode == "hold":
        spec["promoteAfter"] = 10 * records
    job = StreamJob(JobConfig(
        parallelism=1, batch_size=64, test_set_size=64, test=True,
    ))
    tc = {"protocol": "Asynchronous", "syncEvery": 4}
    if mode != "off":
        tc["lifecycle"] = spec
    job.process_event(REQUEST_STREAM, json.dumps({
        "id": 0, "request": "Create",
        "learner": {
            "name": "PA", "hyperParameters": {"C": 1.0},
            "dataStructure": {"nFeatures": int(x.shape[1])},
        },
        "trainingConfiguration": tc,
    }))
    if mode != "off":
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": 0, "request": "Shadow",
            "learner": {
                "name": "PA", "hyperParameters": {"C": 0.5},
                "dataStructure": {"nFeatures": int(x.shape[1])},
            },
        }))
        job.process_event(REQUEST_STREAM, json.dumps(
            {"id": 0, "request": "Promote"}
        ))
    net = job.spokes[0].nets[0]
    for i in range(records):
        if mode == "poison" and i == poison_at:
            entry = net.lifecycle.candidate_entry
            if entry is not None and entry.pipeline is not None:
                flat, _ = entry.pipeline.get_flat_params()
                entry.pipeline.set_flat_params(
                    np.full_like(flat, 1.0e9)
                )
        if i % 2 == 0:
            job.process_event(FORECASTING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), operation=FORECASTING))
        else:
            job.process_event(TRAINING_STREAM, DataInstance(
                numerical_features=x[i].tolist(), target=float(y[i])))
    lc = net.lifecycle.describe() if net.lifecycle is not None else None
    preds = [(p.value, p.version) for p in job.predictions]
    report = job.terminate()
    [stats] = report.statistics
    return {
        "mode": mode,
        "predictions": preds,
        "lifecycle": lc,
        "score": round(stats.score, 4),
        "shadow_scored": stats.shadow_scored,
        "canary_promotions": stats.canary_promotions,
        "canary_rollbacks": stats.canary_rollbacks,
        "active_version": stats.active_version,
        "forecasts_served": stats.forecasts_served,
    }


# codecs swept by --codec sweep, and the host protocols the codec section
# compares (the model-shipping protocols; GM/FGM traffic is mostly votes)
CODEC_SWEEP = ("none", "fp16", "int8", "topk")
CODEC_PROTOCOLS = ("Asynchronous", "Synchronous", "EASGD", "GM")

# the acceptance chaos operating point (ISSUE 4): 5% drop, 5% dup,
# reorder window 4 on both directions of the hub<->spoke bridge
DEFAULT_CHAOS = "seed=7,drop=0.05,dup=0.05,reorder=0.1,window=4"


def run_chaos_resilience(protocols, records, parallelism, batch,
                         chaos=DEFAULT_CHAOS, dim=28):
    """Each protocol on the same stream, fault-free vs under the seeded
    chaos channel: final-score delta (the loss envelope) plus the
    resilience counters the reliable channel accumulated while repairing
    the damage."""
    import numpy as np

    rng = np.random.RandomState(11)
    w = np.random.RandomState(42).randn(dim)
    x = rng.randn(records, dim).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)

    out = {"chaos_spec": chaos, "protocols": {}}
    for protocol in protocols:
        # syncEvery 1: the chaos section measures CHANNEL behavior, so it
        # wants message volume, not the codec section's params economy
        clean = run_one(protocol, x, y, parallelism, batch, sync_every=1)
        chaotic = run_one(
            protocol, x, y, parallelism, batch, chaos=chaos, sync_every=1
        )
        chaotic["score_delta_vs_clean"] = round(
            chaotic["score"] - clean["score"], 4
        )
        chaotic["overhead_examples_per_sec"] = round(
            clean["examples_per_sec"]
            / max(chaotic["examples_per_sec"], 1e-9),
            2,
        )
        out["protocols"][protocol] = {
            "clean_score": clean["score"],
            **chaotic,
        }
    return out


def run_codec_comparison(codecs, records, parallelism, batch,
                         protocols=CODEC_PROTOCOLS, dim=256):
    """Sweep transport codecs over a params-dominated stream: per
    (protocol, codec) bytes-on-wire, wire reduction vs the uncompressed
    run, codec CPU seconds, throughput and final score."""
    import numpy as np

    rng = np.random.RandomState(7)
    w = np.random.RandomState(43).randn(dim)
    x = rng.randn(records, dim).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)

    out = {}
    for protocol in protocols:
        rows = {}
        for codec in codecs:
            r = run_one(protocol, x, y, parallelism, batch, codec=codec)
            rows[codec] = r
        base = max(rows.get("none", {}).get("bytes_on_wire", 0), 1)
        for codec, r in rows.items():
            if codec != "none":
                r["wire_reduction_vs_none"] = round(
                    base / max(r["bytes_on_wire"], 1), 2
                )
                r["score_delta_vs_none"] = round(
                    r["score"] - rows["none"]["score"], 4
                )
        out[protocol] = rows
    return out


def run_distributed_route(codecs, dim=256, steps=24, batch=32):
    """The multi-process model-exchange route: the SPMDTrainer collective
    sync that carries the distributed job's hub<->spoke traffic (the role
    of the reference's psMessages Kafka loop). Measures bytes-on-wire per
    codec on an 8-worker mesh and the parameter drift vs uncompressed."""
    import numpy as np

    from omldm_tpu.api.requests import LearnerSpec, TrainingConfiguration
    from omldm_tpu.parallel.mesh import make_mesh
    from omldm_tpu.parallel.spmd import SPMDTrainer

    mesh = make_mesh(dp=8, hub=1)
    w = np.random.RandomState(44).randn(dim)
    r = np.random.RandomState(5)
    batches = []
    for _ in range(steps):
        x = r.randn(8, batch, dim).astype(np.float32)
        batches.append((x, (x @ w > 0).astype(np.float32),
                        np.ones((8, batch), np.float32)))

    def run(codec):
        extra = {"syncEvery": 4}
        if codec != "none":
            extra["comm"] = {"codec": codec}
        t = SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}), dim=dim,
            protocol="Synchronous", mesh=mesh,
            training_configuration=TrainingConfiguration(
                protocol="Synchronous", extra=extra
            ),
        )
        t0 = time.perf_counter()
        for x, y, m in batches:
            t.step(x, y, m)
        elapsed = time.perf_counter() - t0
        return t, elapsed

    out = {}
    base_t, base_s = run("none")
    base_wire = base_t.bytes_on_wire()
    base_flat = base_t.global_flat_params()
    out["none"] = {
        "bytes_on_wire": base_wire,
        "bytes_shipped": base_t.bytes_shipped(),
        "sync_seconds": round(base_s, 3),
    }
    for codec in codecs:
        if codec in ("none", "topk"):
            continue  # topk is host-plane only (dense allreduce operands)
        t, secs = run(codec)
        drift = float(
            np.linalg.norm(t.global_flat_params() - base_flat)
            / max(np.linalg.norm(base_flat), 1e-9)
        )
        out[codec] = {
            "bytes_on_wire": t.bytes_on_wire(),
            "wire_reduction_vs_none": round(
                base_wire / max(t.bytes_on_wire(), 1), 2
            ),
            "param_drift_rel": round(drift, 4),
            "sync_seconds": round(secs, 3),
        }
    return out


# the incident-smoke operating point (ISSUE 14): a guard-armed supervised
# in-process run with ONE seeded poisoned worker (its params explode at a
# fixed chunk, syncEvery=1 ships them before the worker-side guard can
# roll back) and a one-shot injected worker death a few chunks later. The
# run must leave ONE merged incident bundle whose fleet timeline carries
# the rejection -> strike -> retire -> restart chain in causal order on
# the transport stamps, at least one kind="alert" record on the
# performance sink, and arming the recorder on a clean stream must cost
# <= 3% (paired trials) with BITWISE-equal scores.
INCIDENT_RECORDS = 16_000
INCIDENT_EVENTS_SPEC = "watchdogEvery=2048,shedHigh=1"


def run_incident_smoke() -> None:
    import shutil
    import tempfile

    import numpy as np

    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import PACKED_STREAM, REQUEST_STREAM
    from omldm_tpu.runtime.recovery import (
        FaultInjector,
        JobSupervisor,
        replayable,
    )

    records = INCIDENT_RECORDS
    dim, par, batch, chunk = 28, 2, 64, 512
    rng = np.random.RandomState(11)
    w = np.random.RandomState(42).randn(dim)
    gx = rng.randn(records, dim).astype(np.float32)
    gy = (gx @ w > 0).astype(np.float32)
    op = np.zeros((records,), np.uint8)
    create_line = json.dumps({
        "id": 0, "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                    "dataStructure": {"nFeatures": dim}},
        "trainingConfiguration": {
            "protocol": "Asynchronous", "syncEvery": 1,
            "guard": {"maxStrikes": 1}, "comm": {"reliable": True},
        },
    })
    failures = []
    out = {}

    # --- paired clean legs: overhead + bitwise score identity ------------
    run_one("Asynchronous", gx[:2048], gy[:2048], par, batch, guard=True)
    run_one("Asynchronous", gx[:2048], gy[:2048], par, batch, guard=True,
            events=INCIDENT_EVENTS_SPEC)
    # 4 paired back-to-back trials, best pair (the guard/telemetry-smoke
    # rule: this box is share-throttled ±25%, and throttle noise only
    # ever inflates a pair's ratio, so the minimum over pairs is the
    # tightest available estimate of the systematic recorder overhead)
    pair_ratios = []
    clean_off = clean_on = None
    for _trial in range(4):
        r_off = run_one("Asynchronous", gx, gy, par, batch, guard=True)
        r_on = run_one("Asynchronous", gx, gy, par, batch, guard=True,
                       events=INCIDENT_EVENTS_SPEC)
        pair_ratios.append(
            r_off["examples_per_sec"] / max(r_on["examples_per_sec"], 1e-9)
        )
        if clean_off is None or (
            r_off["examples_per_sec"] > clean_off["examples_per_sec"]
        ):
            clean_off = r_off
        if clean_on is None or (
            r_on["examples_per_sec"] > clean_on["examples_per_sec"]
        ):
            clean_on = r_on
    overhead = min(pair_ratios)
    if clean_on["score"] != clean_off["score"]:
        failures.append(
            f"events-armed clean score {clean_on['score']} != unarmed "
            f"{clean_off['score']} (bitwise identity broken)"
        )
    if overhead > 1.03:
        failures.append(
            f"events-armed clean throughput {overhead:.3f}x slower than "
            "unarmed (> 3% bar)"
        )
    if clean_on["events_recorded"] < 1:
        failures.append("armed clean leg recorded no events at all")

    # --- the supervised incident leg -------------------------------------
    tmp = tempfile.mkdtemp(prefix="omldm-incident-smoke-")
    perf = []
    try:
        job = StreamJob(
            JobConfig(
                parallelism=par, batch_size=batch, test_set_size=64,
                events=INCIDENT_EVENTS_SPEC, blackbox_path=tmp,
            ),
            on_performance=perf.append,
        )
        holder = {"job": job}
        poisoned = [False]
        poison_chunk, death_rows = 6, 2500

        def make_events():
            yield (REQUEST_STREAM, create_line)
            for idx, i in enumerate(range(0, records, chunk)):
                if idx == poison_chunk and not poisoned[0]:
                    # the seeded poisoned worker: spoke 1's params explode
                    # right before this chunk, so its next syncEvery=1
                    # push ships the poison to the hub's admission gate
                    poisoned[0] = True
                    net = holder["job"].spokes[1].nets[0]
                    flat, _ = net.pipeline.get_flat_params()
                    net.pipeline.set_flat_params(np.full_like(flat, 1e9))
                yield (
                    PACKED_STREAM,
                    (gx[i:i + chunk], gy[i:i + chunk], op[i:i + chunk]),
                )

        injector = FaultInjector()
        injector.arm(job, worker_id=0, after_records=death_rows)
        sup = JobSupervisor(
            job, replayable(make_events), max_restarts=1,
            on_failure=lambda rec: holder.update(job=sup.job),
        )
        report = sup.run()
        out["incident"] = {
            "worker_death_fired": injector.fired,
            "restarts": len(sup.failures),
            "bundle": sup.bundle_path,
            "alerts_on_sink": sum(1 for p in perf if p.kind == "alert"),
            "final_score": (
                round(report.statistics[0].score, 4)
                if report is not None and report.statistics else None
            ),
        }
        if injector.fired != 1 or len(sup.failures) != 1:
            failures.append(
                "injected worker death did not produce exactly one "
                f"supervised restart (fired={injector.fired}, "
                f"restarts={len(sup.failures)})"
            )
        if not any(p.kind == "alert" for p in perf):
            failures.append(
                "no kind=\"alert\" record reached the performance sink"
            )
        if sup.bundle_path is None:
            failures.append("supervisor wrote no merged incident bundle")
        else:
            bundle = json.load(open(sup.bundle_path))
            timeline = bundle["timeline"]
            kinds = [e["kind"] for e in timeline]
            out["incident"]["by_kind"] = bundle["byKind"]

            def first(kind, pred=lambda e: True):
                for i, e in enumerate(timeline):
                    if e["kind"] == kind and pred(e):
                        return i
                return None

            i_rej = first(
                "delta_rejected", lambda e: e.get("strikes", 0) >= 1
            )
            i_ret = first(
                "worker_retired", lambda e: e["cause"] == "guard_strikes"
            )
            i_restart = first("restart")
            if i_rej is None or i_ret is None or i_restart is None:
                failures.append(
                    "bundle missing the rejection/strike/retire/restart "
                    f"chain (kinds present: {sorted(set(kinds))})"
                )
            elif not (i_rej < i_ret < i_restart):
                failures.append(
                    "bundle chain out of causal order: rejection@"
                    f"{i_rej}, retire@{i_ret}, restart@{i_restart}"
                )
            if i_rej is not None and timeline[i_rej].get("stamp") is None:
                failures.append(
                    "rejection event carries no transport stamp"
                )
            # stamped events must read in seq order PER SENDER STREAM
            # (merge_timeline's contract: independent seq counters —
            # other workers' channels, other hub shards — are never
            # cross-compared, so a pooled global assertion would be
            # stricter than the guarantee)
            per_stream: dict = {}
            for e in timeline:
                if e.get("stamp") and e["stamp"][0] == 0:
                    key = (e.get("worker"), e.get("hub"),
                           e.get("side", ""))
                    per_stream.setdefault(key, []).append(e["stamp"][1])
            for key, seqs in per_stream.items():
                if seqs != sorted(seqs):
                    failures.append(
                        f"stamped stream {key} not merge-sorted by "
                        f"seq: {seqs}"
                    )
            if "alert" not in kinds:
                failures.append("bundle carries no alert event")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    print(json.dumps({
        "config": "protocol_comparison_incident_smoke",
        "records": records,
        "clean_events_off": clean_off,
        "clean_events_on": clean_on,
        "events_overhead_x": round(overhead, 3),
        **out,
        "failures": failures,
    }))
    if failures:
        sys.exit(1)


# the autoscale-smoke operating point (ISSUE 12): a preloaded burst on
# the file-backed Kafka broker, consumed by a SUPERVISED 1-process fleet
# with pressure-driven autoscaling armed. The burst outpaces the
# backlogCritical threshold every poll window, so the fleet sustains
# CRITICAL, scales out to 2 processes (checkpoint -> relaunch ->
# restore-with-rescale), drains, sustains OK, and scales back in to the
# floor — two full elastic transitions inside one CI run.
AUTOSCALE_ROWS = 8_000
AUTOSCALE_FORE_EVERY = 20


def run_autoscale_smoke() -> None:
    """CI gate (ISSUE 12 acceptance): the supervised fleet must scale
    out under a seeded sustained burst, lose ZERO records across the
    restarts (every training row fitted or held out, every forecast
    served exactly once — the EMITTED/output dedupe contract), and
    return to the floor process count after the burst drains. NONZERO
    EXIT otherwise."""
    import subprocess
    import tempfile

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    sys.path.insert(0, tests)
    import fskafka

    tmp = tempfile.mkdtemp(prefix="omldm-autoscale-smoke-")
    broker = os.path.join(tmp, "broker")
    os.environ["FSKAFKA_DIR"] = broker
    n_fore = 0
    try:
        rng = np.random.RandomState(0)
        w = rng.randn(12)
        for i in range(AUTOSCALE_ROWS):
            x = np.round(rng.randn(12), 6)
            if i % AUTOSCALE_FORE_EVERY == 0:
                n_fore += 1
                line = json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "operation": "forecasting",
                })
            else:
                line = json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "target": float(x @ w > 0),
                    "operation": "training",
                })
            fskafka.append("trainingData", line, partition=i % 4)
        fskafka.append("requests", json.dumps({
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                        "dataStructure": {"nFeatures": 12}},
            "trainingConfiguration": {
                "protocol": "Synchronous", "syncEvery": 1,
            },
        }))
    finally:
        os.environ.pop("FSKAFKA_DIR", None)

    boot = (
        "import sys; sys.path.insert(0, {t!r}); "
        "import fskafka; fskafka.install(); "
        "from omldm_tpu.runtime.distributed_job import run_distributed; "
        "sys.exit(run_distributed(sys.argv[1:]))"
    ).format(t=tests)
    perf = os.path.join(tmp, "perf.jsonl")
    preds = os.path.join(tmp, "preds.jsonl")
    env = dict(os.environ)
    # one CPU device per worker process; the parent's 8-device XLA flag
    # must not leak into the fleet
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["FSKAFKA_DIR"] = broker
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-m", "omldm_tpu.runtime.distributed_job",
         "--supervise", "true", "--processes", "1",
         "--autoscale", "true", "--minProcesses", "1",
         "--maxProcesses", "2",
         "--scaleUpAfterMs", "200", "--scaleDownAfterMs", "1200",
         "--scaleCooldownMs", "400",
         "--overload", "backlogHigh=40,backlogCritical=80",
         "--kafkaBrokers", "fs://local", "--workerBoot", boot,
         "--checkpointDir", os.path.join(tmp, "ckpts"),
         "--checkpointEvery", "8",
         "--chunkRows", "100", "--kafkaPollMs", "50",
         "--idleWindows", "60",
         "--batchSize", "64", "--testSetSize", "32",
         "--restartAttempts", "2", "--restartDelayMs", "50",
         "--performanceOut", perf, "--predictionsOut", preds],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    wall_s = time.perf_counter() - t0
    err = out.stderr
    failures = []
    if out.returncode != 0:
        failures.append(
            f"supervised fleet exited {out.returncode}: {err[-2000:]}"
        )
    if "rescaling fleet 1 -> 2" not in err:
        failures.append("the burst never drove a scale-OUT decision")
    if "rescale-restore: redistributing a 1-process snapshot" not in err:
        failures.append("scale-out relaunch did not restore-with-rescale")
    if "rescaling fleet 2 -> 1" not in err:
        failures.append(
            "the fleet never scaled back IN after the burst drained"
        )
    report = {}
    stats = {}
    if not failures:
        report = json.loads(open(perf).read().strip())
        [stats] = report["statistics"]
        n_train = AUTOSCALE_ROWS - n_fore
        conserved = stats["fitted"] + report["holdout"]["0"]
        if conserved != n_train:
            failures.append(
                f"records lost across the restarts: fitted+holdout "
                f"{conserved} != {n_train} training rows"
            )
        payloads = [json.loads(l) for l in open(preds)]
        if len(payloads) != n_fore:
            failures.append(
                f"forecasts not served exactly once: {len(payloads)} "
                f"outputs for {n_fore} forecasts (output dedupe broken)"
            )
        if report.get("rescalesPerformed") != 2:
            failures.append(
                f"rescalesPerformed {report.get('rescalesPerformed')} != 2"
            )
        if report.get("fleetProcesses") != 1:
            failures.append(
                "fleet did not return to the floor process count "
                f"(fleetProcesses {report.get('fleetProcesses')})"
            )
    print(json.dumps({
        "config": "protocol_comparison_autoscale_smoke",
        "rows": AUTOSCALE_ROWS,
        "forecasts": n_fore,
        "wall_s": round(wall_s, 1),
        "rescales": report.get("rescalesPerformed"),
        "fleet_processes": report.get("fleetProcesses"),
        "fitted": stats.get("fitted"),
        "score": stats.get("score"),
        "failures": failures,
    }))
    if failures:
        sys.exit(1)


# the selfheal-smoke operating point (ISSUE 15): a supervised 2-process
# fleet with slot strikes + the collective hang watchdog armed; a seeded
# SIGSTOP freezes worker 1 at a fixed chunk, the survivor exits HANG_EXIT,
# the supervisor blames the silent slot, shrinks to the survivor via
# restore-with-rescale, probes back to full width once quiet, and heals —
# plus an unarmed-vs-armed-idle identity pair proving the new knobs add
# nothing to the data path when nothing fires.
SELFHEAL_ROWS = 6_000
SELFHEAL_FORE_EVERY = 20
SELFHEAL_IDENTITY_ROWS = 2_000


def _selfheal_identity_pair(tmp: str, env: dict, repo: str) -> list:
    """Two 1-process file-mode runs of the SAME stream — all self-heal
    knobs unset vs armed-but-idle (watchdog + fault state dir, no fault):
    predictions and the report's score/fitted must match BITWISE."""
    import subprocess

    import numpy as np

    rng = np.random.RandomState(1)
    w = rng.randn(12)
    data = os.path.join(tmp, "ident.jsonl")
    with open(data, "w") as f:
        for i in range(SELFHEAL_IDENTITY_ROWS):
            x = np.round(rng.randn(12), 6)
            if i % SELFHEAL_FORE_EVERY == 0:
                f.write(json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "operation": "forecasting",
                }) + "\n")
            else:
                f.write(json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "target": float(x @ w > 0), "operation": "training",
                }) + "\n")
    reqs = os.path.join(tmp, "ident_reqs.jsonl")
    with open(reqs, "w") as f:
        f.write(json.dumps({
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                        "dataStructure": {"nFeatures": 12}},
            "trainingConfiguration": {
                "protocol": "Synchronous", "syncEvery": 1,
            },
        }) + "\n")
    failures = []
    outs = {}
    for leg, extra in (
        ("unarmed", []),
        ("armed_idle", [
            "--collectiveTimeoutMs", "60000",
            "--faultStateDir", os.path.join(tmp, "ident_fault"),
        ]),
    ):
        perf = os.path.join(tmp, f"ident_{leg}_perf.jsonl")
        preds = os.path.join(tmp, f"ident_{leg}_preds.jsonl")
        out = subprocess.run(
            [sys.executable, "-m", "omldm_tpu.runtime.distributed_job",
             "--processes", "1",
             "--trainingData", data, "--requests", reqs,
             "--chunkRows", "200", "--batchSize", "64",
             "--testSetSize", "32",
             "--performanceOut", perf, "--predictionsOut", preds]
            + extra,
            cwd=repo, env=env, capture_output=True, text=True, timeout=300,
        )
        if out.returncode != 0:
            failures.append(
                f"identity leg {leg} exited {out.returncode}: "
                f"{out.stderr[-1500:]}"
            )
            return failures
        report = json.loads(open(perf).read().strip())
        [stats] = report["statistics"]
        outs[leg] = (
            open(preds).read(), stats["score"], stats["fitted"],
        )
    if outs["unarmed"] != outs["armed_idle"]:
        failures.append(
            "armed-but-idle self-heal knobs changed the data path: "
            f"unarmed (score {outs['unarmed'][1]}, fitted "
            f"{outs['unarmed'][2]}) != armed (score "
            f"{outs['armed_idle'][1]}, fitted {outs['armed_idle'][2]}) "
            "or predictions differ"
        )
    return failures


def run_selfheal_smoke() -> None:
    """CI gate (ISSUE 15 acceptance): a SIGSTOP'd worker must be blamed
    (survivors exit HANG_EXIT within --collectiveTimeoutMs — no wedged
    collective), the fleet must shrink to the survivors via restore-with-
    rescale with fitted+holdout exactly equal to the training rows and
    every forecast served exactly once, a later probe must restore the
    full width and heal, the run's bundles must carry the
    classify -> strike -> degrade -> probe chain in causal order, and the
    new knobs must be bit-identical no-ops while nothing fires. NONZERO
    EXIT otherwise."""
    import subprocess
    import tempfile

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    sys.path.insert(0, tests)
    import fskafka

    tmp = tempfile.mkdtemp(prefix="omldm-selfheal-smoke-")
    broker = os.path.join(tmp, "broker")
    os.environ["FSKAFKA_DIR"] = broker
    n_fore = 0
    try:
        rng = np.random.RandomState(0)
        w = rng.randn(12)
        for i in range(SELFHEAL_ROWS):
            x = np.round(rng.randn(12), 6)
            if i % SELFHEAL_FORE_EVERY == 0:
                n_fore += 1
                line = json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "operation": "forecasting",
                })
            else:
                line = json.dumps({
                    "numericalFeatures": [float(v) for v in x],
                    "target": float(x @ w > 0),
                    "operation": "training",
                })
            fskafka.append("trainingData", line, partition=i % 4)
        fskafka.append("requests", json.dumps({
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                        "dataStructure": {"nFeatures": 12}},
            "trainingConfiguration": {
                "protocol": "Synchronous", "syncEvery": 1,
            },
        }))
    finally:
        os.environ.pop("FSKAFKA_DIR", None)

    boot = (
        "import sys; sys.path.insert(0, {t!r}); "
        "import fskafka; fskafka.install(); "
        "from omldm_tpu.runtime.distributed_job import run_distributed; "
        "sys.exit(run_distributed(sys.argv[1:]))"
    ).format(t=tests)
    perf = os.path.join(tmp, "perf.jsonl")
    preds = os.path.join(tmp, "preds.jsonl")
    blackbox = os.path.join(tmp, "blackbox")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["FSKAFKA_DIR"] = broker
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-m", "omldm_tpu.runtime.distributed_job",
         "--supervise", "true", "--processes", "2",
         "--slotStrikes", "1", "--minProcesses", "1",
         "--probeAfterMs", "2000", "--probeWindowMs", "1500",
         "--collectiveTimeoutMs", "5000", "--killDeadlineMs", "1000",
         "--hangProcess", "1", "--hangAfterChunks", "6",
         "--faultStateDir", os.path.join(tmp, "fault"),
         "--flightRecorder", "on", "--blackboxPath", blackbox,
         "--kafkaBrokers", "fs://local", "--workerBoot", boot,
         "--checkpointDir", os.path.join(tmp, "ckpts"),
         "--checkpointEvery", "2",
         "--chunkRows", "100", "--kafkaPollMs", "50",
         "--idleWindows", "60",
         "--batchSize", "64", "--testSetSize", "32",
         "--restartAttempts", "2", "--restartDelayMs", "50",
         "--performanceOut", perf, "--predictionsOut", preds],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    wall_s = time.perf_counter() - t0
    err = out.stderr
    failures = []
    if out.returncode != 0:
        failures.append(
            f"supervised fleet exited {out.returncode}: {err[-2000:]}"
        )
    for marker, missing in (
        ("injected hang: SIGSTOP", "the hang fault never fired"),
        ("collective watchdog: no progress",
         "the survivor never exited HANG_EXIT (wedged collective)"),
        ("blaming wedged process 1",
         "the supervisor blamed the survivor, not the silent slot"),
        ("degrading fleet 2 -> 1",
         "the struck-out slot never triggered shrink-to-survivors"),
        ("redistributing a 2-process snapshot",
         "the degrade relaunch did not restore-with-rescale"),
        ("probing back 1 -> 2",
         "the degraded fleet never probed back toward full width"),
        ("fleet healed at 2", "the healthy probe never cleared the strikes"),
    ):
        if marker not in err:
            failures.append(missing)
    report = {}
    stats = {}
    if not failures:
        report = json.loads(open(perf).read().strip())
        [stats] = report["statistics"]
        n_train = SELFHEAL_ROWS - n_fore
        conserved = stats["fitted"] + report["holdout"]["0"]
        if conserved != n_train:
            failures.append(
                f"records lost across the hang/degrade/probe: "
                f"fitted+holdout {conserved} != {n_train} training rows"
            )
        pred_files = sorted(
            f for f in os.listdir(tmp) if f.startswith("preds.jsonl")
        )
        n_served = sum(
            1 for f in pred_files for _ in open(os.path.join(tmp, f))
        )
        if n_served != n_fore:
            failures.append(
                f"forecasts not served exactly once: {n_served} outputs "
                f"for {n_fore} forecasts"
            )
        if report.get("fleetProcesses") != 2:
            failures.append(
                "fleet did not return to full width "
                f"(fleetProcesses {report.get('fleetProcesses')})"
            )
        if report.get("fleetDegraded") != 0:
            failures.append(
                f"fleetDegraded {report.get('fleetDegraded')} != 0 after "
                "the heal"
            )
        bundles = sorted(
            f for f in os.listdir(blackbox) if f.startswith("incident-")
        )
        if not bundles:
            failures.append("no incident bundle written")
        else:
            final = json.load(open(os.path.join(blackbox, bundles[-1])))
            kinds = [e["kind"] for e in final["timeline"]]
            chain = [
                k for k in kinds if k in ("strike", "degrade", "probe")
            ]
            if chain[:3] != ["strike", "degrade", "probe"]:
                failures.append(
                    "run-end bundle missing the classify->strike->"
                    f"degrade->probe chain in order (saw {chain[:6]})"
                )
            all_kinds = set()
            for b in bundles:
                all_kinds.update(
                    e["kind"]
                    for e in json.load(
                        open(os.path.join(blackbox, b))
                    )["timeline"]
                )
            if "hang" not in all_kinds:
                failures.append(
                    "no bundle carries the worker-side hang event"
                )
    if not failures:
        failures += _selfheal_identity_pair(tmp, env, repo)
    print(json.dumps({
        "config": "protocol_comparison_selfheal_smoke",
        "rows": SELFHEAL_ROWS,
        "forecasts": n_fore,
        "wall_s": round(wall_s, 1),
        "fitted": stats.get("fitted"),
        "score": stats.get("score"),
        "fleet_processes": report.get("fleetProcesses"),
        "fleet_degraded": report.get("fleetDegraded"),
        "failures": failures,
    }))
    if failures:
        sys.exit(1)


SLO_SMOKE_TENANTS = 256
SLO_SMOKE_RECORDS = 256


def run_slo_smoke() -> None:
    """CI gate (ISSUE 19 acceptance): the deterministic load harness end
    to end at ~256 tenants —

    - the full-composition identity leg: every plane configured-but-
      unarmed must be BIT-IDENTICAL to the bare path;
    - a composed in-process storm (churn waves + diurnal curve +
      hot-tenant bursts + addressed traffic) through the ARMED plane
      matrix must pass every deterministic SLO gate (zero healthy-tenant
      forecast loss, exactly-once outputs, no stranded rows, shed scoped
      to the hot tenants), and a same-seed replay must produce a
      byte-identical deterministic report core;
    - a supervised fleet storm with two composed fault classes (launch
      refusal + mid-stream crash) must complete across the restarts with
      every gate green, heals observed and within budget.

    The serve-p99 budget is a throughput gate: ENFORCED only on hosts
    with >= 2 usable cores (on a 1-core box the serving deadline thread
    timeshares the training loop's core, so latency reflects the host,
    not the plane — same basis note as --shard-smoke); the measured p99
    is reported either way. NONZERO EXIT on any enforced breach."""
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import jax

    jax.config.update("jax_platforms", "cpu")

    from benchmarks.load_harness import (
        build_composed_storm,
        default_storm_spec,
        run_composition_identity,
        run_inprocess_storm,
        run_supervised_storm,
    )
    from omldm_tpu.runtime.loadgen import LoadStorm, StormSpec
    from omldm_tpu.runtime.slo import SLOBudgets

    try:
        n_cores = len(os.sched_getaffinity(0))
    except AttributeError:
        n_cores = os.cpu_count() or 1
    failures = []
    warnings = []
    t0 = time.perf_counter()

    # (a) full-composition identity: uniform broadcast traffic, the one
    # regime where EVERY plane must be transparent
    bare, composed = run_composition_identity(LoadStorm(StormSpec(
        seed=5, tenants=SLO_SMOKE_TENANTS, records=128, chunk_rows=64,
        n_features=4, forecast_ratio=0.4,
    )))
    if bare != composed:
        failures.append(
            "configured-but-unarmed plane matrix diverges bitwise from "
            "the bare path"
        )

    # (b) the armed composed storm + same-seed replay
    def _armed_run():
        storm = LoadStorm(default_storm_spec(
            seed=7, tenants=SLO_SMOKE_TENANTS, records=SLO_SMOKE_RECORDS,
            chunk_rows=64,
        ))
        budgets = SLOBudgets(
            serve_p99_ms=250.0,
            allow_shed_tenants=storm.hot_tenant_ids(),
            max_stranded_rows=0,
        )
        return run_inprocess_storm(storm, budgets)[0]

    armed = _armed_run()
    p99_ms = None
    for c in armed.checks:
        if c.name == "serve_p99":
            p99_ms = c.detail.get("p99Ms")
        if c.ok:
            continue
        msg = f"in-process {c.name} breached: {c.detail}"
        if c.name == "serve_p99" and n_cores < 2:
            warnings.append(msg + f" (not enforced: {n_cores} core host)")
        else:
            failures.append(msg)
    if armed.core_digest() != _armed_run().core_digest():
        failures.append(
            "same-seed replay produced a different deterministic "
            "report core"
        )

    # (c) the supervised fleet under the composed fault storm
    storm = build_composed_storm(
        3, tenants=16, records=192, chunk_rows=32, processes=1,
    )
    sup_budgets = SLOBudgets(
        heal_after_fault_s=120.0, expected_heals=2,
        allow_shed_tenants=storm.hot_tenant_ids(), max_stranded_rows=0,
    )
    tmp = tempfile.mkdtemp(prefix="omldm-slo-smoke-")
    sup_report, merged, _ = run_supervised_storm(
        storm, tmp, sup_budgets, processes=1,
    )
    heals = 0
    for c in sup_report.checks:
        if c.name == "heal_after_fault":
            heals = c.detail.get("heals", 0)
        if not c.ok:
            failures.append(f"supervised {c.name} breached: {c.detail}")

    print(json.dumps({
        "config": "protocol_comparison_slo_smoke",
        "tenants": SLO_SMOKE_TENANTS,
        "records": SLO_SMOKE_RECORDS,
        "cores": n_cores,
        "wall_s": round(time.perf_counter() - t0, 1),
        "serve_p99_ms": p99_ms,
        "supervised_heals": heals,
        "core_digest": armed.core_digest(),
        "p99_basis": (
            "serve-p99 enforced (>= 2 usable cores)" if n_cores >= 2
            else "serve-p99 reported only: 1-core host, the serving "
                 "deadline timeshares the training loop's core"
        ),
        "warnings": warnings,
        "failures": failures,
    }))
    if failures:
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=50_000)
    ap.add_argument("--parallelism", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument(
        "--codec", default="none",
        choices=("none", "fp16", "int8", "topk", "sweep"),
        help="transport codec section: one codec (vs none) or sweep",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: small stream, codec sections only, hard asserts",
    )
    ap.add_argument(
        "--chaos", default="",
        help="chaos resilience section: run the parameter protocols "
             "fault-free vs under this seeded chaos spec ('default' for "
             f"'{DEFAULT_CHAOS}') and report score deltas + resilience "
             "counters",
    )
    ap.add_argument(
        "--pipelines", default="",
        help="multi-tenant sweep: comma-separated pipeline counts (e.g. "
             "'1,8,64,256') run per-pipeline vs cohort gang dispatch",
    )
    ap.add_argument(
        "--cohort-smoke", action="store_true",
        help="CI gate: 64 co-hosted same-spec pipelines, cohort gang "
             "dispatch vs per-pipeline dispatch; NONZERO EXIT if the "
             "aggregate-throughput speedup is < 3x or the cohort run's "
             "score diverges from the per-pipeline run",
    )
    ap.add_argument(
        "--shard-smoke", action="store_true",
        help="CI gate: 64 co-hosted tenants on the forced 8-device host "
             "mesh — device-sharded cohort dispatch vs single-device "
             "cohort dispatch. NONZERO EXIT if the sharded leg never "
             "engages the tenant mesh, launch counts stop collapsing to "
             "one sharded launch per gang cycle, shard count 1 diverges "
             "bitwise from the single-device cohort path, the 8-shard "
             "parameter protocols leave the 0.05 score envelope, or (on "
             "hosts with >= 2 usable cores) the sharded aggregate "
             "throughput is < 2x the single-device cohort's",
    )
    ap.add_argument(
        "--forecast-mix", type=float, default=0.0,
        help="serving section: sweep per-record vs adaptive-batching "
             "serving (exact + relaxed) on a forecast-heavy stream with "
             "this forecast fraction (e.g. 0.5), 64 co-hosted tenants",
    )
    ap.add_argument(
        "--serve-smoke", action="store_true",
        help="CI gate: 64 co-hosted tenants on a 50/50 train/forecast "
             "stream; NONZERO EXIT if adaptive-batching serving delivers "
             "< 5x the per-record forecast throughput, exact-mode "
             "predictions/scores diverge from per-record serving, or the "
             "serving p99 latency exceeds the maxDelayMs budget",
    )
    ap.add_argument(
        "--overload-smoke", action="store_true",
        help="CI gate: 64 co-hosted tenants, 50/50 train/forecast "
             "per-record stream, a seeded 10x forecast burst flooding one "
             "hot tenant through the middle of the stream; NONZERO EXIT "
             "if the shed/throttle counters never engage, a healthy "
             "tenant gets shed, healthy tenants' serving p99 leaves the "
             "maxDelayMs budget, healthy forecast throughput drops more "
             "than 10%% vs the no-burst baseline, or the controller "
             "fails to return to OK after the burst",
    )
    ap.add_argument(
        "--lifecycle-smoke", action="store_true",
        help="CI gate: model-lifecycle plane end to end — a healthy "
             "Shadow candidate must ramp 0%%->50%% and auto-PROMOTE, a "
             "seeded-poison candidate must auto-ROLL-BACK via its guard "
             "with zero forecast loss, and with a canary armed the "
             "baseline-version predictions must stay BITWISE equal to a "
             "no-lifecycle run; NONZERO EXIT otherwise",
    )
    ap.add_argument(
        "--autoscale-smoke", action="store_true",
        help="CI gate: pressure-driven elastic autoscaling end to end — "
             "a preloaded burst on a (file-backed) Kafka broker must "
             "drive the supervised 1-process fleet out to 2 processes "
             "(checkpoint -> relaunch -> restore-with-rescale), healthy "
             "tenants must lose ZERO records across the restarts and "
             "serve every forecast exactly once, and the fleet must "
             "scale back in to the floor once the burst drains; NONZERO "
             "EXIT otherwise",
    )
    ap.add_argument(
        "--selfheal-smoke", action="store_true",
        help="CI gate: self-healing fleet end to end — a seeded SIGSTOP "
             "must be detected (survivors exit HANG_EXIT within "
             "--collectiveTimeoutMs, no wedged collective), the fleet "
             "must shrink to the survivors via restore-with-rescale with "
             "fitted+holdout exactly equal to the training rows and every "
             "forecast served exactly once, a later probe must restore "
             "full width, the bundles must carry the classify -> strike "
             "-> degrade -> probe chain in causal order, and unarmed "
             "knobs must be bit-identical no-ops; NONZERO EXIT otherwise",
    )
    ap.add_argument(
        "--chaos-smoke", action="store_true",
        help="CI gate: short Synchronous + Asynchronous runs under seeded "
             "drop+dup+reorder chaos; NONZERO EXIT if a run crashes or "
             "leaves the fault-free loss envelope",
    )
    ap.add_argument(
        "--telemetry-smoke", action="store_true",
        help="CI gate: telemetry plane end to end — the armed leg must "
             "match the unarmed leg's score/counters BITWISE (the plane "
             "only adds performance entries), cost <= 3%% throughput on "
             "paired trials, emit heartbeats on the count-clocked "
             "cadence, attribute the hot loop to phases, and write "
             "sampled round spans; NONZERO EXIT otherwise",
    )
    ap.add_argument(
        "--incident-smoke", action="store_true",
        help="CI gate: flight recorder end to end — a chaos+guard-armed "
             "supervised run with a seeded poisoned worker must leave ONE "
             "merged incident bundle carrying the rejection -> strike -> "
             "retire -> restart chain in causal order on the transport "
             "stamps, at least one kind=\"alert\" record must reach the "
             "performance sink, and arming the recorder on a clean "
             "stream must cost <= 3%% (paired trials) with BITWISE-equal "
             "scores; NONZERO EXIT otherwise",
    )
    ap.add_argument(
        "--slo-smoke", action="store_true",
        help="CI gate: the deterministic load harness end to end at ~256 "
             "tenants — the configured-but-unarmed plane matrix must be "
             "bit-identical to the bare path, a composed armed storm "
             "(churn + diurnal + bursts + addressed traffic) must pass "
             "every deterministic SLO gate with a byte-identical "
             "same-seed replay core, and a supervised fleet storm with "
             "two composed fault classes must heal within budget with "
             "zero healthy-tenant loss and exactly-once outputs; the "
             "serve-p99 budget self-enforces only on hosts with >= 2 "
             "usable cores (basis note in the output); NONZERO EXIT "
             "otherwise",
    )
    ap.add_argument(
        "--guard-smoke", action="store_true",
        help="CI gate: model-integrity guard end to end — a poisoned run "
             "(seeded NaN + exploding deltas) must finish inside the "
             "fault-free score envelope with the guard counters engaged, "
             "and a guard-armed CLEAN run must stay within 3%% of "
             "guard-off throughput on the packed host path; NONZERO EXIT "
             "otherwise",
    )
    args = ap.parse_args()

    if args.autoscale_smoke:
        # subprocess-driven (the fleet runs in real worker processes):
        # dispatch BEFORE the in-process jax/XLA setup below so the
        # parent stays light and its 8-device flag never leaks
        run_autoscale_smoke()
        return

    if args.selfheal_smoke:
        # subprocess-driven like the autoscale gate
        run_selfheal_smoke()
        return

    if args.slo_smoke:
        # dispatched before the 8-device XLA flag below: the in-process
        # legs run single-device and the supervised leg spawns its own
        # clean-env workers
        run_slo_smoke()
        return

    import os

    # the SPMD section wants a real multi-worker mesh: 8 virtual CPU
    # devices (must be set before the backend initializes)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    # host-plane comparison: protocol logic + traffic, not chip perf
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    codecs = (
        CODEC_SWEEP if args.codec == "sweep"
        else ("none", args.codec) if args.codec != "none"
        else ()
    )

    if args.shard_smoke:
        # CI gate (ISSUE 9 acceptance): at 64 co-hosted tenants on the
        # forced 8-device host mesh, device-sharded cohort execution
        # (cohort_shards auto) against single-device cohort dispatch
        # (cohort auto, shards off):
        #   (a) the sharded leg must actually engage the tenant mesh
        #       (cohort_shards gauge > 1, members placed on > 1 shard);
        #   (b) launch counts must stay collapsed — ONE sharded launch
        #       per gang cycle, i.e. no more programLaunches than the
        #       single-device cohort run;
        #   (c) shard count 1 must be BITWISE the single-device cohort
        #       path (holdout-scored parity pair), and the 8-shard parity
        #       leg must match too (lax.map member iteration is exact on
        #       CPU);
        #   (d) the 6 parameter protocols at 8 shards must stay inside
        #       the 0.05 score envelope vs their unsharded runs;
        #   (e) aggregate throughput must beat the single-device cohort
        #       by >= 2x — ENFORCED only on hosts with >= 2 usable cores:
        #       the CI mesh is 8 virtual devices, so the sharded gang
        #       parallelizes across real cores where they exist, but on a
        #       single-core box all 8 devices share one core and parallel
        #       speedup is physically unavailable (same basis note as
        #       protocols_spmd); the measured ratio is reported either way.
        records = min(args.records, 40_000)
        x, y = _mt_stream(records)
        try:
            n_cores = len(os.sched_getaffinity(0))
        except AttributeError:
            n_cores = os.cpu_count() or 1
        # warmup compiles both program families (single-device + sharded)
        run_multi_tenant_one(64, x[:8192], y[:8192], 256, "auto")
        run_multi_tenant_one(
            64, x[:8192], y[:8192], 256, "auto", shards="auto"
        )
        best = None
        for _trial in range(2):
            base = run_multi_tenant_one(64, x, y, 256, "auto")
            shard = run_multi_tenant_one(
                64, x, y, 256, "auto", shards="auto"
            )
            ratio = (
                shard["aggregate_examples_per_sec"]
                / max(base["aggregate_examples_per_sec"], 1e-9)
            )
            if best is None or ratio > best[0]:
                best = (ratio, base, shard)
        ratio, base, shard = best
        failures = []
        warnings = []
        if shard["cohort_shards"] < 2 or not any(
            sum(1 for c in p if c) > 1 for p in shard["tenant_placement"]
        ):
            failures.append(
                "sharded leg never engaged the tenant mesh "
                f"(cohort_shards={shard['cohort_shards']}, "
                f"placement={shard['tenant_placement']})"
            )
        if shard["program_launches"] > base["program_launches"] * 1.1:
            failures.append(
                "sharding broke the one-launch-per-gang-cycle collapse "
                f"({shard['program_launches']} launches vs single-device "
                f"cohort {base['program_launches']})"
            )
        if ratio < 2.0:
            msg = (
                f"sharded aggregate speedup {ratio:.2f}x < 2x at 64 "
                f"tenants on {shard['cohort_shards']} shards"
            )
            if n_cores >= 2:
                failures.append(msg)
            else:
                warnings.append(
                    msg + f" — NOT enforced: {n_cores} usable core "
                    "shares all 8 virtual devices, so parallel speedup "
                    "is physically unavailable on this host"
                )
        # (c) bitwise parity: shards=1 == the single-device cohort path,
        # and the 8-shard leg matches too (exact lax.map on CPU)
        px, py = _mt_stream(MT_PARITY_RECORDS)
        p_base = run_multi_tenant_one(64, px, py, 256, "auto", test=True)
        p_one = run_multi_tenant_one(
            64, px, py, 256, "auto", test=True, shards="1"
        )
        p_shard = run_multi_tenant_one(
            64, px, py, 256, "auto", test=True, shards="auto"
        )
        if p_one["score"] != p_base["score"]:
            failures.append(
                f"shard-count-1 holdout score {p_one['score']} != "
                f"single-device cohort {p_base['score']}"
            )
        if p_shard["score"] != p_base["score"]:
            failures.append(
                f"8-shard holdout score {p_shard['score']} != "
                f"single-device cohort {p_base['score']}"
            )
        if p_base["score"] <= 0.5:
            failures.append(
                f"parity legs never learned (score {p_base['score']}) — "
                "the parity check would be vacuous"
            )
        # (d) protocol envelope at 8 shards, parallelism 2
        ex, ey = _mt_stream(8_192)
        envelope = {}
        for protocol in SPMD_PROTOCOLS:
            s_off = run_shard_protocol_one(protocol, ex, ey, 64, "off")
            s_on = run_shard_protocol_one(protocol, ex, ey, 64, "auto")
            deltas = {
                pid: round(abs(s_on[pid] - s_off[pid]), 4)
                for pid in s_off
            }
            envelope[protocol] = {
                "unsharded": s_off, "sharded": s_on, "abs_delta": deltas,
            }
            worst = max(deltas.values()) if deltas else 1.0
            if worst > 0.05:
                failures.append(
                    f"{protocol}: 8-shard score delta {worst} outside "
                    "the 0.05 envelope"
                )
        print(json.dumps({
            "config": "protocol_comparison_shard_smoke",
            "records": records,
            "usable_cores": n_cores,
            "sharded_speedup_vs_single_device_cohort": round(ratio, 2),
            "single_device_cohort": base,
            "sharded_cohort": shard,
            "shard1_parity": {
                "single_device": p_base, "shard_count_1": p_one,
                "sharded": p_shard,
            },
            "protocol_envelope": envelope,
            "warnings": warnings,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.serve_smoke:
        # CI gate (ISSUE 8 acceptance): at 64 co-hosted tenants on a 50/50
        # train/forecast stream, the adaptive-batching serving plane must
        # deliver >= 5x the forecast throughput of per-record serving
        # (test=False production mode; best of 3 paired trials — the
        # per-record baseline is dispatch-bound and noisy on shared CI
        # boxes). Both legs run SOLO per-tenant dispatch (cohort off):
        # that is the reference's serving semantics — one padded predict
        # launch per tenant per forecasting record (FlinkSpoke.scala:
        # 92-107) — and it isolates the axis THIS plane adds (batching
        # across stream positions and tenants) from PR6's cross-tenant
        # gang, which has its own --cohort-smoke gate; the --forecast-mix
        # sweep records both topologies. Exact-staleness predictions and
        # scores must match the per-record run BITWISE on scored parity
        # pairs (solo AND cohort), and the serving run's p99 enqueue->emit
        # latency must stay under the configured maxDelayMs budget.
        from benchmarks.streams import forecast_stream

        records = min(args.records, 8_192)
        x, y, op = forecast_stream(records, mix=0.5)
        serving = {"maxBatch": SERVE_SMOKE_BATCH,
                   "maxDelayMs": SERVE_SMOKE_DELAY_MS,
                   "staleness": "exact"}
        # warmup compiles both program families (per-record + batched)
        run_serving_one(64, x[:4096], y[:4096], op[:4096], 256, None)
        run_serving_one(64, x[:4096], y[:4096], op[:4096], 256, serving)
        best = None
        for _trial in range(3):
            per = run_serving_one(64, x, y, op, 256, None)
            srv = run_serving_one(64, x, y, op, 256, serving)
            ratio = (
                srv["aggregate_forecasts_per_sec"]
                / max(per["aggregate_forecasts_per_sec"], 1e-9)
            )
            if best is None or ratio > best[0]:
                best = (ratio, per, srv)
        ratio, per, srv = best
        px, py, pop = forecast_stream(6_144, mix=0.5, seed=1)
        parity = {}
        failures = []
        for label, cohort in (("solo", "off"), ("cohort", "auto")):
            pp = run_serving_one(16, px, py, pop, 256, None, cohort=cohort,
                                 test=True, collect_preds=True)
            pc = run_serving_one(16, px, py, pop, 256, serving,
                                 cohort=cohort, test=True,
                                 collect_preds=True)
            if pc.pop("_preds") != pp.pop("_preds"):
                failures.append(
                    f"{label}: exact-staleness predictions diverge from "
                    "per-record serving"
                )
            if pc.pop("_scores") != pp.pop("_scores"):
                failures.append(
                    f"{label}: exact-staleness scores diverge from "
                    "per-record serving"
                )
            if pp["forecasts_served"] == 0:
                failures.append(
                    f"{label}: parity legs served no forecasts — the "
                    "parity check is vacuous"
                )
            parity[label] = {"per_record": pp, "serving": pc}
        if ratio < 5.0:
            failures.append(
                f"serving forecast speedup {ratio:.2f}x < 5x at 64 tenants"
            )
        if srv["serve_latency_p99_ms"] > SERVE_SMOKE_DELAY_MS:
            failures.append(
                f"serving p99 latency {srv['serve_latency_p99_ms']}ms over "
                f"the {SERVE_SMOKE_DELAY_MS}ms maxDelayMs budget"
            )
        if srv["program_launches"] >= per["program_launches"]:
            failures.append(
                "batched serving did not reduce programLaunches "
                f"({srv['program_launches']} vs {per['program_launches']})"
            )
        print(json.dumps({
            "config": "protocol_comparison_serve_smoke",
            "records": records,
            "forecast_speedup": round(ratio, 2),
            "per_record": per,
            "serving": srv,
            "exact_parity": parity,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.cohort_smoke:
        # CI gate (ISSUE 6 acceptance): at 64 same-spec pipelines on the
        # co-hosted serving plane, cohort gang dispatch must deliver >= 3x
        # the aggregate throughput of per-pipeline dispatch (test=False —
        # production serving mode), with programLaunches collapsed, AND a
        # holdout-scored (test=True) parity pair must agree BITWISE (the
        # production-mode scores are trivially 0, so parity needs its own
        # short scored runs). Two throughput trials, best ratio — the
        # per-pipeline baseline is python-dispatch-bound and noisy on
        # shared CI boxes.
        records = min(args.records, 40_000)
        x, y = _mt_stream(records)
        best = None
        for _trial in range(2):
            per = run_multi_tenant_one(64, x, y, 256, "off")
            coh = run_multi_tenant_one(64, x, y, 256, "auto")
            ratio = (
                coh["aggregate_examples_per_sec"]
                / max(per["aggregate_examples_per_sec"], 1e-9)
            )
            if best is None or ratio > best[0]:
                best = (ratio, per, coh)
        ratio, per, coh = best
        px, py = _mt_stream(MT_PARITY_RECORDS)
        pp = run_multi_tenant_one(64, px, py, 256, "off", test=True)
        pc = run_multi_tenant_one(64, px, py, 256, "auto", test=True)
        failures = []
        if ratio < 3.0:
            failures.append(
                f"cohort aggregate speedup {ratio:.2f}x < 3x at 64 pipelines"
            )
        if pc["score"] != pp["score"]:
            failures.append(
                f"cohort holdout score {pc['score']} != per-pipeline "
                f"{pp['score']}"
            )
        if pp["score"] <= 0.5:
            failures.append(
                f"parity leg never learned (score {pp['score']}) — the "
                "parity check would be vacuous"
            )
        if coh["program_launches"] >= per["program_launches"]:
            failures.append(
                "cohort dispatch did not reduce programLaunches "
                f"({coh['program_launches']} vs {per['program_launches']})"
            )
        print(json.dumps({
            "config": "protocol_comparison_cohort_smoke",
            "records": records,
            "aggregate_speedup": round(ratio, 2),
            "per_pipeline": per,
            "cohort": coh,
            "holdout_parity": {"per_pipeline": pp, "cohort": pc},
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.telemetry_smoke:
        # CI gate (ISSUE 13 acceptance):
        #  (a) UNARMED bit-identity — the telemetry-armed leg's score /
        #      fitted / communication counters must equal the unarmed
        #      leg's exactly (the plane only ever ADDS performance
        #      entries; it must never perturb the computation);
        #  (b) armed overhead <= 3% on the packed host path (4 paired
        #      off/on trials, best pair ratio — the same share-throttled-
        #      box methodology as the guard gate);
        #  (c) the plane ENGAGES: count-clocked heartbeats at the
        #      statsEvery cadence, a phase table attributing >= half the
        #      measured wall (stage/holdout/fit; hub protocol math is
        #      deliberately unattributed), and a nonempty sampled-span
        #      JSONL keyed by the transport stamps.
        import tempfile

        records = min(args.records, 48_000)
        par = min(args.parallelism, 4)
        batch = min(args.batch, 64)
        stats_every = 4_096
        rng = np.random.RandomState(13)
        w = np.random.RandomState(42).randn(28)
        tx = rng.randn(records, 28).astype(np.float32)
        ty = (tx @ w > 0).astype(np.float32)
        span_path = os.path.join(
            tempfile.mkdtemp(prefix="omldm-telemetry-smoke-"),
            "spans.jsonl",
        )
        tel_spec = (
            f"statsEvery={stats_every},traceSample=16,spanPath={span_path}"
        )
        failures = []
        # warmup compiles the shared programs for both legs
        run_one("Synchronous", tx[:2048], ty[:2048], par, batch)
        run_one(
            "Synchronous", tx[:2048], ty[:2048], par, batch,
            telemetry=f"statsEvery={stats_every}",
        )
        best_off = best_on = None
        pair_ratios = []
        for _trial in range(4):
            r_off = run_one("Synchronous", tx, ty, par, batch)
            r_on = run_one(
                "Synchronous", tx, ty, par, batch, telemetry=tel_spec
            )
            pair_ratios.append(
                r_off["examples_per_sec"]
                / max(r_on["examples_per_sec"], 1e-9)
            )
            if best_off is None or (
                r_off["examples_per_sec"] > best_off["examples_per_sec"]
            ):
                best_off = r_off
            if best_on is None or (
                r_on["examples_per_sec"] > best_on["examples_per_sec"]
            ):
                best_on = r_on
        overhead = min(pair_ratios)
        for key in ("score", "fitted", "models_shipped", "bytes_on_wire",
                    "num_of_blocks"):
            if best_off[key] != best_on[key]:
                failures.append(
                    f"armed leg diverged on {key}: {best_on[key]} != "
                    f"unarmed {best_off[key]}"
                )
        if overhead > 1.03:
            failures.append(
                f"telemetry-armed throughput {overhead:.3f}x slower than "
                "unarmed (> 3% bar)"
            )
        # heartbeats fire at the first event/block boundary at/after
        # statsEvery records — the packed route feeds 8192-row blocks,
        # so the cadence clamps to block granularity here
        expected_beats = max(records // max(stats_every, 8192) - 1, 1)
        if best_on.get("heartbeats", 0) < expected_beats:
            failures.append(
                f"heartbeat cadence did not engage: "
                f"{best_on.get('heartbeats', 0)} beats < {expected_beats} "
                f"expected at statsEvery={stats_every}"
            )
        coverage = best_on.get("phase_table", {}).get("_coverage", 0.0)
        if coverage < 0.5:
            failures.append(
                f"phase table attributes only {coverage:.2f} of the "
                "measured wall (< 0.5)"
            )
        if best_on.get("spans_completed", 0) == 0:
            failures.append("no protocol-round spans completed")
        try:
            span_lines = open(span_path).read().splitlines()
        except OSError:
            span_lines = []
        if not span_lines:
            failures.append(f"span file {span_path} is empty/missing")
        else:
            span = json.loads(span_lines[0])
            for key in ("networkId", "seq", "op", "rttMs"):
                if key not in span:
                    failures.append(f"span records missing {key!r}")
        print(json.dumps({
            "config": "protocol_comparison_telemetry_smoke",
            "records": records,
            "telemetry_spec": tel_spec,
            "telemetry_overhead_x": round(overhead, 3),
            "pair_ratios": [round(r, 3) for r in pair_ratios],
            "phase_coverage": coverage,
            "spans_written": len(span_lines),
            "unarmed": best_off,
            "armed": best_on,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.incident_smoke:
        # CI gate (ISSUE 14 acceptance): see run_incident_smoke
        run_incident_smoke()
        return

    if args.guard_smoke:
        # CI gate (ISSUE 7 acceptance): (a) seeded poison injection — NaN
        # and exploding worker deltas on the hub<->spoke bridge — against
        # guard-armed Synchronous + Asynchronous runs must finish with the
        # admission counters engaged and the final score inside the 0.05
        # fault-free envelope; (b) arming the guard on a CLEAN stream must
        # cost <= 3% throughput on the packed CPU host path (4 paired
        # off/on trials, best pair ratio — the python-dispatch baseline is
        # noisy on shared CI boxes) and must not move the score at all.
        records = min(args.records, 48_000)
        par = min(args.parallelism, 4)
        batch = min(args.batch, 64)
        rng = np.random.RandomState(11)
        w = np.random.RandomState(42).randn(28)
        gx = rng.randn(records, 28).astype(np.float32)
        gy = (gx @ w > 0).astype(np.float32)
        poison_spec = "seed=7,up.nan=0.02,up.explode=0.02"
        failures = []
        out = {}
        # warmup compiles both program families (guarded + unguarded)
        run_one("Synchronous", gx[:2048], gy[:2048], par, batch)
        run_one("Synchronous", gx[:2048], gy[:2048], par, batch, guard=True)
        for protocol in ("Synchronous", "Asynchronous"):
            # paired back-to-back A/B trials: this box is share-throttled
            # (+-25%, BASELINE notes), so each off/on pair samples the
            # same throttle window and the gate takes the BEST pair ratio
            # — throttle noise only ever inflates a pair's ratio, so the
            # minimum over pairs is the tightest available estimate of
            # the systematic guard overhead
            clean_off = clean_on = None
            pair_ratios = []
            for _trial in range(4):
                r_off = run_one(protocol, gx, gy, par, batch)
                r_on = run_one(protocol, gx, gy, par, batch, guard=True)
                pair_ratios.append(
                    r_off["examples_per_sec"]
                    / max(r_on["examples_per_sec"], 1e-9)
                )
                if clean_off is None or (
                    r_off["examples_per_sec"]
                    > clean_off["examples_per_sec"]
                ):
                    clean_off = r_off
                if clean_on is None or (
                    r_on["examples_per_sec"] > clean_on["examples_per_sec"]
                ):
                    clean_on = r_on
            poisoned = run_one(
                protocol, gx, gy, par, batch, guard=True, chaos=poison_spec
            )
            overhead = min(pair_ratios)
            row = {
                "clean_guard_off": clean_off,
                "clean_guard_on": clean_on,
                "poisoned_guard_on": poisoned,
                "guard_overhead_x": round(overhead, 3),
                "poisoned_score_delta": round(
                    poisoned["score"] - clean_off["score"], 4
                ),
            }
            out[protocol] = row
            if clean_on["score"] != clean_off["score"]:
                failures.append(
                    f"{protocol}: guard-armed clean score "
                    f"{clean_on['score']} != guard-off {clean_off['score']}"
                )
            if overhead > 1.03:
                failures.append(
                    f"{protocol}: guard-armed clean throughput "
                    f"{overhead:.3f}x slower than guard-off (> 3% bar)"
                )
            if poisoned["deltas_rejected"] == 0:
                failures.append(
                    f"{protocol}: poison injection never engaged the "
                    "admission counters — the envelope check is vacuous"
                )
            if abs(row["poisoned_score_delta"]) > 0.05:
                failures.append(
                    f"{protocol}: poisoned score delta "
                    f"{row['poisoned_score_delta']} outside the 0.05 envelope"
                )
        print(json.dumps({
            "config": "protocol_comparison_guard_smoke",
            "records": records,
            "poison_spec": poison_spec,
            **out,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.overload_smoke:
        # CI gate (ISSUE 10 acceptance): at 64 co-hosted tenants on a
        # 50/50 per-record stream with a seeded 10x forecast burst
        # flooding tenant 0:
        #   (a) the overload counters must ENGAGE — the hot tenant sheds
        #       forecasts (reason-coded dead letters) and has training
        #       rows deprioritized, and the pressure gauge records
        #       CRITICAL;
        #   (b) fairness must hold — NO healthy tenant sheds, and every
        #       healthy tenant serves EXACTLY the forecasts it serves in
        #       the no-burst leg (count equality: the schedule is
        #       deterministic);
        #   (c) healthy tenants' serving p99 stays inside the maxDelayMs
        #       budget and their aggregate forecast throughput within 10%
        #       of the no-burst baseline (best of 3 paired trials — the
        #       per-record baseline is dispatch-bound and noisy on shared
        #       CI boxes);
        #   (d) the controller must RECOVER: pressure back to OK by the
        #       end of the post-burst tail, with no stranded queue rows.
        records = min(args.records, 4_096)
        x, y = _mt_stream(records)
        # warmup job compiles the fit + padded-predict program families
        # into the shared jit cache (same-spec jobs reuse them)
        run_overload_one(64, x[:1024], y[:1024], burst=False)
        best = None
        for _trial in range(3):
            base = run_overload_one(64, x, y, burst=False)
            burst = run_overload_one(64, x, y, burst=True)
            ratio = (
                burst["healthy_forecasts_per_sec"]
                / max(base["healthy_forecasts_per_sec"], 1e-9)
            )
            if best is None or ratio > best[0]:
                best = (ratio, base, burst)
        ratio, base, burst = best
        failures = []
        if burst["hot_shed"] == 0:
            failures.append(
                "the burst never engaged shedding (hot_shed == 0) — the "
                "fairness checks are vacuous"
            )
        if burst["hot_throttled"] == 0:
            failures.append(
                "the burst never engaged training deprioritization "
                "(hot_throttled == 0)"
            )
        if burst["pressure_peak"] < 2:
            failures.append(
                f"pressure never reached CRITICAL (peak "
                f"{burst['pressure_peak']})"
            )
        if burst["healthy_shed"] != 0:
            failures.append(
                f"{burst['healthy_shed']} healthy-tenant forecasts were "
                "shed — fairness violated"
            )
        if burst["healthy_forecasts_served"] != base["healthy_forecasts_served"]:
            failures.append(
                "healthy tenants' served-forecast count diverged under "
                f"the burst ({burst['healthy_forecasts_served']} vs "
                f"{base['healthy_forecasts_served']})"
            )
        budget = OVERLOAD_SERVING["maxDelayMs"]
        if burst["healthy_serve_p99_ms"] > budget:
            failures.append(
                f"healthy serving p99 {burst['healthy_serve_p99_ms']}ms "
                f"over the {budget}ms maxDelayMs budget under the burst"
            )
        if burst["healthy_serve_p99_ms"] > base["healthy_serve_p99_ms"] * 1.5:
            failures.append(
                "the burst degraded healthy serving p99 "
                f"({burst['healthy_serve_p99_ms']}ms vs "
                f"{base['healthy_serve_p99_ms']}ms no-burst — > 1.5x)"
            )
        if ratio < 0.9:
            failures.append(
                f"healthy forecast throughput {ratio:.2f}x of the "
                "no-burst baseline (< 0.9x bar)"
            )
        if burst["level_after_feed"] != 0:
            failures.append(
                "controller did not return to OK after the burst "
                f"(level {burst['level_after_feed']})"
            )
        stranded = {
            k: v for k, v in burst["queue_depths"].items()
            if k != "pressure_level" and v
        }
        if stranded:
            failures.append(f"stranded queue rows at terminate: {stranded}")
        print(json.dumps({
            "config": "protocol_comparison_overload_smoke",
            "records": records,
            "overload_spec": OVERLOAD_SPEC,
            "chaos_spec": _overload_chaos(records),
            "healthy_throughput_ratio": round(ratio, 3),
            "no_burst": base,
            "burst": burst,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.lifecycle_smoke:
        # CI gate (ISSUE 11 acceptance): one lifecycle-armed pipeline on
        # a 50/50 per-record stream, four legs on the SAME deterministic
        # stream:
        #   (a) HEALTHY — a Shadow candidate ramps 0 -> 50% and
        #       auto-promotes (canaryPromotions engages, the registry's
        #       active version advances, shadow scoring ran);
        #   (b) HOLD — the canary serves the whole stream without
        #       promoting: every baseline-version (untagged) prediction
        #       must be BITWISE equal to the no-lifecycle leg's value at
        #       the same stream position — candidate training and canary
        #       routing never perturb the active model;
        #   (c) POISON — the candidate's params are seeded with an
        #       exploding vector mid-canary: its guard must trip and
        #       auto-roll the canary back (canaryRollbacks engages, the
        #       active version stays 0) with ZERO forecast loss (every
        #       forecast answered) and the same baseline bitwise pin.
        records = min(args.records, 6_144)
        x, y = _mt_stream(records)
        off = run_lifecycle_one(x, y, "off")
        healthy = run_lifecycle_one(x, y, "healthy")
        hold = run_lifecycle_one(x, y, "hold")
        poison = run_lifecycle_one(x, y, "poison")
        failures = []
        if healthy["canary_promotions"] < 1:
            failures.append(
                "the healthy candidate never promoted "
                f"(canary_promotions {healthy['canary_promotions']})"
            )
        if healthy["canary_rollbacks"]:
            failures.append(
                f"{healthy['canary_rollbacks']} rollbacks on the healthy "
                "candidate"
            )
        if healthy["active_version"] != 1:
            failures.append(
                "the registry's active version did not advance after the "
                f"healthy promotion (gauge {healthy['active_version']})"
            )
        if healthy["shadow_scored"] < 2:
            failures.append(
                "shadow scoring never ran on the healthy candidate "
                f"(shadow_scored {healthy['shadow_scored']})"
            )
        if poison["canary_rollbacks"] < 1:
            failures.append(
                "the seeded-poison candidate never rolled back "
                f"(canary_rollbacks {poison['canary_rollbacks']})"
            )
        if poison["canary_promotions"]:
            failures.append("the poisoned candidate PROMOTED")
        if poison["lifecycle"]["activeVersion"] != 0:
            failures.append(
                "the poison leg's active version moved off the baseline "
                f"({poison['lifecycle']['activeVersion']})"
            )
        for leg in (healthy, hold, poison):
            if len(leg["predictions"]) != len(off["predictions"]):
                failures.append(
                    f"{leg['mode']} leg answered "
                    f"{len(leg['predictions'])} forecasts vs "
                    f"{len(off['predictions'])} without the plane — "
                    "forecast loss"
                )
        for leg in (hold, poison):
            mismatches = sum(
                1
                for (v, ver), (v0, _) in zip(
                    leg["predictions"], off["predictions"]
                )
                if ver is None and v != v0
            )
            if mismatches:
                failures.append(
                    f"{mismatches} baseline-version predictions of the "
                    f"{leg['mode']} leg diverged from the no-lifecycle "
                    "run — the bitwise pin"
                )
        canary_served = sum(
            1 for _v, ver in hold["predictions"] if ver is not None
        )
        if canary_served == 0:
            failures.append(
                "the hold leg's canary never served — the bitwise pin "
                "is vacuous"
            )
        summary = {
            k: {
                "score": leg["score"],
                "shadow_scored": leg["shadow_scored"],
                "canary_promotions": leg["canary_promotions"],
                "canary_rollbacks": leg["canary_rollbacks"],
                "active_version": leg["active_version"],
                "forecasts": len(leg["predictions"]),
                "canary_tagged": sum(
                    1 for _v, ver in leg["predictions"] if ver is not None
                ),
            }
            for k, leg in (
                ("off", off), ("healthy", healthy),
                ("hold", hold), ("poison", poison),
            )
        }
        print(json.dumps({
            "config": "protocol_comparison_lifecycle_smoke",
            "records": records,
            "lifecycle_spec": LIFECYCLE_SPEC,
            **summary,
            "failures": failures,
        }))
        if failures:
            sys.exit(1)
        return

    if args.chaos_smoke:
        # CI gate: a short Sync + Async run under seeded drop+dup+reorder
        # chaos — the job must finish (zero crashes) with the final score
        # inside the fault-free loss envelope, and the reliable channel
        # must actually have worked (nonzero resilience counters). The dup
        # rate is cranked above the acceptance operating point so the
        # ~200-message smoke stream statistically guarantees duplicate
        # deliveries for the counter gate
        res = run_chaos_resilience(
            ("Synchronous", "Asynchronous"),
            min(args.records, 6_000),
            min(args.parallelism, 4),
            min(args.batch, 64),
            chaos="seed=7,drop=0.05,dup=0.25,reorder=0.1,window=4",
        )
        failures = []
        for protocol, r in res["protocols"].items():
            if abs(r["score_delta_vs_clean"]) > 0.05:
                failures.append(
                    f"{protocol} chaos score delta "
                    f"{r['score_delta_vs_clean']} outside the 0.05 envelope"
                )
            if r["duplicates_dropped"] == 0:
                failures.append(
                    f"{protocol} saw no duplicates under dup chaos — the "
                    "reliable channel is not engaged"
                )
        print(
            json.dumps(
                {
                    "config": "protocol_comparison_chaos_smoke",
                    **res,
                    "failures": failures,
                }
            )
        )
        if failures:
            sys.exit(1)
        return

    if args.smoke:
        # CI gate: the codec path end to end on a small stream, with the
        # acceptance bars enforced (nonzero exit on regression)
        records = min(args.records, 6_000)
        par = min(args.parallelism, 4)
        sweep = codecs or ("none", "int8")
        comp = run_codec_comparison(
            sweep, records, par, min(args.batch, 64),
            protocols=("Asynchronous", "Synchronous"),
        )
        dist = run_distributed_route(sweep, steps=12)
        failures = []
        for protocol, rows in comp.items():
            for codec, r in rows.items():
                if codec == "int8":
                    if r["wire_reduction_vs_none"] < 3.5:
                        failures.append(
                            f"{protocol}/int8 host wire reduction "
                            f"{r['wire_reduction_vs_none']}x < 3.5x"
                        )
                    if abs(r["score_delta_vs_none"]) > 0.05:
                        failures.append(
                            f"{protocol}/int8 score drift "
                            f"{r['score_delta_vs_none']} > 0.05"
                        )
        if "int8" in dist:
            if dist["int8"]["wire_reduction_vs_none"] < 3.5:
                failures.append(
                    "distributed route int8 wire reduction "
                    f"{dist['int8']['wire_reduction_vs_none']}x < 3.5x"
                )
            if dist["int8"]["param_drift_rel"] > 0.05:
                failures.append(
                    "distributed route int8 param drift "
                    f"{dist['int8']['param_drift_rel']} > 0.05"
                )
        print(
            json.dumps(
                {
                    "config": "protocol_comparison_smoke",
                    "records": records,
                    "codec_comparison": comp,
                    "distributed_route": dist,
                    "failures": failures,
                }
            )
        )
        if failures:
            sys.exit(1)
        return

    rng = np.random.RandomState(0)
    w = np.random.RandomState(42).randn(28)
    x = rng.randn(args.records, 28).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)

    # untimed warmup: the jitted fit/eval/chained-fit programs are shared
    # by (learner, dim, batch) spec, so one run compiles for all — sized
    # for several full batches per worker so the blocked-batch chain
    # program compiles too (it only traces once >= 2 batches are pending)
    warm = min(args.parallelism * args.batch * 4, args.records)
    run_one(PROTOCOLS[0], x[:warm], y[:warm], args.parallelism, args.batch)

    out = {}
    for protocol in PROTOCOLS:
        # the full-comparison rows run telemetry-armed (heartbeats off,
        # phases on) so every result row carries the phase-breakdown
        # table + launch gauges alongside the traffic counters — BENCH
        # rounds see WHERE each protocol's wall time goes
        out[protocol] = run_one(
            protocol, x, y, args.parallelism, args.batch,
            telemetry="statsEvery=100000000",
        )

    # SPMD collective engine: same stream, same scoring, the 6 protocols
    # with device-plane equivalents on the 8-worker virtual mesh
    run_one(
        SPMD_PROTOCOLS[0], x[:warm], y[:warm], args.parallelism, args.batch,
        engine="spmd",
    )
    out_spmd = {}
    for protocol in SPMD_PROTOCOLS:
        r = run_one(
            protocol, x, y, args.parallelism, args.batch, engine="spmd"
        )
        host = out[protocol]
        r["speedup_vs_host_plane"] = round(
            r["examples_per_sec"] / max(host["examples_per_sec"], 1e-9), 2
        )
        r["score_parity_abs_diff"] = round(
            abs(r["score"] - host["score"]), 4
        )
        out_spmd[protocol] = r

    # transport-codec sections (--codec): params-dominated host stream
    # sweep + the distributed model-exchange route
    codec_out = {}
    if codecs:
        codec_out["codec_comparison"] = run_codec_comparison(
            codecs, max(args.records // 2, 10_000), args.parallelism,
            args.batch,
        )
        codec_out["distributed_route"] = run_distributed_route(codecs)
    # multi-tenant sweep (--pipelines): N co-hosted same-spec pipelines,
    # per-pipeline dispatch vs cohort gang dispatch (runtime.cohort)
    if args.pipelines:
        counts = [int(p) for p in args.pipelines.split(",") if p]
        codec_out["multi_tenant"] = run_multi_tenant(
            counts, min(args.records, 40_000), 256
        )
    # forecast-mix serving section (--forecast-mix): per-record serving vs
    # the adaptive-batching plane (exact + relaxed) on a forecast-heavy
    # stream at 64 co-hosted tenants (runtime/serving.py)
    if args.forecast_mix > 0:
        codec_out["serving"] = run_serving_comparison(
            args.forecast_mix, min(args.records, 40_000), 256
        )
    # chaos resilience section (--chaos): protocols under the seeded lossy
    # channel, score envelope + resilience counters
    if args.chaos:
        spec = DEFAULT_CHAOS if args.chaos == "default" else args.chaos
        codec_out["chaos_resilience"] = run_chaos_resilience(
            SPMD_PROTOCOLS, max(args.records // 4, 8_000),
            args.parallelism, args.batch, chaos=spec,
        )
    print(
        json.dumps(
            {
                "config": "protocol_comparison",
                "metric": "per-protocol examples/sec, score, traffic",
                "parallelism": args.parallelism,
                "records": args.records,
                # the host-plane protocol rows run TELEMETRY-ARMED as of
                # PR 13 (phase tables + launch gauges in every row):
                # examples_per_sec carries the plane's <= 3% hook
                # overhead, so cross-round trends against earlier
                # unarmed rows see that baseline shift, not a protocol
                # change
                "telemetry_armed_rows": True,
                "protocols": out,
                "protocols_spmd": out_spmd,
                **codec_out,
                "spmd_basis": (
                    "virtual 8-device CPU mesh: protocol SEMANTICS, score "
                    "parity and traffic accounting — NOT chip throughput "
                    "(8 virtual devices emulate collectives on one CPU "
                    "core, so examples/sec reflects XLA CPU emulation "
                    "overhead; the engine's chip throughput is the "
                    "avazu_softmax and e2e configs of run_benchmarks.py)"
                ),
                "note": (
                    "protocols_spmd: bytes_physical counts executed "
                    "collective rounds + scalar vote channels (gated "
                    "Async/SSP folds), bytes_shipped the application "
                    "payload accounting"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
