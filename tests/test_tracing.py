"""Tracing/profiling utilities."""

import collections
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from omldm_tpu.runtime.telemetry import PhaseProfile
from omldm_tpu.utils import StepTimer, trace, tracing


def test_step_timer_percentiles():
    t = StepTimer("fit")
    for ms in (1.0, 2.0, 3.0, 4.0, 100.0):
        t.record(ms)
    s = t.summary()
    assert s["count"] == 5
    assert abs(s["p50_ms"] - 3.0) < 1e-9
    assert s["p99_ms"] > 90.0
    assert s["steps_per_sec"] > 0
    t.reset()
    assert t.summary()["count"] == 0


def test_step_timer_bounded_ring():
    """A capped timer retains at most `cap` samples (most recent window)
    while `count` stays the total — a hot-path timer on a long-lived
    streaming job must not grow host memory with the stream."""
    t = StepTimer("serve", cap=4)
    for ms in range(10):
        t.record(float(ms))
    assert t.count == 10
    assert len(t._durations_ms) == 4
    assert sorted(t._durations_ms) == [6.0, 7.0, 8.0, 9.0]
    s = t.summary()
    assert s["count"] == 10
    assert 6.0 <= s["p50_ms"] <= 9.0
    t.reset()
    assert t.count == 0 and t.summary()["count"] == 0


def test_step_timer_context_manager():
    t = StepTimer()
    with t:
        pass
    assert t.count == 1
    assert t.summary()["mean_ms"] >= 0.0


def test_trace_noop_without_dir():
    with trace(None):
        x = 1 + 1
    assert x == 2


def test_trace_writes_profile(tmp_path):
    """jax.profiler trace produces artifacts in the target dir."""
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "prof")
    with trace(d):
        jnp.asarray(np.ones(8)).sum().block_until_ready()
    # the profiler lays out plugins/profile/<run>/...; any content counts
    found = []
    for root, _, files in os.walk(d):
        found.extend(files)
    assert found, "profiler trace produced no files"


def test_cli_accepts_profile_dir(tmp_path):
    """--profileDir flows through the CLI without breaking the run."""
    from omldm_tpu.__main__ import main

    events = tmp_path / "events.jsonl"
    lines = [
        {"stream": "requests", "data": {
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
            "trainingConfiguration": {"protocol": "CentralizedTraining"},
        }},
    ]
    rng = np.random.RandomState(0)
    for i in range(40):
        x = rng.randn(4)
        lines.append({"stream": "trainingData", "data": {
            "id": i, "numericalFeatures": [round(float(v), 4) for v in x],
            "target": float(x.sum() > 0),
        }})
    events.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    perf = tmp_path / "perf.jsonl"
    rc = main([
        "--events", str(events),
        "--parallelism", "1",
        "--performanceOut", str(perf),
        "--profileDir", str(tmp_path / "prof"),
        "--timeout", "1000",
    ])
    assert rc == 0
    assert perf.exists() and perf.read_text().strip()


# --- the span API (utils/tracing.span, Recorder) -----------------------------


def test_span_records_name_times_key_and_counts():
    rec = tracing.Recorder()
    with rec.span("fit", key=7, rows=100) as sp:
        sp.add(rows=28, rows_padded=256)
        sp.set(tail=True)
    [r] = rec.records("fit")
    assert (r.name, r.key, r.parent, r.parent_name) == ("fit", 7, 0, "")
    assert r.start <= r.end and r.self_s == pytest.approx(r.end - r.start)
    assert r.thread == threading.get_ident()
    assert r.attrs == {"tail": True}
    assert rec.counts("fit") == {"rows": 128, "rows_padded": 256}
    assert rec.counts("parse") == {} and rec.count("fit") == 1
    assert rec.total_seconds("fit") == pytest.approx(r.end - r.start)


def test_nesting_gives_parent_and_self_time():
    rec = tracing.Recorder()
    with rec.span("forecast", key=3) as outer:
        with rec.span("quiesce"):
            time.sleep(0.02)
        with rec.span("serve") as inner:
            with rec.span("emit"):
                pass
    [f] = rec.records("forecast")
    [q] = rec.records("quiesce")
    [s] = rec.records("serve")
    [e] = rec.records("emit")
    assert (q.parent, q.parent_name) == (f.id, "forecast")
    assert (s.parent, e.parent, e.parent_name) == (f.id, s.id, "serve")
    assert f.id == outer.id < q.id < s.id == inner.id < e.id
    assert f.start <= q.start <= q.end <= s.start <= e.end <= s.end <= f.end
    # self time: the duration minus what the children cover (grandchildren
    # come off the child, not off the grandparent a second time)
    children = (q.end - q.start) + (s.end - s.start)
    assert f.self_s == pytest.approx((f.end - f.start) - children, abs=1e-9)
    assert f.self_s < 0.01 < q.self_s
    assert rec.summary("forecast")[1] == pytest.approx(f.self_s)
    assert rec.total_seconds("forecast") == pytest.approx(f.end - f.start)
    # after the outermost span the thread is at its top level again
    assert rec.current() is None


def test_span_on_second_thread_names_thread_and_cause():
    rec = tracing.Recorder()
    seen = {}

    def worker(cause):
        rec.adopt(cause)
        seen["ident"] = threading.get_ident()
        with rec.span("launch", key=0):
            with rec.span("fit", key=0):
                pass

    with rec.span("ingest_file", key=5):
        t = threading.Thread(target=worker, args=(rec.current(),), name="omldm-dispatch")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    [file] = rec.records("ingest_file")
    [launch] = rec.records("launch")
    [fit] = rec.records("fit")
    assert launch.thread == fit.thread == seen["ident"] != file.thread
    assert (launch.parent, launch.parent_name) == (file.id, "ingest_file")
    assert (fit.parent, fit.parent_name) == (launch.id, "launch")
    # self time stays per thread: the adopted parent's is not reduced
    assert file.self_s == pytest.approx(file.end - file.start)


def test_ring_bounds_memory_counts_stay_exact():
    rec = tracing.Recorder(cap=8)
    for i in range(100):
        with rec.span("parse", key=i, rows=3):
            pass
    kept = rec.records("parse")
    assert [r.key for r in kept] == list(range(92, 100))  # newest, oldest first
    assert rec.count("parse") == 100 and rec.dropped("parse") == 92
    assert rec.counts("parse") == {"rows": 300}
    assert rec.total_seconds("parse") >= sum(r.end - r.start for r in kept)
    rec.note_seconds("parse", 2.0)  # seconds clocked elsewhere: no record
    assert rec.count("parse") == 101 and len(rec.records("parse")) == 7
    assert rec.summary("parse")[1] >= 2.0


def test_recorder_is_exact_under_contending_threads():
    rec = tracing.Recorder(cap=16)
    n_threads, n_spans = 4 * (os.cpu_count() or 2), 300
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for _ in range(n_spans):
                with rec.span("stage", rows=2):
                    with rec.span("pool_wait"):
                        pass

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    total = n_threads * n_spans
    assert rec.count("stage") == rec.count("pool_wait") == total
    assert rec.counts("stage") == {"rows": 2 * total}
    ids = [r.id for r in rec.records("stage") + rec.records("pool_wait")]
    assert len(set(ids)) == len(ids) == 32
    assert all(r.parent_name == "stage" for r in rec.records("pool_wait"))


def test_spans_lie_in_the_profiler_trace(tmp_path):
    """With a profiler session on, every span is an ``omldm.<name>`` event of
    the trace's host plane: the clock the device's operations are on."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    rec = tracing.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("ingest_file", key=0):
            with rec.span("fit", key=11):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    host = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    events = {e.name: e for p in host for line in p.lines for e in line.events
              if e.name.startswith(tracing.ANNOTATION_PREFIX)}
    assert set(events) == {"omldm.ingest_file", "omldm.fit"}
    outer, inner = events["omldm.ingest_file"], events["omldm.fit"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    # trace and record time the same block
    [fit] = rec.records("fit")
    assert inner.duration_ns / 1e9 == pytest.approx(fit.end - fit.start, abs=2e-3)


def test_compile_events_become_records_under_the_calling_span():
    import jax
    import jax.numpy as jnp

    def fresh(x):  # a new function object: traced, lowered and compiled anew
        return jnp.tanh(x) * 3.0 + 1.0

    with tracing.span("fit", key="compile-test") as sp:
        jax.jit(fresh)(jnp.ones(4)).block_until_ready()
    compiles = [r for r in tracing.RECORDER.records("compile") if r.parent == sp.id]
    stages = {r.attrs["stage"] for r in compiles}
    assert stages == {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                      "backend_compile_duration"}
    assert all(r.parent_name == "fit" for r in compiles)
    assert any(r.key == "fresh" for r in compiles)
    [fit] = [r for r in tracing.RECORDER.records("fit") if r.id == sp.id]
    # an event is reported at its end with its seconds: it lies in the span
    assert all(fit.start - 1e-3 <= r.start <= r.end <= fit.end for r in compiles)
    assert all(0.0 < r.self_s == pytest.approx(r.end - r.start) for r in compiles)


def test_phase_table_reports_self_time_for_nested_phases():
    p = PhaseProfile()
    with p.phase("stage"):
        with p.phase("holdout"):
            time.sleep(0.03)
    t = p.table(1.0)
    assert t["holdout"]["seconds"] >= 0.03 > t["stage"]["seconds"]
    assert t["stage"]["count"] == t["holdout"]["count"] == 1
    # nested phases count once: coverage is the outer phase's wall
    outer = p.recorder.total_seconds("stage")
    assert t["_coverage"] == pytest.approx(outer, abs=2e-4)
    # un-nested phases read as before: seconds, count, share
    q = PhaseProfile()
    q.note("parse", 0.25)
    q.note("parse", 0.25)
    assert q.table(1.0)["parse"] == {
        "seconds": 0.5, "count": 2, "p50_ms": 250.0, "p99_ms": 250.0, "share": 0.5,
    }
    p.merge(q)
    assert p.table()["parse"]["count"] == 2 and p.seconds("parse") == pytest.approx(0.5)


def test_phase_table_counts_from_a_mark_and_adds_another_profile():
    """What ``StreamJob.phase_table`` does: the process-wide recorder read
    from the job's own mark on, added row by row to the armed plane's."""
    rec = tracing.Recorder()
    with rec.span("fit", rows=4096, rows_padded=4096):
        pass
    with rec.span("build_state"):  # another job's: before the mark
        pass
    # what launches counted on the device, noted where their outputs are read
    rec.add_counts("fit", slots=1000, slots_distinct=900, overflow_launches=1)
    since = rec.mark()
    with rec.span("fit", rows=28, rows_padded=256):
        time.sleep(0.01)
    rec.add_counts("fit", slots=400, slots_distinct=76, overflow_launches=0)
    fused = PhaseProfile(rec, since=since)
    assert fused.seconds("fit") == pytest.approx(rec.records("fit")[1].self_s)
    armed = PhaseProfile()
    armed.note("fit", 0.5)
    armed.note("parse", 0.25)
    t = armed.table(1.0, also=fused)
    assert set(t) == {"fit", "parse", "_coverage"}  # no row from before the mark
    assert t["fit"]["count"] == 2 and t["fit"]["seconds"] >= 0.51
    assert (t["fit"]["rows"], t["fit"]["rows_padded"]) == (28, 256)
    assert (t["fit"]["slots"], t["fit"]["overflow_launches"]) == (400, 0)
    assert t["fit"]["distinct_share"] == 0.19
    assert t["fit"]["p99_ms"] > t["fit"]["p50_ms"] >= 10.0
    assert "rows" not in t["parse"] and "distinct_share" not in t["parse"]
    # the recorder itself still holds the whole stream
    assert rec.counts("fit") == {
        "rows": 4124, "rows_padded": 4352, "slots": 1400,
        "slots_distinct": 976, "overflow_launches": 1,
    }
    assert PhaseProfile(rec).table()["fit"]["count"] == 2


def _sparse_job(preds):
    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    job = StreamJob(JobConfig(parallelism=1, batch_size=32, test=True, test_set_size=32))
    job.set_sinks(on_prediction=preds.append)
    job.process_event(REQUEST_STREAM, json.dumps({
        "id": 0, "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 0.5},
                    "dataStructure": {"sparse": True, "nFeatures": 5 + 512,
                                      "hashSpace": 512, "maxNnz": 12}},
        "trainingConfiguration": {"protocol": "Synchronous", "engine": "spmd",
                                  "extra": {"parserThreads": 2}},
    }))
    job.ensure_deployed(5 + 512)
    return job


def test_fused_sparse_route_span_names_and_counters(tmp_path):
    """One small file with forecasts through ``run_file_fused``: the exact
    multiset of span names, their parents, and the counters."""
    from omldm_tpu.ops.native import fast_parser_available

    if not fast_parser_available():
        pytest.skip("native parser unavailable")
    rng = np.random.RandomState(3)
    n_lines, forecast_ids = 1000, (200, 501, 802)
    path = tmp_path / "sparse.jsonl"
    with open(path, "w") as f:
        for i in range(n_lines):
            row = {"numericalFeatures": [round(float(v), 6) for v in rng.randn(5)],
                   "categoricalFeatures": [f"c{j}_{rng.randint(50)}" for j in range(6)]}
            if i in forecast_ids:
                row.update(id=i, operation="forecasting")
            else:
                row.update(target=float(rng.randint(2)), operation="training")
            f.write(json.dumps(row) + "\n")
    preds = []
    job = _sparse_job(preds)
    [bridge] = job.spmd_bridges.values()
    rec = tracing.RECORDER
    t_mark = time.perf_counter()
    counts0 = {name: rec.counts(name) for name in rec.names()}
    steps0 = bridge.trainer._steps_host
    assert job.run_file_fused(str(path))
    records = [r for name in rec.names() if name != "compile"
               for r in rec.records(name) if r.start >= t_mark]
    by_name = collections.defaultdict(list)
    for r in sorted(records, key=lambda r: r.id):
        by_name[r.name].append(r)
    delta = {name + "." + k: v - counts0.get(name, {}).get(k, 0)
             for name in rec.names() for k, v in rec.counts(name).items()}

    n_train = n_lines - len(forecast_ids)
    n_steps = bridge.trainer._steps_host - steps0
    fitted, holdout = bridge.trainer.fitted, len(bridge.test_set)
    assert fitted + holdout == n_train and len(preds) == 3
    # 30 full steps of 32 rows, then the file's tail flush: one padded step
    assert n_steps == fitted // 32 + 1 == 31
    assert {name: len(rs) for name, rs in by_name.items()} == {
        "ingest_file": 1, "dispatcher_open": 1, "dispatcher_close": 1,
        "read": 3,            # buffer and open, the file in one chunk, the empty read
        "parse": 1,
        "split_lines": 1,     # the block holds special lines
        "stage": 4,           # the runs of rows around three forecasts
        "forecast": 3, "decode": 6, "quiesce": 3, "serve": 3, "emit": 3,
        "pool_wait": n_steps, "launch": n_steps, "copy_stage": n_steps, "fit": n_steps,
    }
    assert {k: v for k, v in delta.items() if v} == {
        "ingest_file.rows": n_train, "parse.rows": n_lines,
        "fit.rows": fitted, "fit.rows_padded": 30 * 32 + 32,
    }
    [file] = by_name["ingest_file"]
    # the producer's spans hang off the file's; the forecast's off its own
    assert {r.parent for n in ("dispatcher_open", "dispatcher_close", "read", "parse",
                               "split_lines", "stage", "forecast")
            for r in by_name[n]} == {file.id}
    assert [r.key for r in by_name["forecast"]] == list(forecast_ids)
    for fc in by_name["forecast"]:
        children = [r.name for r in records if r.parent == fc.id]
        assert sorted(children) == ["decode", "decode", "emit", "quiesce", "serve"]
    assert {r.parent_name for r in by_name["pool_wait"]} == {"stage", "ingest_file"}
    # the dispatch thread: launches caused by the file, one fit each, keyed
    # by the trainer's step ordinal
    assert {r.thread for r in by_name["launch"] + by_name["fit"]}.isdisjoint({file.thread})
    assert {r.parent for r in by_name["launch"]} == {file.id}
    launch_ids = [r.id for r in by_name["launch"]]
    assert [r.parent for r in by_name["fit"]] == launch_ids
    assert [r.parent for r in by_name["copy_stage"]] == launch_ids
    assert [r.key for r in by_name["fit"]] == list(range(steps0, steps0 + n_steps))
    assert [r.attrs["tail"] for r in by_name["fit"]] == [False] * 30 + [True]
    # nothing but the file's span is open at the producer's top level
    assert file.self_s < file.end - file.start
    assert rec.current() is None
    # the operator's table covers the engine: spmd pipeline with no plane
    # armed, from this job's start on: its state was built once, its counters
    # are the file's, and another job's spans are not in it
    table = job.phase_table(file.end - file.start)
    assert table["fit"]["count"] == n_steps and table["ingest_file"]["seconds"] > 0.0
    assert table["build_state"]["count"] == table["place_state"]["count"] == 1
    assert (table["fit"]["rows"], table["fit"]["rows_padded"]) == (fitted, 30 * 32 + 32)
    assert table["ingest_file"]["rows"] == n_train and table["parse"]["rows"] == n_lines
    assert "rows" not in table["launch"]
    other = _sparse_job([])
    assert other.phase_table()["build_state"]["count"] == 1
    assert "ingest_file" not in other.phase_table()
    other.terminate()
    job.terminate()
