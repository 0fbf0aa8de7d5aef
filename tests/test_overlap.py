"""The file route's dispatch: SPMDBridge.ingest_file.

Pins the two properties the measured route rests on:

1. EQUIVALENCE — stage sets are launched strictly in order, so a run whose
   launches go through the dispatch thread trains the exact same launch
   sequence as one whose launches run on the calling thread (``depth`` 0,
   what an SSP pipeline always gets): identical parameters, fitted count,
   holdout ring and predictions (including mid-stream forecasts and
   Python-fallback lines, which quiesce the dispatch queue before running
   on the calling thread).
2. OVERLAP — the parse thread demonstrably keeps parsing while the
   dispatch thread is busy: with a sleeping launch, later chunks are
   parsed strictly inside an earlier stage's train interval.
"""

import json
import threading
import time

import numpy as np
import pytest

from omldm_tpu.config import JobConfig
from omldm_tpu.ops.native import fast_parser_available
from omldm_tpu.runtime import StreamJob
from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM

pytestmark = pytest.mark.skipif(
    not fast_parser_available(), reason="native parser unavailable"
)

DIM = 10


def _request(extra=None):
    return {
        "id": 0,
        "request": "Create",
        "learner": {
            "name": "PA",
            "hyperParameters": {"C": 0.1},
            "dataStructure": {"nFeatures": DIM},
        },
        "preProcessors": [],
        "trainingConfiguration": {
            "protocol": "Synchronous",
            "engine": "spmd",
            "extra": {"stageChain": 2, **(extra or {})},
        },
    }


def _write_stream(path, n=6000, seed=0, specials=True):
    rng = np.random.RandomState(seed)
    w = rng.randn(DIM)
    with open(path, "w") as f:
        for i in range(n):
            x = np.round(rng.randn(DIM), 6)
            y = 1.0 if float(x @ w) > 0 else -1.0
            if specials and i % 613 == 100:
                f.write(json.dumps({
                    "numericalFeatures": [round(float(v), 6) for v in x],
                    "operation": "forecasting",
                }) + "\n")
                continue
            if specials and i % 509 == 77:
                # categorical features force the Python-codec fallback
                f.write(json.dumps({
                    "numericalFeatures": [round(float(v), 6) for v in x],
                    "categoricalFeatures": ["blue"],
                    "target": y,
                    "operation": "training",
                }) + "\n")
                continue
            f.write(json.dumps({
                "numericalFeatures": [round(float(v), 6) for v in x],
                "target": y,
                "operation": "training",
            }) + "\n")


def _make_bridge(request=None):
    preds = []
    config = JobConfig(
        parallelism=2, batch_size=32, test=True, test_set_size=32
    )
    job = StreamJob(config)
    job.set_sinks(on_prediction=preds.append)
    job.process_event(REQUEST_STREAM, json.dumps(request or _request()))
    [bridge] = job.spmd_bridges.values()
    return job, bridge, preds


def _flat(bridge):
    return bridge.trainer.global_flat_params()


class TestOverlappedIngest:
    def test_dispatch_thread_bit_identical_to_inline(self, tmp_path):
        path = tmp_path / "stream.jsonl"
        _write_stream(str(path))

        _, serial, serial_preds = _make_bridge()
        serial.ingest_file(str(path), depth=0)
        serial.flush()

        _, over, over_preds = _make_bridge()
        over.ingest_file(str(path), depth=2)
        over.flush()

        assert over.trainer.fitted == serial.trainer.fitted
        assert len(over.test_set) == len(serial.test_set)
        np.testing.assert_array_equal(_flat(over), _flat(serial))
        sx, sy = serial.test_set.arrays()
        ox, oy = over.test_set.arrays()
        np.testing.assert_array_equal(ox, sx)
        np.testing.assert_array_equal(oy, sy)
        # forecasts emitted in order with identical values
        assert len(over_preds) == len(serial_preds) > 0
        for a, b in zip(over_preds, serial_preds):
            assert a.value == b.value

    def test_small_chunks_and_deep_queue(self, tmp_path):
        """Chunk boundaries (partial lines carried) and a deeper buffer
        pool must not change the result."""
        path = tmp_path / "stream.jsonl"
        _write_stream(str(path), n=3000, specials=False)
        _, serial, _ = _make_bridge()
        serial.ingest_file(str(path), depth=0)
        serial.flush()
        _, over, _ = _make_bridge()
        over.ingest_file(str(path), chunk_bytes=777, depth=4)
        over.flush()
        assert over.trainer.fitted == serial.trainer.fitted
        np.testing.assert_array_equal(_flat(over), _flat(serial))

    def test_parse_proceeds_during_device_time(self, tmp_path):
        """With the bridge's one launch method asleep, chunk parses land
        strictly inside a stage's train interval — the parse thread did
        not wait for the 'device'."""
        path = tmp_path / "stream.jsonl"
        _write_stream(str(path), n=4000, specials=False)
        _, bridge, _ = _make_bridge()
        intervals = []
        chunk_times = []

        def stub(stage_set, n):
            t0 = time.perf_counter()
            time.sleep(0.15)
            intervals.append((t0, time.perf_counter()))

        bridge._launch_stage_set = stub
        bridge.ingest_file(
            str(path), chunk_bytes=4096, depth=2,
            on_chunk=lambda: chunk_times.append(time.perf_counter()),
        )
        assert len(intervals) >= 2 and len(chunk_times) >= 3
        overlapped = any(
            a < t < b for t in chunk_times for (a, b) in intervals
        )
        assert overlapped, (
            "no chunk was parsed during any train interval: "
            f"{chunk_times} vs {intervals}"
        )

    def test_worker_exception_propagates(self, tmp_path):
        path = tmp_path / "stream.jsonl"
        _write_stream(str(path), n=4000, specials=False)
        _, bridge, _ = _make_bridge()

        def boom(stage_set, n):
            raise RuntimeError("device on fire")

        bridge._launch_stage_set = boom
        with pytest.raises(RuntimeError, match="device on fire"):
            bridge.ingest_file(str(path), chunk_bytes=4096)
        # the file is closed: the next launch is the bridge's own again
        assert bridge._dispatcher is None

    def test_sparse_dispatch_thread_matches_inline(self, tmp_path):
        """The sparse (padded-COO) bridge runs the same loop: stage sets
        launched on the dispatch thread give identical trained params,
        fitted count, holdout and predictions to launches on the calling
        thread — including mid-stream forecasts (which quiesce the
        dispatch queue) and escape-bearing fallback lines."""
        import json as _json

        rng = np.random.RandomState(3)
        path = tmp_path / "sparse.jsonl"
        with open(path, "w") as f:
            for i in range(4000):
                nums = [round(float(v), 6) for v in rng.randn(5)]
                cats = [f"c{j}_{rng.randint(50)}" for j in range(6)]
                if i % 701 == 200:
                    f.write(_json.dumps({
                        "numericalFeatures": nums,
                        "categoricalFeatures": cats,
                        "operation": "forecasting",
                    }) + "\n")
                    continue
                if i % 997 == 500:  # escaped category -> Python fallback
                    cats[0] = 'a"b'
                f.write(_json.dumps({
                    "numericalFeatures": nums,
                    "categoricalFeatures": cats,
                    "target": float(rng.randint(2)),
                    "operation": "training",
                }) + "\n")

        def make_sparse_bridge():
            preds = []
            config = JobConfig(
                parallelism=2, batch_size=32, test=True, test_set_size=32
            )
            job = StreamJob(config)
            job.set_sinks(on_prediction=preds.append)
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": 0, "request": "Create",
                "learner": {
                    "name": "PA", "hyperParameters": {"C": 0.5},
                    "dataStructure": {
                        "sparse": True, "nFeatures": 5 + 512,
                        "hashSpace": 512, "maxNnz": 12,
                    },
                },
                "trainingConfiguration": {
                    "protocol": "Synchronous", "engine": "spmd",
                    "extra": {"stageChain": 2},
                },
            }))
            [bridge] = job.spmd_bridges.values()
            return bridge, preds

        serial, s_preds = make_sparse_bridge()
        serial.ingest_file(str(path), depth=0)
        serial.flush()
        over, o_preds = make_sparse_bridge()
        over.ingest_file(str(path), depth=2)
        over.flush()
        assert over.trainer.fitted == serial.trainer.fitted > 0
        assert len(over.test_set) == len(serial.test_set)
        np.testing.assert_array_equal(_flat(over), _flat(serial))
        assert len(o_preds) == len(s_preds) > 0
        for a, b in zip(o_preds, s_preds):
            assert a.value == b.value

    def test_ssp_runs_inline_and_matches_per_record(self, tmp_path):
        """An SSP pipeline goes through the same loop with its launches on
        the calling thread (a refused batch re-enters the stage from the
        launch), and ends where feeding the lines one by one ends."""
        req = _request(extra={"staleness": 1})
        req["trainingConfiguration"]["protocol"] = "SSP"
        path = tmp_path / "stream.jsonl"
        _write_stream(str(path), n=1500)

        job, ref, ref_preds = _make_bridge(req)
        with open(path) as f:
            for line in f:
                job.process_event(TRAINING_STREAM, line.rstrip("\n"))
        ref.flush()

        job, bridge, preds = _make_bridge(req)
        assert not bridge.supports_overlapped_ingest()
        launch, threads = bridge._launch_stage_set, set()

        def watched(stage_set, n):
            threads.add(threading.current_thread())
            launch(stage_set, n)

        bridge._launch_stage_set = watched
        job.ensure_deployed(DIM)
        assert job.run_file_fused(str(path))
        bridge.flush()

        assert threads == {threading.current_thread()}
        assert bridge.trainer.fitted == ref.trainer.fitted > 0
        assert bridge.holdout_count == ref.holdout_count
        assert bridge.trainer.fitted + len(bridge.test_set) == (
            bridge.holdout_count
        )
        np.testing.assert_array_equal(_flat(bridge), _flat(ref))
        for a, b in zip(bridge.test_set.arrays(), ref.test_set.arrays()):
            np.testing.assert_array_equal(a, b)
        assert len(preds) == len(ref_preds) > 0
        for a, b in zip(preds, ref_preds):
            assert a.value == b.value
