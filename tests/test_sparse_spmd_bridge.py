"""Sparse (padded-COO) pipelines on the SPMD collective engine.

A Create with ``dataStructure.sparse`` AND ``{"engine": "spmd"}`` deploys on
:class:`SparseSPMDBridge`: the dense model vector is hub-sharded on the
mesh, each record ships only its K active features, and the streaming
contract (holdout, forecasts, termination stats, checkpoints) matches the
host-plane sparse pipeline.
"""

import json

import numpy as np
import pytest

from omldm_tpu.api.data import DataInstance
from omldm_tpu.config import JobConfig
from omldm_tpu.runtime import StreamJob
from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM
from omldm_tpu.runtime.spmd_bridge import SparseSPMDBridge

HASH_SPACE = 1 << 12
DIM = 3 + HASH_SPACE


def _create(protocol="Synchronous", engine=True, extra=None):
    tc = {"protocol": protocol, "syncEvery": 2, **(extra or {})}
    if engine:
        tc["engine"] = "spmd"
    return {
        "id": 0,
        "request": "Create",
        "learner": {
            "name": "PA",
            "hyperParameters": {"C": 1.0, "variant": "PA-II"},
            "dataStructure": {
                "sparse": True, "nFeatures": DIM,
                "hashSpace": HASH_SPACE, "maxNnz": 8,
            },
        },
        "preProcessors": [],
        "trainingConfiguration": tc,
    }


def _lines(n, seed=0, forecast_every=0):
    rng = np.random.RandomState(seed)
    hidden = {}
    lines = []
    for i in range(n):
        num = rng.randn(3)
        cats = [f"c{rng.randint(40)}", f"d{rng.randint(40)}"]
        m = float(num.sum())
        for j, c in enumerate(cats):
            if (j, c) not in hidden:
                hidden[(j, c)] = rng.randn() * 2.0
            m += hidden[(j, c)]
        rec = {
            "numericalFeatures": [round(float(v), 5) for v in num],
            "categoricalFeatures": cats,
        }
        if forecast_every and i % forecast_every == 3:
            rec["operation"] = "forecasting"
        else:
            rec["target"] = float(m > 0)
            rec["operation"] = "training"
        lines.append(json.dumps(rec))
    return lines


def _run_job(create, lines, parallelism=2, batch=32):
    job = StreamJob(JobConfig(
        parallelism=parallelism, batch_size=batch, test_set_size=32,
    ))
    events = [(REQUEST_STREAM, json.dumps(create))] + [
        (TRAINING_STREAM, l) for l in lines
    ]
    report = job.run(events)
    return job, report


def _run_job_events(create, lines, parallelism=2, batch=32):
    """Per-record delivery WITHOUT termination (parity-vs-file runs)."""
    job = StreamJob(JobConfig(
        parallelism=parallelism, batch_size=batch, test_set_size=32,
    ))
    events = [(REQUEST_STREAM, json.dumps(create))] + [
        (TRAINING_STREAM, l) for l in lines
    ]
    job.run(events, terminate_on_end=False)
    return job, None


class TestSparseSPMDBridge:
    def test_deploys_on_sparse_bridge_and_learns(self):
        job, report = _run_job(_create(), _lines(4000))
        [bridge] = job.spmd_bridges.values()
        assert isinstance(bridge, SparseSPMDBridge)
        [stats] = report.statistics
        assert stats.fitted > 2500
        assert stats.score > 0.75
        assert stats.bytes_shipped > 0

    def test_forecasts_served(self):
        job, report = _run_job(_create(), _lines(1200, forecast_every=50))
        assert len(job.predictions) == len(
            [l for l in _lines(1200, forecast_every=50)
             if "forecasting" in l]
        )
        assert all(np.isfinite(p.value) for p in job.predictions)

    def test_score_tracks_host_plane(self):
        """Same stream, same learner: the collective engine and the host
        plane land comparable holdout scores."""
        lines = _lines(4000)
        _, rep_spmd = _run_job(_create(engine=True), lines)
        _, rep_host = _run_job(_create(engine=False), lines)
        s_spmd = rep_spmd.statistics[0].score
        s_host = rep_host.statistics[0].score
        assert s_spmd > 0.7 and s_host > 0.7
        assert abs(s_spmd - s_host) < 0.12

    def test_ssp_requeue_conserves_rows(self):
        create = _create(
            protocol="SSP", extra={"staleness": 1, "syncEvery": 2}
        )
        lines = _lines(1500)
        job, report = _run_job(create, lines)
        [bridge] = job.spmd_bridges.values()
        [stats] = report.statistics
        # every training row either fitted or resident in the holdout ring
        assert stats.fitted + len(bridge.test_set) == 1500

    def test_bulk_coo_ingest_matches_per_record(self, tmp_path):
        """The C padded-COO file route (SPMDBridge.ingest_file) is
        indistinguishable from per-record event delivery: same params,
        fitted count, holdout ring, predictions — forecasts, codec
        fallbacks and drops included."""
        from omldm_tpu.ops.native import fast_parser_available

        if not fast_parser_available():
            pytest.skip("native parser unavailable")
        lines = _lines(2500, forecast_every=90)
        lines.insert(100, "not json")
        lines.insert(700, "EOS")

        job_a, _ = _run_job_events(_create(), lines)
        [bridge_a] = job_a.spmd_bridges.values()

        path = tmp_path / "train.jsonl"
        path.write_text("\n".join(lines) + "\n")
        job_b = StreamJob(JobConfig(
            parallelism=2, batch_size=32, test_set_size=32,
        ))
        job_b.process_event(REQUEST_STREAM, json.dumps(_create()))
        job_b.ensure_deployed(DIM)
        assert job_b.run_file_fused(str(path)), "sparse fused route refused"
        [bridge_b] = job_b.spmd_bridges.values()
        bridge_a.flush()
        bridge_b.flush()
        np.testing.assert_allclose(
            np.asarray(bridge_a.trainer.global_flat_params()),
            np.asarray(bridge_b.trainer.global_flat_params()),
            rtol=1e-6, atol=1e-6,
        )
        assert bridge_a.trainer.fitted == bridge_b.trainer.fitted
        assert bridge_a.holdout_count == bridge_b.holdout_count
        assert len(bridge_a.test_set) == len(bridge_b.test_set)
        assert len(job_a.predictions) == len(job_b.predictions)
        for pa, pb in zip(job_a.predictions, job_b.predictions):
            assert pa.value == pytest.approx(pb.value, rel=1e-6)

    def test_checkpoint_roundtrip(self, tmp_path):
        from omldm_tpu.checkpoint import CheckpointManager

        job = StreamJob(JobConfig(
            parallelism=2, batch_size=32, test_set_size=32,
        ))
        events = [(REQUEST_STREAM, json.dumps(_create()))] + [
            (TRAINING_STREAM, l) for l in _lines(900)
        ]
        job.run(events, terminate_on_end=False)
        [bridge] = job.spmd_bridges.values()
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(job)
        restored = mgr.restore()
        [rbridge] = restored.spmd_bridges.values()
        assert isinstance(rbridge, SparseSPMDBridge)
        np.testing.assert_allclose(
            bridge.trainer.global_flat_params(),
            rbridge.trainer.global_flat_params(),
            rtol=1e-6,
        )
        assert rbridge.trainer.fitted == bridge.trainer.fitted
        assert len(rbridge.test_set) == len(bridge.test_set)
        assert rbridge._stage_n == bridge._stage_n
        # restored job keeps learning
        rep = restored.run(
            [(TRAINING_STREAM, l) for l in _lines(900, seed=1)]
        )
        assert rep.statistics[0].fitted > bridge.trainer.fitted


class TestFusedSparseStaging:
    """The sparse file route — C block parse + C staging
    (omldm_stage_coo_rows), with one parser thread or several, its launches
    on the calling thread or on the dispatch thread — must produce staging
    BIT-IDENTICAL to the per-record route (``handle_data`` line by line:
    the Python codec and the numpy stager, the independent reference):
    same trained params, fitted count, holdout ring and predictions.
    Streams include forecasts, escaped-category fallbacks and
    DUPLICATE-HEAVY categoricals (tiny vocabularies, the hashed-collision
    case the index plan's pre-combine targets)."""

    def _dup_heavy_lines(self, n, seed=7):
        """Categoricals drawn from 3-value vocabularies: most batch rows
        collide onto the same hashed slots."""
        rng = np.random.RandomState(seed)
        lines = []
        for i in range(n):
            num = [round(float(v), 5) for v in rng.randn(3)]
            cats = [f"c{rng.randint(3)}", f"d{rng.randint(3)}"]
            if i % 311 == 50:
                lines.append(json.dumps({
                    "numericalFeatures": num,
                    "categoricalFeatures": cats,
                    "operation": "forecasting",
                }))
                continue
            if i % 401 == 9:  # escaped category -> Python codec fallback
                cats[0] = 'a"b'
            lines.append(json.dumps({
                "numericalFeatures": num, "categoricalFeatures": cats,
                "target": float(rng.randint(2)), "operation": "training",
            }))
        return lines

    def _bridge(self, extra=None):
        from omldm_tpu.ops.native import fast_parser_available

        if not fast_parser_available():
            pytest.skip("native parser unavailable")
        preds = []
        job = StreamJob(JobConfig(
            parallelism=2, batch_size=32, test_set_size=32,
        ))
        job.set_sinks(on_prediction=preds.append)
        job.process_event(
            REQUEST_STREAM, json.dumps(_create(extra=extra or {}))
        )
        [bridge] = job.spmd_bridges.values()
        return bridge, preds

    def _assert_identical(self, a, b, preds_a, preds_b, label):
        assert a.trainer.fitted == b.trainer.fitted, label
        assert a.holdout_count == b.holdout_count, label
        np.testing.assert_array_equal(
            np.asarray(a.trainer.global_flat_params()),
            np.asarray(b.trainer.global_flat_params()),
            err_msg=label,
        )
        ai, av, ay = a.test_set.arrays()
        bi, bv, by = b.test_set.arrays()
        np.testing.assert_array_equal(ai, bi, err_msg=label)
        np.testing.assert_array_equal(av, bv, err_msg=label)
        np.testing.assert_array_equal(ay, by, err_msg=label)
        assert len(preds_a) == len(preds_b) > 0, label
        for pa, pb in zip(preds_a, preds_b):
            assert pa.value == pb.value, label

    def test_serial_routes_bit_identical(self, tmp_path):
        lines = self._dup_heavy_lines(3000)
        path = tmp_path / "dup.jsonl"
        path.write_text("\n".join(lines) + "\n")
        ref, ref_p = self._bridge()
        for line in lines:
            inst = DataInstance.from_json(line)
            if inst is not None:
                ref.handle_data(inst)
        ref.flush()
        for label, extra in (
            ("one parser thread", {"parserThreads": 1}),
            ("two parser threads", {"parserThreads": 2}),
        ):
            b, p = self._bridge(extra)
            b.ingest_file(str(path), depth=0)
            b.flush()
            self._assert_identical(b, ref, p, ref_p, label)

    def test_overlapped_matches_serial_duplicate_heavy(self, tmp_path):
        path = tmp_path / "dup.jsonl"
        path.write_text("\n".join(self._dup_heavy_lines(3000)) + "\n")
        ref, ref_p = self._bridge({"parserThreads": 1})
        ref.ingest_file(str(path), depth=0)
        ref.flush()
        for label, extra, kw in (
            ("dispatch thread, one parser thread", {"parserThreads": 1},
             {"depth": 2}),
            ("dispatch thread, two parser threads", {"parserThreads": 2},
             {"depth": 2}),
            ("dispatch thread, small chunks", {"parserThreads": 2},
             {"depth": 4, "chunk_bytes": 999}),
        ):
            b, p = self._bridge(extra)
            b.ingest_file(str(path), **kw)
            b.flush()
            self._assert_identical(b, ref, p, ref_p, label)

    def test_plan_pipeline_stays_in_twin_envelope(self, tmp_path):
        """A sparse pipeline trained with the index plan pinned
        (dataStructure.scatterImpl) diverges from the plain-pair run by
        <= 2e-5 per parameter on a duplicate-heavy stream — the bridge-level
        form of the ops twin tests — and its launches' device counters reach
        the phase table's ``fit`` row once the statistics are read."""
        path = tmp_path / "dup.jsonl"
        path.write_text("\n".join(self._dup_heavy_lines(2000)) + "\n")
        flats = {}
        for impl in ("scatter", "plan"):
            create = _create()
            create["learner"]["dataStructure"]["scatterImpl"] = impl
            preds = []
            job = StreamJob(JobConfig(
                parallelism=2, batch_size=32, test_set_size=32,
            ))
            job.set_sinks(on_prediction=preds.append)
            job.process_event(REQUEST_STREAM, json.dumps(create))
            [bridge] = job.spmd_bridges.values()
            bridge.ingest_file(str(path))
            bridge.flush()
            flats[impl] = np.asarray(bridge.trainer.global_flat_params())
            bridge.network_statistics()
            fit = job.phase_table()["fit"]
            if impl == "plan":
                # 3-value vocabularies: far fewer addresses than slots
                assert 0.0 < fit["distinct_share"] < 0.5, fit
                assert fit["slots"] == fit["rows_padded"] * (
                    bridge.max_nnz + 1
                )
                assert fit["overflow_launches"] == 0
            else:
                assert "distinct_share" not in fit
        np.testing.assert_allclose(
            flats["plan"], flats["scatter"], rtol=2e-5, atol=2e-5
        )
