"""Failure detection / restart-from-checkpoint / fault-injection tests.

Reference behavior being pinned: restart-from-checkpoint recovery (the
Flink machinery the reference delegates to — RestartStrategies import at
Job.scala:14, Checkpointing.scala:9-25) with resume at the checkpointed
source offset, plus Flink's fixed-delay restart semantics (bounded
attempts; an uncheckpointed job restarts from scratch)."""

import json

import jax
import numpy as np
import pytest

from omldm_tpu.checkpoint import CheckpointManager
from omldm_tpu.config import JobConfig
from omldm_tpu.parallel.spmd import stacked
from omldm_tpu.runtime import StreamJob
from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM
from omldm_tpu.runtime.recovery import (
    FaultInjector,
    InjectedFault,
    JobSupervisor,
    replayable,
    skip_events,
)


def stream_lines(n, dim=5, seed=0):
    w = np.random.RandomState(42).randn(dim)
    rng = np.random.RandomState(seed)
    x = rng.randn(n, dim)
    y = (x @ w > 0).astype(np.float64)
    return [
        json.dumps(
            {"numericalFeatures": list(np.round(x[i], 5)), "target": float(y[i])}
        )
        for i in range(n)
    ]


CREATE = {
    "id": 0,
    "request": "Create",
    "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
    "trainingConfiguration": {"protocol": "Synchronous", "syncEvery": 2},
}


def make_events(n=1200, seed=0):
    return [(REQUEST_STREAM, json.dumps(CREATE))] + [
        (TRAINING_STREAM, l) for l in stream_lines(n, seed=seed)
    ]


def checkpointed_job(tmp_path, **kw):
    cfg = JobConfig(
        parallelism=kw.pop("parallelism", 2),
        batch_size=32,
        test_set_size=32,
        checkpointing=True,
        checkpoint_dir=str(tmp_path / "ck"),
        # force a save on every maybe_save call: deterministic coverage
        check_interval_ms=0,
        **kw,
    )
    return StreamJob(cfg)


class TestSupervisorRecovery:
    def test_transient_crash_recovers_and_finishes(self, tmp_path):
        """A mid-stream worker crash restores the latest checkpoint, resumes
        the replay at the snapshot offset, and the job still terminates with
        a trained model."""
        events = make_events()
        job = checkpointed_job(tmp_path)
        fault = FaultInjector()
        fault.arm(job, worker_id=0, after_records=300)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()
        assert fault.fired == 1
        assert len(sup.failures) == 1
        assert sup.failures[0].restored_from is not None
        [stats] = report.statistics
        assert stats.score > 0.8
        # every event was consumed by the final incarnation
        assert sup.job.events_processed == len(events)

    def test_recovery_matches_unfaulted_run_exactly(self, tmp_path):
        """Checkpoint state corresponds exactly to the saved offset and the
        checkpoint carries the routing cursor, so a recovered run fits the
        same records as a run that never crashed."""
        events = make_events(n=900)
        clean = checkpointed_job(tmp_path / "clean")
        clean_report = clean.run(list(events))

        job = checkpointed_job(tmp_path / "faulted")
        fault = FaultInjector()
        fault.arm(job, worker_id=1, after_records=200)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()

        [clean_stats] = clean_report.statistics
        [stats] = report.statistics
        assert stats.fitted == clean_stats.fitted
        assert stats.score == pytest.approx(clean_stats.score, abs=1e-6)
        w_clean, _ = clean.spokes[0].nets[0].pipeline.get_flat_params()
        w_rec, _ = sup.job.spokes[0].nets[0].pipeline.get_flat_params()
        np.testing.assert_allclose(w_clean, w_rec, rtol=1e-5, atol=1e-6)

    def test_uncheckpointed_job_restarts_from_scratch(self, tmp_path):
        events = make_events(n=600)
        job = StreamJob(JobConfig(parallelism=2, batch_size=32, test_set_size=32))
        fault = FaultInjector()
        fault.arm(job, worker_id=0, after_records=150)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()
        assert sup.failures[0].restored_from is None
        # the fresh incarnation replayed the whole stream
        assert sup.job.events_processed == len(events)
        [stats] = report.statistics
        assert stats.score > 0.8

    def test_poison_event_exhausts_restarts(self, tmp_path):
        """A deterministic fault re-armed on every incarnation crashes each
        attempt until max_restarts is exceeded (Flink semantics)."""
        events = make_events(n=2000)
        job = checkpointed_job(tmp_path)

        def arm(j):
            inj = FaultInjector()
            inj.arm(j, worker_id=0, after_records=50)

        arm(job)
        sup = JobSupervisor(
            job,
            replayable(lambda: list(events)),
            max_restarts=2,
            on_failure=lambda rec: arm(sup.job),
        )
        with pytest.raises(InjectedFault):
            sup.run()
        assert len(sup.failures) == 3  # initial + 2 restarts

    def test_failure_record_contents(self, tmp_path):
        events = make_events(n=400)
        job = checkpointed_job(tmp_path)
        FaultInjector().arm(job, worker_id=0, after_records=100)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        sup.run()
        [rec] = sup.failures
        assert "InjectedFault" in rec.error
        assert rec.offset > 0


class TestOffsetTracking:
    def test_events_processed_counts_and_checkpoints(self, tmp_path):
        events = make_events(n=100)
        job = checkpointed_job(tmp_path)
        job.run(list(events), terminate_on_end=False)
        assert job.events_processed == len(events)
        restored = CheckpointManager(job.config.checkpoint_dir).restore()
        assert restored.events_processed == len(events)

    def test_skip_events(self):
        evs = [("a", 1), ("b", 2), ("c", 3)]
        assert list(skip_events(evs, 2)) == [("c", 3)]
        assert list(skip_events(evs, 5)) == []


class TestSPMDBridgeCheckpoint:
    CREATE_SPMD = {
        "id": 0,
        "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
        "trainingConfiguration": {
            "protocol": "Synchronous",
            "syncEvery": 2,
            "engine": "spmd",
            "stageChain": 1,
        },
    }

    def _events(self, n=800, seed=0):
        return [(REQUEST_STREAM, json.dumps(self.CREATE_SPMD))] + [
            (TRAINING_STREAM, l) for l in stream_lines(n, seed=seed)
        ]

    def test_bridge_state_roundtrip(self, tmp_path):
        """Fleet state, holdout, stage and progress counters all survive a
        save/restore on the same mesh."""
        cfg = JobConfig(parallelism=2, batch_size=16, test_set_size=32)
        job = StreamJob(cfg)
        job.run(self._events(), terminate_on_end=False)
        bridge = job.spmd_bridges[0]
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(job)
        restored = mgr.restore()
        rbridge = restored.spmd_bridges[0]
        np.testing.assert_allclose(
            bridge.trainer.global_flat_params(),
            rbridge.trainer.global_flat_params(),
            rtol=1e-6,
        )
        assert rbridge.trainer.fitted == bridge.trainer.fitted
        assert rbridge.holdout_count == bridge.holdout_count
        assert len(rbridge.test_set) == len(bridge.test_set)
        assert rbridge._stage_n == bridge._stage_n

    def test_bridge_continues_training_after_restore(self, tmp_path):
        cfg = JobConfig(parallelism=2, batch_size=16, test_set_size=32)
        job = StreamJob(cfg)
        job.run(self._events(), terminate_on_end=False)
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(job)
        restored = mgr.restore()
        report = restored.run(
            [(TRAINING_STREAM, l) for l in stream_lines(800, seed=1)]
        )
        [stats] = report.statistics
        assert stats.score > 0.8
        assert stats.fitted > job.spmd_bridges[0].trainer.fitted

    def test_supervised_recovery_with_spmd_bridge(self, tmp_path):
        """Crash-and-restore through the supervisor with the pipeline on the
        SPMD engine: the bridge resumes from the checkpointed fleet state."""
        events = self._events(n=1000)
        cfg = JobConfig(
            parallelism=2,
            batch_size=16,
            test_set_size=32,
            checkpointing=True,
            checkpoint_dir=str(tmp_path / "ck"),
            check_interval_ms=0,
        )
        job = StreamJob(cfg)
        fault = FaultInjector()
        # SPMD-engine records still route through host spokes round-robin,
        # so a spoke trip-wire models a worker crash mid-stream
        fault.arm(job, worker_id=0, after_records=120)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()
        assert fault.fired == 1
        assert sup.failures[0].restored_from is not None
        [stats] = report.statistics
        assert stats.score > 0.8


    def test_rescale_restore_merges_diverged_replicas(self, tmp_path):
        """Restoring under a DIFFERENT mesh shape must seed every replica
        from the MEAN of the saved dp replicas, not worker 0's shard —
        checkpoints land between events, and under Asynchronous the
        replicas diverge mid-round (worker-0-only would silently discard
        the other workers' progress since the last fold)."""
        import pickle

        create = dict(self.CREATE_SPMD)
        create["trainingConfiguration"] = {
            "protocol": "Asynchronous",
            "syncEvery": 8,  # long rounds: snapshot lands mid-round
            "engine": "spmd",
            "stageChain": 1,
        }
        cfg = JobConfig(parallelism=2, batch_size=16, test_set_size=32)
        job = StreamJob(cfg)
        events = [(REQUEST_STREAM, json.dumps(create))] + [
            (TRAINING_STREAM, l) for l in stream_lines(500, seed=0)
        ]
        job.run(events, terminate_on_end=False)
        # drain the stage so the restore trains nothing (staged rows are
        # re-staged on restore and would retrain on the new mesh)
        job.spmd_bridges[0].flush()
        mgr = CheckpointManager(str(tmp_path / "ck"))
        path = mgr.save(job)
        with open(path, "rb") as f:
            snapshot = pickle.load(f)
        fleet = snapshot["bridges"][0]["fleet"]
        leaves = jax.tree_util.tree_leaves(fleet["params"])
        saved = stacked(  # [dp, hub, ...]
            np.asarray(leaves[0]), *snapshot["bridges"][0]["mesh"]
        )
        assert saved.shape[0] == 2
        # the premise: replicas actually diverged mid-round
        assert not np.allclose(saved[0, 0], saved[1, 0])
        restored = mgr.restore(parallelism=1)
        trainer = restored.spmd_bridges[0].trainer
        rleaves = jax.tree_util.tree_leaves(trainer.state["params"])
        got = trainer.host_stacked(rleaves[0])
        expect = saved[:, 0].mean(axis=0)
        np.testing.assert_allclose(got[0, 0], expect, rtol=1e-6, atol=1e-7)


    @pytest.mark.parametrize("protocol", ["Synchronous", "EASGD"])
    @pytest.mark.parametrize("parallelism", [2, 1])
    def test_snapshot_with_an_unread_est_restores_without_it(
        self, tmp_path, parallelism, protocol
    ):
        """A job snapshot from before Synchronous / EASGD dropped the
        ``est`` that nothing read holds one in the bridge's fleet state: it
        restores on the same mesh and under a rescale (where the merge
        takes ``est`` only where the trainer keeps it), and trains on."""
        import pickle

        create = dict(self.CREATE_SPMD)
        create["trainingConfiguration"] = {
            **create["trainingConfiguration"], "protocol": protocol,
        }
        cfg = JobConfig(parallelism=2, batch_size=16, test_set_size=32)
        job = StreamJob(cfg)
        events = [(REQUEST_STREAM, json.dumps(create))] + [
            (TRAINING_STREAM, l) for l in stream_lines(500, seed=0)
        ]
        job.run(events, terminate_on_end=False)
        job.spmd_bridges[0].flush()
        mgr = CheckpointManager(str(tmp_path / "ck"))
        path = mgr.save(job)
        with open(path, "rb") as f:
            snapshot = pickle.load(f)
        bd = snapshot["bridges"][0]
        assert "est" not in bd["fleet"]
        vec = bd["fleet"]["params"]["w"]
        bd["fleet"] = {**bd["fleet"], "est": vec + 3.0}
        bd["fleet"].setdefault("center", vec + 5.0)
        with open(path, "wb") as f:
            pickle.dump(snapshot, f)
        restored = mgr.restore(parallelism=parallelism)
        trainer = restored.spmd_bridges[0].trainer
        donor = job.spmd_bridges[0].trainer
        assert sorted(trainer.state) == sorted(donor.state)
        saved = donor.host_stacked(donor.state["params"]["w"])
        got = trainer.host_stacked(trainer.state["params"]["w"])
        if parallelism == 2:
            np.testing.assert_array_equal(got, saved)
        else:
            np.testing.assert_allclose(
                got[0, 0], saved[:, 0].mean(axis=0), rtol=1e-6, atol=1e-7
            )
        report = restored.run(
            [(TRAINING_STREAM, l) for l in stream_lines(400, seed=1)]
        )
        [stats] = report.statistics
        assert stats.score > 0.8


class TestCentralModelRescaleRestore:
    CREATE_SL = {
        "id": 0,
        "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
        "trainingConfiguration": {"protocol": "SingleLearner"},
    }

    def test_rescale_restore_keeps_hub_model(self, tmp_path):
        """SingleLearner: THE model lives on the hub; restoring under a
        DIFFERENT parallelism must still carry it (round state resets, the
        central model must not)."""
        cfg = JobConfig(parallelism=2, batch_size=32, test_set_size=32)
        job = StreamJob(cfg)
        job.run(
            [(REQUEST_STREAM, json.dumps(self.CREATE_SL))]
            + [(TRAINING_STREAM, l) for l in stream_lines(600)],
            terminate_on_end=False,
        )
        central = job.hub_manager.hubs[(0, 0)].node.pipeline
        w_before, _ = central.get_flat_params()
        assert central.fitted > 0
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(job)
        restored = mgr.restore(parallelism=4)
        rcentral = restored.hub_manager.hubs[(0, 0)].node.pipeline
        w_after, _ = rcentral.get_flat_params()
        np.testing.assert_allclose(w_before, w_after, rtol=1e-6)
        assert rcentral.fitted == central.fitted


class TestStaleCheckpointGuard:
    def test_supervisor_ignores_preexisting_checkpoint(self, tmp_path):
        """A snapshot left in a reused checkpoint directory by an EARLIER
        run must not be restored — it would skip (and mask) nearly the
        whole new stream."""
        events = make_events(n=600)
        old = checkpointed_job(tmp_path)
        old.run(list(events), terminate_on_end=False)  # leaves snapshots

        # new run, same directory, checkpoint INTERVAL too long to ever
        # save; crashes on its first records
        cfg = JobConfig(
            parallelism=2,
            batch_size=32,
            test_set_size=32,
            checkpointing=True,
            checkpoint_dir=str(tmp_path / "ck"),
            check_interval_ms=10_000_000,
        )
        job = StreamJob(cfg)
        import time as _time

        job.checkpoint_manager._last_save = _time.time()  # arm the interval
        FaultInjector().arm(job, worker_id=0, after_records=50)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()
        # fresh restart, not a restore of the stale snapshot
        assert sup.failures[0].restored_from is None
        assert sup.job.events_processed == len(events)
        [stats] = report.statistics
        assert stats.score > 0.8


class TestCLIRecoveryFlags:
    def test_restart_attempts_flag_supervises(self, tmp_path, monkeypatch):
        """--restartAttempts routes file replay through the supervisor."""
        train = tmp_path / "train.jsonl"
        train.write_text("\n".join(stream_lines(400)) + "\n")
        reqs = tmp_path / "reqs.jsonl"
        reqs.write_text(json.dumps(CREATE) + "\n")
        perf = tmp_path / "perf.jsonl"

        from omldm_tpu.__main__ import main

        calls = {"n": 0}
        from omldm_tpu.runtime import recovery

        orig_run = recovery.JobSupervisor.run

        def spy_run(self, *a, **kw):
            calls["n"] += 1
            return orig_run(self, *a, **kw)

        monkeypatch.setattr(recovery.JobSupervisor, "run", spy_run)
        rc = main(
            [
                "--trainingData", str(train),
                "--requests", str(reqs),
                "--parallelism", "2",
                "--restartAttempts", "2",
                "--performanceOut", str(perf),
            ]
        )
        assert rc == 0
        assert calls["n"] == 1
        out = json.loads(perf.read_text().strip().splitlines()[-1])
        assert out["statistics"][0]["fitted"] > 0


class TestRescaleRecoveryInterplay:
    def test_recover_after_live_rescale_restores_new_parallelism(
        self, tmp_path
    ):
        """A live rescale mid-stream changes config.parallelism; a
        checkpoint taken AFTER it must restore the rescaled worker count
        and keep training through recovery."""
        events = make_events(n=1200)
        job = checkpointed_job(tmp_path, parallelism=2)
        # the supervisor's stale-snapshot floor is recorded at construction:
        # build it BEFORE the deliberate post-rescale checkpoint so that
        # snapshot is above the floor and genuinely restorable
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        # consume half the stream, rescale live, checkpoint, then crash
        job.run(list(events)[:600], terminate_on_end=False)
        job.rescale(4)
        assert len(job.spokes) == 4
        job.checkpoint_manager.maybe_save(job)  # interval 0: saves now

        fault = FaultInjector()
        fault.arm(job, worker_id=3, after_records=30)
        report = sup.run()
        assert fault.fired == 1
        assert sup.failures[0].restored_from is not None
        assert len(sup.job.spokes) == 4
        assert sup.job.config.parallelism == 4
        [stats] = report.statistics
        assert stats.score > 0.8
        assert sup.job.events_processed == len(events)


class TestSparseCheckpointRecovery:
    HASH_SPACE = 1 << 12
    DIM = 3 + HASH_SPACE

    def _create(self):
        return {
            "id": 0,
            "request": "Create",
            "learner": {
                "name": "PA",
                "hyperParameters": {"C": 1.0, "variant": "PA-II"},
                "dataStructure": {
                    "sparse": True, "nFeatures": self.DIM,
                    "hashSpace": self.HASH_SPACE, "maxNnz": 8,
                },
            },
            "preProcessors": [],
            "trainingConfiguration": {"protocol": "Synchronous"},
        }

    def _lines(self, n, seed=0):
        rng = np.random.RandomState(seed)
        hidden = {}
        lines = []
        for _ in range(n):
            num = rng.randn(3)
            cats = [f"c{rng.randint(30)}", f"d{rng.randint(30)}"]
            m = float(num.sum())
            for i, c in enumerate(cats):
                if (i, c) not in hidden:
                    hidden[(i, c)] = rng.randn() * 2.0
                m += hidden[(i, c)]
            lines.append(json.dumps({
                "numericalFeatures": [round(float(v), 5) for v in num],
                "categoricalFeatures": cats,
                "target": float(m > 0),
            }))
        return lines

    def test_sparse_job_checkpoints_and_recovers(self, tmp_path):
        """A job hosting a sparse (padded-COO) pipeline must checkpoint —
        including PENDING rows in the SparseMicroBatcher — and recover
        through the supervisor (previously save() crashed on the sparse
        batcher's attribute layout, making recovery impossible)."""
        events = [(REQUEST_STREAM, json.dumps(self._create()))] + [
            (TRAINING_STREAM, l) for l in self._lines(1800)
        ]
        cfg = JobConfig(
            parallelism=2,
            batch_size=64,
            test_set_size=32,
            checkpointing=True,
            checkpoint_dir=str(tmp_path / "ck"),
            check_interval_ms=0,
        )
        job = StreamJob(cfg)
        fault = FaultInjector()
        fault.arm(job, worker_id=0, after_records=400)
        sup = JobSupervisor(job, replayable(lambda: list(events)))
        report = sup.run()
        assert fault.fired == 1
        assert sup.failures[0].restored_from is not None
        [stats] = report.statistics
        assert stats.fitted > 1200
        # the sparse task at 1800 records is hard; the pin here is the
        # recovery mechanics (save no longer crashes, restore resumes),
        # not asymptotic accuracy
        assert stats.score > 0.6

    def test_sparse_pending_rows_survive_roundtrip(self, tmp_path):
        cfg = JobConfig(parallelism=1, batch_size=64, test_set_size=16)
        job = StreamJob(cfg)
        # 30 records: far fewer than one batch, so rows sit PENDING in the
        # sparse batcher at save time
        job.run(
            [(REQUEST_STREAM, json.dumps(self._create()))]
            + [(TRAINING_STREAM, l) for l in self._lines(30)],
            terminate_on_end=False,
        )
        net = job.spokes[0].nets[0]
        assert len(net.batcher) > 0
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(job)  # crashed before the fix
        restored = mgr.restore()
        rnet = restored.spokes[0].nets[0]
        assert len(rnet.batcher) == len(net.batcher)
        np.testing.assert_array_equal(rnet.batcher._idx, net.batcher._idx)
        # and a rescale restore re-feeds the sparse rows without error
        mgr.restore(parallelism=2)
