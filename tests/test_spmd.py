"""SPMD engine tests on the 8-device virtual CPU mesh."""

import jax
import numpy as np
import pytest

from omldm_tpu.api.requests import LearnerSpec, PreprocessorSpec, TrainingConfiguration
from omldm_tpu.parallel import SPMDTrainer, make_mesh


def make_data(n_steps, dp, batch, dim, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    steps = []
    for _ in range(n_steps):
        x = rng.randn(dp, batch, dim).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        steps.append((x, y, np.ones((dp, batch), np.float32)))
    xt = rng.randn(2048, dim).astype(np.float32)
    yt = (xt @ w > 0).astype(np.float32)
    return steps, (xt, yt, np.ones(2048, np.float32))


def run_trainer(protocol, hub=1, dp=None, extra=None, steps=40, dim=10, batch=64,
                preps=(), learner=None):
    mesh = make_mesh(dp=dp if dp is not None else 8 // hub, hub=hub)
    tc = TrainingConfiguration(
        protocol=protocol, extra={"syncEvery": 2, **(extra or {})}
    )
    trainer = SPMDTrainer(
        learner or LearnerSpec("PA", hyper_parameters={"C": 1.0}),
        [PreprocessorSpec(p) for p in preps],
        dim=dim,
        protocol=protocol,
        mesh=mesh,
        training_configuration=tc,
        batch_size=batch,
    )
    data, test = make_data(steps, mesh.shape["dp"], batch, dim)
    for x, y, m in data:
        trainer.step(x, y, m)
    loss, score = trainer.evaluate(*test)
    return trainer, loss, score


class TestSPMDProtocols:
    @pytest.mark.parametrize(
        "protocol", ["Synchronous", "EASGD", "GM", "FGM", "Asynchronous", "SSP"]
    )
    def test_learns(self, protocol):
        trainer, loss, score = run_trainer(protocol)
        assert score > 0.85, f"{protocol}: score={score}"
        assert trainer.fitted == 8 * 64 * 40

    @pytest.mark.parametrize("protocol", ["Synchronous", "GM", "Asynchronous"])
    def test_step_many_matches_sequential_steps(self, protocol):
        """One scanned launch over T stacked batches == T step() calls:
        same final params, fitted count, sync count, and curve watermarks."""
        mesh = make_mesh(dp=4, hub=2)
        tc = TrainingConfiguration(
            protocol=protocol, extra={"syncEvery": 2, "threshold": 0.1}
        )

        def build():
            return SPMDTrainer(
                LearnerSpec("PA", hyper_parameters={"C": 1.0}),
                dim=6, protocol=protocol, mesh=mesh,
                training_configuration=tc, batch_size=32,
            )

        data, _ = make_data(5, 4, 32, 6, seed=3)
        seq = build()
        for x, y, m in data:
            seq.step(x, y, m)
        many = build()
        xs = np.stack([d[0] for d in data])
        ys = np.stack([d[1] for d in data])
        ms = np.stack([d[2] for d in data])
        losses = many.step_many(xs, ys, ms)
        assert losses.shape[0] == 5
        assert many.fitted == seq.fitted == 5 * 4 * 32
        assert many.sync_count() == seq.sync_count()
        np.testing.assert_allclose(
            many.global_flat_params(), seq.global_flat_params(), atol=1e-5
        )
        assert [f for _, f in many.curve_slice()] == [
            f for _, f in seq.curve_slice()
        ]

    def test_synchronous_replicas_identical_after_sync(self):
        trainer, _, _ = run_trainer("Synchronous")
        # step 40 with syncEvery 2 => last step synced; all replicas equal
        shards = trainer.shard_params()
        w0 = np.asarray(shards[0]["w"])
        for s in shards[1:]:
            np.testing.assert_allclose(np.asarray(s["w"]), w0, rtol=1e-5)

    def test_gm_skips_communication(self):
        loose, _, score_l = run_trainer("GM", extra={"threshold": 50.0})
        tight, _, _ = run_trainer("GM", extra={"threshold": 0.01})
        assert loose.sync_count() < tight.sync_count()
        assert loose.bytes_shipped() < tight.bytes_shipped()

    def test_fgm_safe_zone_fires(self):
        trainer, _, score = run_trainer("FGM", extra={"threshold": 0.1})
        assert trainer.sync_count() > 0
        assert score > 0.85

    def test_async_staggered_syncs(self):
        trainer, _, _ = run_trainer("Asynchronous")
        # every worker folded at least once over 40 steps at cadence 2
        syncs = trainer.host_stacked(trainer.state["syncs"])[:, 0]
        assert (syncs > 0).all()


class TestSPMDHubSharding:
    @pytest.mark.parametrize("hub", [2, 4])
    def test_sharded_ps_matches_semantics(self, hub):
        trainer, loss, score = run_trainer("Synchronous", hub=hub)
        assert score > 0.85
        # param vector padded to hub multiple; shard math consistent
        assert trainer.flat_size % hub == 0

    def test_hub_sharded_equals_unsharded(self):
        # same dp fleet (same data), PS sharded over 1 vs 2 hubs
        t1, _, s1 = run_trainer("Synchronous", hub=1, dp=4)
        t2, _, s2 = run_trainer("Synchronous", hub=2, dp=4)
        np.testing.assert_allclose(
            t1.global_flat_params(), t2.global_flat_params(), rtol=1e-4, atol=1e-5
        )


class TestSPMDWithPreprocessors:
    def test_scaler_pipeline(self):
        trainer, loss, score = run_trainer(
            "Synchronous", preps=("StandardScaler",)
        )
        assert score > 0.85


class TestSPMDRejects:
    def test_host_side_learner_rejected(self):
        with pytest.raises(ValueError):
            SPMDTrainer(LearnerSpec("HT"), dim=4, protocol="Synchronous",
                        mesh=make_mesh(dp=8))

    def test_unknown_protocol_rejected(self):
        with pytest.raises(ValueError):
            SPMDTrainer(LearnerSpec("PA"), dim=4, protocol="SingleLearner",
                        mesh=make_mesh(dp=8))


class TestSPMDNN:
    def test_mlp_data_parallel(self):
        """NN learner (the reference's DL4J case) under the SPMD engine."""
        trainer, loss, score = run_trainer(
            "Synchronous",
            steps=60,
            learner=LearnerSpec(
                "NN",
                hyper_parameters={"learningRate": 0.01},
                data_structure={"hiddenLayers": [16]},
            ),
        )
        assert score > 0.85


class TestAsyncSharedGlobal:
    @pytest.mark.parametrize("protocol", ["Asynchronous", "SSP", "EASGD"])
    def test_random_init_converges_to_shared_model(self, protocol):
        """The shared global / center must start identical across workers;
        with per-worker random NN inits the replicas must still converge
        (regression: center was seeded per-worker and never reconciled)."""
        trainer, loss, score = run_trainer(
            protocol,
            steps=40,
            extra={"syncEvery": 1},
            learner=LearnerSpec(
                "NN",
                hyper_parameters={"learningRate": 0.01},
                data_structure={"hiddenLayers": [8]},
            ),
        )
        # the center / shared global itself must be bit-identical on every
        # worker — its updates are pure collectives from an identical seed
        centers = trainer.host_stacked(trainer.state["center"])
        assert float(np.abs(centers - centers[:1]).max()) == 0.0
        shards = trainer.shard_params()
        flats = [
            np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(s)])
            for s in shards
        ]
        ref = flats[0]
        scale = max(float(np.linalg.norm(ref)), 1e-6)
        for f in flats[1:]:
            if protocol == "EASGD":
                # EASGD keeps replicas distinct but elastically bound
                assert float(np.linalg.norm(f - ref)) / scale < 1.0
            else:
                # async/SSP replicas adopt the shared global on their turn;
                # with syncEvery=1 every worker synced on the last step
                assert float(np.linalg.norm(f - ref)) / scale < 0.35


class TestBoundedStaleness:
    """True SSP on the device plane: per-worker clocks advance only on
    ticks with data; the staleness bound `fastest - slowest <= s` BINDS —
    a too-fast worker's batch is refused (state untouched, accepted=0) and
    the host requeues it. Ref: the SSPWorker/SSPParameterServer pair
    (MLNodeGenerator.scala) and the host plane's clock-tracked SSP
    (protocols/sync.py)."""

    def _trainer(self, protocol, s):
        mesh = make_mesh(dp=4, hub=1)
        tc = TrainingConfiguration(
            protocol=protocol,
            extra={"syncEvery": 1, "staleness": s},
        )
        return SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}),
            dim=6,
            protocol=protocol,
            mesh=mesh,
            training_configuration=tc,
            batch_size=16,
        )

    def _skewed_batch(self, dim=6, batch=16, seed=0):
        """Only worker 0 has data this tick."""
        rng = np.random.RandomState(seed)
        x = rng.randn(4, batch, dim).astype(np.float32)
        y = (x.sum(axis=2) > 0).astype(np.float32)
        m = np.zeros((4, batch), np.float32)
        m[0] = 1.0
        return x, y, m

    def test_ssp_bound_binds_under_skew(self):
        s = 2
        tr = self._trainer("SSP", s)
        for t in range(8):  # worker 0 alone receives 8 batches
            tr.step(*self._skewed_batch(seed=t), valid_count=16)
        clocks = tr.worker_clocks()
        # the bound stopped worker 0 at s; the excess batches were refused
        assert clocks[0] == s, clocks
        assert (clocks[1:] == 0).all(), clocks
        acc = tr.last_accepted()
        assert not acc[0]  # latest skewed batch was refused
        # refused steps must leave params untouched: refusal implies the
        # flag, and the fitted counter only moves via the host's accounting

    def test_ssp_catchup_releases_fast_worker(self):
        s = 2
        tr = self._trainer("SSP", s)
        for t in range(5):
            tr.step(*self._skewed_batch(seed=t), valid_count=16)
        assert tr.worker_clocks()[0] == s
        # now everyone gets data: slow workers advance; worker 0 is still
        # refused THIS tick (the bound reads clocks as of decision time)
        # and released on the next
        rng = np.random.RandomState(99)
        x = rng.randn(4, 16, 6).astype(np.float32)
        y = (x.sum(axis=2) > 0).astype(np.float32)
        m = np.ones((4, 16), np.float32)
        tr.step(x, y, m, valid_count=64)
        clocks = tr.worker_clocks()
        assert (clocks[1:] == 1).all(), clocks
        assert clocks[0] == s  # gap still == s at decision time
        assert not tr.last_accepted()[0]
        tr.step(x, y, m, valid_count=64)
        clocks = tr.worker_clocks()
        assert clocks[0] == s + 1  # within bound again -> consumed
        assert tr.last_accepted().all()

    def test_async_has_no_bound(self):
        """Asynchronous: the same skewed feed runs unbounded — the gap a
        bound-off run reaches is exactly the violation SSP prevents."""
        tr = self._trainer("Asynchronous", 2)
        for t in range(8):
            tr.step(*self._skewed_batch(seed=t), valid_count=16)
        clocks = tr.worker_clocks()
        assert clocks[0] == 8, clocks          # violation: gap 8 > s=2
        assert (clocks[1:] == 0).all(), clocks
        assert tr.last_accepted()[0]

    def test_ssp_refused_batch_leaves_params_untouched(self):
        s = 1
        tr = self._trainer("SSP", s)
        tr.step(*self._skewed_batch(seed=0), valid_count=16)  # clock 1, bound hit
        import jax as _jax

        before = _jax.device_get(tr.state["params"])
        tr.step(*self._skewed_batch(seed=1), valid_count=16)  # refused
        after = _jax.device_get(tr.state["params"])
        assert not tr.last_accepted()[0]
        for a, b in zip(
            _jax.tree_util.tree_leaves(before), _jax.tree_util.tree_leaves(after)
        ):
            np.testing.assert_array_equal(a, b)

    def test_bridge_requeues_refused_rows(self):
        """The streaming bridge repairs SSP refusals: refused rows re-enter
        the stage and fitted counts only consumed rows."""
        import json as _json

        from omldm_tpu.config import JobConfig
        from omldm_tpu.runtime import StreamJob
        from omldm_tpu.runtime.job import REQUEST_STREAM

        create = {
            "id": 0,
            "request": "Create",
            "learner": {
                "name": "Softmax",
                "hyperParameters": {"learningRate": 0.1, "nClasses": 2},
                "dataStructure": {"nFeatures": 6},
            },
            "preProcessors": [],
            "trainingConfiguration": {
                "protocol": "SSP",
                "engine": "spmd",
                "extra": {"syncEvery": 1, "staleness": 2},
            },
        }
        cfg = JobConfig(parallelism=4, batch_size=32, test=False)
        job = StreamJob(cfg)
        job.process_event(REQUEST_STREAM, _json.dumps(create))
        [bridge] = job.spmd_bridges.values()
        assert bridge._paced and bridge.chain == 1
        rng = np.random.RandomState(0)
        n = 3000
        x = rng.randn(n, 6).astype(np.float32)
        y = (x.sum(axis=1) > 0).astype(np.float32)
        job.process_packed_batch(x, y, np.zeros(n, np.uint8))
        bridge.flush()
        tr = bridge.trainer
        clocks = tr.worker_clocks()
        assert clocks.max() - clocks.min() <= 2, clocks
        # fitted never exceeds the rows offered
        assert tr.fitted <= n


class TestCollectiveByteAccounting:
    """bytesShipped from call-site counters (FlinkHub.scala:118-127 parity):
    the SPMD plane's accounting must agree with the host plane's measured
    message sizes on an equivalent synchronized run, and the GM/FGM control
    channel (per-step votes) must be counted."""

    def test_spmd_matches_host_plane_on_synchronized_run(self):
        import json as _json

        from omldm_tpu.config import JobConfig
        from omldm_tpu.runtime import StreamJob
        from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM

        dim, batch, sync_every, dp = 256, 32, 2, 4
        n = dp * batch * 40  # 40 fleet steps' worth of records
        rng = np.random.RandomState(0)
        w = rng.randn(dim)

        # host plane: 4 workers, batch 32, sync every 2 batches
        cfg = JobConfig(
            parallelism=dp, batch_size=batch, test_set_size=16, test=False
        )
        job = StreamJob(cfg)
        create = {
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
            "trainingConfiguration": {"protocol": "Synchronous",
                                      "syncEvery": sync_every},
        }
        job.process_event(REQUEST_STREAM, _json.dumps(create))
        x = rng.randn(n, dim)
        y = (x @ w > 0).astype(np.float64)
        for i in range(n):
            job.process_event(TRAINING_STREAM, _json.dumps({
                "numericalFeatures": list(np.round(x[i], 5)),
                "target": float(y[i]),
            }))
        host_stats = job.hub_manager.network_statistics(0)
        host_bytes = host_stats.bytes_shipped

        # SPMD plane: same dim/batch/cadence/steps
        mesh = make_mesh(dp=dp, hub=1)
        tc = TrainingConfiguration(
            protocol="Synchronous", extra={"syncEvery": sync_every}
        )
        tr = SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}),
            dim=dim, protocol="Synchronous", mesh=mesh,
            training_configuration=tc, batch_size=batch,
        )
        steps = n // (dp * batch)
        for t in range(steps):
            sl = slice(t * dp * batch, (t + 1) * dp * batch)
            xs = x[sl].reshape(dp, batch, dim).astype(np.float32)
            ys = y[sl].reshape(dp, batch).astype(np.float32)
            tr.step(xs, ys, np.ones((dp, batch), np.float32),
                    valid_count=dp * batch)
        spmd_bytes = tr.bytes_shipped()
        # both count: rounds x dp workers x (params up + global down).
        # The host plane's payloads add piggyback metadata (curve floats,
        # fitted counters); at dim=256 params dominate, so the planes must
        # agree closely.
        assert spmd_bytes > 0
        ratio = host_bytes / spmd_bytes
        assert 0.9 < ratio < 1.35, (host_bytes, spmd_bytes, ratio)
        # round counts agree exactly
        assert tr.sync_count() == steps // sync_every

    def test_gm_vote_channel_counted(self):
        """GM pays a tiny per-step vote even in silent rounds — the
        accounting must show traffic with ZERO parameter syncs."""
        mesh = make_mesh(dp=4, hub=1)
        tc = TrainingConfiguration(
            protocol="GM",
            extra={"syncEvery": 1, "threshold": 1e9},  # never violated
        )
        tr = SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}),
            dim=16, protocol="GM", mesh=mesh,
            training_configuration=tc, batch_size=8,
        )
        rng = np.random.RandomState(1)
        for _ in range(10):
            x = rng.randn(4, 8, 16).astype(np.float32)
            y = (x.sum(axis=2) > 0).astype(np.float32)
            tr.step(x, y, np.ones((4, 8), np.float32), valid_count=32)
        assert tr.sync_count() == 0          # communication skipped
        assert tr.bytes_shipped() == 10 * 4 * 2 * 4  # votes only
        assert tr.collective_bytes_physical() == tr.bytes_shipped()

    def test_async_fold_gating_physical_tracks_payload(self):
        """The Async fold allreduce is vote-gated (GM's pattern): steps
        where nobody folds ship only the 1-scalar vote, so physical bytes
        track logical folds — syncEvery x fewer param collectives than the
        previous lockstep-every-step traffic."""
        mesh = make_mesh(dp=4, hub=1)
        tc = TrainingConfiguration(
            protocol="Asynchronous", extra={"syncEvery": 2}
        )
        tr = SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}),
            dim=16, protocol="Asynchronous", mesh=mesh,
            training_configuration=tc, batch_size=8,
        )
        rng = np.random.RandomState(2)
        for _ in range(8):
            x = rng.randn(4, 8, 16).astype(np.float32)
            y = (x.sum(axis=2) > 0).astype(np.float32)
            tr.step(x, y, np.ones((4, 8), np.float32), valid_count=32)
        payload = tr.bytes_shipped()
        physical = tr.collective_bytes_physical()
        flat_b = 2 * tr.flat_size * 4
        votes = 8 * 4 * 2 * 4  # 1 scalar channel x 8 steps x 4 workers
        # all workers fold together every syncEvery steps: 4 fold rounds
        assert tr.sync_count() == 16
        assert payload == 16 * flat_b + votes
        assert physical == 4 * 4 * flat_b + votes
        # the gate saved syncEvery x vs the old lockstep per-step allreduce
        assert physical < 8 * 4 * flat_b

    def test_async_no_folds_ships_votes_only(self):
        """With a cadence longer than the run, the param collective never
        executes — physical traffic is the scalar vote channel alone."""
        mesh = make_mesh(dp=4, hub=1)
        tc = TrainingConfiguration(
            protocol="Asynchronous", extra={"syncEvery": 1000}
        )
        tr = SPMDTrainer(
            LearnerSpec("PA", hyper_parameters={"C": 1.0}),
            dim=16, protocol="Asynchronous", mesh=mesh,
            training_configuration=tc, batch_size=8,
        )
        rng = np.random.RandomState(3)
        for _ in range(6):
            x = rng.randn(4, 8, 16).astype(np.float32)
            y = (x.sum(axis=2) > 0).astype(np.float32)
            tr.step(x, y, np.ones((4, 8), np.float32), valid_count=32)
        assert tr.sync_count() == 0
        assert tr.collective_bytes_physical() == 6 * 4 * 2 * 4
        assert tr.bytes_shipped() == tr.collective_bytes_physical()
