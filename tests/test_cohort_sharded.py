"""Device-sharded cohort execution (runtime/cohort.py, ISSUE 9).

The cohort tenant axis lays across the forced 8-device host mesh
(conftest.py sets ``--xla_force_host_platform_device_count=8``) as a
``tenants`` shard_map axis. Pins, per the ISSUE 9 acceptance:

- shard count 1 resolves to the EXACT single-device cohort path (no mesh,
  no sharded programs) and is bitwise identical to it end to end;
- sharded gang execution (2 and 8 shards) is BIT-IDENTICAL to solo
  per-pipeline execution for every dense learner at the engine level, and
  sharded jobs are bitwise identical to cohort-off jobs at parallelism 1;
- members balance across shards; churn compacts within a shard (capacity
  unchanged — no recompile); capacity stays a multiple of the shard count;
- the composition matrix holds: sharded cohort x codec int8 x serving
  exact x guard armed, mid-stream churn, and rescale grow/shrink with
  shards active;
- the 6 parameter protocols stay inside the 0.05 score envelope at
  parallelism 2 with 8 shards;
- the tenant-mesh width gauge (Statistics.cohort_shards) and the
  serving-launch timing keys (launch_timing serve_*) are populated.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.config import JobConfig
from omldm_tpu.pipelines import MLPipeline
from omldm_tpu.runtime import StreamJob
from omldm_tpu.runtime.cohort import (
    Cohort,
    CohortEngine,
    resolve_cohort_shards,
)
from omldm_tpu.runtime.job import REQUEST_STREAM
from tests.test_cohort import _assert_job_bitwise

DIM = 8

DENSE_LEARNERS = [
    ("PA", {"C": 1.0}, False),
    ("PA", {"C": 1.0}, True),
    ("RegressorPA", {"C": 0.1, "epsilon": 0.1}, False),
    ("ORR", {"lambda": 1.0}, False),
    ("SVM", {}, False),
    ("MultiClassPA", {"C": 1.0, "nClasses": 3}, False),
    ("NN", {"hidden": 8}, False),
    ("Softmax", {"learningRate": 0.05, "nClasses": 2}, False),
]


class _Cfg:
    def __init__(self, cohort="on", cohort_min=1, cohort_impl="map",
                 cohort_shards="off"):
        self.cohort = cohort
        self.cohort_min = cohort_min
        self.cohort_impl = cohort_impl
        self.cohort_shards = cohort_shards


def _engine(**kw):
    return CohortEngine(_Cfg(**kw))


def _pipes(name, hp, per_record, n, dim=DIM):
    return [
        MLPipeline(
            LearnerSpec(name, hyper_parameters=hp),
            dim=dim,
            rng=jax.random.PRNGKey(11 + i),
            per_record=per_record,
        )
        for i in range(n)
    ]


def _batches(n, t, b, dim=DIM, seed=0):
    rng = np.random.RandomState(seed)
    w = np.random.RandomState(1).randn(dim)
    xs = rng.randn(n, t, b, dim).astype(np.float32)
    ys = (xs @ w > 0).astype(np.float32)
    ms = np.ones((n, t, b), np.float32)
    return xs, ys, ms


def _assert_tree_equal(a, b, msg=""):
    for la, lb in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), msg)


# --- shard resolution --------------------------------------------------------


class TestShardResolution:
    def test_off_and_one_are_single_device(self):
        assert resolve_cohort_shards(_Cfg(cohort_shards="off")) == 1
        assert resolve_cohort_shards(_Cfg(cohort_shards="")) == 1
        assert resolve_cohort_shards(_Cfg(cohort_shards="1")) == 1

    def test_auto_takes_pow2_of_mesh(self):
        assert resolve_cohort_shards(_Cfg(cohort_shards="auto")) == 8

    def test_integer_clamps_and_floors_pow2(self):
        assert resolve_cohort_shards(_Cfg(cohort_shards="64")) == 8
        assert resolve_cohort_shards(_Cfg(cohort_shards="5")) == 4
        assert resolve_cohort_shards(_Cfg(cohort_shards="2")) == 2

    def test_unrecognized_spelling_degrades_to_single_device(self):
        """Misconfigured knob must not kill the job — same degrade-to-
        default policy as the sibling cohort/cohort_impl fields."""
        assert resolve_cohort_shards(_Cfg(cohort_shards="on")) == 1
        assert resolve_cohort_shards(_Cfg(cohort_shards="banana")) == 1

    def test_shard_count_one_builds_no_mesh(self):
        """The PR6 single-device path is the shards=1 path verbatim: no
        mesh object, no sharding constraint anywhere."""
        engine = _engine(cohort_shards="1")
        p = _pipes("PA", {"C": 1.0}, False, 1)[0]
        engine.consider(p)
        cohort = p._cohort
        assert engine.n_shards == 1
        assert cohort._mesh is None and cohort._sharding is None


# --- engine-level bit-identity, sharded vs solo ------------------------------


class TestShardedBitIdentity:
    @pytest.mark.parametrize("name,hp,per_record", DENSE_LEARNERS)
    @pytest.mark.parametrize("shards", ["2", "8"])
    def test_sharded_gang_fit_matches_solo(self, name, hp, per_record,
                                           shards):
        """Members are independent, so the per-member math under the
        sharded launch is the SAME program: params, losses, predictions
        and flat params all bitwise equal to detached solo execution —
        including ragged staging depths across members."""
        n, t, b = 5, 2, 16
        solo = _pipes(name, hp, per_record, n)
        gang = _pipes(name, hp, per_record, n)
        engine = _engine(cohort_shards=shards)
        for p in gang:
            engine.consider(p)
        cohort = gang[0]._cohort
        assert cohort.n_shards == int(shards)
        assert cohort.capacity % cohort.n_shards == 0

        xs, ys, ms = _batches(n, t, b)
        ms[n - 1, 1:] = 0.0  # ragged depth for the last member
        losses_solo, losses_gang = [], []
        for i in range(n):
            t_i = 1 if i == n - 1 else t
            for ti in range(t_i):
                losses_solo.append(
                    float(solo[i].fit(xs[i, ti], ys[i, ti], ms[i, ti]))
                )
        for i in range(n):
            t_i = 1 if i == n - 1 else t
            for ti in range(t_i):
                losses_gang.append(
                    gang[i].fit(xs[i, ti], ys[i, ti], ms[i, ti])
                )
        engine.flush()
        assert [float(l) for l in losses_gang] == losses_solo
        xq = np.random.RandomState(9).randn(8, DIM).astype(np.float32)
        for i in range(n):
            _assert_tree_equal(solo[i].state, gang[i].state, f"member {i}")
            np.testing.assert_array_equal(
                np.asarray(solo[i].predict(xq)),
                np.asarray(gang[i].predict(xq)),
            )
            fa, _ = solo[i].get_flat_params()
            fb, _ = gang[i].get_flat_params()
            np.testing.assert_array_equal(fa, fb)

    def test_flat_writes_scatter_back_sharded(self):
        pipes = _pipes("PA", {"C": 1.0}, False, 6)
        engine = _engine(cohort_shards="8")
        for p in pipes:
            engine.consider(p)
        new = [p.get_flat_params()[0] * 2.0 + 1.0 for p in pipes]
        for p, r in zip(pipes, new):
            p.set_flat_params(r)
        for p, r in zip(pipes, new):
            np.testing.assert_array_equal(p.get_flat_params()[0], r)
        # and the scattered rows feed the next sharded launch
        xs, ys, ms = _batches(6, 1, 16)
        for i, p in enumerate(pipes):
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
        engine.flush()
        solo = _pipes("PA", {"C": 1.0}, False, 6)
        for i, p in enumerate(solo):
            p.set_flat_params(new[i])
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
            np.testing.assert_array_equal(
                p.get_flat_params()[0], pipes[i].get_flat_params()[0]
            )

    def test_state_checkout_mutation_lands_sharded(self):
        pipes = _pipes("PA", {"C": 1.0}, False, 3)
        engine = _engine(cohort_shards="2")
        for p in pipes:
            engine.consider(p)
        xs, ys, ms = _batches(3, 1, 16)
        for i, p in enumerate(pipes):
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
        engine.flush()
        sib_before, _ = pipes[1].get_flat_params()
        st = pipes[0].state
        st["params"] = jax.tree_util.tree_map(
            lambda l: l * 0.0, st["params"]
        )
        flat, _ = pipes[0].get_flat_params()
        np.testing.assert_array_equal(flat, np.zeros_like(flat))
        sib, _ = pipes[1].get_flat_params()
        np.testing.assert_array_equal(sib, sib_before)
        assert np.any(sib != 0.0)


# --- placement, balance and churn --------------------------------------------


class TestShardPlacement:
    def test_members_balance_across_shards(self):
        pipes = _pipes("PA", {"C": 1.0}, False, 8)
        engine = _engine(cohort_shards="4")
        for p in pipes:
            engine.consider(p)
        cohort = pipes[0]._cohort
        assert cohort.capacity == 8  # multiple of 4, pow2 bucket
        assert cohort.shard_placement() == [2, 2, 2, 2]

    def test_churn_compacts_within_least_loaded_shard(self):
        pipes = _pipes("PA", {"C": 1.0}, False, 8)
        engine = _engine(cohort_shards="4")
        for p in pipes:
            engine.consider(p)
        cohort = pipes[0]._cohort
        victim = pipes[3]
        victim_shard = cohort._shard_of(victim._slot)
        engine.retire(victim)
        assert cohort.shard_placement()[victim_shard] == 1
        late = _pipes("PA", {"C": 1.0}, False, 1)[0]
        engine.consider(late)
        # the freed slot on the least-loaded shard is reused: capacity
        # unchanged (no recompile), balance restored
        assert cohort.capacity == 8
        assert cohort._shard_of(late._slot) == victim_shard
        assert cohort.shard_placement() == [2, 2, 2, 2]

    def test_growth_keeps_shard_multiple(self):
        pipes = _pipes("PA", {"C": 1.0}, False, 9)
        engine = _engine(cohort_shards="4")
        for p in pipes:
            engine.consider(p)
        cohort = pipes[0]._cohort
        assert cohort.capacity == 16
        assert cohort.capacity % 4 == 0
        assert sorted(cohort.shard_placement(), reverse=True) == [3, 2, 2, 2]
        # survivors keep training bitwise after the grow reshard
        solo = _pipes("PA", {"C": 1.0}, False, 9)
        xs, ys, ms = _batches(9, 1, 16)
        for i in range(9):
            pipes[i].fit(xs[i, 0], ys[i, 0], ms[i, 0])
            solo[i].fit(xs[i, 0], ys[i, 0], ms[i, 0])
        engine.flush()
        for i in range(9):
            _assert_tree_equal(solo[i].state, pipes[i].state, f"member {i}")


# --- job-level composition matrix --------------------------------------------


def _mt_job(cohort, n_pipe, records, protocol="Asynchronous", test=True,
            parallelism=1, learner=None, tc_extra=None, chaos="",
            cohort_shards="off", serving=""):
    cfg = JobConfig(
        parallelism=parallelism, batch_size=32, test_set_size=32,
        cohort=cohort, cohort_min=2, chaos=chaos,
        cohort_shards=cohort_shards, serving=serving,
    )
    job = StreamJob(cfg)
    job.config.test = test
    learner = learner or {"name": "PA", "hyperParameters": {"C": 1.0}}
    for pid in range(n_pipe):
        tc = {"protocol": protocol, "syncEvery": 4}
        if tc_extra:
            tc.update(tc_extra)
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": pid, "request": "Create",
            "learner": {**learner, "dataStructure": {"nFeatures": DIM}},
            "trainingConfiguration": tc,
        }))
    rng = np.random.RandomState(3)
    w = np.random.RandomState(5).randn(DIM)
    x = rng.randn(records, DIM).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    op = np.zeros((records,), np.uint8)
    op[::61] = 1
    for i in range(0, records, 256):
        job.process_packed_batch(x[i:i+256], y[i:i+256], op[i:i+256])
    report = job.terminate()
    preds = {}
    for p in job.predictions:
        preds.setdefault(p.mlp_id, []).append(p.value)
    return job, report, preds


class TestShardedJobBitIdentity:
    @pytest.mark.parametrize("test", [True, False])
    def test_sharded_job_bitwise_vs_cohort_off(self, test):
        """Both serving modes: test=True (holdout harness, per-member
        staging) and test=False (production mode — the SHARED-ingest fast
        path, whose one-[T,B,D]-input program broadcasts in-program on
        every shard)."""
        off = _mt_job("off", 6, 2000, test=test)
        sh = _mt_job("on", 6, 2000, cohort_shards="8", test=test)
        _assert_job_bitwise(off, sh)

    def test_shard_count_one_bitwise_vs_single_device_cohort(self):
        """ISSUE 9 acceptance: shards=1 is bitwise the PR6 cohort path."""
        base = _mt_job("on", 6, 2000)
        one = _mt_job("on", 6, 2000, cohort_shards="1")
        _assert_job_bitwise(base, one)

    def test_sharded_serving_exact_bitwise(self):
        off = _mt_job("off", 4, 1600, serving="on")
        sh = _mt_job("on", 4, 1600, cohort_shards="8", serving="on")
        _assert_job_bitwise(off, sh)

    def test_mesh_width_gauge_and_serve_timing(self):
        job, report, _ = _mt_job(
            "on", 4, 1200, cohort_shards="8", serving="on"
        )
        for s in report.statistics:
            assert s.cohort_shards == 8
            assert "cohortShards" in s.to_dict()
        timing = job.launch_timing()
        assert timing["count"] > 0
        assert timing["serve_count"] > 0
        assert timing["serve_p50_ms"] >= 0.0
        topo = job.tenant_topology()
        assert topo["cohort_shards"] == 8
        assert topo["placement"] and all(
            sum(p) > 0 for p in topo["placement"]
        )

    def test_unsharded_job_reports_zero_gauge(self):
        _, report, _ = _mt_job("on", 3, 600)
        for s in report.statistics:
            assert s.cohort_shards == 0

    def test_never_cohorted_pipeline_reports_zero_gauge(self):
        """Sharding configured but never engaged (auto pool below
        cohort_min): the gauge must stay 0 — it records the ACTUAL mesh
        width the pipeline's launches ran across, not the config."""
        cfg = JobConfig(parallelism=1, batch_size=32, test_set_size=32,
                        cohort="auto", cohort_min=8, cohort_shards="auto")
        job = StreamJob(cfg)
        for pid in range(2):  # below the auto threshold: pooled, solo
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": pid, "request": "Create",
                "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
        rng = np.random.RandomState(3)
        x = rng.randn(512, DIM).astype(np.float32)
        y = np.ones((512,), np.float32)
        job.process_packed_batch(x, y, np.zeros((512,), np.uint8))
        report = job.terminate()
        for s in report.statistics:
            assert s.cohort_shards == 0


class TestShardedComposition:
    def test_sharded_codec_serving_guard_bitwise_vs_off(self):
        """The full composition cell: sharded cohort x int8 codec x exact
        serving x armed guard, bitwise against the same stack cohort-off
        at parallelism 1."""
        extra = {"comm": {"codec": "int8"}, "guard": True}
        off = _mt_job("off", 4, 1600, tc_extra=extra, serving="on")
        sh = _mt_job(
            "on", 4, 1600, tc_extra=extra, serving="on", cohort_shards="8"
        )
        _assert_job_bitwise(off, sh)

    def test_sharded_churn_mid_stream(self):
        """Create/Delete/Update churn against a live SHARDED cohort:
        survivors bitwise vs the cohort-off run of the same events."""
        def run(cohort, shards):
            cfg = JobConfig(parallelism=1, batch_size=16, test_set_size=16,
                            cohort=cohort, cohort_min=2,
                            cohort_shards=shards)
            job = StreamJob(cfg)
            rng = np.random.RandomState(7)
            w = np.random.RandomState(5).randn(DIM)
            x = rng.randn(1500, DIM).astype(np.float32)
            y = (x @ w > 0).astype(np.float32)
            op = np.zeros((1500,), np.uint8)

            def create(pid):
                job.process_event(REQUEST_STREAM, json.dumps({
                    "id": pid, "request": "Create",
                    "learner": {"name": "PA",
                                "hyperParameters": {"C": 1.0},
                                "dataStructure": {"nFeatures": DIM}},
                    "trainingConfiguration": {"protocol": "Asynchronous"},
                }))

            for pid in range(3):
                create(pid)
            job.process_packed_batch(x[:500], y[:500], op[:500])
            create(3)
            job.process_packed_batch(x[500:800], y[500:800], op[500:800])
            job.process_event(REQUEST_STREAM, json.dumps(
                {"id": 1, "request": "Delete"}))
            job.process_packed_batch(x[800:1100], y[800:1100], op[800:1100])
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": 2, "request": "Update",
                "learner": {"name": "PA", "hyperParameters": {"C": 0.5},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
            job.process_packed_batch(x[1100:], y[1100:], op[1100:])
            report = job.terminate()
            return {s.pipeline: (s.score, s.fitted, tuple(s.learning_curve))
                    for s in report.statistics}

        assert run("off", "off") == run("on", "8")

    def test_rescale_grow_shrink_with_shards(self):
        cfg = JobConfig(parallelism=2, batch_size=16, test_set_size=16,
                        cohort="on", cohort_min=1, cohort_shards="8")
        job = StreamJob(cfg)
        for pid in range(3):
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": pid, "request": "Create",
                "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
        rng = np.random.RandomState(3)
        w = np.random.RandomState(5).randn(DIM)
        x = rng.randn(3072, DIM).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        op = np.zeros((3072,), np.uint8)
        for i in range(0, 1024, 256):
            job.process_packed_batch(x[i:i+256], y[i:i+256], op[i:i+256])
        job.rescale(4)
        for spoke in job.spokes:
            for net in spoke.nets.values():
                assert net.pipeline._cohort is not None
                assert net.pipeline._cohort.n_shards == 8
        for i in range(1024, 2048, 256):
            job.process_packed_batch(x[i:i+256], y[i:i+256], op[i:i+256])
        job.rescale(1)
        for i in range(2048, 3072, 256):
            job.process_packed_batch(x[i:i+256], y[i:i+256], op[i:i+256])
        report = job.terminate()
        assert len(report.statistics) == 3
        for s in report.statistics:
            assert s.score > 0.8
            assert s.fitted > 0


class TestShardedProtocolParity:
    """At parallelism 2 the gang schedule differs from the sequential
    path (same caveat as PR6's TestMultiWorkerParity), so the sharded
    runs pin the 0.05 convergence envelope, not bit-identity."""

    @pytest.mark.parametrize(
        "protocol",
        ["Asynchronous", "Synchronous", "SSP", "EASGD", "GM", "FGM"],
    )
    def test_score_parity_at_8_shards(self, protocol):
        off = _mt_job("off", 3, 2000, protocol=protocol, parallelism=2)
        sh = _mt_job("on", 3, 2000, protocol=protocol, parallelism=2,
                     cohort_shards="8")
        s_off = {s.pipeline: s.score for s in off[1].statistics}
        s_sh = {s.pipeline: s.score for s in sh[1].statistics}
        for pid in s_off:
            assert abs(s_off[pid] - s_sh[pid]) <= 0.05, (
                f"{protocol} pid {pid}: {s_off[pid]} vs {s_sh[pid]}"
            )
        assert {k: len(v) for k, v in off[2].items()} == \
               {k: len(v) for k, v in sh[2].items()}
