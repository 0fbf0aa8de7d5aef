"""Cohort execution engine (runtime/cohort.py): gang-scheduled
multi-pipeline co-hosting.

Pins, per ISSUE 6 acceptance:

- cohort-OFF jobs run the exact pre-cohort code path (no engine, no gang
  objects anywhere);
- cohort-ON execution is BIT-IDENTICAL to per-pipeline execution for every
  dense learner — at the engine level (stage+launch vs direct fit /
  predict / flat params) and end-to-end for multi-tenant jobs (the
  cohort-off job is the per-pipeline reference);
- membership churn (Create/Delete/Update) compacts slots without
  perturbing surviving members; rescale grow/shrink works with cohorts
  active; cohort + codec + reliable-transport compose;
- the bounded `_JIT_CACHE` LRU stays bounded under create/delete churn;
- `programLaunches` counts host-plane program launches (and collapses
  under gang dispatch);
- the strided liveness walk still retires silent workers off records.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.config import JobConfig
from omldm_tpu.pipelines import MLPipeline
from omldm_tpu.pipelines.pipeline import _JIT_CACHE
from omldm_tpu.runtime import StreamJob
from omldm_tpu.runtime.cohort import Cohort, CohortEngine
from omldm_tpu.runtime.job import (
    FORECASTING_STREAM,
    REQUEST_STREAM,
    TRAINING_STREAM,
)

DIM = 8

# every dense (device-side) learner spec: HT is host-side, K-means params
# carry int counts (flat dtype != f32) — both stay per-pipeline by design
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


class _Cfg:
    """Minimal config stub for CohortEngine construction in unit tests."""

    def __init__(self, cohort="on", cohort_min=1, cohort_impl="map"):
        self.cohort = cohort
        self.cohort_min = cohort_min
        self.cohort_impl = cohort_impl


def _engine(**kw):
    return CohortEngine(_Cfg(**kw))


# --- engine-level bit-identity across every dense learner --------------------


class TestGangBitIdentity:
    @pytest.mark.parametrize("name,hp,per_record", DENSE_LEARNERS)
    def test_staged_gang_fit_matches_solo_fit(self, name, hp, per_record):
        """N attached pipelines staged+launched == N detached pipelines
        fit directly: params, losses, predictions, flat params all
        BITWISE equal (the map-based gang program is the same fit_impl)."""
        n, t, b = 3, 2, 16
        solo = _pipes(name, hp, per_record, n)
        gang = _pipes(name, hp, per_record, n)
        engine = _engine()
        for p in gang:
            engine.consider(p)
        assert all(p._cohort is not None for p in gang)
        cohort = gang[0]._cohort
        assert cohort is gang[-1]._cohort

        xs, ys, ms = _batches(n, t, b)
        ms[n - 1, 1:] = 0.0  # ragged staging depth for the last member
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
            assert solo[i].fitted == gang[i].fitted

    def test_gang_flat_roundtrip_and_writes(self):
        """member_flat reads one shared launch; set_flat_params scatters
        back bitwise (the batched unravel + scatter path)."""
        pipes = _pipes("PA", {"C": 1.0}, False, 4)
        engine = _engine()
        for p in pipes:
            engine.consider(p)
        ref = [p.get_flat_params()[0] for p in pipes]
        new = [r * 2.0 + 1.0 for r in ref]
        for p, r in zip(pipes, new):
            p.set_flat_params(r)
        for p, r in zip(pipes, new):
            np.testing.assert_array_equal(p.get_flat_params()[0], r)
        # and the scattered state is what the next fit consumes
        xs, ys, ms = _batches(4, 1, 16)
        for i, p in enumerate(pipes):
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
        engine.flush()
        solo = _pipes("PA", {"C": 1.0}, False, 4)
        for i, p in enumerate(solo):
            p.set_flat_params(new[i])
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
            np.testing.assert_array_equal(
                p.get_flat_params()[0], pipes[i].get_flat_params()[0]
            )

    def test_state_checkout_mutation_lands(self):
        """In-place edits of `pipeline.state` (checkpoint restore path)
        reach the stacked tree before the next launch."""
        pipes = _pipes("PA", {"C": 1.0}, False, 2)
        engine = _engine()
        for p in pipes:
            engine.consider(p)
        # train both so params are nonzero (PA initializes at zero)
        xs, ys, ms = _batches(2, 1, 16)
        for i, p in enumerate(pipes):
            p.fit(xs[i, 0], ys[i, 0], ms[i, 0])
        engine.flush()
        sib_before, _ = pipes[1].get_flat_params()
        st = pipes[0].state
        st["params"] = jax.tree_util.tree_map(lambda l: l * 0.0, st["params"])
        flat, _ = pipes[0].get_flat_params()
        np.testing.assert_array_equal(flat, np.zeros_like(flat))
        # the sibling is untouched
        sib, _ = pipes[1].get_flat_params()
        np.testing.assert_array_equal(sib, sib_before)
        assert np.any(sib != 0.0)


# --- membership churn --------------------------------------------------------


class TestCohortChurn:
    def test_detach_preserves_survivors_bitwise(self):
        n = 5
        gang = _pipes("PA", {"C": 1.0}, False, n)
        solo = _pipes("PA", {"C": 1.0}, False, n)
        engine = _engine()
        for p in gang:
            engine.consider(p)
        cohort = gang[0]._cohort
        xs, ys, ms = _batches(n, 4, 16)
        for t in range(2):
            for i in range(n):
                gang[i].fit(xs[i, t], ys[i, t], ms[i, t])
                solo[i].fit(xs[i, t], ys[i, t], ms[i, t])
            engine.flush()
        # detach the middle member mid-stream; its slot frees for reuse
        engine.retire(gang[2])
        assert gang[2]._cohort is None
        freed = cohort.n_active
        late = _pipes("PA", {"C": 1.0}, False, 1)[0]
        engine.consider(late)
        assert cohort.n_active == freed + 1
        for t in range(2, 4):
            for i in range(n):
                gang[i].fit(xs[i, t], ys[i, t], ms[i, t])
                solo[i].fit(xs[i, t], ys[i, t], ms[i, t])
            engine.flush()
        for i in range(n):
            _assert_tree_equal(solo[i].state, gang[i].state, f"member {i}")

    def test_capacity_buckets_and_slot_reuse(self):
        engine = _engine()
        pipes = _pipes("PA", {"C": 1.0}, False, 5)
        for p in pipes:
            engine.consider(p)
        cohort = pipes[0]._cohort
        assert cohort.capacity == 8  # pow2 bucket
        engine.retire(pipes[1])
        engine.retire(pipes[3])
        assert cohort.n_active == 3
        p6 = _pipes("PA", {"C": 1.0}, False, 1)[0]
        engine.consider(p6)
        # churn compacts: the freed slot is reused, capacity unchanged
        assert cohort.capacity == 8
        assert p6._slot in (1, 3)

    def test_empty_cohort_is_dropped(self):
        engine = _engine()
        pipes = _pipes("PA", {"C": 1.0}, False, 2)
        for p in pipes:
            engine.consider(p)
        for p in pipes:
            engine.retire(p)
        assert not engine.cohorts

    def test_auto_threshold(self):
        engine = CohortEngine(_Cfg(cohort="auto", cohort_min=3))
        pipes = _pipes("PA", {"C": 1.0}, False, 3)
        engine.consider(pipes[0])
        engine.consider(pipes[1])
        assert pipes[0]._cohort is None  # below the threshold: pooled
        engine.consider(pipes[2])
        assert all(p._cohort is not None for p in pipes)

    def test_ineligible_learners_stay_solo(self):
        engine = _engine()
        ht = MLPipeline(LearnerSpec("HT"), dim=DIM)
        engine.consider(ht)
        assert ht._cohort is None
        km = MLPipeline(
            LearnerSpec("K-means", hyper_parameters={"k": 2}), dim=DIM
        )
        engine.consider(km)
        assert km._cohort is None


# --- job-level: multi-tenant cohort-on == cohort-off -------------------------


def _mt_job(cohort, n_pipe, records, protocol="Asynchronous", test=True,
            parallelism=1, learner=None, tc_extra=None, chaos=""):
    cfg = JobConfig(
        parallelism=parallelism, batch_size=32, test_set_size=32,
        cohort=cohort, cohort_min=2, chaos=chaos,
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


# A learning_curve point is a float32 mean of one batch's losses. The gang
# program (vmapped, or sharded over a mesh) and the solo program are two XLA
# programs, and XLA's CPU backend does not promise two programs the same
# order of a reduction, on one machine or across machines: read on two hosts
# (PR 31), 10 to 30% of the points sit 1 to 2 ulp apart while score, fitted,
# lcx, every prediction and every final weight are equal. So the curve is
# held to a few ulp, and everything else stays exact.
CURVE_MAX_ULP = 4


def _final_weights(job):
    return {
        net_id: np.asarray(net.pipeline.get_flat_params()[0])
        for spoke in job.spokes
        for net_id, net in spoke.nets.items()
    }


def _assert_job_bitwise(off, on):
    j_off, r_off, p_off = off
    j_on, r_on, p_on = on
    s_off = {s.pipeline: s for s in r_off.statistics}
    s_on = {s.pipeline: s for s in r_on.statistics}
    assert s_off.keys() == s_on.keys()
    for pid, a in s_off.items():
        b = s_on[pid]
        assert a.score == b.score, f"pid {pid} score"
        assert a.fitted == b.fitted, f"pid {pid} fitted"
        assert a.lcx == b.lcx, f"pid {pid} lcx"
        assert len(a.learning_curve) == len(b.learning_curve), f"pid {pid}"
        np.testing.assert_array_max_ulp(
            np.asarray(a.learning_curve, np.float32),
            np.asarray(b.learning_curve, np.float32),
            maxulp=CURVE_MAX_ULP,
        )
    assert p_off == p_on
    w_off, w_on = _final_weights(j_off), _final_weights(j_on)
    assert w_off.keys() == w_on.keys()
    for net_id, w in w_off.items():
        np.testing.assert_array_equal(w, w_on[net_id], f"net {net_id} weights")


class TestMultiTenantBitIdentity:
    """Multi-tenant serving jobs (parallelism 1 — the CentralizedTraining
    route with no mid-stream hub replies): cohort-on is bit-identical to
    the per-pipeline job, for every dense learner, with and without the
    holdout/test harness (the shared-ingest fast path)."""

    @pytest.mark.parametrize("name,hp,per_record", DENSE_LEARNERS)
    def test_bitwise_all_dense_learners(self, name, hp, per_record):
        learner = {"name": name, "hyperParameters": hp}
        tc = {"perRecord": True} if per_record else None
        off = _mt_job("off", 4, 1200, learner=learner, tc_extra=tc)
        on = _mt_job("on", 4, 1200, learner=learner, tc_extra=tc)
        _assert_job_bitwise(off, on)

    @pytest.mark.parametrize("test", [True, False])
    def test_bitwise_serving_modes(self, test):
        off = _mt_job("off", 6, 2000, test=test)
        on = _mt_job("on", 6, 2000, test=test)
        _assert_job_bitwise(off, on)
        # the whole point: gang dispatch collapses program launches
        pl_off = sum(s.program_launches for s in off[1].statistics)
        pl_on = sum(s.program_launches for s in on[1].statistics)
        assert 0 < pl_on < pl_off / 2

    def test_per_record_stream_bitwise(self):
        """The per-record route (handle_data incl. gang forecast serving)."""
        def run(cohort):
            cfg = JobConfig(parallelism=1, batch_size=16, test_set_size=16,
                            cohort=cohort, cohort_min=2)
            job = StreamJob(cfg)
            for pid in range(3):
                job.process_event(REQUEST_STREAM, json.dumps({
                    "id": pid, "request": "Create",
                    "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                                "dataStructure": {"nFeatures": DIM}},
                    "trainingConfiguration": {"protocol": "Asynchronous"},
                }))
            rng = np.random.RandomState(2)
            w = np.random.RandomState(5).randn(DIM)
            for i in range(600):
                feats = rng.randn(DIM).astype(np.float32)
                if i % 53 == 0:
                    job.process_event(FORECASTING_STREAM, json.dumps(
                        {"numericalFeatures": feats.tolist()}))
                else:
                    job.process_event(TRAINING_STREAM, json.dumps(
                        {"numericalFeatures": feats.tolist(),
                         "target": float(feats @ w > 0)}))
            report = job.terminate()
            preds = [(p.mlp_id, p.value) for p in job.predictions]
            return report, preds

        r_off, p_off = run("off")
        r_on, p_on = run("on")
        assert p_off == p_on
        a = {s.pipeline: (s.score, s.fitted, tuple(s.learning_curve))
             for s in r_off.statistics}
        b = {s.pipeline: (s.score, s.fitted, tuple(s.learning_curve))
             for s in r_on.statistics}
        assert a == b

    def test_churn_mid_stream_does_not_perturb_survivors(self):
        """Create/Delete/Update joining and leaving a cohort mid-stream:
        the surviving members' results stay bitwise equal to the
        cohort-off run of the same event sequence."""
        def run(cohort):
            cfg = JobConfig(parallelism=1, batch_size=16, test_set_size=16,
                            cohort=cohort, cohort_min=2)
            job = StreamJob(cfg)
            rng = np.random.RandomState(7)
            w = np.random.RandomState(5).randn(DIM)
            x = rng.randn(1500, DIM).astype(np.float32)
            y = (x @ w > 0).astype(np.float32)
            op = np.zeros((1500,), np.uint8)

            def create(pid):
                job.process_event(REQUEST_STREAM, json.dumps({
                    "id": pid, "request": "Create",
                    "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                                "dataStructure": {"nFeatures": DIM}},
                    "trainingConfiguration": {"protocol": "Asynchronous"},
                }))

            for pid in range(3):
                create(pid)
            job.process_packed_batch(x[:500], y[:500], op[:500])
            create(3)  # joins the live cohort
            job.process_packed_batch(x[500:800], y[500:800], op[500:800])
            job.process_event(REQUEST_STREAM, json.dumps(
                {"id": 1, "request": "Delete"}))  # leaves mid-stream
            job.process_packed_batch(x[800:1100], y[800:1100], op[800:1100])
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": 2, "request": "Update",
                "learner": {"name": "PA", "hyperParameters": {"C": 0.5},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
            job.process_packed_batch(x[1100:], y[1100:], op[1100:])
            return job.terminate()

        r_off = run("off")
        r_on = run("on")
        a = {s.pipeline: (s.score, s.fitted, tuple(s.learning_curve))
             for s in r_off.statistics}
        b = {s.pipeline: (s.score, s.fitted, tuple(s.learning_curve))
             for s in r_on.statistics}
        assert a == b


# --- multi-worker protocols: convergence parity ------------------------------


class TestMultiWorkerParity:
    """At parallelism > 1 the gang replaces the cooperative pause-toggle
    time slicing, so stream partitioning into batches differs from the
    sequential path — pinned here: every protocol still converges to the
    same quality (the reference makes no cross-pipeline scheduling
    guarantee either; Flink rebalance order is nondeterministic)."""

    @pytest.mark.parametrize(
        "protocol", ["Asynchronous", "Synchronous", "SSP", "EASGD", "GM", "FGM"]
    )
    def test_score_parity(self, protocol):
        off = _mt_job("off", 3, 2000, protocol=protocol, parallelism=2)
        on = _mt_job("on", 3, 2000, protocol=protocol, parallelism=2)
        s_off = {s.pipeline: s.score for s in off[1].statistics}
        s_on = {s.pipeline: s.score for s in on[1].statistics}
        for pid in s_off:
            assert abs(s_off[pid] - s_on[pid]) <= 0.05, (
                f"{protocol} pid {pid}: {s_off[pid]} vs {s_on[pid]}"
            )
        # forecasts all served in both schedules
        assert {k: len(v) for k, v in off[2].items()} == \
               {k: len(v) for k, v in on[2].items()}


# --- rescale with cohorts active ---------------------------------------------


class TestRescaleWithCohorts:
    def _job(self, n_pipe=3, parallelism=2):
        cfg = JobConfig(parallelism=parallelism, batch_size=16,
                        test_set_size=16, cohort="on", cohort_min=1)
        job = StreamJob(cfg)
        for pid in range(n_pipe):
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": pid, "request": "Create",
                "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
        return job

    def _stream(self, job, lo, hi, seed=3):
        rng = np.random.RandomState(seed)
        w = np.random.RandomState(5).randn(DIM)
        x = rng.randn(hi, DIM).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        op = np.zeros((hi,), np.uint8)
        for i in range(lo, hi, 256):
            job.process_packed_batch(x[i:i+256], y[i:i+256], op[i:i+256])

    def test_grow_then_shrink(self):
        job = self._job()
        self._stream(job, 0, 1024)
        job.rescale(4)   # new spokes host + cohort the live pipelines
        for spoke in job.spokes:
            assert spoke.cohorts is not None
            for net in spoke.nets.values():
                assert net.pipeline._cohort is not None
        self._stream(job, 1024, 2048)
        job.rescale(1)   # retiring spokes dissolve cohorts and merge in
        self._stream(job, 2048, 3072)
        report = job.terminate()
        assert len(report.statistics) == 3
        for s in report.statistics:
            assert s.score > 0.8
            assert s.fitted > 0

    def test_shrink_marks_shared_taint(self):
        job = self._job()
        self._stream(job, 0, 512)
        job.rescale(1)
        for net in job.spokes[0].nets.values():
            assert net.shared_taint


# --- composition: cohort + codec + reliable transport ------------------------


class TestCohortComposition:
    def test_cohort_codec_chaos_smoke(self):
        """Cohorts + int8 transport codec + seeded chaos (which arms the
        reliable channel): the job converges and the resilience plane
        engaged."""
        chaos = "seed=7,drop=0.03,dup=0.1,reorder=0.1,window=4"
        job, report, _ = _mt_job(
            "on", 3, 3000, protocol="Synchronous", parallelism=2,
            tc_extra={"comm": {"codec": "int8"}}, chaos=chaos,
        )
        for s in report.statistics:
            assert s.score > 0.75
            assert s.bytes_on_wire > 0
        total_dup = sum(s.duplicates_dropped for s in report.statistics)
        assert total_dup > 0, "reliable channel never engaged under chaos"

    def test_cohort_with_codec_bitwise_vs_off_at_par1(self):
        off = _mt_job("off", 3, 1200, tc_extra={"comm": {"codec": "int8"}})
        on = _mt_job("on", 3, 1200, tc_extra={"comm": {"codec": "int8"}})
        _assert_job_bitwise(off, on)


# --- satellites --------------------------------------------------------------


class TestJitCacheLRU:
    def test_churn_keeps_cache_bounded(self):
        """A long Create/Delete churn over varying dims must not grow the
        jit cache without bound (it was an unbounded dict)."""
        start = len(_JIT_CACHE)
        for i in range(_JIT_CACHE.cap + 40):
            MLPipeline(
                LearnerSpec("PA", hyper_parameters={"C": 1.0}),
                dim=3 + i,  # a fresh spec every time
            )
        assert len(_JIT_CACHE) <= _JIT_CACHE.cap

    def test_lru_evicts_oldest_and_reuses_hot(self):
        from omldm_tpu.pipelines.pipeline import _LRUCache

        lru = _LRUCache(2)
        lru.put("a", 1)
        lru.put("b", 2)
        assert lru.get("a") == 1  # refresh a
        lru.put("c", 3)           # evicts b
        assert "b" not in lru and "a" in lru and "c" in lru


class TestProgramLaunchCounter:
    def test_counts_solo_dispatches(self):
        job, report, _ = _mt_job("off", 2, 600)
        for s in report.statistics:
            assert s.program_launches > 0
        # merge carries it
        a = report.statistics[0]
        merged = a.merge(
            type(a)(pipeline=a.pipeline, program_launches=5)
        )
        assert merged.program_launches == a.program_launches + 5
        assert "programLaunches" in a.to_dict()

    def test_gang_dispatch_collapses_counts(self):
        off = _mt_job("off", 6, 1500)
        on = _mt_job("on", 6, 1500)
        pl_off = sum(s.program_launches for s in off[1].statistics)
        pl_on = sum(s.program_launches for s in on[1].statistics)
        assert pl_on < pl_off / 2

    def test_spoke_flush_timer_records(self):
        job, _, _ = _mt_job("on", 3, 600)
        timing = job.launch_timing()
        assert timing["count"] > 0
        assert timing["p50_ms"] >= 0.0


class TestLivenessStride:
    def test_strided_walk_still_retires_silent_worker(self):
        """The liveness walk now strides over data events; a silent worker
        must still retire within a stride's worth of records."""
        cfg = JobConfig(parallelism=3, batch_size=16, test_set_size=16,
                        liveness_stride=8)
        job = StreamJob(cfg)
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                        "dataStructure": {"nFeatures": 6}},
            "trainingConfiguration": {
                "protocol": "Synchronous", "syncEvery": 1,
                "comm": {"quorum": 2, "workerTimeoutMs": 1000},
            },
        }))
        hub = job.hub_manager.hubs[(0, 0)].node
        now = [0.0]
        hub._clock = lambda: now[0]
        rng = np.random.RandomState(0)
        w = np.random.RandomState(1).randn(6)

        def lines(n, seed):
            r = np.random.RandomState(seed)
            return [
                json.dumps({"numericalFeatures": f.tolist(),
                            "target": float(f @ w > 0)})
                for f in r.randn(n, 6).astype(np.float32)
            ]

        job.spokes[2].nets[0].node.send = lambda *a, **k: None
        for l in lines(200, 2):
            job.process_event(TRAINING_STREAM, l)
        assert hub._retired_live == set()
        now[0] = 2.0
        for l in lines(64, 3):
            job.process_event(TRAINING_STREAM, l)
        assert hub._retired_live == {2}

    def test_unarmed_job_never_walks(self):
        cfg = JobConfig(parallelism=2, batch_size=16)
        job = StreamJob(cfg)
        assert not job.hub_manager.any_liveness
        job.hub_manager.check_liveness()  # flag-read fast path, no-op
        assert job.hub_manager._liveness_tick == 0


class TestCohortOffIsInert:
    def test_off_builds_no_engine(self):
        cfg = JobConfig(parallelism=1, cohort="off")
        job = StreamJob(cfg)
        assert all(s.cohorts is None for s in job.spokes)
        assert job.hub_manager.gang is None

    def test_auto_below_threshold_stays_solo(self):
        job, _, _ = _mt_job("auto", 2, 300)  # cohort_min is 2 in _mt_job
        # _mt_job sets cohort_min=2, so 2 pipelines DO cohort; rebuild
        cfg = JobConfig(parallelism=1, cohort="auto", cohort_min=8)
        job = StreamJob(cfg)
        for pid in range(3):
            job.process_event(REQUEST_STREAM, json.dumps({
                "id": pid, "request": "Create",
                "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                            "dataStructure": {"nFeatures": DIM}},
                "trainingConfiguration": {"protocol": "Asynchronous"},
            }))
        for net in job.spokes[0].nets.values():
            assert net.pipeline._cohort is None
