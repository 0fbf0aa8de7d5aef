"""Elastic rescale-restore: an N-process distributed snapshot restores
across M != N processes (ISSUE 12 tentpole).

Pins, per acceptance:

- the pure redistribution contracts: shard map (old shard q -> survivor
  q % M), fleet-leaf merge rules (params/preps group-MEAN, cum_loss
  group-SUM, EF reset, counters survivor-row; grow seeds new rows from
  the fleet model), cursor union (Kafka per-partition offsets max-merge,
  file cursors fleet-global), round-robin buffer interleave;
- a fabricated 2-process snapshot restores in one process (shrink):
  merged model state, summed partition counters, merged predictions,
  holdout overflow RE-FED to training (row conservation), cursor union;
- rescale-restore disabled (--rescaleRestore false) degrades a count
  mismatch to a warned fresh start naming the knob — never a crash;
- (slow) a REAL 4-process snapshot restores at 2 and at 6 processes with
  bit-exact request-line redeploy, exact row conservation, and scores
  inside the 0.05 envelope of the unrescaled restore;
- (slow) N->M and N->N restores of the same faulted stream converge to
  the same per-protocol scores within the 0.05 envelope for all 6
  parameter protocols.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from omldm_tpu.config import JobConfig
from omldm_tpu.runtime.distributed_job import (
    DistributedStreamJob,
    _interleave_perm,
    _interleave_rows,
    _merge_cursors,
    _rescale_fleet_leaf,
    rescale_shard_map,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 6


# --- pure redistribution contracts -------------------------------------------


class TestShardMap:
    def test_same_count_is_identity(self):
        for n in (1, 2, 4):
            for pid in range(n):
                assert rescale_shard_map(n, n, pid) == [pid]

    def test_shrink_merges_mod_new_count(self):
        assert rescale_shard_map(4, 2, 0) == [0, 2]
        assert rescale_shard_map(4, 2, 1) == [1, 3]
        assert rescale_shard_map(3, 2, 0) == [0, 2]
        assert rescale_shard_map(3, 2, 1) == [1]

    def test_grow_identity_plus_empty_new(self):
        assert rescale_shard_map(2, 6, 0) == [0]
        assert rescale_shard_map(2, 6, 1) == [1]
        for pid in range(2, 6):
            assert rescale_shard_map(2, 6, pid) == []

    def test_every_old_shard_owned_exactly_once(self):
        for old_n in range(1, 7):
            for new_n in range(1, 7):
                owned = [
                    q
                    for pid in range(new_n)
                    for q in rescale_shard_map(old_n, new_n, pid)
                ]
                assert sorted(owned) == list(range(old_n))


class TestInterleave:
    def test_perm_round_robins(self):
        assert _interleave_perm([2, 3]) == [0, 2, 1, 3, 4]
        assert _interleave_perm([0, 2]) == [0, 1]
        assert _interleave_perm([]) == []

    def test_rows_fair_mix(self):
        a = np.zeros((3, 2), np.float32)
        b = np.ones((2, 2), np.float32)
        out = _interleave_rows([a, b])
        assert out.shape == (5, 2)
        assert out[:, 0].tolist() == [0.0, 1.0, 0.0, 1.0, 0.0]


class TestFleetLeafRescale:
    def _full(self):
        return np.arange(8, dtype=np.float32).reshape(4, 2)

    def test_same_count_untouched(self):
        full = self._full()
        assert _rescale_fleet_leaf(full, "params", 4) is full

    def test_grow_seeds_from_row0(self):
        g = _rescale_fleet_leaf(self._full(), "params", 6)
        assert g.shape == (6, 2)
        assert (g[4] == g[0]).all() and (g[5] == g[0]).all()

    def test_grow_zero_seeds_accumulators(self):
        for key in ("ef", "cum_loss"):
            g = _rescale_fleet_leaf(self._full(), key, 6)
            assert (g[:4] == self._full()).all()
            assert (g[4:] == 0).all()

    def test_shrink_params_group_mean(self):
        full = self._full()
        s = _rescale_fleet_leaf(full, "params", 2)
        assert np.allclose(s[0], (full[0] + full[2]) / 2)
        assert np.allclose(s[1], (full[1] + full[3]) / 2)
        assert s.dtype == full.dtype

    def test_shrink_cum_loss_group_sum(self):
        full = self._full()
        s = _rescale_fleet_leaf(full, "cum_loss", 2)
        assert np.allclose(s[0], full[0] + full[2])

    def test_shrink_counters_keep_survivor_row(self):
        full = self._full()
        for key in ("step", "syncs", "clock", "accepted", "est", "center"):
            s = _rescale_fleet_leaf(full, key, 2)
            assert (s == full[:2]).all()

    def test_shrink_ef_resets(self):
        s = _rescale_fleet_leaf(self._full(), "ef", 2)
        assert s.shape == (2, 2) and (s == 0).all()


class TestCursorMerge:
    def test_kafka_union_max(self):
        merged = _merge_cursors([
            {"data": {"t:0": 5, "t:1": 2}, "requests": {}},
            {"data": {"t:1": 7, "t:2": 3}, "requests": {"r:0": 4}},
        ])
        assert merged == {
            "data": {"t:0": 5, "t:1": 7, "t:2": 3},
            "requests": {"r:0": 4},
        }

    def test_file_cursors_fleet_global(self):
        assert _merge_cursors([300, 300]) == 300
        assert _merge_cursors(
            [{"bytes": 10, "lines": 4}, {"bytes": 10, "lines": 4}]
        ) == {"bytes": 10, "lines": 4}

    def test_empty_and_none(self):
        assert _merge_cursors([]) is None
        assert _merge_cursors([None, 7]) == 7


# --- in-process restore (fabricated multi-process snapshots) -----------------
#
# A real M-process fleet needs M jax processes (the slow tests below); the
# fast path fabricates a 2-process snapshot from a REAL 1-process one —
# the on-disk layout is the restore contract, so exercising it directly
# pins the merge semantics at tier-1 cost.


CREATE = json.dumps({
    "id": 0, "request": "Create",
    "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                "dataStructure": {"nFeatures": DIM}},
    "preProcessors": [],
    "trainingConfiguration": {"protocol": "Synchronous", "syncEvery": 1},
})


def _one_proc_job(test_cap=16, create=CREATE):
    job = DistributedStreamJob(
        JobConfig(batch_size=8, test_set_size=test_cap)
    )
    job.sync_requests([create])
    return job


def _feed(job, n=200, seed=0):
    rng = np.random.RandomState(seed)
    w = np.random.RandomState(5).randn(DIM)
    x = rng.randn(n, DIM).astype(np.float32)
    y = (x @ w > 0).astype(np.float32)
    job.handle_partition_rows(x, y)
    return x


def _rewrite_fleet_file(d, fleet):
    """Replace the snapshot's fleet file and refresh its integrity digest."""
    from omldm_tpu.runtime.distributed_job import _file_sha256

    np.savez(os.path.join(d, "fleet_0.npz"), **fleet)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("digests"):
        manifest["digests"]["fleet_0.npz"] = _file_sha256(
            os.path.join(d, "fleet_0.npz")
        )
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)


def _fabricate_two_proc_snapshot(d, scale_row1=1.5, preds1=(9.0,)):
    """Turn a 1-process snapshot into a format-valid 2-process one: fleet
    leaves gain a second worker row (float leaves scaled so merges are
    detectable), proc1 duplicates proc0's shard with marker predictions."""
    fleet = dict(np.load(os.path.join(d, "fleet_0.npz")))
    for k, leaf in fleet.items():
        row1 = leaf * scale_row1 if leaf.dtype.kind == "f" else leaf.copy()
        fleet[k] = np.concatenate([leaf, row1], axis=0)
    _rewrite_fleet_file(d, fleet)
    with open(os.path.join(d, "proc0.json")) as f:
        meta1 = json.load(f)
    meta1["pipelines"]["0"]["predictions"] = list(preds1)
    with open(os.path.join(d, "proc1.json"), "w") as f:
        json.dump(meta1, f)
    shutil.copy(
        os.path.join(d, "proc0.npz"), os.path.join(d, "proc1.npz")
    )
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    manifest["processes"] = 2
    manifest["dp_global"] = 2
    # (proc1's npz is a byte copy of proc0's, so its meta digest still
    # matches)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)


def _params_leaf(state):
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if str(getattr(path[0], "key", path[0])) == "params":
            return np.asarray(leaf.addressable_shards[0].data)
    raise AssertionError("no params leaf")


class TestShrinkRestoreInProcess:
    def test_two_proc_snapshot_restores_in_one(self, tmp_path):
        job = _one_proc_job()
        _feed(job)
        job.handle_forecast_rows(np.zeros((3, DIM), np.float32))
        job.pump()
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        base_params = _params_leaf(job.pipelines[0].trainer.state)
        base = job.pipelines[0]
        _fabricate_two_proc_snapshot(d)

        restored = _one_proc_job()
        cur = restored.restore_checkpoint(root)
        assert cur == 200
        assert restored.rescales_performed == 1
        p = restored.pipelines[0]
        # partition counters SUM across the merged shards
        assert p.holdout_count == 2 * base.holdout_count
        assert p.trainer._fitted_host == 2 * base.trainer._fitted_host
        # predictions of both shards survive the merge
        assert 9.0 in p.predictions
        # params = mean(row0, 1.5*row0) = 1.25*row0 — the group-mean merge
        assert np.allclose(
            _params_leaf(p.trainer.state), 1.25 * base_params, atol=1e-6
        )
        # bit-exact request-line redeploy: the manifest line rebuilt the
        # same pipeline spec
        assert p.raw_line == base.raw_line
        # the restored fleet trains + checkpoints again without complaint
        _feed(restored, n=40, seed=1)
        restored.pump(final=True)
        restored.save_checkpoint(root, 240)

    def test_holdout_overflow_refeeds_training(self, tmp_path):
        """Two full 16-row holdout rings merge into one: the 16 evicted
        rows must land back in the pending training buffer (conservation
        — rows never vanish with a retired partition)."""
        job = _one_proc_job(test_cap=16)
        _feed(job)
        job.pump(final=True)
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        _fabricate_two_proc_snapshot(d)

        restored = _one_proc_job(test_cap=16)
        restored.restore_checkpoint(root)
        p = restored.pipelines[0]
        assert len(p.test_set) == 16
        assert p.pend_n >= 16  # evicted holdout rows re-fed

    def test_rescale_restore_disabled_warns_with_knob(
        self, tmp_path, capsys
    ):
        """Satellite: the old bare ValueError is now a reason-coded
        fresh-start degradation naming --rescaleRestore."""
        job = _one_proc_job()
        _feed(job)
        job.pump()
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        _fabricate_two_proc_snapshot(d)

        restored = DistributedStreamJob(
            JobConfig(batch_size=8, test_set_size=16)
        )
        restored.rescale_restore = False
        cur = restored.restore_checkpoint(root)
        err = capsys.readouterr().err
        assert cur is None
        assert restored.pipelines == {}
        assert "--rescaleRestore" in err
        assert "starting fresh" in err
        assert restored.rescales_performed == 0

    def test_same_count_restore_unchanged(self, tmp_path):
        """A same-count restore is the exact pre-rescale path: no
        rescale counter tick, identical state."""
        job = _one_proc_job()
        _feed(job)
        job.pump()
        root = str(tmp_path / "ck")
        job.save_checkpoint(root, 200)
        base_params = _params_leaf(job.pipelines[0].trainer.state)

        restored = _one_proc_job()
        cur = restored.restore_checkpoint(root)
        assert cur == 200
        assert restored.rescales_performed == 0
        assert (
            _params_leaf(restored.pipelines[0].trainer.state) == base_params
        ).all()

    def test_supervisor_pinned_count_not_double_counted(self, tmp_path):
        """With --rescaleCount pinned by the supervisor, a mismatch
        restore must NOT self-increment (the supervisor's tally already
        includes the rescale that caused this relaunch)."""
        job = _one_proc_job()
        _feed(job)
        job.pump()
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        _fabricate_two_proc_snapshot(d)

        restored = _one_proc_job()
        restored.rescales_performed = 3
        restored._rescale_count_pinned = True
        restored.restore_checkpoint(root)
        assert restored.rescales_performed == 3


def _create(protocol, codec="none"):
    return json.dumps({
        **json.loads(CREATE),
        "trainingConfiguration": {
            "protocol": protocol, "syncEvery": 1, "codec": codec,
            "threshold": 0.05,
        },
    })


def _saved_leaves(d):
    fleet = np.load(os.path.join(d, "fleet_0.npz"))
    return [fleet[f"leaf_{i}"] for i in range(len(fleet.files))]


class TestFleetFileLeafOrder:
    """The fleet file names its leaves by their place in ``tree_leaves``
    order. A file from before Synchronous / EASGD dropped the ``est`` that
    nothing read holds it in between the others."""

    @pytest.mark.parametrize("codec", ["none", "int8"])
    @pytest.mark.parametrize("protocol", ["Synchronous", "EASGD"])
    def test_file_in_the_old_leaf_order_restores_every_leaf_where_it_belongs(
        self, tmp_path, protocol, codec
    ):
        create = _create(protocol, codec)
        job = _one_proc_job(create=create)
        _feed(job)
        job.pump()
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        state = job.pipelines[0].trainer.state
        assert "est" not in state and ("ef" in state) == (codec != "none")
        # the old tree: the same leaves with ``est`` among them, flattened
        # in the order the old save walked
        treedef = jax.tree_util.tree_structure(state)
        saved = jax.tree_util.tree_unflatten(treedef, _saved_leaves(d))
        vec = jax.tree_util.tree_leaves(saved["params"])[0]
        old = {**saved, "est": vec * 0.5 + 7.0}
        if "center" not in saved:  # Synchronous let go of it as well
            old["center"] = vec * 0.25 + 3.0
        old_leaves = jax.tree_util.tree_leaves(old)
        assert len(old_leaves) == treedef.num_leaves + (
            2 if protocol == "Synchronous" else 1
        )
        _rewrite_fleet_file(
            d, {f"leaf_{i}": l for i, l in enumerate(old_leaves)}
        )

        restored = _one_proc_job(create=create)
        assert restored.restore_checkpoint(root) == 200
        got = restored.pipelines[0].trainer.state
        assert jax.tree_util.tree_structure(got) == treedef
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_leaves(state),
        ):
            assert a.dtype == b.dtype and a.shape == b.shape, path
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), str(path))
        # and the restored fleet goes on as the donor does
        for j in (job, restored):
            _feed(j, n=40, seed=1)
            j.pump(final=True)
        assert (
            _params_leaf(restored.pipelines[0].trainer.state)
            == _params_leaf(job.pipelines[0].trainer.state)
        ).all()

    @pytest.mark.parametrize(
        "protocol,extra", [("Synchronous", 3), ("Synchronous", -1), ("GM", 2)]
    )
    def test_any_other_leaf_count_is_refused(self, tmp_path, protocol, extra):
        create = _create(protocol)
        job = _one_proc_job(create=create)
        _feed(job)
        job.pump()
        root = str(tmp_path / "ck")
        d = job.save_checkpoint(root, 200)
        leaves = _saved_leaves(d)
        leaves = leaves[:extra] if extra < 0 else leaves + leaves[:extra]
        _rewrite_fleet_file(
            d, {f"leaf_{i}": l for i, l in enumerate(leaves)}
        )
        restored = _one_proc_job(create=create)
        with pytest.raises(ValueError, match=r"fleet_0\.npz holds \d+ leaves"):
            restored.restore_checkpoint(root)


# --- real multi-process fleets (slow) ----------------------------------------


def _rows(n, dim=12, seed=0, forecast_every=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    lines = []
    for i in range(n):
        x = np.round(rng.randn(dim), 6)
        if forecast_every and i % forecast_every == 0:
            lines.append(json.dumps({
                "numericalFeatures": [float(v) for v in x],
                "operation": "forecasting",
            }))
        else:
            lines.append(json.dumps({
                "numericalFeatures": [float(v) for v in x],
                "target": float(x @ w > 0),
                "operation": "training",
            }))
    return lines


def _create_line(protocol="Synchronous", dim=12, **tc):
    return json.dumps({
        "id": 0, "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                    "dataStructure": {"nFeatures": dim}},
        "preProcessors": [],
        "trainingConfiguration": {
            "protocol": protocol, "syncEvery": 1, **tc
        },
    })


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(tmp_path, nproc, extra, tag, expect_rc=0, timeout=420):
    """nproc worker processes of the distributed CLI; returns
    (report or None, prediction payloads, joined stderr)."""
    port = _free_port()
    perf = tmp_path / f"perf_{tag}.jsonl"
    preds = tmp_path / f"preds_{tag}.jsonl"
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        args = [
            sys.executable, "-m", "omldm_tpu.runtime.distributed_job",
            "--performanceOut", str(perf), "--predictionsOut", str(preds),
            "--batchSize", "64", "--testSetSize", "32",
        ] + extra
        if nproc > 1:
            args += [
                "--coordinator", f"127.0.0.1:{port}",
                "--processes", str(nproc), "--processId", str(pid),
            ]
        procs.append(subprocess.Popen(
            args, cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    errs = []
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        errs.append(err)
        assert p.returncode == expect_rc, (
            f"rc {p.returncode} (wanted {expect_rc}):\n{out}\n{err[-3000:]}"
        )
    report = None
    if perf.exists():
        [line] = perf.read_text().strip().splitlines()
        report = json.loads(line)
    predictions = []
    pred_paths = (
        [preds] if nproc == 1
        else [tmp_path / f"preds_{tag}.jsonl.p{i}" for i in range(nproc)]
    )
    for pf in pred_paths:
        if pf.exists() and pf.read_text().strip():
            predictions.extend(
                json.loads(l) for l in pf.read_text().strip().splitlines()
            )
    return report, predictions, "\n".join(errs)


def _stat(report):
    [s] = report["statistics"]
    return s


@pytest.mark.slow
def test_4proc_snapshot_restores_at_2_and_6(tmp_path):
    """The acceptance shape: a 4-process snapshot (faulted run leaves
    ckpts behind) restores at 2 and at 6 processes — bit-exact request
    redeploy, exact row conservation, merged/seeded model state scoring
    inside the 0.05 envelope of the unrescaled (4->4) restore, and
    replay from the recorded cursor."""
    train = tmp_path / "train.jsonl"
    reqs = tmp_path / "reqs.jsonl"
    ckpt = tmp_path / "ckpts"
    n_rows = 3000
    train.write_text(
        "\n".join(_rows(n_rows, forecast_every=50)) + "\n"
    )
    reqs.write_text(_create_line() + "\n")
    base = ["--requests", str(reqs), "--trainingData", str(train),
            "--chunkRows", "128"]
    # faulted 4-proc run: snapshots every 2 chunks, dies after chunk 5
    _launch(
        tmp_path, 4,
        base + ["--checkpointDir", str(ckpt), "--checkpointEvery", "2",
                "--failAfterChunks", "5"],
        "faulted", expect_rc=3,
    )
    assert (ckpt / "LATEST").exists()
    n_fore = len([i for i in range(n_rows) if i % 50 == 0])
    results = {}
    for m in (4, 2, 6):
        # each restore resumes the SAME snapshot: work on a copy so one
        # leg's later checkpoints don't feed the next leg
        root = tmp_path / f"ck_{m}"
        shutil.copytree(ckpt, root)
        report, preds, err = _launch(
            tmp_path, m,
            base + ["--checkpointDir", str(root), "--restore", "true"],
            f"resume{m}",
        )
        if m != 4:
            assert "rescale-restore: redistributing a 4-process" in err
        s = _stat(report)
        # conservation: every training row fitted or held out, exactly
        assert s["fitted"] + report["holdout"]["0"] == n_rows - n_fore, (
            m, s["fitted"], report["holdout"])
        # every forecast served exactly once across the fleet
        assert len(preds) == n_fore
        # bit-exact request-line redeploy
        assert s["protocol"] == "Synchronous"
        assert s["fleetProcesses"] == m
        assert s["rescalesPerformed"] == (0 if m == 4 else 1)
        results[m] = s["score"]
    assert abs(results[2] - results[4]) <= 0.05, results
    assert abs(results[6] - results[4]) <= 0.05, results


@pytest.mark.slow
@pytest.mark.parametrize(
    "protocol", ["Asynchronous", "Synchronous", "SSP", "EASGD", "GM", "FGM"]
)
def test_rescale_restore_determinism_per_protocol(tmp_path, protocol):
    """Same stream, same fault: the N->N and N->M restores of each
    parameter protocol converge to the same score within the established
    0.05 envelope (2-proc snapshot, restored at 2 and at 1)."""
    train = tmp_path / "train.jsonl"
    reqs = tmp_path / "reqs.jsonl"
    ckpt = tmp_path / "ckpts"
    train.write_text("\n".join(_rows(2000, seed=11)) + "\n")
    tc = {"staleness": 2} if protocol == "SSP" else {}
    reqs.write_text(_create_line(protocol=protocol, **tc) + "\n")
    base = ["--requests", str(reqs), "--trainingData", str(train),
            "--chunkRows", "256"]
    _launch(
        tmp_path, 2,
        base + ["--checkpointDir", str(ckpt), "--checkpointEvery", "2",
                "--failAfterChunks", "4"],
        "faulted", expect_rc=3,
    )
    assert (ckpt / "LATEST").exists()
    scores = {}
    for m in (2, 1):
        root = tmp_path / f"ck_{m}"
        shutil.copytree(ckpt, root)
        report, _, err = _launch(
            tmp_path, m,
            base + ["--checkpointDir", str(root), "--restore", "true"],
            f"resume{m}",
        )
        s = _stat(report)
        assert s["fitted"] + report["holdout"]["0"] == 2000
        scores[m] = s["score"]
    assert abs(scores[1] - scores[2]) <= 0.05, (protocol, scores)
