"""A configuration of another kind is new files only. ``dense_kind/`` beside
this file holds a second kind that tests alone use: the dense fused route
(two-class softmax regression over 28 numeric features, the HIGGS shape, tiny,
on the CPU) with its own kind module, configuration, cell files, numpy
reference and one reader, found through the loaders' directory argument. It is
never a cell (0.03 GiB on the chip; ledger, PR 22).

The harness runs it to ``correct`` true, reads its timed path broken through
the hook as not correct, and names nothing of the sparse stream."""

import os
import re
import time

import numpy as np
import pytest

import conftest  # noqa: F401  (the CPU backend and the path)
from perfbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "dense_kind")
CELLS = ("higgs_lr.train_sat", "higgs_lr.serve_only")


def run(cell, trace=False, hooks=None, seed=2**31 + 99):
    return harness.run_cell(cell, seed, 1.0, trace, time.perf_counter(),
                            need_chip=False, hooks=hooks, root=ROOT)


def failed(checks: dict) -> list:
    return [name for name, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", CELLS)
def test_the_second_kind_runs_to_correct(cell):
    result = run(cell)
    assert result["correct"] is True and result["failed"] == 0 and failed(result["checks"]) == []
    assert list(result["checks"]) == ["rows_lost", "forecasts_bad", "answers_wrong", "loss_gap", "w_diff_rel"]
    assert list(result)[-1] == "checks"
    if cell == "higgs_lr.train_sat":
        assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
        assert result["attempted"] == result["counters"]["window_rows"] > 0
    else:  # no training rows in the window: no rate in the line, forecasts only
        assert set(result["metrics"]) == {"predict_p95_ms", "setup_s"}
        assert result["counters"]["window_rows"] == 0 and result["attempted"] == 200
        assert result["counters"]["probe_answers"] == 25


def test_a_reader_takes_its_numbers_from_the_kind():
    seen = []
    result = run("higgs_lr.train_sat", trace=True, hooks={"finished": lambda run, result: seen.append(run)})
    # 3 x 2 x 2 x 29 operations a row, 64 rows a step
    assert result["metrics"] == {"step_flops": {"value": 22272.0, "unit": "flop"}}
    [ctx] = seen
    assert ctx.kind.flops_per_row == 348 and ctx.batch == 64 and result["correct"] is True


def state_unchanged(system):
    import jax

    trainer = system.bridge.trainer

    def keep(real):
        def step(*args, **kwargs):
            kept = jax.tree_util.tree_map(lambda leaf: leaf + 0, trainer.state)  # the real step donates
            out = real(*args, **kwargs)
            trainer.state = kept
            return out
        return step

    trainer.step, trainer.step_many_dense = keep(trainer.step), keep(trainer.step_many_dense)


def half_batch(system):
    trainer = system.bridge.trainer
    one, many = trainer.step, trainer.step_many_dense

    def step(x, y, mask, valid_count=None):
        mask = np.array(mask, np.float32)
        mask[:, mask.shape[1] // 2:] = 0.0
        return one(x, y, mask, valid_count=valid_count)

    def step_many(xs, ys):
        for x, y in zip(xs, ys):  # a chained stage, one masked step at a time
            step(x, y, np.ones(y.shape, np.float32), valid_count=y.size)

    trainer.step, trainer.step_many_dense = step, step_many


def answer_altered(system):
    real = system.bridge._emit_prediction

    def emit(pred):
        pred.value = 1.0 - float(pred.value)
        real(pred)

    system.bridge._emit_prediction = emit


@pytest.mark.parametrize("fault,cell,number", [
    (state_unchanged, "higgs_lr.train_sat", "w_diff_rel"),
    (half_batch, "higgs_lr.train_sat", "w_diff_rel"),
    (answer_altered, "higgs_lr.serve_only", "answers_wrong"),
])
def test_the_timed_path_broken_reads_not_correct(fault, cell, number):
    result = run(cell, hooks={"after_build": fault})
    assert result["correct"] is False
    assert number in failed(result["checks"])


@pytest.mark.parametrize("seed", [5, 2**31 + 7, 91])
def test_the_second_kinds_control_is_not_correct(seed):
    probe = harness.probe_only_run("higgs_lr.serve_only", seed, root=ROOT)
    assert failed(probe.control("float32")) == []
    wants = {"bfloat16": "w_diff_rel", "state_unchanged": "w_diff_rel", "half_batch": "w_diff_rel",
             "answer_altered": "answers_wrong"}
    for precision, fault in probe.spec["kind"].STAND_INS:
        assert wants[fault or precision] in failed(probe.control(precision, fault))


def test_a_configuration_without_a_known_kind_is_an_error():
    with pytest.raises(ValueError):
        harness.load_kind({"name": "x"}, ROOT)
    with pytest.raises(FileNotFoundError):
        harness.load_kind({"name": "x", "kind": "sparse_stream"}, ROOT)  # the repo's, not this directory's


SPARSE_NAMES = ("hashSpace", "maxNnz", "Schema", "SparseFastParser", "fused_file_bridge", "test_set", '["w"]',
                "hash_space", "max_nnz", "run_file_fused", "curve_slice", "trainer")


@pytest.mark.parametrize("name", ["harness.py", "generator.py", "run.py"])
def test_the_shared_files_name_nothing_of_the_sparse_stream(name):
    with open(os.path.join(os.path.dirname(HERE), name)) as f:
        text = f.read()
    assert [word for word in SPARSE_NAMES if word in text] == []
    assert re.search(r"kinds[./]sparse_stream|import sparse_stream", text) is None
