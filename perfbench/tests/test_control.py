"""The control has to come out as not correct: the reference, put in the
program's place and computed in bfloat16 (the step below the float32 the
configuration states), fails at least one of each cell's numbers, and so does
each fault planted in it; the sound float32 reference passes every one. Kept
here at a test's size; ``perfbench/control.py`` reads the same numbers at the
cell's own size."""

import pytest

from conftest import CELLS, SCALE
from perfbench import harness


def probe_only_run(cell: str, seed: int) -> harness.Run:
    return harness.probe_only_run(cell, seed, SCALE)


def failed(checks: dict) -> list:
    return [name for name, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_float32_reference_passes_itself(cell, seed):
    assert failed(probe_only_run(cell, seed).control("float32")) == []


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_bfloat16_control_is_not_correct(cell, seed):
    assert "w_diff_rel" in failed(probe_only_run(cell, seed).control("bfloat16"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "w_diff_rel"),
    ("half_batch", "w_diff_rel"),
    # at a test's 1,024 buckets every bucket is touched, so the stray energy
    # this fault leaves at the cell's 2^28 (PERF.md) shows here as wrong weights
    ("wrong_bucket", "w_diff_rel"),
])
def test_planted_training_fault_is_not_correct(cell, fault, number):
    assert number in failed(probe_only_run(cell, 11).control("float32", fault))


def test_altered_answer_is_not_correct():
    checks = probe_only_run("criteo_pa_2e28.serve_paced", 11).control("float32", "answer_altered")
    assert "probe_pred_mismatch" in failed(checks)
