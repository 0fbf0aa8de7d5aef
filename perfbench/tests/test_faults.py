"""The rest of a run with the timed path broken underneath: the harness's look
for a chip is skipped (CPU, a test's size), everything else runs as in a
benchmark run, and ``correct`` has to come out false, once for each fault a
one-chip cell can have: a step that returns its state unchanged; half of the
batch left out, the mean taken over the rest; an answer altered where it is
produced. (No exchange between chips exists in a one-chip cell.)"""

import time

import numpy as np
import pytest

from conftest import CELLS, SCALE
from perfbench import harness


def run(cell, hooks=None, seed=2**31 + 21):
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                            need_chip=False, scale=SCALE, hooks=hooks)


def failed(result) -> list:
    return [name for name, c in result["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = run(cell)
    assert result["correct"] is True and failed(result) == []
    assert list(result)[-1] == "checks"


def state_unchanged(system):
    import jax

    bridge = system.bridge
    real = bridge.trainer._step

    def step(state, x, y, mask):
        # the real step donates its argument: keep a copy, hand that back
        kept = jax.tree_util.tree_map(lambda leaf: leaf + 0, state)
        _new_state, loss = real(state, x, y, mask)
        return kept, loss

    bridge.trainer._step = step


def half_batch(system):
    bridge = system.bridge
    real = bridge.trainer.step

    def step(x, y, mask, valid_count=None):
        mask = np.array(mask, np.float32)
        mask[:, mask.shape[1] // 2:] = 0.0
        return real(x, y, mask, valid_count=valid_count)

    bridge.trainer.step = step


def answer_altered(system):
    bridge = system.bridge
    real = bridge._emit_prediction

    def emit(pred):
        pred.value = -float(pred.value)
        real(pred)

    bridge._emit_prediction = emit


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_training_fault_reads_not_correct(cell, fault):
    result = run(cell, hooks={"after_build": fault})
    assert result["correct"] is False
    assert "w_diff_rel" in failed(result)


def test_altered_answer_reads_not_correct():
    result = run("criteo_pa_2e28.serve_paced", hooks={"after_build": answer_altered})
    assert result["correct"] is False
    assert "probe_pred_mismatch" in failed(result)
