"""The seam of PR 27 moved the sparse stream's code and rewrote none of it:
on one seed the harness renders, byte for byte, the files the parent
rendered, counts what it counted, compares the same eight numbers against the
same limits and prints a line with the same keys in the same order. The
parent's readings are in ``parent_digests.json``, taken on the parent."""

import hashlib
import json
import os
import time

import pytest

from conftest import CELLS, SCALE
from perfbench import harness

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "parent_digests.json")) as f:
    PARENT = json.load(f)


def digest(mem_file) -> str:
    with open(mem_file.path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_has_the_parents_bytes(cell):
    run = harness.Run(harness.scaled(harness.load_cell(cell), SCALE), PARENT["seed"], PARENT["seconds"], False)
    run.make_probe()
    run.make_files()
    files = {}
    for name in ("probe", "part", "slice"):
        for k, mem_file in enumerate(getattr(run, name + "_files", [])):
            files[f"{name}{k}"] = digest(mem_file)
            mem_file.close()
    assert files == PARENT["cells"][cell]["files"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_the_line_has_the_parents_shape(cell, trace):
    want = PARENT["cells"][cell]
    result = harness.run_cell(cell, PARENT["seed"], PARENT["seconds"], trace, time.perf_counter(),
                              need_chip=False, scale=SCALE)
    assert list(result) == want["result_keys_traced" if trace else "result_keys"]
    assert list(result["counters"]) == want["counters_keys"]
    assert {k: c["limit"] for k, c in result["checks"].items()} == want["limits"]
    assert list(result["checks"]) == list(want["limits"])
    assert result["correct"] is True and result["failed"] == 0
    counts = {k: result["counters"][k] for k in want["counts"]}
    if cell.endswith("serve_paced"):  # an open loop offers a fixed amount of work
        assert counts == want["counts"] and result["attempted"] == want["attempted"]
    else:  # a closed loop as much as the machine takes: the probe's part is fixed
        assert counts["holdout"] == want["counts"]["holdout"]
        assert counts["offered_rows"] == counts["fitted"] + counts["holdout"]
