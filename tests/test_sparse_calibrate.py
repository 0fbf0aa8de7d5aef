"""Sparse update calibration: table format, lookup, dispatch wiring.

The crossover table (ops/sparse_dispatch.json) replaces round 5's guessed
``D >= 2^16`` TPU threshold: `sparse_update` resolves its formulation
from the nearest measured (D, updates) grid point for the active backend.
These tests pin the table format the CI smoke run
(``python -m omldm_tpu.ops.sparse_calibrate --smoke``) regenerates, the
nearest-neighbor lookup, the merge-per-backend write, and the dispatch
order (an explicit impl, the table, the plain pair). A test points the
reader at a table of its own by patching ``DEFAULT_TABLE``."""

import json

import jax
import numpy as np
import pytest

from omldm_tpu.ops import sparse_calibrate as cal
from omldm_tpu.ops.sparse import IMPLS, _resolve_impl


# the chip's verdict at the cells' shapes (rows of a launch, winner): what
# ops/sparse_dispatch.json's tpu section holds (my chip run, PR 30)
CELL_WINNERS = [(4096, "plan"), (256, "scatter"), (16, "scatter")]


def _table(backends):
    return {"version": 1, "backends": backends}


def _entry(d, updates, winner):
    return {
        "d": d, "batch": 32, "nnz": 4, "updates": updates,
        "duplicate_factor": 1.0,
        "rates_updates_per_sec": {"scatter": 1.0, "plan": 1.0},
        "winner": winner,
    }


class TestLookup:
    def test_nearest_grid_point_log2(self, tmp_path, monkeypatch):
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "cpu": {"entries": [
                _entry(1 << 12, 1 << 10, "scatter"),
                _entry(1 << 18, 1 << 10, "plan"),
            ]},
        })))
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(path))
        assert cal.lookup_winner("cpu", 1 << 12, 1 << 10) == "scatter"
        assert cal.lookup_winner("cpu", 1 << 19, 2048) == "plan"
        # log2-nearest: D=2^15 ties split by first-wins, D=2^16 -> plan
        assert cal.lookup_winner("cpu", 1 << 16, 1 << 10) == "plan"
        # unmeasured backend: None (callers fall back to the guess)
        assert cal.lookup_winner("tpu", 1 << 18, 1 << 10) is None

    def test_missing_or_corrupt_table(self, tmp_path, monkeypatch):
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(tmp_path / "absent.json"))
        assert cal.lookup_winner("cpu", 1 << 18, 1 << 10) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(bad))
        assert cal.lookup_winner("cpu", 1 << 18, 1 << 10) is None

    def test_auto_dispatch_reads_table(self, tmp_path, monkeypatch):
        """sparse_update's trace-time resolution follows the table for the
        active backend."""
        import jax

        backend = jax.default_backend()
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            backend: {"entries": [_entry(1 << 10, 256, "plan")]},
        })))
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(path))
        assert _resolve_impl(1 << 10, 256) == "plan"
        # where the plan's spare addresses would leave int32, or the
        # weights are not 4 bytes wide, the table's choice gives way to the
        # plain pair
        assert _resolve_impl(2 ** 31 - 8, 256) == "scatter"
        assert _resolve_impl(1 << 10, 256, dtype="bfloat16") == "scatter"
        # an explicit impl beats the table
        assert _resolve_impl(1 << 10, 256, impl="scatter") == "scatter"


class TestCalibrate:
    def test_measure_entry_covers_all_kernels(self):
        e = cal.measure_entry(256, 16, 4, steps=2)
        assert set(e["rates_updates_per_sec"]) == set(IMPLS)
        assert e["winner"] in IMPLS
        assert e["updates"] == 16 * 4
        assert e["duplicate_factor"] >= 1.0

    def test_calibrate_merges_per_backend(self, tmp_path, monkeypatch):
        """A re-calibration on one backend must not clobber another
        backend's committed section."""
        import jax

        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "faux-tpu": {"entries": [_entry(1 << 18, 1 << 10, "plan")]},
        })))
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(path))
        table = cal.calibrate([(256, 16, 4)], steps=2)
        assert "faux-tpu" in table["backends"]
        assert jax.default_backend() in table["backends"]
        on_disk = json.loads(path.read_text())
        assert set(on_disk["backends"]) == set(table["backends"])
        [e] = on_disk["backends"][jax.default_backend()]["entries"]
        assert e["winner"] in IMPLS

    @pytest.mark.parametrize("backend,shipped", [("cpu", False), ("tpu", True)])
    def test_committed_table_sections(self, backend, shipped):
        """The repo ships the section measured on the chip (PR 30) and none
        for the tier-1 host: the CPU's named the plain pair at every point,
        which is what a backend without a section gets; the smoke CI run
        regenerates the same shape."""
        table = cal.load_table(cal.DEFAULT_TABLE)
        assert table is not None, "ops/sparse_dispatch.json missing/corrupt"
        section = table["backends"].get(backend)
        if not shipped:
            assert section is None
            assert cal.lookup_winner(backend, 1 << 18, 1 << 12) is None
            return
        assert section["entries"], f"no {backend} section"
        for e in section["entries"]:
            # the winner is the faster of the two formulations there are;
            # a retired third one's rate may stay on record beside them
            rates = e["rates_updates_per_sec"]
            assert set(IMPLS) <= set(rates)
            assert e["winner"] == max(IMPLS, key=rates.__getitem__)
            assert e["updates"] == e["batch"] * e["nnz"]

    @pytest.mark.parametrize("batch,winner", CELL_WINNERS)
    def test_committed_tpu_section_at_the_cells_shapes(
        self, batch, winner, monkeypatch
    ):
        """What the benchmark's cells run (2^28 + 14 weights, maxNnz 40 plus
        the bias slot) is IN the chip's section, so the nearest point is the
        shape itself: a launch of 4096 rows, a tail step of 256, a forecast's
        padded 16. The same shapes on a backend without a section, and any
        shape with no table at all, get the plain pair."""
        from omldm_tpu.ops import sparse as sp

        d, n = (1 << 28) + 14, batch * 41
        [entry] = [
            e for e in cal.load_table(cal.DEFAULT_TABLE)["backends"]["tpu"][
                "entries"
            ] if (e["d"], e["updates"]) == (d, n)
        ]
        assert entry["winner"] == winner
        assert cal.lookup_winner("tpu", d, n) == winner
        assert cal.lookup_winner("gpu", d, n) is None
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert sp._resolve_impl(d, n) == winner
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert sp._resolve_impl(d, n) == "scatter"

    def test_tpu_guess_retired(self, tmp_path, monkeypatch):
        """The round-5 ``D >= 2^16`` TPU guess is retired: an UNCALIBRATED
        backend (no table section) resolves to the plain scatter at any D —
        the guessed crossover was never measured, and a number nobody
        measured must not steer the dispatch. Nor does a table that names
        something other than the plan (an older calibration's ``mxu``)."""
        import jax

        from omldm_tpu.ops import sparse as sp

        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(tmp_path / "absent.json"))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert sp._resolve_impl(1 << 20, 1 << 10) == "scatter"
        assert sp._resolve_impl(1 << 10, 1 << 10) == "scatter"
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "tpu": {"entries": [_entry(1 << 20, 1 << 10, "mxu")]},
        })))
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(path))
        assert sp._resolve_impl(1 << 20, 1 << 10) == "scatter"


class TestLearnerWiring:
    def test_sparse_pa_update_honors_scatter_override(self, monkeypatch):
        """The learner hot path reaches sparse_update; pinning
        the impl via dataStructure.scatterImpl stays numerically inside
        the twin envelope."""
        import jax.numpy as jnp

        from omldm_tpu.api.requests import LearnerSpec
        from omldm_tpu.learners.registry import make_learner

        rng = np.random.RandomState(0)
        d, b, k = 512, 16, 6
        idx = rng.randint(0, d, size=(b, k)).astype(np.int32)
        val = rng.randn(b, k).astype(np.float32)
        y = (rng.randn(b) > 0).astype(np.float32)
        mask = np.ones(b, np.float32)
        params = {}
        for impl in ("scatter", "plan"):
            learner = make_learner(LearnerSpec(
                "PA", hyper_parameters={"C": 0.5, "variant": "PA-II"},
                data_structure={"sparse": True, "scatterImpl": impl},
            ))
            p = learner.init(d, None)
            p, _ = learner.update(
                p, (jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y),
                jnp.asarray(mask),
            )
            params[impl] = np.asarray(p["w"])
        np.testing.assert_allclose(
            params["plan"], params["scatter"], rtol=2e-5, atol=2e-5
        )
