"""Sparse scatter calibration: table format, lookup, dispatch wiring.

The crossover table (ops/sparse_dispatch.json) replaces round 5's guessed
``D >= 2^16`` TPU threshold: `sparse_scatter_add_auto` resolves its kernel
from the nearest measured (D, updates) grid point for the active backend.
These tests pin the table format the CI smoke run
(``python -m omldm_tpu.ops.sparse_calibrate --smoke``) regenerates, the
nearest-neighbor lookup, the merge-per-backend write, and the dispatch
precedence (env/config overrides beat the table)."""

import json

import numpy as np
import pytest

from omldm_tpu.ops import sparse_calibrate as cal
from omldm_tpu.ops.sparse import SCATTER_IMPLS, _resolve_impl


def _table(backends):
    return {"version": 1, "backends": backends}


def _entry(d, updates, winner):
    return {
        "d": d, "batch": 32, "nnz": 4, "updates": updates,
        "duplicate_factor": 1.0,
        "rates_updates_per_sec": {"scatter": 1.0, "mxu": 1.0, "segsum": 1.0},
        "winner": winner,
    }


class TestLookup:
    def test_nearest_grid_point_log2(self, tmp_path, monkeypatch):
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "cpu": {"entries": [
                _entry(1 << 12, 1 << 10, "scatter"),
                _entry(1 << 18, 1 << 10, "segsum"),
            ]},
        })))
        monkeypatch.setenv(cal.ENV_TABLE, str(path))
        assert cal.lookup_winner("cpu", 1 << 12, 1 << 10) == "scatter"
        assert cal.lookup_winner("cpu", 1 << 19, 2048) == "segsum"
        # log2-nearest: D=2^15 ties split by first-wins, D=2^16 -> segsum
        assert cal.lookup_winner("cpu", 1 << 16, 1 << 10) == "segsum"
        # unmeasured backend: None (callers fall back to the guess)
        assert cal.lookup_winner("tpu", 1 << 18, 1 << 10) is None

    def test_missing_or_corrupt_table(self, tmp_path, monkeypatch):
        monkeypatch.setenv(cal.ENV_TABLE, str(tmp_path / "absent.json"))
        assert cal.lookup_winner("cpu", 1 << 18, 1 << 10) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        monkeypatch.setenv(cal.ENV_TABLE, str(bad))
        assert cal.lookup_winner("cpu", 1 << 18, 1 << 10) is None

    def test_auto_dispatch_reads_table(self, tmp_path, monkeypatch):
        """sparse_scatter_add_auto's trace-time resolution follows the
        committed table for the active backend."""
        import jax

        backend = jax.default_backend()
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            backend: {"entries": [_entry(1 << 10, 256, "segsum")]},
        })))
        monkeypatch.setenv(cal.ENV_TABLE, str(path))
        monkeypatch.delenv("OMLDM_SPARSE_SCATTER", raising=False)
        assert _resolve_impl(1 << 10, 256) == "segsum"
        # env knob beats the table
        monkeypatch.setenv("OMLDM_SPARSE_SCATTER", "scatter")
        assert _resolve_impl(1 << 10, 256) == "scatter"


class TestCalibrate:
    def test_measure_entry_covers_all_kernels(self):
        e = cal.measure_entry(256, 16, 4, steps=2)
        assert set(e["rates_updates_per_sec"]) == set(SCATTER_IMPLS)
        assert e["winner"] in SCATTER_IMPLS
        assert e["updates"] == 16 * 4
        assert e["duplicate_factor"] >= 1.0

    def test_calibrate_merges_per_backend(self, tmp_path, monkeypatch):
        """A re-calibration on one backend must not clobber another
        backend's committed section."""
        import jax

        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "faux-tpu": {"entries": [_entry(1 << 18, 1 << 10, "mxu")]},
        })))
        monkeypatch.setenv(cal.ENV_TABLE, str(path))
        table = cal.calibrate([(256, 16, 4)], steps=2)
        assert "faux-tpu" in table["backends"]
        assert jax.default_backend() in table["backends"]
        on_disk = json.loads(path.read_text())
        assert set(on_disk["backends"]) == set(table["backends"])
        [e] = on_disk["backends"][jax.default_backend()]["entries"]
        assert e["winner"] in SCATTER_IMPLS

    def test_committed_table_has_cpu_section(self):
        """The repo ships a calibrated CPU section so the dispatch never
        falls back to the guess on the tier-1 host; the smoke CI run
        regenerates the same shape."""
        table = cal.load_table(cal.DEFAULT_TABLE)
        assert table is not None, "ops/sparse_dispatch.json missing/corrupt"
        cpu = table["backends"].get("cpu")
        assert cpu and cpu["entries"], "no CPU section in committed table"
        for e in cpu["entries"]:
            assert e["winner"] in SCATTER_IMPLS
            assert set(e["rates_updates_per_sec"]) == set(SCATTER_IMPLS)

    def test_tpu_guess_retired(self, tmp_path, monkeypatch):
        """The round-5 ``D >= 2^16 -> mxu`` TPU guess is retired: an
        UNCALIBRATED backend (no table section) resolves to the plain
        scatter at any D — the guessed crossover was never measured, and a
        number nobody measured must not steer the dispatch. A real TPU
        table section, once calibrated, still wins."""
        import jax

        from omldm_tpu.ops import sparse as sp

        monkeypatch.delenv("OMLDM_SPARSE_SCATTER", raising=False)
        monkeypatch.setenv(cal.ENV_TABLE, str(tmp_path / "absent.json"))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert sp._resolve_impl(1 << 20, 1 << 10) == "scatter"
        assert sp._resolve_impl(1 << 10, 1 << 10) == "scatter"
        # a measured tpu section reinstates mxu where it actually won
        path = tmp_path / "table.json"
        path.write_text(json.dumps(_table({
            "tpu": {"entries": [_entry(1 << 20, 1 << 10, "mxu")]},
        })))
        monkeypatch.setenv(cal.ENV_TABLE, str(path))
        assert sp._resolve_impl(1 << 20, 1 << 10) == "mxu"
        # the committed table has no tpu section until a chip run writes one
        committed = cal.load_table(cal.DEFAULT_TABLE)
        assert "tpu" not in committed["backends"]


class TestLearnerWiring:
    def test_sparse_pa_update_honors_scatter_override(self, monkeypatch):
        """The learner hot path reaches sparse_scatter_add_auto; pinning
        the impl via dataStructure.scatterImpl (config twin of the env
        knob) stays numerically inside the twin envelope."""
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
        for impl in ("scatter", "segsum"):
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
            params["segsum"], params["scatter"], rtol=2e-5, atol=2e-5
        )
