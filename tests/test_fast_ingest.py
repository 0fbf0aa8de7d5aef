"""Native parser + bulk ingest tests: parity with the Python codec path."""

import json

import numpy as np
import pytest

from omldm_tpu.ops.native import FastParser, fast_parser_available
from omldm_tpu.runtime.fast_ingest import iter_file_batches

needs_native = pytest.mark.skipif(
    not fast_parser_available(), reason="g++ toolchain unavailable"
)


@needs_native
class TestFastParser:
    def test_training_record(self):
        p = FastParser(4)
        x, y, op, valid = p.parse(
            b'{"numericalFeatures": [1.5, -2.0, 3.25], "target": 1.0, "operation": "training"}\n'
        )
        assert valid[0] == 1
        assert op[0] == 0
        np.testing.assert_allclose(x[0], [1.5, -2.0, 3.25, 0.0])
        assert y[0] == 1.0

    def test_forecasting_and_discrete(self):
        p = FastParser(5)
        x, y, op, valid = p.parse(
            b'{"numericalFeatures": [1.0], "discreteFeatures": [2, 3], "operation": "forecasting"}\n'
        )
        assert valid[0] == 1 and op[0] == 1
        np.testing.assert_allclose(x[0], [1.0, 2.0, 3.0, 0.0, 0.0])

    def test_drop_semantics_match_python(self):
        # EOS, blank, garbage, NaN, featureless -> dropped outright; a
        # string target defers to the Python codec (valid=2), whose
        # float() coercion decides — float("high") raises, so the
        # fallback drops it (float("0") would keep; pinned by the fuzz
        # parity suite)
        lines = (
            b"EOS\n"
            b"\n"
            b"garbage {\n"
            b'{"numericalFeatures": [NaN], "target": 1.0}\n'
            b'{"operation": "training"}\n'
            b'{"numericalFeatures": [1.0], "target": "high"}\n'
        )
        p = FastParser(3)
        x, y, op, valid = p.parse(lines)
        assert valid.tolist() == [0, 0, 0, 0, 0, 2]

    def test_fallback_flag_for_categorical(self):
        p = FastParser(3)
        _, _, _, valid = p.parse(
            b'{"numericalFeatures": [1.0], "categoricalFeatures": ["a"], "target": 0}\n'
        )
        assert valid[0] == 2  # python fallback

    def test_truncates_to_dim(self):
        p = FastParser(2)
        x, y, op, valid = p.parse(
            b'{"numericalFeatures": [1, 2, 3, 4], "target": 1}\n'
        )
        assert valid[0] == 1
        np.testing.assert_allclose(x[0], [1.0, 2.0])


class TestIterFileBatches:
    def test_matches_python_path(self, tmp_path):
        rng = np.random.RandomState(0)
        rows = []
        for i in range(1000):
            rows.append(
                {
                    "numericalFeatures": list(np.round(rng.randn(6), 4)),
                    "target": float(i % 2),
                    "operation": "training" if i % 3 else "forecasting",
                }
            )
        path = tmp_path / "stream.jsonl"
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
            f.write("EOS\n")

        got_x, got_y, got_op = [], [], []
        for x, y, op in iter_file_batches(str(path), dim=6, batch_size=128):
            got_x.append(x)
            got_y.append(y)
            got_op.append(op)
        X = np.concatenate(got_x)
        Y = np.concatenate(got_y)
        OP = np.concatenate(got_op)
        assert X.shape == (1000, 6)
        np.testing.assert_allclose(
            X, [r["numericalFeatures"] for r in rows], atol=1e-6
        )
        np.testing.assert_allclose(Y, [r["target"] for r in rows])
        assert OP.tolist() == [0 if i % 3 else 1 for i in range(1000)]

    def test_mixed_fallback_records(self, tmp_path):
        path = tmp_path / "mixed.jsonl"
        with open(path, "w") as f:
            f.write('{"numericalFeatures": [1, 2], "target": 1}\n')
            f.write(
                '{"numericalFeatures": [3], "categoricalFeatures": ["x"], "target": 0}\n'
            )
            f.write("junk\n")
            f.write('{"numericalFeatures": [5, 6], "target": 0}\n')
        batches = list(iter_file_batches(str(path), dim=4, batch_size=8, hash_dims=2))
        x, y, op = batches[0]
        assert x.shape[0] == 3  # junk dropped; categorical went via fallback
        np.testing.assert_allclose(x[0], [1, 2, 0, 0])
        assert x[1][0] == 3.0 and np.abs(x[1][2:]).sum() > 0  # hashed cat
        np.testing.assert_allclose(x[2], [5, 6, 0, 0])


class TestHashDimsLayout:
    def test_c_and_python_paths_agree_with_hash_dims(self):
        """Dense features must stay in the first dim - hash_dims slots on
        BOTH parse paths; the trailing hashed-categorical region is reserved
        (regression: the C parser used to pack into the full width)."""
        from omldm_tpu.runtime.fast_ingest import PackedBatcher

        line = b'{"numericalFeatures": [1, 2, 3], "target": 1}\n'
        with_parser = PackedBatcher(dim=4, batch_size=1, hash_dims=2)
        without = PackedBatcher(dim=4, batch_size=1, hash_dims=2)
        without.parser = None  # force the Python fallback
        if with_parser.parser is None:
            import pytest

            pytest.skip("native parser unavailable")
        (bx, _, _), = list(with_parser.feed(line))
        (px, _, _), = list(without.feed(line))
        np.testing.assert_allclose(bx, px)
        np.testing.assert_allclose(bx[0], [1.0, 2.0, 0.0, 0.0])


class TestNativeBuildCache:
    """The library's file name is a hash of the source bytes, the compile
    flags and the host tag, so an object copied in with the tree (or left
    from older source) is never loaded for this source."""

    def test_name_follows_source_bytes_and_flags(self, tmp_path, monkeypatch):
        from omldm_tpu.ops.native import loader

        flags = loader._FLAG_SETS[0]
        committed = loader._lib_path(flags)
        assert loader._lib_path(flags) == committed  # a fixed name per triple
        assert loader._lib_path(loader._FLAG_SETS[1]) != committed
        edited = tmp_path / "fastparse.cpp"
        with open(loader._SRC, "rb") as f:
            edited.write_bytes(f.read() + b"\n// one more line\n")
        monkeypatch.setattr(loader, "_SRC", str(edited))
        assert loader._lib_path(flags) != committed

    def test_failed_build_warns_and_falls_back(self, tmp_path, monkeypatch):
        from omldm_tpu.ops.native import loader

        # no compiler on PATH, and an empty build directory
        monkeypatch.setenv("PATH", str(tmp_path))
        monkeypatch.setattr(loader, "_BUILD_DIR", str(tmp_path / "_build"))
        with pytest.warns(RuntimeWarning, match="native parser did not build"):
            assert loader._compile() is None
