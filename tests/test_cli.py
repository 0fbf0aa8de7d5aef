"""CLI entry-point tests: ``python -m omldm_tpu`` file-replay jobs
(the Job.main analogue, reference Job.scala:110-171)."""

import json
import os

import numpy as np
import pytest

from omldm_tpu.__main__ import build_job, combined_events, main, parse_flags


def _write_stream(path, n=800, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    x = rng.randn(n, dim)
    y = (x @ w > 0).astype(float)
    with open(path, "w") as f:
        for i in range(n):
            f.write(
                json.dumps(
                    {
                        "numericalFeatures": list(np.round(x[i], 5)),
                        "target": y[i],
                        "operation": "training",
                    }
                )
                + "\n"
            )
        f.write("EOS\n")
    return x, y


CREATE = {
    "id": 0,
    "request": "Create",
    "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
    "preProcessors": [],
    "trainingConfiguration": {"protocol": "CentralizedTraining"},
}


class TestParseFlags:
    def test_pairs_and_booleans(self):
        flags = parse_flags(
            ["--parallelism", "4", "--test", "--jobName", "run1"]
        )
        assert flags == {"parallelism": "4", "test": "true", "jobName": "run1"}

    def test_rejects_positional(self):
        with pytest.raises(SystemExit):
            parse_flags(["oops"])


class TestFileReplayJob:
    def test_end_to_end_files(self, tmp_path):
        train = tmp_path / "train.jsonl"
        reqs = tmp_path / "requests.jsonl"
        perf = tmp_path / "perf.jsonl"
        _write_stream(str(train))
        reqs.write_text(json.dumps(CREATE) + "\n")
        rc = main(
            [
                "--trainingData", str(train),
                "--requests", str(reqs),
                "--performanceOut", str(perf),
                "--parallelism", "2",
                "--batchSize", "64",
                "--testSetSize", "32",
            ]
        )
        assert rc == 0
        [line] = perf.read_text().strip().splitlines()
        report = json.loads(line)
        [stats] = report["statistics"]
        assert stats["pipeline"] == 0
        assert stats["fitted"] > 400

    def test_fused_route_matches_packed(self, tmp_path):
        """An SPMD-plane file job takes the fused C ingest route and lands
        the same fitted count / score as the packed event route."""
        train = tmp_path / "train.jsonl"
        reqs = tmp_path / "requests.jsonl"
        _write_stream(str(train))
        create = json.loads(json.dumps(CREATE))
        create["trainingConfiguration"] = {
            "protocol": "Synchronous",
            "engine": "spmd",
        }
        create["learner"]["dataStructure"] = {"nFeatures": 6}
        reqs.write_text(json.dumps(create) + "\n")
        reports = {}
        for route, flag in (("fused", "auto"), ("packed", "false")):
            perf = tmp_path / f"perf_{route}.jsonl"
            rc = main(
                [
                    "--trainingData", str(train),
                    "--requests", str(reqs),
                    "--performanceOut", str(perf),
                    "--parallelism", "2",
                    "--batchSize", "64",
                    "--testSetSize", "32",
                    "--fusedIngest", flag,
                ]
            )
            assert rc == 0
            [line] = perf.read_text().strip().splitlines()
            [stats] = json.loads(line)["statistics"]
            reports[route] = stats
        assert reports["fused"]["fitted"] == reports["packed"]["fitted"]
        assert reports["fused"]["score"] == pytest.approx(
            reports["packed"]["score"], rel=1e-5
        )

    def test_combined_events_preserves_order(self, tmp_path):
        combined = tmp_path / "events.jsonl"
        resp_out = tmp_path / "responses.jsonl"
        rng = np.random.RandomState(1)
        dim, n = 4, 600
        w = rng.randn(dim)
        lines = [{"stream": "requests", "data": CREATE}]
        for i in range(n):
            x = rng.randn(dim)
            lines.append(
                {
                    "stream": "trainingData",
                    "data": {
                        "numericalFeatures": list(np.round(x, 5)),
                        "target": float(x @ w > 0),
                        "operation": "training",
                    },
                }
            )
        # Query arrives AFTER training — combined mode must preserve that
        lines.append(
            {
                "stream": "requests",
                "data": {"id": 0, "request": "Query", "requestId": 7},
            }
        )
        combined.write_text("\n".join(json.dumps(l) for l in lines))
        rc = main(
            [
                "--events", str(combined),
                "--responsesOut", str(resp_out),
                "--performanceOut", str(tmp_path / "perf.jsonl"),
                "--parallelism", "1",
                "--batchSize", "32",
            ]
        )
        assert rc == 0
        responses = [
            json.loads(l) for l in resp_out.read_text().strip().splitlines()
        ]
        assert any(r["responseId"] == 7 for r in responses)

    def test_no_sources_exits(self):
        with pytest.raises(SystemExit):
            main(["--parallelism", "2"])


class TestCompileCache:
    """utils/compile_cache.enable_compile_cache: the one place the
    persistent XLA compilation cache is configured."""

    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them (the
        suite's own jax configuration stays untouched)."""
        import jax

        seen = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: seen.__setitem__(k, v)
        )
        return seen

    def test_env_placed_cache_sets_no_directory(
        self, tmp_path, monkeypatch, updates
    ):
        from omldm_tpu.utils import compile_cache as cc

        monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path / "placed"))
        assert cc.enable_compile_cache() == str(tmp_path / "placed")
        assert updates == {}

    def test_default_is_fixed_path_under_checkout(self, monkeypatch, updates):
        import omldm_tpu
        from omldm_tpu.utils import compile_cache as cc

        monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
        checkout = os.path.dirname(os.path.dirname(omldm_tpu.__file__))
        expected = os.path.join(checkout, ".jax_cache")
        assert cc.enable_compile_cache() == expected
        assert updates == {"jax_compilation_cache_dir": expected}
        assert os.path.isdir(expected)

    def test_off_disables_without_touching_directory(self, updates):
        from omldm_tpu.utils import compile_cache as cc

        assert cc.enable_compile_cache("off") is None
        assert updates == {"jax_enable_compilation_cache": False}

    def test_directory_form_is_rejected(self, tmp_path, updates):
        from omldm_tpu.utils import compile_cache as cc

        with pytest.raises(ValueError, match=cc.CACHE_DIR_ENV):
            cc.enable_compile_cache(str(tmp_path))
        assert updates == {}
