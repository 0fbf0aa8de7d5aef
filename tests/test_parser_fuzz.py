"""Fuzz the native bulk-ingest parser against the Python codec.

The C++ parser must NEVER crash, and for every line it must either (a)
produce exactly what `DataInstance.from_json` + `Vectorizer` produce, or
(b) flag the line for the Python fallback / drop it — the same contract
`tests/test_packed_path.py` pins on well-formed streams, here pushed
through mutated/garbage input (truncation, byte flips, spliced structure,
huge numbers, unicode)."""

import json

import numpy as np
import pytest

from omldm_tpu.api.data import FORECASTING, DataInstance
from omldm_tpu.runtime.fast_ingest import PackedBatcher
from omldm_tpu.runtime.vectorizer import Vectorizer


DIM = 8


def reference_rows(block: bytes):
    """What the pure-Python path produces for a byte block (including the
    float32-range clamp both production paths apply to targets)."""
    from omldm_tpu.runtime.vectorizer import F32_MAX

    vec = Vectorizer(DIM, 0)
    xs, ys, ops = [], [], []
    for line in block.split(b"\n"):
        inst = DataInstance.from_json(line.decode("utf-8", errors="replace"))
        if inst is None:
            continue
        xs.append(vec.vectorize(inst))
        ys.append(
            0.0 if inst.target is None
            else min(max(float(inst.target), -F32_MAX), F32_MAX)
        )
        ops.append(1 if inst.operation == FORECASTING else 0)
    if not xs:
        return (
            np.zeros((0, DIM), np.float32),
            np.zeros((0,), np.float32),
            np.zeros((0,), np.uint8),
        )
    return np.stack(xs), np.asarray(ys, np.float32), np.asarray(ops, np.uint8)


def packed_rows(block: bytes):
    b = PackedBatcher(DIM, batch_size=1 << 20)
    list(b.feed(block))
    tail = b.flush()
    if tail is None:
        return (
            np.zeros((0, DIM), np.float32),
            np.zeros((0,), np.float32),
            np.zeros((0,), np.uint8),
        )
    return tail


def make_lines(rng, n):
    """Valid lines + adversarial mutations."""
    lines = []
    for i in range(n):
        kind = rng.randint(0, 10)
        x = np.round(rng.randn(rng.randint(1, DIM + 1)), 5)
        base = {"numericalFeatures": list(x), "target": float(i % 2)}
        if kind == 0:
            lines.append(json.dumps(base))
        elif kind == 1:  # forecast record
            lines.append(json.dumps({"numericalFeatures": list(x),
                                     "operation": "forecasting"}))
        elif kind == 2:  # truncate a valid line at a random byte
            s = json.dumps(base)
            lines.append(s[: rng.randint(0, len(s))])
        elif kind == 3:  # flip one byte of a valid line
            s = bytearray(json.dumps(base).encode())
            s[rng.randint(0, len(s))] = rng.randint(1, 255)
            lines.append(s.decode("utf-8", errors="replace"))
        elif kind == 4:  # huge / extreme numbers
            lines.append(json.dumps({
                "numericalFeatures": [1e308, -1e308, 1e-320, 0.0],
                "target": 12345678901234567890.0,
            }))
        elif kind == 5:  # string-typed numerics, nulls
            lines.append(
                '{"numericalFeatures": ["1.5", null, 2], "target": "0"}'
            )
        elif kind == 6:  # nested garbage / unknown keys
            lines.append(json.dumps({
                "numericalFeatures": list(x),
                "metadata": {"a": [1, {"b": 2}]},
                "target": 1.0,
            }))
        elif kind == 7:  # categorical features (python-fallback route)
            lines.append(json.dumps({
                "numericalFeatures": list(x),
                "categoricalFeatures": ["a", "b"],
                "target": 0.0,
            }))
        elif kind == 8:  # pure garbage
            raw = bytes(rng.randint(1, 255, size=rng.randint(1, 40)))
            lines.append(raw.decode("utf-8", errors="replace")
                         .replace("\n", " "))
        else:  # EOS markers and blanks
            lines.append(rng.choice(["EOS", '"EOS"', "", "   "]))
    # deterministic adversarial grammar cases (strict json.loads drops and
    # near-misses that must stay keeps), shuffled into the stream
    lines.extend([
        '{"numericalFeatures": [.5, 2.0], "target": 1.0}',     # drop
        '{"numericalFeatures": [1., 2.0], "target": 1.0}',     # drop
        '{"numericalFeatures": [01.0, 2.0], "target": 1.0}',   # drop
        '{"numericalFeatures": [+1.5, 2.0], "target": 1.0}',   # drop
        '{"numericalFeatures": [-0.5, 0.0, 0], "target": 1.0}',  # keep
        '{"numericalFeatures": [1.0], "k": "a\\qb", "target": 1.0}',  # drop
        '{"numericalFeatures": [1.0], "k": "a\\u12зb", "target": 1.0}',  # drop
        '{"numericalFeatures": [1.0], "k": "a\\u12ab\\n", "target": 1.0}',  # keep
        '{"numericalFeatures": [1.0, 2.0], "target": 1.0}\x0c',  # keep
        '{"numericalFeatures": [1.0, 2.0], "target": 1.0}\x1d',  # keep
        '{"numericalFeatures": [1.0, 2.0], "target": 1.0} x',  # drop
        '{"numericalFeatures": [1.0, 2.0], "target": 1.0',     # drop
        '{"numericalFeatures": [1e3, 1E+2, 1e-2], "target": 0.0}',  # keep
        '{"numericalFeatures": [1e, 2.0], "target": 1.0}',     # drop
        # object-level grammar (comma discipline)
        '{"numericalFeatures": [1.0, 2.0] "target": 1.0}',     # drop
        '{"numericalFeatures": [1.0], "target": 1.0,}',        # drop
        '{,"numericalFeatures": [1.0]}',                       # drop
        '{"numericalFeatures": [1.0], , "target": 1.0}',       # drop
        # unknown-key values must be valid JSON; composites defer to Python
        '{"numericalFeatures": [1.0], "zz": blah garbage, "target": 1.0}',
        '{"numericalFeatures": [1.0], "zz": true, "id": null, "w": false}',
        '{"numericalFeatures": [1.0], "zz": {"n": [1, "x"]}, "target": 1.0}',
        # overflow under an ignored key: json.loads -> inf, record KEPT
        '{"numericalFeatures": [1.0], "zz": 1e999, "target": 1.0}',
        '{"numericalFeatures": [1.0], "id": 1e1234567, "target": 1.0}',
        # overflow in FEATURES: is_valid rejects non-finite -> drop
        '{"numericalFeatures": [1e999], "target": 1.0}',
        # finite-but-beyond-float32 magnitudes: KEPT, clamped to +/-FLT_MAX
        # identically by the C parser and the Python boundary (no inf may
        # reach device state)
        '{"numericalFeatures": [1e308, -4e38], "target": 1e308}',
        '{"numericalFeatures": [3.5e38], "target": -1e40}',
        '{"numericalFeatures": [1.0], "target": 4.1e38}',
        # operation: exact spelling, last key wins, non-strings drop
        '{"numericalFeatures": [1.0], "operation": "forecaster"}',  # drop
        '{"numericalFeatures": [1.0], "operation": "forecasting"}',  # keep
        '{"numericalFeatures": [1.0], "operation": "training", '
        '"operation": "bogus"}',                               # drop
        '{"numericalFeatures": [1.0], "operation": 5}',        # drop
        # target coercion corners (the codec's float() decides)
        '{"numericalFeatures": [1.0], "target": null}',        # keep
        '{"numericalFeatures": [1.0], "target": "0"}',         # keep!
        '{"numericalFeatures": [1.0], "target": "x"}',         # drop
        '{"numericalFeatures": [1.0], "target": true}',        # keep!
        '{"numericalFeatures": [1.0], "target": 1.0, "target": null}',
    ])
    rng.shuffle(lines)
    return lines


@pytest.mark.parametrize("seed", range(8))
def test_fuzzed_blocks_match_python_codec(seed):
    rng = np.random.RandomState(seed)
    block = ("\n".join(make_lines(rng, 300)) + "\n").encode()
    px, py, pop = packed_rows(block)
    rx, ry, rop = reference_rows(block)
    assert px.shape == rx.shape
    np.testing.assert_allclose(px, rx, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(py, ry, rtol=1e-6, atol=0)
    np.testing.assert_array_equal(pop, rop)


@pytest.mark.parametrize("seed", range(4))
def test_template_shape_mutations_match_python_codec(seed):
    """The whole-line schema-template fast path (fastparse.cpp) must agree
    with the general walk AND the Python codec on near-misses of its exact
    shape: every mutation must fall through to identical semantics."""
    rng = np.random.RandomState(1000 + seed)
    base = (
        '{"numericalFeatures": [%s], "target": %s, '
        '"operation": "training"}'
    )
    lines = []
    for _ in range(200):
        vals = ", ".join(
            "%.6f" % v for v in rng.randn(rng.randint(1, 8))
        )
        line = base % (vals, "%.1f" % rng.rand())
        r = rng.rand()
        if r < 0.5:
            lines.append(line)  # exact template shape
        elif r < 0.7:  # single-byte mutation anywhere
            i = rng.randint(len(line))
            line = line[:i] + chr(rng.randint(32, 127)) + line[i + 1 :]
            lines.append(line)
        elif r < 0.8:  # truncation
            lines.append(line[: rng.randint(1, len(line))])
        elif r < 0.9:  # trailing junk / whitespace
            lines.append(line + rng.choice([" ", "\t", " x", "\x0c", "}"]))
        else:  # near-miss keys and values
            lines.append(
                line.replace("training", rng.choice(
                    ["Training", "training ", "train", "forecasting"]
                ))
            )
    block = ("\n".join(lines) + "\n").encode()
    px, py, pop = packed_rows(block)
    rx, ry, rop = reference_rows(block)
    assert px.shape == rx.shape
    np.testing.assert_allclose(px, rx, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(py, ry, rtol=1e-6, atol=0)
    np.testing.assert_array_equal(pop, rop)


def test_binary_garbage_never_crashes():
    rng = np.random.RandomState(99)
    blob = bytes(rng.randint(0, 256, size=100_000, dtype=np.uint8).data)
    x, y, op = packed_rows(blob)  # must not raise
    # and whatever it kept, the python codec would have kept too
    rx, _, _ = reference_rows(blob)
    assert x.shape == rx.shape


def test_request_codec_fuzz_never_raises():
    """Request.from_json mirrors RequestParser.scala:12-17: malformed
    requests drop silently — no mutation may raise. A full StreamJob must
    likewise survive a hostile request stream without deploying anything
    invalid."""
    from omldm_tpu.api.requests import Request
    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM

    base = {
        "id": 0,
        "request": "Create",
        "learner": {"name": "PA", "hyperParameters": {"C": 1.0}},
        "trainingConfiguration": {"protocol": "Synchronous"},
    }
    rng = np.random.RandomState(7)
    payloads = []
    for i in range(400):
        kind = rng.randint(0, 8)
        if kind == 0:
            payloads.append(json.dumps(base))
        elif kind == 1:  # byte flip
            s = bytearray(json.dumps(base).encode())
            s[rng.randint(0, len(s))] = rng.randint(1, 255)
            payloads.append(s.decode("utf-8", errors="replace"))
        elif kind == 2:  # truncation
            s = json.dumps(base)
            payloads.append(s[: rng.randint(0, len(s))])
        elif kind == 3:  # wrong types
            payloads.append(json.dumps({
                "id": "zero", "request": 5, "learner": "PA",
            }))
        elif kind == 4:  # unknown request kinds / missing fields
            payloads.append(json.dumps({"id": i, "request": "Explode"}))
        elif kind == 5:  # deep nesting
            payloads.append(json.dumps({
                "id": i % 4, "request": "Query",
                "requestId": i,
                "learner": {"name": "PA", "dataStructure": {"a": [[[1]]]}},
            }))
        elif kind == 6:  # non-object JSON
            payloads.append(rng.choice(["[]", "5", '"x"', "null", "true"]))
        else:  # binary garbage
            raw = bytes(rng.randint(1, 255, size=rng.randint(1, 50)))
            payloads.append(raw.decode("utf-8", errors="replace"))
    for text in payloads:
        Request.from_json(text)  # must not raise
    job = StreamJob(JobConfig(parallelism=1))
    for text in payloads:
        job.process_event(REQUEST_STREAM, text)  # must not raise
    # nothing hostile deployed except well-formed Creates (id 0)
    assert set(job.pipeline_manager.live_pipelines) <= {0}


@pytest.mark.parametrize("seed", range(2))
def test_fuzzed_stream_quarantined_not_silently_dropped(seed):
    """Every fuzzed-invalid record fed through the per-record JSON route
    must land in the dead-letter sink with a reason code (EOS markers and
    blank lines are protocol, not poison), must never crash the job, and
    must never mutate model state — the quarantine twin of the reference's
    silent ``DataInstance.isValid`` drop (DataPointParser.scala:13-21)."""
    from omldm_tpu.config import JobConfig
    from omldm_tpu.runtime import StreamJob
    from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM

    rng = np.random.RandomState(500 + seed)
    lines = make_lines(rng, 150)

    # the reference verdict per line, via the SAME parse the job uses
    expected_reasons = []
    n_valid = 0
    for line in lines:
        inst, reason = DataInstance.parse(line)
        if reason is not None:
            expected_reasons.append(reason)
        elif inst is not None:
            n_valid += 1

    def run(stream_lines):
        job = StreamJob(JobConfig(parallelism=1, batch_size=8, test=False))
        job.process_event(REQUEST_STREAM, json.dumps({
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "hyperParameters": {"C": 1.0},
                        "dataStructure": {"nFeatures": DIM}},
            "trainingConfiguration": {"protocol": "Asynchronous"},
        }))
        for line in stream_lines:
            job.process_event(TRAINING_STREAM, line)  # must not raise
        return job

    job = run(lines)
    assert job.dead_letter.record_count == len(expected_reasons)
    assert [e["reason"] for e in job.dead_letter.entries] == expected_reasons
    assert all(e["payload"] for e in job.dead_letter.entries)
    # invalid records never mutate model state: the mixed stream's final
    # params equal a valid-only replay's, bitwise
    valid_only = [l for l in lines if DataInstance.parse(l)[0] is not None]
    assert len(valid_only) == n_valid
    job_valid = run(valid_only)
    np.testing.assert_array_equal(
        job.spokes[0].nets[0].pipeline.get_flat_params()[0],
        job_valid.spokes[0].nets[0].pipeline.get_flat_params()[0],
    )


def test_cli_never_selects_a_platform(tmp_path, monkeypatch):
    """main() runs on the platform jax was given (JAX_PLATFORMS, or the
    accelerator it finds) and announces it; it never switches platforms in
    code, so a run that lost its chip cannot quietly continue on the CPU."""
    import jax

    from omldm_tpu.__main__ import main

    updates = []
    real_update = jax.config.update

    def recording_update(key, value):
        updates.append(key)
        return real_update(key, value)

    monkeypatch.setattr(jax.config, "update", recording_update)
    events = tmp_path / "events.jsonl"
    events.write_text("")
    assert main(["--events", str(events), "--performanceOut",
                 str(tmp_path / "perf.jsonl")]) == 0
    assert "jax_platforms" not in updates
