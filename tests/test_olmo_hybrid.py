"""The hybrid decoder (``models/olmo_hybrid.py``, the learner ``LM``) against
the benchmark's plain reference (``perfbench/reference/olmo_hybrid.py``: float32,
the delta rule one position at a time, attention as a masked softmax), on
seeded random weights at a small size in the published ratios (keys half as
wide as values, three linear layers to one full, a row that is no multiple of
the delta rule's chunk).

Tolerances. With float32 operands program and reference compute the same
mathematics in another order of float32 sums (chunks against positions,
online softmax against a whole row, a fused loss against whole logits): losses
to 1e-5, gradients to 1e-3 of each leaf's norm (the smallest leaves, ``A_log``
and ``dt_bias``, collect the most cancellation). With bfloat16 operands (the
model's precision) every matrix product reads operands rounded to 2^-9:
losses to 2e-3, the update as a whole to 0.1 of its norm."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.__main__ import build_job
from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.learners.registry import make_learner
from omldm_tpu.models import olmo_hybrid
from omldm_tpu.ops import delta_rule
from omldm_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = dict(
    vocab_size=96, hidden_size=32, intermediate_size=80, num_attention_heads=2,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
)
L = 150


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference",
        os.path.join(ROOT, "perfbench", "reference", "olmo_hybrid.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def learner(dtype, lr=1.0):
    """The learner as a request makes it, its products reading ``dtype``:
    the precision is the program's own and no key of a request."""
    lm = make_learner(LearnerSpec(
        "LM", hyper_parameters={"learningRate": lr, "seed": 3}, data_structure=dict(ARCH)))
    lm.cfg = dataclasses.replace(lm.cfg, operand_dtype=dtype)
    return lm


def host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def rows(seed, n=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 96, (n, L)), rng.integers(0, 96, (n,))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_and_gradients_equal_the_references(ref, dtype):
    lm = learner(dtype)  # learningRate 1: the update is the gradient
    p0 = lm.init(L, jax.random.PRNGKey(0))
    x, y = rows(0)
    p1, loss = jax.jit(lm.update)(
        p0, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((1,)))
    model = ref.Model(ARCH, "float32", None)
    rp = model.place(host(p0))
    with jax.default_matmul_precision("highest"):
        want_loss = model.sgd_step(rp, x, y, 1.0)
    assert abs(float(loss) - want_loss) / want_loss < (1e-5 if dtype == "float32" else 2e-3)
    got = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), p0, p1)
    want = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), host(p0), host(rp))
    norm = lambda t: np.sqrt(sum(np.sum(np.square(l, dtype=np.float64)) for l in jax.tree_util.tree_leaves(t)))
    diff = jax.tree_util.tree_map(lambda a, b: a - b, got, want)
    if dtype == "float32":
        for (path, d), w in zip(jax.tree_util.tree_flatten_with_path(diff)[0], jax.tree_util.tree_leaves(want)):
            assert np.linalg.norm(d) < 1e-3 * np.linalg.norm(w), jax.tree_util.keystr(path)
    else:
        assert norm(diff) < 0.1 * norm(want)


def traced_paths(mark):
    counts = tracing.RECORDER.summary("delta_rule_path", since=mark)[2]
    return {k: v for k, v in counts.items() if v}


def test_the_delta_rule_kernels_give_the_fallbacks_loss_and_gradients(monkeypatch):
    """``LM.update`` at the model's precision through either path of the
    delta rule. On the CPU the dispatcher takes ``jax.numpy``, and says so in
    the recorder, once a linear layer. Told the backend is a TPU (attention
    kept off its own kernels, the delta rule's interpreted) it takes the
    kernels, under the model's own ``jax.checkpoint``: same loss, same
    update, within what bfloat16 operands allow."""
    lm = learner("bfloat16")  # learningRate 1: the update is the gradient
    p0 = lm.init(L, jax.random.PRNGKey(0))
    x, y = rows(4)
    args = (p0, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((1,)))
    mark = tracing.RECORDER.mark()
    p_jnp, loss_jnp = jax.jit(lm.update)(*args)
    assert traced_paths(mark) == {"jnp": 3, "chunks": 3 * 3, "heads": 3 * 2}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(olmo_hybrid, "attention",
                        functools.partial(olmo_hybrid.attention, use_pallas=False))
    monkeypatch.setattr(delta_rule, "gated_delta_rule_pallas",
                        functools.partial(delta_rule.gated_delta_rule_pallas, interpret=True))
    mark = tracing.RECORDER.mark()
    p_pallas, loss_pallas = jax.jit(lambda *a: lm.update(*a))(*args)
    assert traced_paths(mark) == {"pallas": 3, "chunks": 3 * 3, "heads": 3 * 2}

    assert abs(float(loss_pallas) - float(loss_jnp)) / float(loss_jnp) < 2e-3
    norm = lambda t: np.sqrt(sum(np.sum(np.square(l, dtype=np.float64)) for l in jax.tree_util.tree_leaves(t)))
    change = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), p_jnp, host(p0))
    apart = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), p_pallas, p_jnp)
    assert norm(apart) < 0.1 * norm(change)


def test_masked_rows_contribute_nothing(ref):
    lm = learner("float32", lr=0.1)
    p0 = lm.init(L, jax.random.PRNGKey(0))
    x, y = rows(1, n=3)
    x, y = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
    update = jax.jit(lm.update)
    p_all, loss_all = update(p0, x, y, jnp.asarray([1.0, 1.0, 0.0]))
    p_two, loss_two = update(p0, x[:2], y[:2], jnp.ones((2,)))
    assert abs(float(loss_all) - float(loss_two)) < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(p_all), jax.tree_util.tree_leaves(p_two)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)
    p_none, loss_none = update(p0, x, y, jnp.zeros((3,)))
    assert float(loss_none) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p_none), jax.tree_util.tree_leaves(p0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_merge_averages_the_models():
    lm = learner("float32")
    a, b = lm.init(L, jax.random.PRNGKey(0)), lm.init(L, jax.random.PRNGKey(1))
    m = lm.merge([a, b])
    np.testing.assert_allclose(np.asarray(m["head"]), (np.asarray(a["head"]) + np.asarray(b["head"])) / 2)


def test_an_optimizer_with_state_is_refused():
    with pytest.raises(ValueError, match="sgd"):
        make_learner(LearnerSpec("LM", hyper_parameters={"optimizer": "adam"}))


@pytest.mark.parametrize("key,value", [("operand_dtype", "float32"), ("chunk", 16), ("loss_chunk", 8)])
def test_a_request_names_published_keys_only(key, value):
    """How the program computes the model (its precision, its block sizes) is
    no option of the learner: such a key in ``dataStructure`` changes nothing."""
    plain = make_learner(LearnerSpec("LM", data_structure=dict(ARCH)))
    keyed = make_learner(LearnerSpec("LM", data_structure=dict(ARCH, **{key: value})))
    assert keyed.cfg == plain.cfg and plain.cfg.operand_dtype == "bfloat16"
    assert not hasattr(plain.cfg, "chunk") and not hasattr(plain.cfg, "loss_chunk")


def test_stream_job_follows_the_reference(ref, tmp_path, monkeypatch):
    """``build_job``, a Create request under ``engine: spmd`` and
    ``run_file_fused`` on JSON token rows: per-step losses, the parameters
    after the file and a forecast's answer equal the reference's. Compared
    with float32 products (the tolerances above), which the test sets on the
    model's config underneath the request."""
    from_mapping = olmo_hybrid.OlmoHybridConfig.from_mapping
    monkeypatch.setattr(
        olmo_hybrid.OlmoHybridConfig, "from_mapping",
        lambda m: dataclasses.replace(from_mapping(m), operand_dtype="float32"))
    create = {
        "id": 0, "request": "Create",
        "learner": {"name": "LM",
                    "hyperParameters": {"learningRate": 0.05, "optimizer": "sgd", "seed": 7},
                    "dataStructure": dict(ARCH, nFeatures=L)},
        "preProcessors": [],
        "trainingConfiguration": {"protocol": "Synchronous", "engine": "spmd",
                                  "extra": {"stageChain": 1}},
    }
    job, _ = build_job({"parallelism": "1", "batchSize": "1", "test": "false"})
    preds = []
    job.set_sinks(on_prediction=preds.append, on_response=lambda r: None,
                  on_performance=lambda r: None)
    job.process_event("requests", json.dumps(create))
    job.ensure_deployed(L)
    bridge = job.fused_file_bridge()
    assert type(bridge).__name__ == "SPMDBridge" and bridge.supports_overlapped_ingest()
    trainer = bridge.trainer
    assert "center" not in trainer.state and "est" not in trainer.state
    p0 = host(trainer.shard0(jax.device_get(trainer.state["params"])))

    x, y = rows(2, n=4)
    forecast = np.random.default_rng(5).integers(0, 96, (L,))
    lines = []
    for i in range(4):
        lines.append(json.dumps({"numericalFeatures": x[i].tolist(), "target": int(y[i]),
                                 "operation": "training"}))
        if i == 2:
            lines.append(json.dumps({"numericalFeatures": forecast.tolist(),
                                     "operation": "forecasting"}))
    path = tmp_path / "rows.jsonl"
    path.write_text("\n".join(lines) + "\n")
    mark = tracing.RECORDER.mark()
    assert job.run_file_fused(str(path))
    jax.block_until_ready(trainer.state)
    # the dense route's spans: a launch is one row, a fit counts its tokens
    fits, _, counted, _ = tracing.RECORDER.summary("fit", since=mark)
    assert fits == 4 and counted["rows"] == 4 and counted["tokens"] == 4 * L
    assert tracing.RECORDER.summary("parse_stage", since=mark)[2]["rows"] == 4
    assert tracing.RECORDER.summary("ingest_file", since=mark)[2]["rows"] == 4
    losses = [l for l, _ in trainer.curve_slice()]
    assert trainer.fitted == 4 and len(losses) == 4 and len(preds) == 1

    model = ref.Model(ARCH, "float32", None)
    rp = model.place(p0)
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            want = model.sgd_step(rp, x[i : i + 1], y[i : i + 1], 0.05)
            assert abs(losses[i] - want) / want < 1e-5, i
            if i == 2:
                logits = model.logits_after(rp, jnp.asarray(forecast, jnp.int32))
                assert float(preds[0].value) == float(np.argmax(logits))
    got = host(trainer.shard0(jax.device_get(trainer.state["params"])))
    change = np.sqrt(sum(np.sum(np.square(a - b, dtype=np.float64)) for a, b in zip(
        jax.tree_util.tree_leaves(host(rp)), jax.tree_util.tree_leaves(p0))))
    apart = np.sqrt(sum(np.sum(np.square(a - b, dtype=np.float64)) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(host(rp)))))
    # the four updates together, to 1e-3 of their norm
    assert apart < 1e-3 * change
    job.terminate()
