"""The looped decoder (``models/ouro.py``, the learner ``LM`` under
``model_type: ouro``) against the benchmark's plain reference
(``perfbench/reference/ouro.py``: float32, Python loops over the loop steps
and the layers, rotary angles in float64, attention as a masked softmax, exit
probabilities multiplied out), on seeded random weights at a small size (four
heads of 16, three layers looped four times, a row that is no multiple of any
block).

Tolerances. With float32 operands program and reference compute the same
mathematics in another order of float32 sums (a written-out backward pass
over two scans against one ``jax.vjp`` a layer application, online softmax
against a whole row, a fused loss against whole logits, log-probabilities
against products): objectives to 1e-5, gradients to 1e-3 of each leaf's norm.
With bfloat16 operands (the model's precision) every matrix product reads
operands rounded to 2^-9: objectives to 2e-3, the update as a whole to 0.1 of
its norm."""

import dataclasses
import hashlib
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.__main__ import build_job
from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.learners.registry import make_learner
from omldm_tpu.models import ouro
from omldm_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = dict(
    model_type="ouro", vocab_size=96, hidden_size=64, intermediate_size=176, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, rms_norm_eps=1e-6,
    rope_theta=1000000, total_ut_steps=4, early_exit_threshold=1.0,
)
L = 150


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ouro_reference", os.path.join(ROOT, "perfbench", "reference", "ouro.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def learner(dtype, lr=1.0, **arch):
    """The learner as a request makes it, its products reading ``dtype``:
    the precision is the program's own and no key of a request."""
    lm = make_learner(LearnerSpec(
        "LM", hyper_parameters={"learningRate": lr, "seed": 3}, data_structure=dict(ARCH, **arch)))
    lm.cfg = dataclasses.replace(lm.cfg, operand_dtype=dtype)
    return lm


def host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def rows(seed, n=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 96, (n, L)), rng.integers(0, 96, (n,))


def norm(tree):
    return np.sqrt(sum(np.sum(np.square(l, dtype=np.float64)) for l in jax.tree_util.tree_leaves(tree)))


def minus(a, b):
    return jax.tree_util.tree_map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "perfbench", "reference", "ouro.py")) as f:
        assert "omldm_tpu" not in f.read().split('"""', 2)[2]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_objective_and_gradients_equal_the_references(ref, dtype, seed):
    lm = learner(dtype)  # learningRate 1: the update is the gradient
    p0 = lm.init(L, jax.random.PRNGKey(seed))
    x, y = rows(seed)
    p1, loss = jax.jit(lm.update)(
        p0, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((1,)))
    model = ref.Model(ARCH, "float32", None)
    rp = model.place(host(p0))
    with jax.default_matmul_precision("highest"):
        want_loss = model.sgd_step(rp, x, y, 1.0)
    assert abs(float(loss) - want_loss) / want_loss < (1e-5 if dtype == "float32" else 2e-3)
    got = minus(p0, p1)
    want = minus(host(p0), model.host(rp))
    diff = minus(got, want)
    if dtype == "float32":
        for (path, d), w in zip(jax.tree_util.tree_flatten_with_path(diff)[0], jax.tree_util.tree_leaves(want)):
            assert np.linalg.norm(d) < 1e-3 * np.linalg.norm(w), jax.tree_util.keystr(path)
    else:
        assert norm(diff) < 0.1 * norm(want)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_loops_gradient_is_the_sum_over_its_four_untied_copies(monkeypatch, seed):
    """The same stack unrolled: ``T x N`` layers with weights of their own,
    copy ``t`` holding the stack's. The looped model's gradient of a weight
    is the sum of the four copies' gradients, and every copy gives one."""
    lm = learner("float32")
    cfg, (t, n) = lm.cfg, (lm.cfg.total_ut_steps, lm.cfg.num_hidden_layers)
    p0 = lm.init(L, jax.random.PRNGKey(seed))
    x, y = rows(seed + 10, n=2)
    args = (jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((2,)))
    tied = jax.jit(jax.grad(lm.loss))(p0, *args)

    def unrolled(cfg, layers, gain, h0):
        rope, hs, h = ouro.rope_angles(cfg, h0.shape[1]), [], h0
        for step in range(t):
            for i in range(n):
                h = ouro._layer(cfg, rope, jax.tree_util.tree_map(lambda w: w[step * n + i], layers), h)
            h = ouro.rms_norm(h, gain, cfg.rms_norm_eps)
            hs.append(h)
        return jnp.stack(hs)

    monkeypatch.setattr(ouro, "loop_steps", unrolled)
    copies = dict(p0, layers=jax.tree_util.tree_map(lambda w: jnp.tile(w, (t,) + (1,) * (w.ndim - 1)), p0["layers"]))
    untied = jax.jit(jax.grad(lm.loss))(copies, *args)
    for (path, got), each in zip(jax.tree_util.tree_flatten_with_path(tied["layers"])[0],
                                 jax.tree_util.tree_leaves(untied["layers"])):
        each = np.asarray(each).reshape((t, n) + each.shape[1:])
        assert all(np.linalg.norm(each[step]) > 0 for step in range(t)), jax.tree_util.keystr(path)
        np.testing.assert_allclose(np.asarray(got), each.sum(0), rtol=0,
                                   atol=2e-5 * np.abs(each).sum(0).max(), err_msg=jax.tree_util.keystr(path))
    for name in ("embed", "head", "norm", "gate"):
        for got, want in zip(jax.tree_util.tree_leaves(tied[name]), jax.tree_util.tree_leaves(untied[name])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5 * np.abs(want).max())


def test_one_loop_step_is_a_plain_decoders_next_token_loss():
    """``total_ut_steps`` 1: the exit distribution is all on the one step (no
    gate is read, no entropy left), and the objective is the mean
    cross-entropy of a plain decoder of ``N`` layers."""
    lm = learner("float32", total_ut_steps=1)
    cfg = lm.cfg
    p = lm.init(L, jax.random.PRNGKey(2))
    x, y = rows(2, n=2)
    got = float(jax.jit(lm.loss)(p, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((2,))))
    h = p["embed"][jnp.asarray(x)]
    rope = ouro.rope_angles(cfg, L)
    for i in range(cfg.num_hidden_layers):
        h = ouro._layer(cfg, rope, jax.tree_util.tree_map(lambda w: w[i], p["layers"]), h)
    logp = jax.nn.log_softmax(ouro.rms_norm(h, p["norm"], cfg.rms_norm_eps) @ p["head"], axis=-1)
    targets = np.concatenate([x[:, 1:], y[:, None]], axis=1)
    want = -float(jnp.mean(jnp.take_along_axis(logp, jnp.asarray(targets)[..., None], axis=-1)))
    assert abs(got - want) < 1e-5 * want
    # and its gate gets no gradient
    g = jax.grad(lm.loss)(p, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), jnp.ones((2,)))
    assert float(jnp.abs(g["gate"]["w"]).max()) == 0.0 and float(g["gate"]["b"][0]) == 0.0


def test_rotary_is_a_complex_rotation():
    cfg = ouro.OuroConfig.from_mapping(ARCH)
    half = cfg.head_dim // 2
    x = np.random.default_rng(0).standard_normal((2, L, cfg.num_attention_heads, cfg.head_dim)).astype(np.float32)
    got = np.asarray(ouro.rotate(jnp.asarray(x), *ouro.rope_angles(cfg, L)))
    angle = np.arange(L)[:, None] * 1e6 ** (-np.arange(half) / half)
    z = (x[..., :half] + 1j * x[..., half:]) * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag], axis=-1), rtol=0, atol=2e-5)
    # position 0 is left alone, and a rotation keeps the norm of every pair
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    np.testing.assert_allclose(np.hypot(got[..., :half], got[..., half:]), np.hypot(x[..., :half], x[..., half:]), rtol=1e-5)


@pytest.mark.parametrize("threshold", [1.0, 0.4])
def test_predict_is_the_argmax_of_the_exit_step(ref, threshold):
    """At the published threshold of 1.0 the exit step is the last: the
    arg-max of ``logits_T``. Under a lower one it is the first step whose
    cumulative exit probability reaches it, as the reference reads it."""
    lm = learner("float32", early_exit_threshold=threshold)
    p = lm.init(L, jax.random.PRNGKey(4))
    x, _ = rows(4, n=3)
    got = np.asarray(jax.jit(lm.predict)(p, jnp.asarray(x, jnp.float32)))
    hs = ouro.hidden_states(lm.cfg, p, jnp.asarray(x))[:, :, -1]  # [T, rows, hidden]
    model = ref.Model(dict(ARCH, early_exit_threshold=threshold), "float32", None)
    rp = model.place(host(p))
    steps = []
    for i in range(3):
        with jax.default_matmul_precision("highest"):
            assert got[i] == np.argmax(model.logits_after(rp, jnp.asarray(x[i], jnp.int32)))
        reached = np.cumsum(np.exp(np.asarray(ouro.exit_log_probs(p["gate"], hs[:, i])))) >= threshold
        steps.append(int(np.argmax(reached)) if reached[:-1].any() else lm.cfg.total_ut_steps - 1)
        assert got[i] == np.argmax(np.asarray(hs[steps[-1], i] @ p["head"]))
    if threshold == 1.0:
        assert steps == [lm.cfg.total_ut_steps - 1] * 3
    else:
        assert min(steps) < lm.cfg.total_ut_steps - 1  # the rule is exercised


@pytest.mark.parametrize("key,value", [("operand_dtype", "float32"), ("loss_chunk", 8),
                                       ("entropy_weight", 0.5), ("beta", 0.5), ("checkpoint", "none")])
def test_a_request_names_published_keys_only(key, value):
    """How the program computes the model (its precision, its loss block, its
    entropy weight, what its backward pass keeps) is no option of the learner:
    such a key in ``dataStructure`` changes nothing."""
    plain = make_learner(LearnerSpec("LM", data_structure=dict(ARCH)))
    keyed = make_learner(LearnerSpec("LM", data_structure=dict(ARCH, **{key: value})))
    assert keyed.cfg == plain.cfg and plain.cfg.operand_dtype == "bfloat16"
    assert {f.name for f in dataclasses.fields(plain.cfg)} == set(ouro.PUBLISHED_KEYS) | {"operand_dtype"}
    assert set(ouro.PUBLISHED_KEYS) | {"model_type"} == set(ARCH)


def test_the_request_picks_the_model():
    from omldm_tpu.models import olmo_hybrid

    assert type(make_learner(LearnerSpec("LM", data_structure=dict(ARCH))).cfg) is ouro.OuroConfig
    for ds in ({}, {"model_type": "olmo_hybrid"}):
        assert type(make_learner(LearnerSpec("LM", data_structure=ds)).cfg) is olmo_hybrid.OlmoHybridConfig
    with pytest.raises(ValueError, match="model_type"):
        make_learner(LearnerSpec("LM", data_structure={"model_type": "gpt"}))
    with pytest.raises(ValueError, match="key-value heads"):
        make_learner(LearnerSpec("LM", data_structure=dict(ARCH, num_key_value_heads=2)))


def test_stream_job_follows_the_reference(ref, tmp_path, monkeypatch):
    """``build_job``, a Create request under ``engine: spmd`` with
    ``model_type: ouro`` and ``run_file_fused`` on JSON token rows: per-step
    objectives, the parameters after the file and a forecast's answer equal
    the reference's; the launch names the model's parts and the trace counted
    the loop. Compared with float32 products (the tolerances above), which
    the test sets on the model's config underneath the request."""
    from_mapping = ouro.OuroConfig.from_mapping
    monkeypatch.setattr(
        ouro.OuroConfig, "from_mapping",
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
    mark = tracing.RECORDER.mark()
    job.process_event("requests", json.dumps(create))
    job.ensure_deployed(L)
    bridge = job.fused_file_bridge()
    assert type(bridge).__name__ == "SPMDBridge" and bridge.supports_overlapped_ingest()
    trainer = bridge.trainer
    assert "center" not in trainer.state and "est" not in trainer.state
    p0 = host(trainer.shard0(jax.device_get(trainer.state["params"])))
    # the layers are stored stacked: one leaf a kind of weight
    assert p0["layers"]["w_gate"].shape == (3, 64, 176) and p0["gate"]["w"].shape == (64,)

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
    assert job.run_file_fused(str(path))
    jax.block_until_ready(trainer.state)
    fits, _, counted, _ = tracing.RECORDER.summary("fit", since=mark)
    assert fits == 4 and counted["rows"] == 4 and counted["tokens"] == 4 * L
    # every traced model counted its loop once: four steps over three layers
    loops = tracing.RECORDER.summary("lm_loop", since=mark)[2]
    assert loops["ut_steps"] > 0 and loops["ut_steps"] * 3 == loops["layers"] * 4
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
    after = model.host(rp)
    # the four updates together, to 1e-3 of their norm
    assert norm(minus(got, after)) < 1e-3 * norm(minus(after, p0))
    job.terminate()

    # the launch's operations carry the model's scopes
    lm = trainer.learner
    text = jax.jit(lm.update).lower(
        jax.tree_util.tree_map(jnp.asarray, p0), jnp.zeros((1, L)), jnp.zeros((1,)), jnp.ones((1,))
    ).compile().as_text()
    assert {"embed", "attn_proj", "rope", "flash_attn", "ffn", "head_loss", "exit_gate", "sgd"} <= set(
        re.findall(r"omldm\.lm\.([a-z_]+)", text))


# sha256 of ``str(jax.make_jaxpr(...))`` of ``LM.update`` and of ``LM.init`` for the
# Olmo model below, taken on the parent of PR 36 (commit 8cf7e0d) with object
# addresses struck out
PARENT_OLMO_JAXPRS = {"update": "5f54b6856b0fc79caa9f7f9dcbb0260be38e0abd7cba3e5aa2962fb4f6f19508",
                      "init": "3ce19fe640d9a6d6893e6d793b1a47bfb2cdfd1da036ce6ba12a6050bfe553b9"}


def test_the_olmo_learners_update_is_the_parents():
    """What both models share moved to ``models/blocks.py``; a request
    without ``model_type`` still traces, equation for equation, the initial
    weights and the update the parent traced: so on any backend it computes
    the parent's bits (on this CPU the updated parameters and the loss were
    compared bit for bit beside it: ``CHANGES.md``, PR 36)."""
    arch = dict(
        vocab_size=96, hidden_size=32, intermediate_size=80, num_attention_heads=2,
        layer_types=["linear_attention"] * 3 + ["full_attention"], linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rms_norm_eps=1e-6)
    lm = make_learner(LearnerSpec("LM", hyper_parameters={"learningRate": 0.05, "seed": 3}, data_structure=arch))
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 96, (2, 150)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 96, (2,)), jnp.float32)
    traced = {"init": jax.make_jaxpr(lambda k: lm.init(150, k))(key),
              "update": jax.make_jaxpr(lm.update)(lm.init(150, key), x, y, jnp.ones((2,)))}
    for name, jaxpr in traced.items():
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT_OLMO_JAXPRS[name], name
