"""The stored form of the SPMD trainer's state and the one view of it
(``parallel/spmd.py``: ``shard_value`` / ``shard_block``, ``stacked`` /
``stored``): vector leaves live flat so that the step and the serve programs
read and write them in place, and every reader sees the same values as what
the mesh shards hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from omldm_tpu.api.requests import (
    LearnerSpec, PreprocessorSpec, TrainingConfiguration,
)
from omldm_tpu.parallel import SPMDTrainer, make_mesh
from omldm_tpu.parallel.ckpt import save_tree
from omldm_tpu.parallel.spmd import SPMD_PROTOCOLS, stacked, stored

SYNC_EVERY = 3
# the protocols whose step reads ``est`` (drift under GM / FGM, the delta's
# base under Asynchronous / SSP): the state holds the leaf under these alone
READ_EST = ("GM", "FGM", "Asynchronous", "SSP")
READ_CENTER = ("EASGD", "Asynchronous", "SSP")


def _trainer(learner, dim, protocol="Synchronous", dp=1, hub=1, batch=16,
             preps=(), extra=None):
    tc = TrainingConfiguration(
        protocol=protocol, hub_parallelism=hub,
        extra={"syncEvery": SYNC_EVERY, **(extra or {})},
    )
    return SPMDTrainer(
        learner, [PreprocessorSpec(p) for p in preps], dim=dim,
        protocol=protocol, mesh=make_mesh(dp=dp, hub=hub),
        training_configuration=tc, batch_size=batch,
    )


def _sparse_spec(name, dim, hp=None):
    return LearnerSpec(
        name, hyper_parameters=hp or {},
        data_structure={"sparse": True, "nFeatures": dim, "maxNnz": 6},
    )


def _sparse_batches(k, dp, batch, dim, nnz=6, seed=0, classes=2):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(k):
        idx = rng.randint(0, dim, size=(dp, batch, nnz)).astype(np.int32)
        val = rng.randn(dp, batch, nnz).astype(np.float32)
        y = rng.randint(0, classes, size=(dp, batch)).astype(np.float32)
        out.append(((idx, val), y, np.ones((dp, batch), np.float32)))
    return out


def _dense_batches(k, dp, batch, dim, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(dim)
    out = []
    for _ in range(k):
        x = rng.randn(dp, batch, dim).astype(np.float32)
        y = (x @ w > 0).astype(np.float32)
        out.append((x, y, np.ones((dp, batch), np.float32)))
    return out


def _shard_values(trainer, leaf):
    """What each mesh shard holds of a state leaf, read shard by shard from
    the devices and squeezed of unit stacking axes: {(w, h): value}. Knows
    nothing of the view functions."""
    where = {
        d.id: (w, h)
        for (w, h), d in np.ndenumerate(trainer.mesh.devices)
    }
    out = {}
    for s in leaf.addressable_shards:
        block = np.asarray(s.data)
        if block.ndim >= 2:
            assert block.shape[:2] == (1, 1)
            block = block[0, 0]
        out[where[s.device.id]] = block
    return out


# --- (a) the step is the learner's update, bit for bit ----------------------

ONE_CHIP_LEARNERS = {
    "sparse_pa2": (_sparse_spec("PA", 301, {"C": 0.1, "variant": "PA-II"}), 301),
    "sparse_regressor_pa": (_sparse_spec("RegressorPA", 301, {"C": 0.1}), 301),
    "sparse_svm": (_sparse_spec("SVM", 301), 301),
    "sparse_softmax": (
        _sparse_spec("Softmax", 301, {"nClasses": 3, "learningRate": 0.05}),
        301,
    ),
    "dense_lr": (
        LearnerSpec(
            "Softmax", hyper_parameters={"learningRate": 0.05, "nClasses": 2},
            data_structure={"nFeatures": 9},
        ),
        9,
    ),
    "dense_ridge": (LearnerSpec("ORR", hyper_parameters={"lambda": 1.0}), 9),
}


# every learner under Synchronous, and one under GM with a threshold that
# every cadence step violates: on one chip its sync, too, changes no weight
STEP_CASES = [(c, "Synchronous") for c in sorted(ONE_CHIP_LEARNERS)] + [
    ("sparse_pa2", "GM")
]


@pytest.mark.parametrize(
    "case,protocol", STEP_CASES,
    ids=[c if p == "Synchronous" else f"{c}-{p}" for c, p in STEP_CASES],
)
def test_steps_equal_learner_updates_bit_for_bit(case, protocol):
    spec, dim = ONE_CHIP_LEARNERS[case]
    tr = _trainer(spec, dim, protocol, extra={"threshold": 1e-9})
    sparse = getattr(tr.learner, "sparse", False)
    k = 2 * SYNC_EVERY + 1
    batches = (
        _sparse_batches(k, 1, 16, dim, classes=3 if "softmax" in case else 2)
        if sparse else _dense_batches(k, 1, 16, dim)
    )
    # the trainer seeds worker w from split(PRNGKey(seed), dp)[w]
    params = tr.learner.init(dim, jax.random.split(jax.random.PRNGKey(0), 1)[0])
    update = jax.jit(tr.learner.update)
    for i, (x, y, m) in enumerate(batches, start=1):
        tr.step(x, y, m)
        x0 = tuple(a[0] for a in x) if sparse else x[0]
        params, _ = update(params, x0, y[0], m[0])
        got = tr.shard_params()[0]
        for a, b in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(params)
        ):
            np.testing.assert_array_equal(a, np.asarray(b))
        # nothing reads a center under either protocol: none is held
        assert "center" not in tr.state
        if protocol == "Synchronous":
            # nor an estimate at the last sync
            assert "est" not in tr.state
        elif i % SYNC_EVERY == 0:
            # the protocol's state after a fired sync: est == w
            est = tr.host_stacked(tr.state["est"])[0, 0]
            np.testing.assert_array_equal(est, tr.global_flat_params())
    assert tr.sync_count() == k // SYNC_EVERY


# --- (b) every reader agrees with what the shards hold -----------------------

MESHES = [(1, 1), (2, 1), (2, 2)]


@pytest.mark.parametrize("protocol", SPMD_PROTOCOLS)
@pytest.mark.parametrize("dp,hub", MESHES)
def test_readers_agree_with_the_shards(protocol, dp, hub, tmp_path):
    dim = 6  # 7 parameters: padded to 8 at hub = 2
    spec = LearnerSpec("PA", hyper_parameters={"C": 1.0})
    extra = {"threshold": 0.05}

    def build():
        return _trainer(spec, dim, protocol, dp, hub, preps=("StandardScaler",),
                        extra=extra)

    tr = build()
    for x, y, m in _dense_batches(2 * SYNC_EVERY + 1, dp, 16, dim, seed=dp + hub):
        tr.step(x, y, m)
    state = tr.state

    # vector leaves are stored flat, one block a shard; the others stacked
    assert ("est" in state) == (protocol in READ_EST)
    assert ("center" in state) == (protocol in READ_CENTER)
    if protocol in READ_CENTER:
        assert state["center"].shape == (dp * hub * tr.flat_size,)
    if protocol in READ_EST:
        assert state["est"].shape == (dp * hub * tr.flat_size,)
    assert state["params"]["w"].shape == (dp * hub * (dim + 1),)
    assert state["step"].shape == (dp, hub)
    for leaf in jax.tree_util.tree_leaves(state):
        assert len(leaf.addressable_shards) == dp * hub

    held = jax.tree_util.tree_map(lambda l: _shard_values(tr, l), state)
    w = held["params"]["w"]
    np.testing.assert_array_equal(tr.global_flat_params(), w[(0, 0)])
    for k, p in enumerate(tr.shard_params()):
        np.testing.assert_array_equal(p["w"], w[(k, 0)])
    np.testing.assert_array_equal(
        tr.worker_clocks(), [held["clock"][(k, 0)] for k in range(dp)]
    )
    np.testing.assert_array_equal(
        tr.last_accepted(), [held["accepted"][(k, 0)] > 0 for k in range(dp)]
    )
    syncs = [int(held["syncs"][(k, 0)]) for k in range(dp)]
    staggered = protocol in ("Asynchronous", "SSP")
    assert tr.sync_count() == (sum(syncs) if staggered else syncs[0])
    assert tr.bytes_shipped() == tr.protocol_traffic_bytes(
        protocol, dp, tr.flat_size, sum(syncs), syncs[0],
        int(held["step"][(0, 0)]),
    )[1]
    assert tr.collective_bytes_physical() > 0 or tr.sync_count() == 0
    # the stacked view of the whole leaf is the shards, in mesh order
    for key in ("est", "center", "step", "cum_loss"):
        if key not in state:
            continue
        full = tr.host_stacked(state[key])
        assert full.shape[:2] == (dp, hub)
        for (i, j), v in held[key].items():
            np.testing.assert_array_equal(full[i, j], v)

    # serving: worker 0's model and preprocessor state, on the host
    xt, yt, mt = (a[0] for a in _dense_batches(1, 1, 32, dim, seed=99)[0])
    prep_state = jax.tree_util.tree_map(
        lambda shards: shards[(0, 0)], held["preps"][0],
        is_leaf=lambda v: isinstance(v, dict) and (0, 0) in v,
    )
    z = tr.preps[0].transform(prep_state, jnp.asarray(xt))
    params0 = {"w": jnp.asarray(w[(0, 0)])}
    np.testing.assert_allclose(
        tr.predict(xt), np.asarray(tr.learner.predict(params0, z)), rtol=1e-6
    )
    loss, score = tr.evaluate(xt, yt, mt)
    np.testing.assert_allclose(
        loss, float(tr.learner.loss(params0, z, yt, mt)), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(
        score, float(tr.learner.score(params0, z, yt, mt)), rtol=1e-6
    )

    # save / load: a fresh trainer holds the same shards, in the same form
    tr.save(str(tmp_path / "snap"))
    fresh = build()
    fresh.load(str(tmp_path / "snap"))
    for a, b in zip(
        jax.tree_util.tree_leaves(fresh.state), jax.tree_util.tree_leaves(state)
    ):
        assert a.shape == b.shape
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a snapshot whose vector leaves were saved [dp, hub, n] (the form
    # before they were stored flat) loads to the same state
    old_form = jax.tree_util.tree_map(
        lambda l: stacked(np.asarray(l), dp, hub), state
    )
    assert old_form["params"]["w"].shape == (dp, hub, dim + 1)
    save_tree(str(tmp_path / "old"), old_form)
    fresh.load(str(tmp_path / "old"))
    for a, b in zip(
        jax.tree_util.tree_leaves(fresh.state), jax.tree_util.tree_leaves(state)
    ):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x, y, m = _dense_batches(1, dp, 16, dim, seed=5)[0]
    np.testing.assert_array_equal(
        np.asarray(fresh.step(x, y, m)), np.asarray(tr.step(x, y, m))
    )


@pytest.mark.parametrize("codec", ["none", "int8"])
@pytest.mark.parametrize("protocol", ["Synchronous", "EASGD"])
def test_snapshot_with_an_unread_est_loads_without_it(protocol, codec, tmp_path):
    """A snapshot from before the state dropped the ``est`` that Synchronous
    and EASGD never read (and the ``center`` that Synchronous never read)
    holds them: it loads into the tree as it is now, and
    the next step equals the donor's bit for bit. Only that leaf is let go:
    any other that the live tree lacks, or misses, still fails."""
    dp, hub, dim = 2, 2, 6
    spec = LearnerSpec("PA", hyper_parameters={"C": 1.0})

    def build(protocol=protocol):
        return _trainer(spec, dim, protocol, dp, hub, preps=("StandardScaler",),
                        extra={"codec": codec, "threshold": 0.05})

    tr = build()
    for x, y, m in _dense_batches(SYNC_EVERY + 1, dp, 16, dim, seed=3):
        tr.step(x, y, m)
    assert "est" not in tr.state and ("ef" in tr.state) == (codec != "none")
    host = jax.tree_util.tree_map(np.asarray, tr.state)
    # the old form: est, and under Synchronous center too, beside the
    # others, vector leaves like the weights
    assert ("center" in tr.state) == (protocol == "EASGD")
    old = {**host, "est": host["params"]["w"] * 0.5}
    old.setdefault("center", host["params"]["w"] * 0.25)
    save_tree(str(tmp_path / "old"), old)
    fresh = build()
    fresh.load(str(tmp_path / "old"))
    assert (
        jax.tree_util.tree_structure(fresh.state)
        == jax.tree_util.tree_structure(tr.state)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(fresh.state), jax.tree_util.tree_leaves(tr.state)
    ):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for x, y, m in _dense_batches(SYNC_EVERY, dp, 16, dim, seed=5):
        np.testing.assert_array_equal(
            np.asarray(fresh.step(x, y, m)), np.asarray(tr.step(x, y, m))
        )
    np.testing.assert_array_equal(
        fresh.global_flat_params(), tr.global_flat_params()
    )
    assert fresh.sync_count() == tr.sync_count() > 0
    assert fresh.bytes_shipped() == tr.bytes_shipped()
    # a leaf the tree has none of, other than est, is not let go
    save_tree(str(tmp_path / "odd"), {**host, "drift": host["params"]["w"]})
    with pytest.raises(ValueError):
        build().load(str(tmp_path / "odd"))
    # and a protocol that reads est does not load a snapshot without one
    with pytest.raises(ValueError):
        build("GM").load(str(tmp_path / "odd"))


@pytest.mark.parametrize("shape", [(2, 3), (2, 3, 5), (2, 3, 5, 4), (1, 1, 7)])
def test_stacked_inverts_stored(shape):
    leaf = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    kept = stored(leaf)
    assert kept.ndim == (1 if len(shape) == 3 else len(shape))
    np.testing.assert_array_equal(stacked(kept, *shape[:2]), leaf)
    assert stored(kept) is kept or np.shares_memory(stored(kept), kept)


# --- the static guard: the view is free --------------------------------------

_CONTAINERS = ("jit", "pjit", "shard_map", "cond", "while", "scan",
               "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call")


def _wide_primitives(jaxpr, d, path=()):
    """(path, primitive) of every equation with a result of ``d`` or more
    elements, outside the branch a ``cond`` takes when its predicate holds."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CONTAINERS:
            if name == "cond":
                subs = [eqn.params["branches"][0]]  # index 0: predicate false
            else:
                subs = [
                    v for v in eqn.params.values()
                    if hasattr(getattr(v, "jaxpr", v), "eqns")
                ]
            for sub in subs:
                found += _wide_primitives(
                    getattr(sub, "jaxpr", sub), d, path + (name,)
                )
            continue
        if any(
            int(np.prod(getattr(o.aval, "shape", ()))) >= d for o in eqn.outvars
        ):
            found.append(("/".join(path), name))
    return found


@pytest.mark.parametrize("impl", ["scatter", "plan"])
@pytest.mark.parametrize("learner", ["PA", "RegressorPA"])
def test_only_the_scatter_is_model_wide_in_the_non_sync_step(learner, impl):
    """Counts only (the CPU says nothing of the chip's layouts): at a small
    odd width, the only primitive of the sparse step's non-sync path whose
    result is as wide as the model is the scatter, under the plain pair and
    under the index plan alike (whose overflow branch, like the sync, is the
    one taken when its predicate holds). ``chip_smoke.py``'s
    ``stream_sparse`` leg holds the compiled programs to the same on the
    chip."""
    d = 2 ** 16 + 14
    batch, nnz = 32, 8
    spec = LearnerSpec(
        learner, hyper_parameters={"C": 0.1, "variant": "PA-II"},
        data_structure={
            "sparse": True, "nFeatures": d - 1, "maxNnz": nnz,
            "scatterImpl": impl,
        },
    )
    tr = _trainer(spec, d - 1, batch=batch)
    assert tr.n_params == d
    idx = np.zeros((1, batch, nnz), np.int32)
    val = np.zeros((1, batch, nnz), np.float32)
    y = np.zeros((1, batch), np.float32)
    jaxpr = jax.make_jaxpr(tr._step)(tr.state, (idx, val), y, y)
    wide = _wide_primitives(jaxpr.jaxpr, d)
    assert [p for _, p in wide] == ["scatter-add"], wide
    # and the serve program reads the stored leaf as it is
    predict_fn, _ = tr._serve_fns()
    jaxpr = jax.make_jaxpr(predict_fn)(tr.state, (idx[0], val[0]))
    assert _wide_primitives(jaxpr.jaxpr, d) == []


_HLO_RELAYOUT = """\
HloModule jit_step_fn, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={()->()}

%body.1 (p: (u32[], f32[1000])) -> (u32[], f32[1000]) {
  %p = (u32[], f32[1000]{0:T(1024)}) parameter(0)
  %dynamic-slice.1 = f32[1000]{0:T(1024)} dynamic-slice(%p), dynamic_slice_sizes={1000}
}

%fused_scatter (a: f32[1000]) -> f32[1000] {
  %a = f32[1000]{0:T(1024)} parameter(0)
  ROOT %scatter-add.1 = f32[1000]{0:T(1024)} scatter(%a, %a, %a), to_apply=%add
}

%sync (a: f32[1000]) -> f32[1000] {
  %a.1 = f32[1000]{0:T(1024)} parameter(0)
  ROOT %copy.7 = f32[1000]{0:T(1024)} copy(%a.1)
}

%keep (a: f32[1000]) -> f32[1000] {
  ROOT %a.2 = f32[1000]{0:T(1024)} parameter(0)
}

ENTRY %main (w: f32[1,1,1000]) -> f32[1,1,1000] {
  %w = f32[1,1,1000]{2,1,0:T(1,128)} parameter(0)
  %reduce.1 = f32[1000]{0:T(1024)} reduce(%w, %c), dimensions={0,1}, to_apply=%add
  %fusion.1 = f32[1000]{0:T(1024)} fusion(%reduce.1), kind=kCustom, calls=%fused_scatter
  %cond.8 = f32[1000]{0:T(1024)} conditional(%pred, %fusion.1, %fusion.1), branch_computations={%keep, %sync}
  %broadcast.46 = f32[1,1,1000]{2,1,0:T(1,128)} broadcast(%c), dimensions={}
  %small = f32[10]{0:T(1024)} broadcast(%c), dimensions={}
  ROOT %while.1 = (u32[], f32[1000]{0:T(1024)}) while(%t), condition=%cnd, body=%body.1
}
"""


def test_hlo_guard_names_a_relayout_and_spares_the_scatter_and_the_sync():
    found = chip_smoke.hlo_wide_passes(_HLO_RELAYOUT, 1000)
    assert sorted(name for _, name, _ in found) == [
        "broadcast.46", "dynamic-slice.1", "reduce.1", "while.1",
    ]
    # walked too, the sync branch gives its copy away
    every = chip_smoke.hlo_wide_passes(_HLO_RELAYOUT, 1000, every_branch=True)
    assert sorted(set(every) - set(found)) == [("sync", "copy.7", "copy")]
    assert chip_smoke.hlo_aliased_parameters(_HLO_RELAYOUT) == [0]
    clean = "\n".join(
        l for l in _HLO_RELAYOUT.splitlines()
        if not any(k in l for k in ("reduce.1 =", "broadcast.46", "while.1"))
    )
    assert chip_smoke.hlo_wide_passes(clean, 1000) == []


# --- the model-sized leaves the state holds, and where the model is flattened -


@pytest.mark.parametrize("protocol", SPMD_PROTOCOLS)
def test_the_state_holds_est_and_center_only_where_the_step_reads_them(protocol):
    """``est`` under GM, FGM, Asynchronous, SSP; ``center`` under EASGD,
    Asynchronous, SSP; Synchronous holds neither: a model-sized leaf that no
    code reads is a model more on the device."""
    from omldm_tpu.parallel.spmd import drop_unread_leaves, unread_leaves

    tr = _trainer(LearnerSpec("NN", hyper_parameters={"optimizer": "sgd"}), 6, protocol, dp=2)
    held = {k for k in ("est", "center") if k in tr.state}
    assert held == {k for k, ps in (("est", READ_EST), ("center", READ_CENTER)) if protocol in ps}
    assert set(unread_leaves(protocol)) == {"est", "center"} - held
    for x, y, m in _dense_batches(SYNC_EVERY, 2, 16, 6):
        tr.step(x, y, m)
    assert {k for k in ("est", "center") if k in tr.state} == held
    # an old snapshot's unread leaves are dropped, and nothing else is
    old = {**{k: 0 for k in tr.state}, "est": 1, "center": 2, "other": 3}
    assert set(drop_unread_leaves(old, protocol)) == set(tr.state) | held | {"other"}


def _concatenations_of(tr, x, y, m, n_least):
    """Sizes of the ``concatenate`` results of the compiled step that hold at
    least ``n_least`` elements."""
    import re

    text = tr._step.lower(tr.state, x, y, m).compile().as_text()
    sizes = []
    for dims in re.findall(r"= f32\[([\d,]*)\][^=]*? concatenate\(", text):
        size = int(np.prod([int(d) for d in dims.split(",") if d]))
        if size >= n_least:
            sizes.append(size)
    return sizes


@pytest.mark.parametrize("protocol,codec,dp,flattens", [
    ("Synchronous", "none", 1, False),  # nothing reads the flat form
    ("Synchronous", "int8", 1, True),   # the codec quantizes it
    ("Synchronous", "none", 2, True),   # the collective averages it
    ("GM", "none", 1, True),            # the drift is measured on it
    ("EASGD", "none", 1, True),         # the center pulls on it
])
def test_the_model_is_flattened_only_where_something_reads_the_flat_form(
    protocol, codec, dp, flattens
):
    """At ``dp x hub = 1`` under Synchronous with no codec the learner's new
    parameters are the state's: the compiled step concatenates no model. The
    rule reads the mesh and the protocol, never the learner."""
    spec = LearnerSpec("NN", hyper_parameters={"optimizer": "sgd"},
                       data_structure={"hiddenLayers": [8]})
    tr = _trainer(spec, 6, protocol, dp=dp, extra={"codec": codec})
    x, y, m = _dense_batches(1, dp, 16, 6)[0]
    wide = _concatenations_of(tr, x, y, m, tr.n_params)
    assert bool(wide) == flattens
