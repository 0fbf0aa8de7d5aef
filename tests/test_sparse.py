"""Sparse (padded-COO) feature path: ops, learners, pipeline, vectorizer.

The reference treats SparseVector as a first-class input type
(DataPointParser.scala:4,20-47); these tests pin the TPU-native equivalent:
dense/sparse twin-equality on the same data, high-dimensional training at
Criteo/Avazu-class widths (where densifying would be wrong or impossible),
and the end-to-end sparse pipeline surface.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.api.data import DataInstance
from omldm_tpu.api.requests import LearnerSpec
from omldm_tpu.learners.registry import make_learner
from omldm_tpu.ops.sparse import sparse_matvec, sparse_scatter_add
from omldm_tpu.pipelines import MLPipeline
from omldm_tpu.runtime.vectorizer import SparseMicroBatcher, SparseVectorizer


def dense_to_coo(x: np.ndarray, k: int):
    """Dense [B, D] -> padded COO (idx[B, k], val[B, k])."""
    b = x.shape[0]
    idx = np.zeros((b, k), np.int32)
    val = np.zeros((b, k), np.float32)
    for i in range(b):
        nz = np.nonzero(x[i])[0][:k]
        idx[i, : nz.size] = nz
        val[i, : nz.size] = x[i, nz]
    return idx, val


def _dup_heavy(d, vocab, seed=11):
    """Every slot draws from a tiny vocabulary; pad slots (idx 0, val 0) and
    a whole-record duplicate on top."""
    def make():
        rng = np.random.RandomState(seed)
        b, k = 32, 9
        idx = rng.choice(
            rng.randint(0, d, size=vocab), size=(b, k)
        ).astype(np.int32)
        idx[:, -2:] = 0
        val = rng.randn(b, k).astype(np.float32)
        val[:, -2:] = 0.0
        idx[3] = idx[2]
        return d, idx, val
    return make


def _with_distinct(n_distinct, b=8, k=5, d=1000):
    """[b, k] slots over exactly ``n_distinct`` addresses."""
    def make():
        rng = np.random.RandomState(n_distinct)
        addr = rng.choice(d, size=n_distinct, replace=False)
        flat = np.concatenate(
            [addr, rng.choice(addr, size=b * k - n_distinct)]
        )
        idx = rng.permutation(flat).reshape(b, k).astype(np.int32)
        return d, idx, rng.randn(b, k).astype(np.float32)
    return make


def _bias_in_every_row():
    d, idx, val = _dup_heavy(4096, 50, seed=3)()
    idx[:, 0] = d - 1
    val[:, 0] = 1.0
    return d, idx, val


# 8 x 5 = 40 slots: the plan's capacity is a quarter of them, 10
_PLAN_CASES = {
    "dup_heavy_d37_vocab5": _dup_heavy(37, 5),
    "dup_heavy_d4096_vocab3": _dup_heavy(4096, 3),
    "dup_heavy_d4096_vocab500": _dup_heavy(4096, 500),
    "dup_heavy_d32768_vocab7": _dup_heavy(1 << 15, 7),
    "all_duplicate": _with_distinct(1),
    "all_distinct": _with_distinct(40),
    "u_eq_cap": _with_distinct(10),
    "u_eq_cap_plus_1": _with_distinct(11),
    "pads_only": lambda: (
        64, np.zeros((8, 5), np.int32), np.zeros((8, 5), np.float32)
    ),
    "bias_address": _bias_in_every_row,
}


class TestSparseOps:
    def test_matvec_matches_dense(self):
        rng = np.random.RandomState(0)
        d, b, k = 50, 8, 12
        w = rng.randn(d).astype(np.float32)
        x = np.zeros((b, d), np.float32)
        for i in range(b):
            cols = rng.choice(d, k, replace=False)
            x[i, cols] = rng.randn(k)
        idx, val = dense_to_coo(x, k)
        np.testing.assert_allclose(
            np.asarray(sparse_matvec(jnp.asarray(w), jnp.asarray(idx), jnp.asarray(val))),
            x @ w, rtol=1e-5, atol=1e-5,
        )

    def test_scatter_add_matches_dense_and_pads_inert(self):
        rng = np.random.RandomState(1)
        d, b, k = 30, 4, 6
        w = np.zeros(d, np.float32)
        x = np.zeros((b, d), np.float32)
        for i in range(b):
            cols = rng.choice(d, 3, replace=False)  # k=6 budget, 3 used
            x[i, cols] = rng.randn(3)
        idx, val = dense_to_coo(x, k)
        coef = rng.randn(b).astype(np.float32)
        out = sparse_scatter_add(
            jnp.asarray(w), jnp.asarray(idx), jnp.asarray(coef), jnp.asarray(val)
        )
        np.testing.assert_allclose(
            np.asarray(out), coef @ x, rtol=1e-5, atol=1e-5
        )

    def test_committed_tpu_table_names_the_cells_formulations(self):
        """The choice both benchmark cells depend on, read from the file
        itself: ``backends.tpu`` of ops/sparse_dispatch.json names ``plan``
        at a launch of 4096 x 41 slots over 2^28 + 14 weights and
        ``scatter`` at the tail step's 256 x 41 and a forecast's padded
        16 x 41; the table has no other backend's section (the CPU's named
        the plain pair everywhere, which is what no section gives)."""
        import json
        import os

        from omldm_tpu.ops import sparse as sp

        path = os.path.join(os.path.dirname(sp.__file__), "sparse_dispatch.json")
        with open(path) as f:
            backends = json.load(f)["backends"]
        assert list(backends) == ["tpu"]
        winners = {
            (e["d"], e["batch"], e["nnz"]): e["winner"]
            for e in backends["tpu"]["entries"]
        }
        d = (1 << 28) + 14
        assert winners[(d, 4096, 41)] == "plan"
        assert winners[(d, 256, 41)] == "scatter"
        assert winners[(d, 16, 41)] == "scatter"
        assert set(winners.values()) == set(sp.IMPLS)

    def test_update_dispatch_matches_plain_pair_under_jit(self):
        """sparse_update resolves at trace time and must be jittable; with
        the explicit scatter impl pinned it is the plain pair bit-for-bit
        and counts nothing."""
        from omldm_tpu.ops.sparse import sparse_update

        rng = np.random.RandomState(8)
        d, b, k = 300, 8, 5
        w = rng.randn(d).astype(np.float32)
        idx = rng.randint(0, d, size=(b, k)).astype(np.int32)
        val = rng.randn(b, k).astype(np.float32)
        coef = rng.randn(b).astype(np.float32)

        def update(w, idx, val, coef):
            margins, add, counters = sparse_update(w, idx, val, impl="scatter")
            assert counters is None
            return margins, add(w, coef)

        args = [jnp.asarray(a) for a in (w, idx, val, coef)]
        margins, out = jax.jit(update)(*args)
        np.testing.assert_array_equal(
            np.asarray(margins), np.asarray(jax.jit(sparse_matvec)(*args[:3]))
        )
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(sparse_scatter_add(args[0], args[1], args[3], args[2])),
        )

    @pytest.mark.parametrize("case", sorted(_PLAN_CASES))
    def test_plan_matches_plain_pair(self, case):
        """The index plan (one sort of the launch's indices, duplicates
        combined, w addressed once per distinct index) against the plain
        pair: gathered weights bit for bit, weights up to the f32 order in which one
        address's duplicates are summed (per-run totals are plain sums of
        the run's own updates, no prefix differences). The first four cases
        are the duplicate-heavy streams the pre-combine exists for, with
        pad slots and whole-record duplicates; the others its edges: one
        address, none repeated, exactly the plan's capacity, one more (an
        overflowing launch runs the plain pair itself, so its weights are
        the plain scatter's bit for bit), nothing but pads, the bias
        address in every row."""
        from omldm_tpu.ops.sparse import (
            index_plan, plan_capacity, plan_gather, sparse_update,
        )

        d, idx, val = _PLAN_CASES[case]()
        b = idx.shape[0]
        rng = np.random.RandomState(5)
        w = rng.randn(d).astype(np.float32)
        coef = rng.randn(b).astype(np.float32)
        args = [jnp.asarray(a) for a in (w, idx, val, coef)]

        def update(w, idx, val, coef):
            margins, add, counters = sparse_update(w, idx, val, impl="plan")
            return margins, add(w, coef), counters

        margins, out, counters = jax.jit(update)(*args)
        # what the plan hands the margin is w[idx] bit for bit, and the
        # margin is the same sum(g * val, axis=1) over it; once the gather
        # no longer sits inside that reduction's fusion the CPU's compiler
        # may contract it differently, so the sums agree to the last bits
        g, _ = jax.jit(lambda w, i: plan_gather(w, index_plan(i)))(*args[:2])
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(jnp.take(args[0], args[1], axis=0))
        )
        np.testing.assert_allclose(
            np.asarray(margins), np.asarray(sparse_matvec(*args[:3])),
            rtol=1e-6, atol=1e-6,
        )
        ref = sparse_scatter_add(args[0], args[1], args[3], args[2])
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"plan diverged from the plain scatter: {case}",
        )
        distinct = len(np.unique(idx))
        cap = plan_capacity(idx.size)
        assert list(np.asarray(counters)) == [
            distinct, int(distinct > cap), idx.size
        ]
        if case == "u_eq_cap":
            assert distinct == cap
        if case == "u_eq_cap_plus_1":
            assert distinct == cap + 1
        if distinct > cap:
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_dispatch_impl_then_table_then_plain_pair(
        self, tmp_path, monkeypatch
    ):
        """_resolve_impl's three sources in order: the explicit ``impl``
        (validated loudly), the table's section for the backend, the plain
        pair."""
        import json

        from omldm_tpu.ops import sparse as sp
        from omldm_tpu.ops import sparse_calibrate as cal

        table = tmp_path / "table.json"
        table.write_text(json.dumps({"version": 1, "backends": {
            jax.default_backend(): {"entries": [
                {"d": 300, "updates": 40, "winner": "plan"},
            ]},
        }}))
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(table))
        assert sp._resolve_impl(300, 40) == "plan"
        assert sp._resolve_impl(300, 40, impl="scatter") == "scatter"
        monkeypatch.setattr(cal, "DEFAULT_TABLE", str(tmp_path / "absent"))
        assert sp._resolve_impl(300, 40) == "scatter"
        assert sp._resolve_impl(300, 40, impl="plan") == "plan"
        with pytest.raises(ValueError, match="unknown sparse scatter"):
            sp._resolve_impl(300, 40, impl="bogus")
        with pytest.raises(ValueError, match="unknown sparse scatter"):
            sp._resolve_impl(300, 40, impl="mxu")
        # the plan's spare addresses d + j must stay int32: pinned where
        # they cannot, it says so
        with pytest.raises(ValueError, match="2\\^31"):
            sp._resolve_impl(2 ** 31 - 8, 40, impl="plan")
        # nor can it move weights that are not 4 bytes wide as int32 bits
        with pytest.raises(ValueError, match="4-byte"):
            sp._resolve_impl(300, 40, impl="plan", dtype=jnp.bfloat16)


class TestPlanInTheSPMDStep:
    """The index plan inside the collective engine's step program."""

    def _trainer(self, impl, dp, hub, d, batch, nnz):
        from omldm_tpu.api.requests import TrainingConfiguration
        from omldm_tpu.parallel.mesh import make_mesh
        from omldm_tpu.parallel.spmd import SPMDTrainer

        spec = LearnerSpec(
            "PA", hyper_parameters={"C": 0.1, "variant": "PA-II"},
            data_structure={
                "sparse": True, "nFeatures": d - 1, "maxNnz": nnz,
                "scatterImpl": impl,
            },
        )
        return SPMDTrainer(
            spec, dim=d - 1, protocol="Synchronous",
            mesh=make_mesh(dp=dp, hub=hub), batch_size=batch,
            training_configuration=TrainingConfiguration(
                protocol="Synchronous", extra={"syncEvery": 2}
            ),
        )

    def test_plan_step_under_shard_map_2x2(self):
        """dp x hub = 2 x 2: each worker plans its own batch inside
        ``shard_map``; the fleet's model stays in the plain pair's envelope
        and every launch hands its counters out."""
        d, batch, nnz, steps = 4096 + 14, 32, 8, 5
        rng = np.random.RandomState(2)
        vocab = rng.randint(0, d - 1, size=40)
        batches = [
            (
                (
                    rng.choice(vocab, size=(2, batch, nnz)).astype(np.int32),
                    rng.randn(2, batch, nnz).astype(np.float32),
                ),
                (rng.rand(2, batch) > 0.5).astype(np.float32),
                np.ones((2, batch), np.float32),
            )
            for _ in range(steps)
        ]
        flats = {}
        for impl in ("scatter", "plan"):
            tr = self._trainer(impl, 2, 2, d, batch, nnz)
            for x, y, m in batches:
                tr.step(x, y, m)
            flats[impl] = np.asarray(tr.global_flat_params())
            counts = tr.plan_counts()
            if impl == "scatter":
                assert counts == {}
                continue
            n = batch * (nnz + 1)
            assert counts["slots"] == steps * 2 * n
            assert counts["slots_distinct"] == sum(
                len(np.unique(np.append(x[0][w], d - 1)))
                for x, _, _ in batches for w in range(2)
            )
            # 41 addresses at most against a capacity of 72
            assert counts["overflow_launches"] == 0
            assert tr.plan_counts() == {}  # read once
        assert np.abs(flats["scatter"]).max() > 0
        np.testing.assert_allclose(
            flats["plan"], flats["scatter"], rtol=2e-5, atol=2e-5
        )

    def test_donated_plan_step_leaves_no_second_copy_of_w(self):
        """The compiled step (this backend's compiler; ``chip_smoke.py``'s
        ``stream_sparse`` leg reads the chip's, ``tests/test_tpu_compile.py``
        the TPU compiler's at 2^28 + 14 weights): the vector leaves are
        donated, and outside the sync branch and an overflowing launch's
        plain pair nothing is as wide as the model but the in-place scatter
        and, on the CPU alone, one copy right before it: the CPU's compiler
        copies the operand of a conditional whose branches both scatter into
        it, where the TPU's updates it in place."""
        import chip_smoke

        d, batch, nnz = 2 ** 16 + 14, 64, 8
        tr = self._trainer("plan", 1, 1, d, batch, nnz)
        idx = np.zeros((1, batch, nnz), np.int32)
        val = np.zeros((1, batch, nnz), np.float32)
        y = np.zeros((1, batch), np.float32)
        text = tr._step.lower(tr.state, (idx, val), y, y).compile().as_text()
        passes = chip_smoke.hlo_wide_passes(text, d)
        in_branch = [p for p in passes if p[2] == "copy" and p[0] != "ENTRY"]
        assert [p for p in passes if p not in in_branch] == []
        assert len(in_branch) <= (jax.default_backend() == "cpu")
        n_vector_leaves = sum(
            leaf.ndim == 1 for leaf in jax.tree_util.tree_leaves(tr.state)
        )
        assert len(chip_smoke.hlo_aliased_parameters(text)) >= n_vector_leaves


class TestSparseLearnerTwinEquality:
    """A sparse learner on the COO form of a dense batch must produce the
    same model as its dense twin."""

    def _data(self, n=400, d=24, seed=0):
        rng = np.random.RandomState(seed)
        w = rng.randn(d)
        x = np.zeros((n, d), np.float32)
        for i in range(n):
            cols = rng.choice(d, 6, replace=False)
            x[i, cols] = rng.randn(6)
        y = (x @ w > 0).astype(np.float32)
        return x, y

    @pytest.mark.parametrize("variant", ["PA", "PA-I", "PA-II"])
    def test_pa_matches_dense_twin(self, variant):
        x, y = self._data()
        d = x.shape[1]
        hp = {"C": 0.5, "variant": variant}
        dense = make_learner(LearnerSpec("PA", hyper_parameters=hp))
        sparse = make_learner(
            LearnerSpec("PA", hyper_parameters=hp,
                        data_structure={"sparse": True})
        )
        pd = dense.init(d, jax.random.PRNGKey(0))
        ps = sparse.init(d, jax.random.PRNGKey(0))
        idx, val = dense_to_coo(x, 8)
        mask = np.ones(len(y), np.float32)
        for s in range(0, len(y), 64):
            sl = slice(s, s + 64)
            m = mask[sl]
            pd, ld = dense.update(pd, jnp.asarray(x[sl]), jnp.asarray(y[sl]), jnp.asarray(m))
            ps, ls = sparse.update(
                ps, (jnp.asarray(idx[sl]), jnp.asarray(val[sl])),
                jnp.asarray(y[sl]), jnp.asarray(m),
            )
            np.testing.assert_allclose(float(ld), float(ls), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(pd["w"]), np.asarray(ps["w"]), rtol=1e-4, atol=1e-5
        )

    def test_softmax_matches_dense_twin(self):
        x, y = self._data(seed=3)
        d = x.shape[1]
        hp = {"learningRate": 0.1, "nClasses": 2}
        dense = make_learner(LearnerSpec("Softmax", hyper_parameters=hp))
        sparse = make_learner(
            LearnerSpec("Softmax", hyper_parameters=hp,
                        data_structure={"sparse": True})
        )
        pd = dense.init(d, jax.random.PRNGKey(0))
        ps = sparse.init(d, jax.random.PRNGKey(0))
        idx, val = dense_to_coo(x, 8)
        mask = np.ones(len(y), np.float32)
        for s in range(0, len(y), 64):
            sl = slice(s, s + 64)
            pd, _ = dense.update(pd, jnp.asarray(x[sl]), jnp.asarray(y[sl]), jnp.asarray(mask[sl]))
            ps, _ = sparse.update(
                ps, (jnp.asarray(idx[sl]), jnp.asarray(val[sl])),
                jnp.asarray(y[sl]), jnp.asarray(mask[sl]),
            )
        wd = np.asarray(jax.tree_util.tree_leaves(pd)[0])
        ws = np.asarray(jax.tree_util.tree_leaves(ps)[0])
        np.testing.assert_allclose(wd, ws, rtol=1e-4, atol=1e-5)


class TestSparseHighDim:
    """Criteo/Avazu-class widths: the whole point of the sparse path."""

    def _hashed_stream(self, n, d_dense, hash_space, k_cat, seed=0):
        """Synthetic categorical stream: k_cat categorical slots drawn from
        per-slot vocabularies; label decided by a hidden weight over the
        hashed space."""
        rng = np.random.RandomState(seed)
        dim = d_dense + hash_space
        k = d_dense + k_cat
        idx = np.zeros((n, k), np.int32)
        val = np.zeros((n, k), np.float32)
        xs_dense = rng.randn(n, d_dense).astype(np.float32)
        idx[:, :d_dense] = np.arange(d_dense)
        val[:, :d_dense] = xs_dense
        for c in range(k_cat):
            vocab = rng.randint(0, hash_space, size=50)
            picks = vocab[rng.randint(0, 50, size=n)]
            idx[:, d_dense + c] = d_dense + picks
            val[:, d_dense + c] = 1.0
        w_hid = rng.randn(dim) * 0.5
        margins = np.array(
            [val[i] @ w_hid[idx[i]] for i in range(n)], np.float32
        )
        y = (margins > 0).astype(np.float32)
        return dim, k, idx, val, y

    def test_pa_learns_at_2e18_width(self):
        dim_target = (1 << 18) + 13
        n = 4096
        dim, k, idx, val, y = self._hashed_stream(
            n, d_dense=13, hash_space=1 << 18, k_cat=26
        )
        assert dim == dim_target
        learner = make_learner(
            LearnerSpec("PA", hyper_parameters={"C": 0.5, "variant": "PA-II"},
                        data_structure={"sparse": True, "nFeatures": dim})
        )
        p = learner.init(dim, jax.random.PRNGKey(0))
        mask = np.ones(n, np.float32)
        # per-record online semantics (the reference's pipePoint loop)
        upd = jax.jit(learner.update_per_record)
        for _ in range(3):
            for s in range(0, n, 256):
                sl = slice(s, s + 256)
                p, _ = upd(p, (jnp.asarray(idx[sl]), jnp.asarray(val[sl])),
                           jnp.asarray(y[sl]), jnp.asarray(mask[sl]))
        score = float(learner.score(
            p, (jnp.asarray(idx), jnp.asarray(val)), jnp.asarray(y), jnp.asarray(mask)
        ))
        assert score > 0.8, score

    def test_sparse_pipeline_surface(self):
        """MLPipeline hosts a sparse learner: fit/fit_many/predict/evaluate/
        query-path flat params all work on (idx, val) batches."""
        dim, k, idx, val, y = self._hashed_stream(
            1024, d_dense=4, hash_space=1 << 12, k_cat=8, seed=5
        )
        pipe = MLPipeline(
            LearnerSpec("Softmax",
                        hyper_parameters={"learningRate": 0.2, "nClasses": 2},
                        data_structure={"sparse": True}),
            dim=dim,
            per_record=True,  # reference pipePoint semantics
        )
        mask = np.ones(256, np.float32)
        for _ in range(8):
            for s in range(0, 1024, 256):
                sl = slice(s, s + 256)
                pipe.fit((idx[sl], val[sl]), y[sl], mask)
        loss, score = pipe.evaluate((idx, val), y, np.ones(1024, np.float32))
        assert score > 0.75, score
        preds = np.asarray(pipe.predict((idx[:16], val[:16])))
        assert preds.shape == (16,)
        flat, _ = pipe.get_flat_params()
        assert flat.size == (dim + 1) * 2  # W[D+1, 2]
        # fit_many chained launch
        xs = (np.stack([idx[:256]] * 3), np.stack([val[:256]] * 3))
        pipe.fit_many(xs, np.stack([y[:256]] * 3), np.stack([mask] * 3))

    def test_sparse_rejects_preprocessors(self):
        with pytest.raises(ValueError):
            MLPipeline(
                LearnerSpec("PA", data_structure={"sparse": True}),
                [__import__("omldm_tpu.api.requests", fromlist=["PreprocessorSpec"]).PreprocessorSpec("StandardScaler")],
                dim=64,
            )


class TestSparseVectorizer:
    def test_dense_slots_and_hashed_cats(self):
        v = SparseVectorizer(dim=8 + 64, hash_space=64, max_nnz=6)
        inst = DataInstance(
            numerical_features=[1.5, 0.0, -2.0],
            discrete_features=[3],
            categorical_features=["a", "b"],
        )
        idx, val = v.vectorize(inst)
        # zero numeric feature skipped; slots: 0->1.5, 2->-2.0, 3->3
        assert list(idx[:3]) == [0, 2, 3]
        np.testing.assert_allclose(val[:3], [1.5, -2.0, 3.0])
        assert (idx[3:5] >= 8).all()  # hashed region
        assert set(np.abs(val[3:5])) == {1.0}

    def test_matches_dense_vectorizer_model(self):
        """A model trained on sparse records equals one trained on the
        dense Vectorizer's output when the hash space matches."""
        from omldm_tpu.runtime.vectorizer import Vectorizer

        dv = Vectorizer(dim=4 + 32, hash_dims=32)
        sv = SparseVectorizer(dim=4 + 32, hash_space=32, max_nnz=8)
        inst = DataInstance(
            numerical_features=[0.5, -1.0, 2.0, 3.0],
            categorical_features=["x", "y"],
        )
        dense = dv.vectorize(inst)
        idx, val = sv.vectorize(inst)
        rebuilt = np.zeros_like(dense)
        np.add.at(rebuilt, idx, val)
        # pad slots add 0 at index 0
        np.testing.assert_allclose(rebuilt, dense)

    def test_batcher_roundtrip(self):
        b = SparseMicroBatcher(max_nnz=4, batch_size=3)
        b.add(np.array([1, 2, 0, 0]), np.array([1.0, -1.0, 0, 0]), 1.0)
        b.add(np.array([5, 0, 0, 0]), np.array([2.0, 0, 0, 0]), 0.0)
        (idx, val), y, mask = b.flush()
        assert idx.shape == (3, 4)
        assert list(mask) == [1.0, 1.0, 0.0]
        assert list(y[:2]) == [1.0, 0.0]
        assert len(b) == 0


class TestSparseRuntimeE2E:
    """A sparse pipeline through the full streaming runtime: JSON records
    with categorical features -> SparseVectorizer -> padded-COO micro-
    batches -> protocol training -> predictions + final statistics."""

    def _events(self, n, seed=0):
        rng = np.random.RandomState(seed)
        hidden = {}
        lines = []
        labels = []
        for _ in range(n):
            num = rng.randn(3)
            cats = [f"c{rng.randint(40)}", f"d{rng.randint(40)}"]
            m = float(num.sum())
            for i, c in enumerate(cats):
                if (i, c) not in hidden:
                    hidden[(i, c)] = rng.randn() * 2.0
                m += hidden[(i, c)]
            y = float(m > 0)
            labels.append(y)
            lines.append(json.dumps({
                "numericalFeatures": [round(float(v), 5) for v in num],
                "categoricalFeatures": cats,
                "target": y,
                "operation": "training",
            }))
        return lines, labels

    def test_sparse_pipeline_streams_end_to_end(self):
        from omldm_tpu.config import JobConfig
        from omldm_tpu.runtime import StreamJob
        from omldm_tpu.runtime.job import REQUEST_STREAM, TRAINING_STREAM

        hash_space = 1 << 14
        dim = 3 + hash_space
        create = {
            "id": 0,
            "request": "Create",
            "learner": {
                "name": "PA",
                "hyperParameters": {"C": 1.0, "variant": "PA-II"},
                "dataStructure": {
                    "sparse": True, "nFeatures": dim,
                    "hashSpace": hash_space, "maxNnz": 8,
                },
            },
            "preProcessors": [],
            "trainingConfiguration": {
                "protocol": "Synchronous", "perRecord": True,
            },
        }
        job = StreamJob(JobConfig(parallelism=2, batch_size=64, test_set_size=64))
        lines, _ = self._events(6000)
        events = [(REQUEST_STREAM, json.dumps(create))] + [
            (TRAINING_STREAM, l) for l in lines
        ]
        report = job.run(events)
        [stats] = report.statistics
        assert stats.fitted > 4000
        assert stats.score > 0.8, stats.score
        # the model is genuinely wide: flat params = dim + 1 bias
        [spoke] = job.spokes[:1]
        flat, _ = spoke.nets[0].pipeline.get_flat_params()
        assert flat.size == dim + 1

    def test_sparse_create_without_width_rejected(self):
        from omldm_tpu.config import JobConfig
        from omldm_tpu.runtime import StreamJob
        from omldm_tpu.runtime.job import REQUEST_STREAM

        job = StreamJob(JobConfig(parallelism=1))
        bad = {
            "id": 0, "request": "Create",
            "learner": {"name": "PA", "dataStructure": {"sparse": True}},
            "trainingConfiguration": {"protocol": "Synchronous"},
        }
        job.process_event(REQUEST_STREAM, json.dumps(bad))
        assert job.pipeline_manager.live_pipelines == []
