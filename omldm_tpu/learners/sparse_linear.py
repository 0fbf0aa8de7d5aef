"""Sparse-input linear learners: padded-COO batches over a dense device model.

Reference counterpart: the mlAPI learners consume ``SparseVector`` inputs
transparently (reference:
src/main/scala/omldm/utils/parsers/dataStream/DataPointParser.scala:4,20-47)
— Criteo/Avazu-class categorical streams reach PA/SVM/Softmax as sparse
points. Here the sparse variants are selected by
``dataStructure: {"sparse": true, "nFeatures": D}`` on the standard learner
names (registry.make_learner); the learner's ``x`` is the padded-COO pair
``(idx[B, K] int32, val[B, K] float32)`` instead of a dense ``[B, D]``.

The weight vector stays DENSE on device (a 2^20-feature f32 vector is 4 MB
of HBM); each record's forward is a K-row gather-dot and each update a
K-row scatter-add — O(B*K) work per batch regardless of D, where the dense
path would burn O(B*D). Update rules, hyper-parameters, and loss/score
semantics mirror the dense twins in learners/linear.py exactly.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from omldm_tpu.learners.base import Learner, Params, masked_mean, sign_labels
from omldm_tpu.learners.linear import _pa_tau
from omldm_tpu.ops.sparse import (
    append_bias_sparse,
    sparse_matmat,
    sparse_matvec,
    sparse_scatter_add_outer,
    sparse_sq_norm,
    sparse_update,
)


class SparseLinear(Learner):
    """Shared plumbing: dense ``w[D+1]`` (bias row at index D), sparse x."""

    sparse = True

    def init(self, dim: int, rng: Optional[jax.Array] = None) -> Params:
        self._dim = dim
        return {"w": jnp.zeros((dim + 1,), jnp.float32)}

    def _with_bias(self, params, x):
        idx, val = x
        return append_bias_sparse(idx, val, params["w"].shape[0] - 1)

    def update_per_record(self, params, x, y, mask):
        """Exact per-record online pass over a sparse batch (the base-class
        default slices dense rows; COO batches slice per leaf)."""
        idx, val = x

        def step(p, row):
            ii, vv, yi, mi = row
            new_p, l = self.update(p, (ii[None, :], vv[None, :]), yi[None], mi[None])
            return new_p, l

        params, losses = jax.lax.scan(step, params, (idx, val, y, mask))
        total = jnp.maximum(jnp.sum(mask), 1.0)
        return params, jnp.sum(losses * mask) / total


class SparseVectorLinear(SparseLinear):
    """The learners whose model is ONE weight vector ``w[D+1]``: margins by
    gather-dot, updates by scatter-add, both through ``ops.sparse``."""

    def _margins(self, params, x):
        return sparse_matvec(params["w"], *self._with_bias(params, x))

    def _touch(self, params, x):
        """What an update needs of the weights: the margins, ``val`` with
        the bias slot, the function that adds ``coef[b] * val[b, k]`` back,
        and the formulation's counters (``ops.sparse.sparse_update``: one
        formulation for both halves, from the calibration table;
        ``dataStructure.scatterImpl`` pins it per pipeline)."""
        idx, val = self._with_bias(params, x)
        margins, add, counters = sparse_update(
            params["w"], idx, val, impl=self.ds.get("scatterImpl")
        )
        return margins, val, add, counters

    def update(self, params, x, y, mask) -> Tuple[Params, jnp.ndarray]:
        """``update_counting`` (what each learner below defines: the update
        plus the counters of the formulation it ran, which the SPMD step
        carries out beside the loss) without the counters."""
        params, loss, _ = self.update_counting(params, x, y, mask)
        return params, loss


class SparsePAClassifier(SparseVectorLinear):
    """Passive-Aggressive classifier on sparse inputs (PA / PA-I / PA-II,
    mirroring learners.linear.PAClassifier)."""

    name = "PA"
    task = "classification"

    def predict(self, params, x):
        margins = self._margins(params, x)
        return jnp.where(margins >= 0, 1.0, -1.0)

    def loss(self, params, x, y, mask):
        margins = self._margins(params, x)
        hinge = jnp.maximum(0.0, 1.0 - sign_labels(y) * margins)
        return masked_mean(hinge, mask)

    def update_counting(self, params, x, y, mask):
        variant = str(self.hp.get("variant", "PA-I"))
        C = float(self.hp.get("C", 0.01))
        margins, val, add, counters = self._touch(params, x)
        ys = sign_labels(y)
        hinge = jnp.maximum(0.0, 1.0 - ys * margins)
        tau = _pa_tau(hinge, sparse_sq_norm(val), variant, C)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        w = add(params["w"], tau * ys * mask / denom)
        return {"w": w}, masked_mean(hinge, mask), counters


class SparsePARegressor(SparseVectorLinear):
    """Epsilon-insensitive PA regressor on sparse inputs (RegressorPA)."""

    name = "RegressorPA"
    task = "regression"

    def predict(self, params, x):
        margins = self._margins(params, x)
        return margins

    def loss(self, params, x, y, mask):
        eps = float(self.hp.get("epsilon", 0.1))
        margins = self._margins(params, x)
        return masked_mean(jnp.maximum(0.0, jnp.abs(margins - y) - eps), mask)

    def update_counting(self, params, x, y, mask):
        variant = str(self.hp.get("variant", "PA-I"))
        C = float(self.hp.get("C", 0.01))
        eps = float(self.hp.get("epsilon", 0.1))
        margins, val, add, counters = self._touch(params, x)
        err = margins - y
        l = jnp.maximum(0.0, jnp.abs(err) - eps)
        tau = _pa_tau(l, sparse_sq_norm(val), variant, C)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        w = add(params["w"], -jnp.sign(err) * tau * mask / denom)
        return {"w": w}, masked_mean(l, mask), counters


class SparseSVM(SparseVectorLinear):
    """Pegasos SVM on raw sparse features (the dense twin lifts through RFF;
    random Fourier features densify by construction, so the sparse variant
    is the standard linear pegasos on the hashed space)."""

    name = "SVM"
    task = "classification"

    def init(self, dim: int, rng: Optional[jax.Array] = None) -> Params:
        self._dim = dim
        return {
            "w": jnp.zeros((dim + 1,), jnp.float32),
            "t": jnp.ones((), jnp.float32),
        }

    def predict(self, params, x):
        margins = self._margins(params, x)
        return jnp.where(margins >= 0, 1.0, -1.0)

    def loss(self, params, x, y, mask):
        margins = self._margins(params, x)
        hinge = jnp.maximum(0.0, 1.0 - sign_labels(y) * margins)
        return masked_mean(hinge, mask)

    def update_counting(self, params, x, y, mask):
        """Mini-batch pegasos: eta = 1/(lambda*t); w <- (1-eta*lambda)w +
        eta * mean_violators(y x). The decay is the only O(D) op."""
        lam = float(self.hp.get("lambda", 1e-4))
        margins, _, add, counters = self._touch(params, x)
        ys = sign_labels(y)
        hinge = jnp.maximum(0.0, 1.0 - ys * margins)
        viol = (hinge > 0).astype(jnp.float32) * mask
        eta = 1.0 / (lam * params["t"])
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        w = add(params["w"] * (1.0 - eta * lam), eta * ys * viol / denom)
        return (
            {"w": w, "t": params["t"] + 1.0},
            masked_mean(hinge, mask),
            counters,
        )


class SparseSoftmax(SparseLinear):
    """Multiclass softmax regression with SGD on sparse inputs
    (mirrors learners.linear.SoftmaxClassifier; BASELINE.md config 5 at
    real Avazu hashed dimensionality)."""

    name = "Softmax"
    task = "classification"

    def init(self, dim: int, rng: Optional[jax.Array] = None) -> Params:
        self._dim = dim
        k = int(self.hp.get("nClasses", 2))
        return {"W": jnp.zeros((dim + 1, k), jnp.float32)}

    def _logits(self, params, x):
        idx, val = x
        idx, val = append_bias_sparse(idx, val, params["W"].shape[0] - 1)
        return sparse_matmat(params["W"], idx, val), (idx, val)

    def predict(self, params, x):
        logits, _ = self._logits(params, x)
        k = params["W"].shape[1]
        cls = jnp.argmax(logits, axis=1)
        # binary models report signed labels like the other classifiers
        return jnp.where(k == 2, cls.astype(jnp.float32) * 2.0 - 1.0,
                         cls.astype(jnp.float32))

    def _xent(self, logits, y):
        k = logits.shape[1]
        yi = jnp.clip(y.astype(jnp.int32), 0, k - 1)
        logp = jax.nn.log_softmax(logits, axis=1)
        return -jnp.take_along_axis(logp, yi[:, None], axis=1)[:, 0]

    def loss(self, params, x, y, mask):
        logits, _ = self._logits(params, x)
        return masked_mean(self._xent(logits, y), mask)

    def update(self, params, x, y, mask) -> Tuple[Params, jnp.ndarray]:
        lr = float(self.hp.get("learningRate", 0.05))
        logits, (idx, val) = self._logits(params, x)
        k = logits.shape[1]
        yi = jnp.clip(y.astype(jnp.int32), 0, k - 1)
        probs = jax.nn.softmax(logits, axis=1)
        grad = probs - jax.nn.one_hot(yi, k, dtype=probs.dtype)  # [B, K_cls]
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        coef = -lr * grad * (mask / denom)[:, None]
        W = sparse_scatter_add_outer(params["W"], idx, coef, val)
        return {"W": W}, masked_mean(self._xent(logits, y), mask)

    def score(self, params, x, y, mask):
        logits, _ = self._logits(params, x)
        k = params["W"].shape[1]
        yi = jnp.clip(y.astype(jnp.int32), 0, k - 1)
        correct = (jnp.argmax(logits, axis=1) == yi).astype(jnp.float32)
        return masked_mean(correct, mask)


SPARSE_LEARNERS = {
    "PA": SparsePAClassifier,
    "RegressorPA": SparsePARegressor,
    "SVM": SparseSVM,
    "Softmax": SparseSoftmax,
}
