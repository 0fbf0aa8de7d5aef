"""Language-model learner (``LM``): a decoder trained online on token rows.

A row is ``dim`` token ids (the numeric features of a record) and its target
is the token after the last. ``update`` is one SGD step in float32 on the
model's training objective over all ``dim`` positions of the micro-batch
(position ``i`` predicts token ``i + 1``, the last predicts ``y``); masked
rows contribute nothing. ``predict`` is the most likely next token after
each row.

The model is the one the request's ``dataStructure`` names by the published
key ``model_type`` (``MODELS``): ``olmo_hybrid``
(:mod:`omldm_tpu.models.olmo_hybrid`, also where the key is absent: gated
delta-rule and full-attention layers, the next-token cross-entropy) or
``ouro`` (:mod:`omldm_tpu.models.ouro`: a looped decoder, the exit gate's
expected cross-entropy less its entropy). A model module gives ``Config``
(``from_mapping`` of the published keys), ``init_params``, ``objective_sum``,
``last_logits`` and ``all_logits``.

Data-structure config: the architecture's keys as a published
``config.json`` names them (the model module's ``PUBLISHED_KEYS``).
Hyper-parameters: ``learningRate`` (default 1e-2), ``optimizer`` (``sgd``,
the only one: the SPMD state has no place for an optimizer's), ``seed``
(default 0, folded into the key the initial weights are drawn from).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from omldm_tpu.learners.base import Learner, Params
from omldm_tpu.models import olmo_hybrid, ouro

MODELS = {"olmo_hybrid": olmo_hybrid, "ouro": ouro}


class SequenceLM(Learner):
    name = "LM"
    task = "classification"

    def __init__(self, hyper_parameters=None, data_structure=None):
        super().__init__(hyper_parameters, data_structure)
        optimizer = str(self.hp.get("optimizer", "sgd")).lower()
        if optimizer != "sgd":
            raise ValueError(f"LM trains with plain sgd, got optimizer {optimizer!r}")
        self.lr = float(self.hp.get("learningRate", 1e-2))
        model_type = self.ds.get("model_type", "olmo_hybrid")
        if model_type not in MODELS:
            raise ValueError(f"LM knows the models {sorted(MODELS)}, got model_type {model_type!r}")
        self.model = MODELS[model_type]
        self.cfg = self.model.Config.from_mapping(self.ds)

    def init(self, dim: int, rng: Optional[jax.Array] = None) -> Params:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return self.model.init_params(self.cfg, jax.random.fold_in(rng, int(self.hp.get("seed", 0))))

    # --- loss over the rows of a micro-batch ---

    def _objective(self, params, x, y, mask):
        """Mean of the model's objective over the positions of valid rows."""
        tokens = x.astype(jnp.int32)
        targets = jnp.concatenate([tokens[:, 1:], y.astype(jnp.int32)[:, None]], axis=1)
        mask = mask.astype(jnp.float32)
        per_token = jnp.broadcast_to(mask[:, None], tokens.shape)
        total = self.model.objective_sum(self.cfg, params, tokens, targets, per_token)
        return total / jnp.maximum(jnp.sum(mask) * tokens.shape[1], 1.0)

    def loss(self, params, x, y, mask):
        return self._objective(params, x, y, mask)

    def update(self, params, x, y, mask):
        loss, grads = jax.value_and_grad(self._objective)(params, x, y, mask)
        with jax.named_scope("omldm.lm.sgd"):
            params = jax.tree_util.tree_map(lambda p, g: p - self.lr * g, params, grads)
        return params, loss

    # --- serving ---

    def predict(self, params, x):
        """The arg-max next token after each row, one row at a time (a
        padded serving batch of long rows never stands on the device whole)."""

        def one(row):
            logits = self.model.last_logits(self.cfg, params, row[None].astype(jnp.int32))
            return jnp.argmax(logits[0]).astype(jnp.float32)

        return jax.lax.map(one, x)

    def score(self, params, x, y, mask):
        """Next-token accuracy over the positions of valid rows."""
        mask = mask.astype(jnp.float32)

        def one(row_y):
            row, target = row_y
            tokens = row[None].astype(jnp.int32)
            targets = jnp.concatenate([tokens[0, 1:], target.astype(jnp.int32)[None]])
            guess = jnp.argmax(self.model.all_logits(self.cfg, params, tokens)[0], axis=-1)
            return jnp.mean((guess == targets).astype(jnp.float32))

        per_row = jax.lax.map(one, (x, y))
        total = jnp.sum(mask)
        return jnp.where(total > 0, jnp.sum(per_row * mask) / jnp.maximum(total, 1.0), 0.0)
