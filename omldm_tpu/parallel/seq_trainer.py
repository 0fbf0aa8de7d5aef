"""SeqTrainer: dp x sp x tp (+ expert-parallel) transformer training.

The long-context/distributed counterpart of :class:`SPMDTrainer`
(omldm_tpu.parallel.spmd) for the sequence-model family: one jitted,
donated ``shard_map`` step over a 3-axis ``("dp", "sp", "tp")`` mesh —

- batch split over ``dp`` (gradients reduced by the global-mean loss psum);
- sequence split over ``sp`` with ring attention rotating K/V over ICI;
- heads / MLP hidden (Megatron layout) split over ``tp`` with one psum per
  block; MoE experts split over the ``dp`` axis (expert parallelism) with
  all_to_all dispatch/combine.

Parameter placement uses ``NamedSharding`` of the GLOBAL pytree — XLA
slices each leaf onto its shards; inside ``shard_map`` the same leaf names
arrive as local slices and the forward in omldm_tpu.models.transformer is
shape-polymorphic over them. shard_map's varying-axis tracking makes
``jax.grad`` insert the correct gradient psums for replicated leaves.

No counterpart exists in the reference (SURVEY.md section 2.4: tensor /
pipeline / sequence parallelism ABSENT there) — this is the framework's
first-class long-context + multi-chip scope.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from omldm_tpu.models.transformer import (
    AxisSpec,
    TransformerConfig,
    classify_loss,
    init_transformer,
    lm_loss,
)
from omldm_tpu.parallel.optim import adam_opt_specs, adam_update, init_adam_state
from omldm_tpu.utils import batch_valid_counts


def make_seq_mesh(dp: int = 1, sp: int = 1, tp: int = 1,
                  devices=None) -> Mesh:
    """("dp", "sp", "tp") mesh over dp*sp*tp devices."""
    devices = list(devices if devices is not None else jax.devices())
    need = dp * sp * tp
    if need > len(devices):
        raise ValueError(f"mesh ({dp}x{sp}x{tp}) needs {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(dp, sp, tp)
    return Mesh(grid, ("dp", "sp", "tp"))


def _param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec tree mirroring init_transformer's pytree."""
    rep = P()
    layer_spec = {
        "ln1": {"g": rep},
        "ln2": {"g": rep},
        "wqkv": P(None, None, "tp"),   # heads over tp
        "wo": P("tp", None),
    }
    if cfg.n_experts > 0:
        layer_spec["router"] = rep
        layer_spec["w1"] = P("dp", None, None)   # experts over dp (= ep)
        layer_spec["w2"] = P("dp", None, None)
    else:
        layer_spec["w1"] = P(None, "tp")         # Megatron column-parallel
        layer_spec["w2"] = P("tp", None)         # Megatron row-parallel
    return {
        "embed": rep,
        "pos": rep,
        "ln_f": {"g": rep},
        "layers": [dict(layer_spec) for _ in range(cfg.n_layers)],
        "head": rep,
    }


class SeqTrainer:
    """Adam-trained transformer over a ("dp", "sp", "tp") mesh.

    Batches arrive as GLOBAL host arrays ``tokens/targets/mask: [B, L]``
    (targets/mask pre-shifted for "lm"; ``labels: [B]`` for "classify");
    they are split over (dp, sp) by the step's in_specs."""

    def __init__(
        self,
        cfg: TransformerConfig,
        mesh: Optional[Mesh] = None,
        lr: float = 1e-3,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_seq_mesh()
        dp, sp, tp = (self.mesh.shape[a] for a in ("dp", "sp", "tp"))
        if cfg.n_heads % tp:
            raise ValueError(f"n_heads {cfg.n_heads} not divisible by tp {tp}")
        if cfg.n_experts == 0 and cfg.d_ff % tp:
            raise ValueError(f"d_ff {cfg.d_ff} not divisible by tp {tp}")
        if cfg.n_experts > 0 and cfg.n_experts % dp:
            raise ValueError(f"n_experts {cfg.n_experts} not divisible by dp {dp}")
        if cfg.seq_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel must be 'ring' or 'ulysses', got "
                f"{cfg.seq_parallel!r}"
            )
        if cfg.seq_parallel == "ulysses" and sp > 1 and (cfg.n_heads // tp) % sp:
            raise ValueError(
                f"ulysses needs the per-tp-shard head count "
                f"({cfg.n_heads // tp}) divisible by sp {sp}"
            )
        # always name the axes: collectives over size-1 axes compile to
        # no-ops, and the vma typing then works uniformly on any mesh shape
        self.axes = AxisSpec(
            dp="dp", sp="sp", tp="tp",
            ep="dp" if cfg.n_experts > 0 else None,
        )
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

        pspecs = _param_specs(cfg)
        params_global = init_transformer(cfg, jax.random.PRNGKey(seed))
        self.params = jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(self.mesh, spec)),
            params_global, pspecs,
            is_leaf=lambda x: isinstance(x, jnp.ndarray),
        )
        self.opt = init_adam_state(self.params, self.mesh)
        self._pspecs = pspecs
        ospecs = adam_opt_specs(pspecs)
        # tokens/mask are [B, L] and sequence-sharded for BOTH objectives —
        # classify pools with pmean over sp, so its tokens must be real
        # chunks, not replicas (replicated copies would double-count keys in
        # ring attention and misapply position offsets)
        data_spec = P("dp", "sp")
        label_spec = P("dp", "sp") if cfg.objective == "lm" else P("dp")

        # check_vma=True (default): shard_map tracks which mesh axes every
        # intermediate varies over, so jax.grad's transpose inserts the
        # gradient psums for replicated parameter leaves automatically.
        step = jax.shard_map(
            self._step_impl,
            mesh=self.mesh,
            in_specs=(pspecs, ospecs, data_spec, label_spec, data_spec),
            out_specs=(pspecs, ospecs, P()),
        )
        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._ospecs = ospecs
        self._data_spec = data_spec
        self._label_spec = label_spec
        self._step_many = None  # built lazily on first step_many call
        self._fitted = 0

    # --- the per-shard step ---

    def _loss(self, params, tokens, targets, mask):
        if self.cfg.objective == "lm":
            return lm_loss(self.cfg, params, tokens, targets, mask, self.axes)
        return classify_loss(self.cfg, params, tokens, targets, self.axes)

    def _step_impl(self, params, opt, tokens, targets, mask):
        loss, grads = jax.value_and_grad(self._loss)(params, tokens, targets, mask)
        new_params, new_opt = adam_update(
            params, grads, opt, self.lr, self.b1, self.b2, self.eps
        )
        return new_params, new_opt, loss

    # --- public API ---

    def step(self, tokens, targets, mask=None, valid_count=None) -> jnp.ndarray:
        """One global training step; returns the (lazy) global mean loss.
        Pass ``valid_count`` when ``mask`` is device-resident to avoid a
        device->host copy for the fitted counter.

        NOTE: steps dispatch asynchronously. On the CPU backend (virtual
        multi-device testing) queueing hundreds of sharded steps without
        ever materializing a result can deadlock XLA's in-process
        collective rendezvous — materialize a loss periodically, or use
        :meth:`step_many`, which bounds the queue to one program per T
        batches (and is faster everywhere)."""
        if mask is None:
            mask = np.ones(np.shape(tokens), np.float32)
            valid_count = int(mask.sum()) if valid_count is None else valid_count
        self.params, self.opt, loss = self._step(
            self.params, self.opt, tokens, targets, mask
        )
        self._fitted += (
            int(valid_count) if valid_count is not None
            else int(np.asarray(mask).sum())
        )
        return loss

    def step_many(self, tokens_s, targets_s, masks_s=None, valid_counts=None):
        """T chained global steps in ONE program launch (lax.scan carrying
        (params, opt) over staged batches — the device never waits on the
        host between steps). tokens_s/targets_s/masks_s have a leading [T]
        dim; returns the lazy [T] losses."""
        if masks_s is None:
            masks_s = np.ones(np.shape(tokens_s), np.float32)
        if self._step_many is None:
            lead = lambda s: P(*((None,) + tuple(s)))  # noqa: E731

            def many_impl(params, opt, ts, gs, ms):
                def body(carry, b):
                    p, o = carry
                    tok, tgt, m = b
                    p, o, loss = self._step_impl(p, o, tok, tgt, m)
                    return (p, o), loss

                (params, opt), losses = jax.lax.scan(
                    body, (params, opt), (ts, gs, ms)
                )
                return params, opt, losses

            self._step_many = jax.jit(
                jax.shard_map(
                    many_impl,
                    mesh=self.mesh,
                    in_specs=(
                        self._pspecs, self._ospecs,
                        lead(self._data_spec), lead(self._label_spec),
                        lead(self._data_spec),
                    ),
                    out_specs=(self._pspecs, self._ospecs, P()),
                ),
                donate_argnums=(0, 1),
            )
        counts = batch_valid_counts(masks_s, valid_counts)
        self.params, self.opt, losses = self._step_many(
            self.params, self.opt, tokens_s, targets_s, masks_s
        )
        self._fitted += sum(counts)
        return losses

    @property
    def fitted(self) -> int:
        return self._fitted

    def host_params(self):
        """Global (unsharded) parameter pytree on host."""
        return jax.tree_util.tree_map(
            lambda l: np.asarray(jax.device_get(l)), self.params
        )

    def save(self, directory: str) -> None:
        """Orbax snapshot of {params, opt, fitted} (SURVEY.md section 7
        step 8 — the trainer-side checkpoint/resume path)."""
        from omldm_tpu.parallel.ckpt import save_trainer_state

        save_trainer_state(self, directory)

    def load(self, directory: str) -> None:
        """Restore a snapshot onto this trainer's mesh (same cfg/mesh)."""
        from omldm_tpu.parallel.ckpt import load_trainer_state

        load_trainer_state(self, directory)
