"""Pipeline parallelism: transformer layers sharded over a ``"pp"`` axis.

GPipe-style collective pipelining done the TPU-native way (the pattern of
the public scaling-book recipe): every device runs the SAME program under
``shard_map``; each pp stage owns ``n_layers / pp`` stacked transformer
blocks; a ``lax.scan`` over ``M + pp - 1`` ticks drives M microbatches
through the ring — stage 0 injects the next embedded microbatch each tick,
``ppermute`` hands activations to the next stage over ICI, and the last
stage collects logits. The warmup/drain bubble is ``(pp-1)/(M+pp-1)`` of
the schedule, amortized by more microbatches.

Composes with a leading ``"dp"`` axis (batch split, loss psum). Autodiff
runs straight through the scan + ppermute (shard_map vma transposes), so
one ``jax.grad`` gives exact pipeline-parallel backprop — verified
numerically against the single-device stacked-layer model in
tests/test_pipeline_parallel.py.

No counterpart exists in the reference (SURVEY.md section 2.4: pipeline
parallelism ABSENT) — long-context/multi-chip scope, TPU-first design.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from omldm_tpu.models.transformer import (
    TransformerConfig,
    _rms_norm,
    cast_params,
    init_transformer,
)
from omldm_tpu.parallel.optim import adam_opt_specs, adam_update, init_adam_state
from omldm_tpu.ops.attention import attention


def make_pp_mesh(dp: int = 1, pp: int = 1, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    need = dp * pp
    if need > len(devices):
        raise ValueError(f"mesh ({dp}x{pp}) needs {need} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:need]).reshape(dp, pp), ("dp", "pp"))


def stack_layer_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Convert the per-layer list pytree of ``init_transformer`` into one
    stacked pytree with a leading [n_layers] dim per leaf — the layout
    pipeline (and scan-over-layers) execution shards over pp."""
    layers = params["layers"]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layers)
    out = dict(params)
    out["layers"] = stacked
    return out


def _apply_block(cfg: TransformerConfig, layer, x):
    """One dense transformer block on a full (non-sp/tp) activation."""
    b, lc, _ = x.shape
    dh = cfg.d_model // cfg.n_heads
    z = _rms_norm(x, layer["ln1"]["g"])
    qkv = jnp.einsum("bld,dke->blke", z, layer["wqkv"])
    q = qkv[:, :, 0].reshape(b, lc, cfg.n_heads, dh)
    k = qkv[:, :, 1].reshape(b, lc, cfg.n_heads, dh)
    v = qkv[:, :, 2].reshape(b, lc, cfg.n_heads, dh)
    # backend dispatch: Pallas flash kernel on TPU, blockwise scan on CPU
    o = attention(q, k, v, causal=cfg.causal)
    x = x + o.reshape(b, lc, cfg.n_heads * dh) @ layer["wo"]
    z = _rms_norm(x, layer["ln2"]["g"])
    return x + jax.nn.relu(z @ layer["w1"]) @ layer["w2"]


def _apply_stage(cfg: TransformerConfig, stage_layers, x):
    """Run this stage's local stacked blocks (scan over the layer dim)."""

    def body(h, layer):
        return _apply_block(cfg, layer, h), None

    h, _ = jax.lax.scan(body, x, stage_layers)
    return h


def pp_lm_loss(
    cfg: TransformerConfig,
    params: Dict[str, Any],     # local slice: layers [L/pp, ...] on each stage
    tokens: jnp.ndarray,        # [M, B_local, L] microbatches (replicated over pp)
    targets: jnp.ndarray,       # [M, B_local, L]
    mask: jnp.ndarray,          # [M, B_local, L]
    dp_axis: str = "dp",
    pp_axis: str = "pp",
) -> jnp.ndarray:
    """Global-mean LM loss of the pipelined forward. Runs INSIDE shard_map
    over a ("dp", "pp") mesh."""
    params = cast_params(params, cfg.dtype)
    n = jax.lax.axis_size(pp_axis)
    i = jax.lax.axis_index(pp_axis)
    m = tokens.shape[0]
    lc = tokens.shape[2]

    # every stage embeds (embed/pos replicated; only stage 0's copy is
    # injected, but computing on all stages keeps one SPMD program)
    emb = params["embed"][tokens] + params["pos"][None, None, :lc]  # [M,B,L,D]

    fwd_perm = [(j, j + 1) for j in range(n - 1)]
    # carries must be varying over (dp, pp) to match the scan body's outputs.
    # the nll accumulators are scalars: carrying logits for all microbatches
    # would checkpoint an [M, B, L, vocab] buffer per tick — at real vocab
    # sizes that dominates HBM and defeats the pipelining.
    state0, num0, den0 = jax.lax.pcast(
        (jnp.zeros(emb.shape[1:], emb.dtype), jnp.float32(0.0), jnp.float32(0.0)),
        (dp_axis, pp_axis), to="varying",
    )

    def tick(carry, t):
        state, num, den = carry
        inject = jax.lax.dynamic_index_in_dim(emb, jnp.clip(t, 0, m - 1), 0,
                                              keepdims=False)
        x = jnp.where(i == 0, inject, state)
        out = _apply_stage(cfg, params["layers"], x)
        # last stage finishes microbatch t-(n-1) at tick t: fold its nll
        # into the scalar accumulators (head projection is computed on every
        # stage to stay one SPMD program, but only the last stage's counts)
        idx = t - (n - 1)
        slot = jnp.clip(idx, 0, m - 1)
        logits = _rms_norm(out, params["ln_f"]["g"]) @ params["head"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = jax.lax.dynamic_index_in_dim(targets, slot, 0, keepdims=False)
        msk = jax.lax.dynamic_index_in_dim(mask, slot, 0, keepdims=False)
        nll = -jnp.take_along_axis(
            logp, tgt[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        take = jnp.where(jnp.logical_and(i == n - 1, idx >= 0), 1.0, 0.0)
        num = num + take * jnp.sum(nll * msk)
        den = den + take * jnp.sum(msk)
        # hand activations to the next stage (one ICI hop per tick)
        state = jax.lax.ppermute(out, pp_axis, fwd_perm)
        return (state, num, den), None

    (_, num, den), _ = jax.lax.scan(
        tick, (state0, num0, den0), jnp.arange(m + n - 1)
    )

    # only the last stage accumulated: the psum shares the scalars with
    # every stage so the loss (and its cotangent) is uniform
    num = jax.lax.psum(num, pp_axis)
    den = jax.lax.psum(den, pp_axis)
    num = jax.lax.psum(num, dp_axis)
    den = jax.lax.psum(den, dp_axis)
    return num / jnp.maximum(den, 1.0)


class PPTrainer:
    """Adam-trained dense transformer over a ("dp", "pp") mesh.

    Layers are stacked [n_layers, ...] and sharded over pp (n_layers % pp
    == 0); embed/pos/head/ln_f are replicated. Batches arrive as global
    host arrays [B, L] and are split into ``n_micro`` microbatches per dp
    shard."""

    def __init__(
        self,
        cfg: TransformerConfig,
        mesh: Optional[Mesh] = None,
        n_micro: int = 4,
        lr: float = 1e-3,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
        seed: int = 0,
    ):
        if cfg.n_experts:
            raise ValueError("PPTrainer supports dense blocks only")
        if cfg.objective != "lm":
            raise ValueError("PPTrainer supports the lm objective")
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_pp_mesh()
        pp = self.mesh.shape["pp"]
        if cfg.n_layers % pp:
            raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp {pp}")
        self.n_micro = n_micro
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

        stacked = stack_layer_params(
            init_transformer(cfg, jax.random.PRNGKey(seed))
        )
        pspecs = {
            "embed": P(),
            "pos": P(),
            "ln_f": {"g": P()},
            "head": P(),
            "layers": jax.tree_util.tree_map(
                lambda _: P("pp"), stacked["layers"]
            ),
        }
        self.params = jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(self.mesh, spec)),
            stacked, pspecs,
            is_leaf=lambda x: isinstance(x, jnp.ndarray),
        )
        self.opt = init_adam_state(self.params, self.mesh)
        ospecs = adam_opt_specs(pspecs)
        self._pspecs = pspecs
        self._ospecs = ospecs
        data_spec = P(None, "dp", None)  # [M, B, L] microbatches, B over dp

        def step_impl(params, opt, tokens, targets, mask):
            loss, grads = jax.value_and_grad(
                lambda p: pp_lm_loss(cfg, p, tokens, targets, mask)
            )(params)
            new_params, new_opt = adam_update(params, grads, opt, lr, b1, b2, eps)
            return new_params, new_opt, loss

        self._step = jax.jit(
            jax.shard_map(
                step_impl,
                mesh=self.mesh,
                in_specs=(pspecs, ospecs, data_spec, data_spec, data_spec),
                out_specs=(pspecs, ospecs, P()),
            ),
            donate_argnums=(0, 1),
        )
        self._fitted = 0

    def step(self, tokens, targets, mask=None, valid_count=None) -> jnp.ndarray:
        """tokens/targets/mask: [B, L] global arrays; B must divide by
        dp * n_micro. Returns the (lazy) global mean loss. Pass
        ``valid_count`` when ``mask`` is device-resident to avoid a
        device->host copy for the fitted counter."""
        if mask is None:
            mask = np.ones(np.shape(tokens), np.float32)
        b, l = np.shape(tokens)
        m = self.n_micro
        dp = self.mesh.shape["dp"]
        if b % (m * dp):
            raise ValueError(f"batch {b} not divisible by n_micro*dp {m * dp}")

        def to_micro(a):
            # [B, L] -> [M, B/M, L] with dp-contiguous rows per microbatch;
            # device arrays reshape lazily on device (no host round trip)
            if isinstance(a, jnp.ndarray):
                return a.reshape(m, b // m, l)
            return np.asarray(a).reshape(m, b // m, l)

        self.params, self.opt, loss = self._step(
            self.params, self.opt,
            to_micro(tokens), to_micro(targets), to_micro(mask),
        )
        self._fitted += (
            int(valid_count) if valid_count is not None
            else int(np.asarray(mask).sum())
        )
        return loss

    @property
    def fitted(self) -> int:
        return self._fitted

    def host_params(self):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), self.params
        )

    def save(self, directory: str) -> None:
        """Orbax snapshot of {params, opt, fitted}."""
        from omldm_tpu.parallel.ckpt import save_trainer_state

        save_trainer_state(self, directory)

    def load(self, directory: str) -> None:
        """Restore a snapshot onto this trainer's mesh (same cfg/mesh)."""
        from omldm_tpu.parallel.ckpt import load_trainer_state

        load_trainer_state(self, directory)
