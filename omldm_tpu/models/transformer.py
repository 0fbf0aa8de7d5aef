"""Pure-functional transformer (dense MLP or switch-MoE blocks).

One forward works everywhere: call it plainly for a single device, or inside
``shard_map`` with any subset of the mesh axes

- ``sp`` — sequence/context parallelism: tokens arrive pre-sharded
  ``[B, L/sp]``; attention runs as ring attention (K/V rotating over ICI,
  omldm_tpu.ops.ring_attention) and position embeddings are offset by the
  shard's absolute start.
- ``tp`` — tensor parallelism (Megatron layout): attention heads and MLP /
  expert hidden width are sharded; params arrive as local slices and the
  only communication is one ``psum`` after each block's output projection.
- ``ep`` — expert parallelism for MoE blocks: each shard owns
  ``n_experts/ep`` experts; tokens are routed with capacity-bounded top-1
  (switch) dispatch through a pair of ``all_to_all``s.

Axis presence is declared via ``AxisSpec``; with no axes the collectives
vanish and the same code is the single-chip model. No counterpart exists in
the reference (no sequence dimension, SURVEY.md section 5 "long-context") —
this is the framework's long-context scope, designed TPU-first.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from omldm_tpu.ops.attention import attention
from omldm_tpu.ops.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 2048
    n_classes: int = 2          # classify head width
    causal: bool = True
    objective: str = "lm"       # "lm" (token logits) | "classify" (pooled)
    # MoE: n_experts == 0 => dense MLP blocks
    n_experts: int = 0
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    # sequence-parallel attention strategy over the sp axis:
    # "ring" (ppermute K/V rotation, O(L/sp) memory) or "ulysses"
    # (all_to_all head/seq re-shard; needs (n_heads // tp) % sp == 0)
    seq_parallel: str = "ring"
    # rematerialize each block's activations in the backward pass
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(n_layers) less HBM —
    # the standard long-context memory lever
    remat: bool = False
    # fused chunked LM cross-entropy: > 0 computes the loss in token
    # chunks of this size — logits for a chunk are produced by a bf16
    # matmul with f32 accumulation, reduced to (lse, target-logit) and
    # DISCARDED; the backward recomputes them per chunk (jax.checkpoint
    # over a lax.scan). The full [B*L, V] f32 logits tensor (the HBM
    # round-trip that dominates the non-attention time at V=8192) is
    # never materialized. 0 = unfused (whole-tensor log_softmax).
    loss_chunk: int = 0


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """Mesh axis names the forward runs under (None = axis not used).
    ``dp`` only affects loss reductions (batch is split over it)."""
    dp: Optional[str] = None
    sp: Optional[str] = None
    tp: Optional[str] = None
    ep: Optional[str] = None

    @property
    def any(self) -> bool:
        return bool(self.dp or self.sp or self.tp or self.ep)

    def loss_axes(self):
        return tuple(a for a in (self.dp, self.sp) if a)


def _dense(rng, fan_in, fan_out, dtype):
    scale = jnp.sqrt(2.0 / fan_in).astype(jnp.float32)
    return (scale * jax.random.normal(rng, (fan_in, fan_out), jnp.float32)).astype(dtype)


def init_transformer(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """Full (unsharded) parameter pytree. The seq trainer slices tp/ep dims
    before placing shards; shapes here are the logical globals."""
    dh = cfg.d_model // cfg.n_heads
    assert cfg.n_heads * dh == cfg.d_model
    keys = iter(
        jax.random.split(rng, 6 + cfg.n_layers * (4 + 2 * max(cfg.n_experts, 1)))
    )
    params: Dict[str, Any] = {
        "embed": _dense(next(keys), cfg.vocab_size, cfg.d_model, jnp.float32),
        "pos": 0.02 * jax.random.normal(next(keys), (cfg.max_len, cfg.d_model), jnp.float32),
        "ln_f": {"g": jnp.ones((cfg.d_model,), jnp.float32)},
        "layers": [],
    }
    for _ in range(cfg.n_layers):
        layer = {
            "ln1": {"g": jnp.ones((cfg.d_model,), jnp.float32)},
            "ln2": {"g": jnp.ones((cfg.d_model,), jnp.float32)},
            # [D, 3, D] so tensor parallelism shards the trailing (head) dim
            # without splitting the q|k|v packing
            "wqkv": _dense(next(keys), cfg.d_model, 3 * cfg.d_model, jnp.float32)
            .reshape(cfg.d_model, 3, cfg.d_model),
            "wo": _dense(next(keys), cfg.d_model, cfg.d_model, jnp.float32),
        }
        if cfg.n_experts > 0:
            layer["router"] = _dense(next(keys), cfg.d_model, cfg.n_experts, jnp.float32)
            layer["w1"] = jnp.stack(
                [_dense(next(keys), cfg.d_model, cfg.d_ff, jnp.float32)
                 for _ in range(cfg.n_experts)]
            )  # [E, D, F]
            layer["w2"] = jnp.stack(
                [_dense(next(keys), cfg.d_ff, cfg.d_model, jnp.float32)
                 for _ in range(cfg.n_experts)]
            )  # [E, F, D]
        else:
            layer["w1"] = _dense(next(keys), cfg.d_model, cfg.d_ff, jnp.float32)
            layer["w2"] = _dense(next(keys), cfg.d_ff, cfg.d_model, jnp.float32)
        params["layers"].append(layer)
    if cfg.objective == "classify":
        params["head"] = _dense(next(keys), cfg.d_model, cfg.n_classes, jnp.float32)
    else:
        params["head"] = _dense(next(keys), cfg.d_model, cfg.vocab_size, jnp.float32)
    return params


def cast_params(params, dtype):
    """Mixed precision: master weights stay fp32 in the optimizer; the
    forward computes in ``cfg.dtype`` (bfloat16 on TPU halves HBM traffic
    and doubles MXU rate). The cast is a no-op for fp32 and differentiable
    (its transpose casts gradients back to fp32)."""
    if dtype == jnp.float32:
        return params
    return jax.tree_util.tree_map(
        lambda w: w.astype(dtype)
        if isinstance(w, jnp.ndarray) and jnp.issubdtype(w.dtype, jnp.floating)
        else w,
        params,
    )


def _rms_norm(x, g):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (x32 * scale).astype(x.dtype) * g


def _psum_if(x, axis: Optional[str]):
    return jax.lax.psum(x, axis) if axis else x


def _attention_block(cfg, layer, x, axes: AxisSpec):
    """x: [B, Lc, D_model]; wqkv [D, 3, h_local] / wo [h_local, D] hold this
    shard's heads when tp is set (h_local = heads_local * dh)."""
    b, lc, _ = x.shape
    h = layer["wqkv"].shape[2]  # local qkv width (= heads_local * dh)
    dh = cfg.d_model // cfg.n_heads
    heads_local = h // dh
    qkv = jnp.einsum("bld,dke->blke", x, layer["wqkv"])  # [B, Lc, 3, h_local]
    q = qkv[:, :, 0].reshape(b, lc, heads_local, dh)
    k = qkv[:, :, 1].reshape(b, lc, heads_local, dh)
    v = qkv[:, :, 2].reshape(b, lc, heads_local, dh)
    if axes.sp and jax.lax.axis_size(axes.sp) > 1:
        if cfg.seq_parallel == "ulysses":
            from omldm_tpu.ops.ulysses import ulysses_attention

            o = ulysses_attention(q, k, v, axes.sp, causal=cfg.causal)
        else:
            o = ring_attention(q, k, v, axes.sp, causal=cfg.causal)
    else:
        # single sequence shard: backend dispatch — Pallas flash kernel on
        # TPU (differentiable via its blockwise-derived VJP), blockwise scan
        # on CPU; avoids ring_attention's per-chunk full score matrix
        o = attention(q, k, v, causal=cfg.causal)
    o = o.reshape(b, lc, h) @ layer["wo"]  # [B, Lc, D]
    # tp: each shard computed a partial output projection over its heads
    return _psum_if(o, axes.tp)


def _mlp_block(layer, x, axes: AxisSpec):
    h = jax.nn.relu(x @ layer["w1"])       # [B, Lc, F_local]
    out = h @ layer["w2"]                  # partial over tp shards
    return _psum_if(out, axes.tp)


def _moe_block_dense(layer, x, capacity_factor: float):
    """Single-device switch MoE: dense compute (all experts), top-1 gate
    select — with the SAME per-expert capacity rule as the EP path, so a
    model trained dense and served expert-parallel (or vice versa) computes
    the same function: over-capacity tokens drop to the residual in both."""
    b, lc, d = x.shape
    t = x.reshape(-1, d)                              # [T, D]
    T = t.shape[0]
    n_experts = layer["w1"].shape[0]
    cap = max(int(capacity_factor * T / n_experts), 1)
    logits = t @ layer["router"]                      # [T, E]
    gate = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(gate, axis=-1)                # [T]
    gval = jnp.max(gate, axis=-1)                     # [T]
    # same capacity/priority rule as _moe_block_ep: position order within
    # each expert, tokens past the expert's cap drop to the residual
    onehot_i = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot_i, axis=0) * onehot_i
    keep = (jnp.sum(pos_in_e, axis=-1) - 1) < cap
    h = jax.nn.relu(jnp.einsum("td,edf->tef", t, layer["w1"]))
    y = jnp.einsum("tef,efd->ted", h, layer["w2"])    # [T, E, D]
    onehot = onehot_i.astype(y.dtype)
    out = jnp.einsum("ted,te->td", y, onehot) * gval[:, None].astype(y.dtype)
    out = jnp.where(keep[:, None], out, 0.0)
    return out.reshape(b, lc, d)


def _moe_block_ep(layer, x, ep_axis: str, capacity_factor: float):
    """Expert-parallel switch MoE: shards own E_local experts; tokens move
    through all_to_all dispatch/combine with per-(shard, expert) capacity.

    Token t on shard s with top-1 expert e is granted a slot if fewer than C
    earlier local tokens chose e; over-capacity tokens are dropped (standard
    switch semantics) — their block output is 0 and the residual carries
    them through."""
    b, lc, d = x.shape
    ep = jax.lax.axis_size(ep_axis)
    e_local = layer["w1"].shape[0]        # experts owned by this shard
    n_experts = ep * e_local
    t = x.reshape(-1, d)                  # [T, D] local tokens
    T = t.shape[0]
    cap = max(int(capacity_factor * T / n_experts), 1)

    logits = t @ layer["router"]  # router is small and replicated
    gate = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E_total]
    expert = jnp.argmax(gate, axis=-1)                          # [T]
    gval = jnp.max(gate, axis=-1)                               # [T]

    # slot of token within its expert's capacity (priority by position)
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)   # [T, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot                # 1-based
    slot = jnp.sum(pos_in_e, axis=-1) - 1                         # [T]
    keep = slot < cap

    # dispatch buffer [E_total, C, D] via scatter
    disp = jnp.zeros((n_experts, cap, d), x.dtype)
    idx_e = jnp.where(keep, expert, 0)
    idx_c = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[:, None], t, 0.0).astype(x.dtype)
    disp = disp.at[idx_e, idx_c].add(contrib)

    # all_to_all: [E_total, C, D] -> [ep, E_local, C, D] -> exchange shards
    disp = disp.reshape(ep, e_local, cap, d)
    recv = jax.lax.all_to_all(disp, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    # recv: [ep(src shard), E_local, C, D] — all tokens for MY experts
    ht = jax.nn.relu(jnp.einsum("secd,edf->secf", recv, layer["w1"]))
    yt = jnp.einsum("secf,efd->secd", ht, layer["w2"])  # [ep, E_local, C, D]

    # send results back: inverse all_to_all
    back = jax.lax.all_to_all(yt, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    back = back.reshape(n_experts, cap, d)              # [E_total, C, D]

    # combine: gather each kept token's result, scale by its gate
    out_t = back[idx_e, idx_c] * gval[:, None].astype(x.dtype)
    out_t = jnp.where(keep[:, None], out_t, 0.0)
    return out_t.reshape(b, lc, d)


def transformer_hidden(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    tokens: jnp.ndarray,          # [B, Lc] int32 (local chunk when sp)
    axes: AxisSpec = AxisSpec(),
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Final-norm hidden states [B, Lc, D] plus the compute-dtype params
    (so loss heads reuse the cast instead of re-casting)."""
    params = cast_params(params, cfg.dtype)
    b, lc = tokens.shape
    pos_offset = jax.lax.axis_index(axes.sp) * lc if axes.sp else 0
    x = params["embed"][tokens] + jax.lax.dynamic_slice(
        params["pos"], (pos_offset, 0) if axes.sp else (0, 0),
        (lc, params["pos"].shape[1]),
    )
    def block(x, layer):
        x = x + _attention_block(cfg, layer, _rms_norm(x, layer["ln1"]["g"]), axes)
        z = _rms_norm(x, layer["ln2"]["g"])
        if cfg.n_experts > 0:
            if axes.ep:
                y = _moe_block_ep(layer, z, axes.ep, cfg.capacity_factor)
            else:
                y = _moe_block_dense(layer, z, cfg.capacity_factor)
        else:
            y = _mlp_block(layer, z, axes)
        return x + y

    if cfg.remat:
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x = block(x, layer)
    x = _rms_norm(x, params["ln_f"]["g"])
    return x, params


def transformer_forward(
    cfg: TransformerConfig,
    params: Dict[str, Any],
    tokens: jnp.ndarray,          # [B, Lc] int32 (local chunk when sp)
    axes: AxisSpec = AxisSpec(),
) -> jnp.ndarray:
    """Returns token logits [B, Lc, V] ("lm") or pooled class logits
    [B, n_classes] ("classify")."""
    x, params = transformer_hidden(cfg, params, tokens, axes)
    if cfg.objective == "classify":
        pooled = jnp.mean(x, axis=1)                       # local mean over Lc
        if axes.sp:
            # global mean over the full sequence = mean of shard means
            pooled = jax.lax.pmean(pooled, axes.sp)
        return pooled @ params["head"]                     # [B, n_classes]
    return x @ params["head"]                              # [B, Lc, V]


def _lm_nll_fused(head, x, targets, mask, chunk):
    """Masked NLL sum over all local tokens WITHOUT materializing the
    [T, V] logits: lax.scan over token chunks, each chunk's logits built
    by a bf16 matmul with f32 accumulation, reduced to (logsumexp,
    target logit) and dropped; jax.checkpoint recomputes them in the
    backward, where dlogits -> (dx, dhead) contract chunk-locally. The
    V=8192 head's f32 logits tensor — 2 full HBM round trips forward and
    more backward in the unfused form — never exists."""
    d = x.shape[-1]
    xs = x.reshape(-1, d)
    ts = targets.reshape(-1).astype(jnp.int32)
    ms = mask.reshape(-1).astype(jnp.float32)
    t_total = xs.shape[0]
    n_chunks = -(-t_total // chunk)
    pad = n_chunks * chunk - t_total
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad, d), xs.dtype)])
        ts = jnp.concatenate([ts, jnp.zeros((pad,), ts.dtype)])
        ms = jnp.concatenate([ms, jnp.zeros((pad,), ms.dtype)])
    xs = xs.reshape(n_chunks, chunk, d)
    ts = ts.reshape(n_chunks, chunk)
    ms = ms.reshape(n_chunks, chunk)

    @jax.checkpoint
    def body(acc, inp):
        xc, tc, mc = inp
        logits = jnp.dot(
            xc, head, preferred_element_type=jnp.float32
        )                                                  # [chunk, V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((lse - tl) * mc), None

    # carry derived from the data so it has the same varying-axes type as
    # the body output under shard_map (a plain 0.0 literal is unvarying
    # and scan rejects the carry-type mismatch)
    acc0 = jnp.sum(ms) * jnp.float32(0.0)
    total, _ = jax.lax.scan(body, acc0, (xs, ts, ms))
    return total


def lm_loss(cfg, params, tokens, targets, mask, axes: AxisSpec = AxisSpec()):
    """GLOBAL mean next-token cross-entropy. targets/mask are pre-shifted
    host-side and sharded like tokens; the mean reduces over the dp and sp
    axes so every shard returns the same scalar. With ``cfg.loss_chunk``
    the NLL is computed by the fused chunked head (no [T, V] logits in
    HBM); numerics match the unfused path to f32 accumulation order —
    tighter, in fact: the unfused path rounds logits to bf16 before the
    f32 log_softmax."""
    if cfg.loss_chunk > 0:
        x, cparams = transformer_hidden(cfg, params, tokens, axes)
        num = _lm_nll_fused(
            cparams["head"], x, targets, mask, cfg.loss_chunk
        )
        den = jnp.sum(mask)
    else:
        logits = transformer_forward(cfg, params, tokens, axes)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
        num = jnp.sum(nll * mask)
        den = jnp.sum(mask)
    for ax in axes.loss_axes():
        num = jax.lax.psum(num, ax)
        den = jax.lax.psum(den, ax)
    return num / jnp.maximum(den, 1.0)


def classify_loss(cfg, params, tokens, labels, axes: AxisSpec = AxisSpec()):
    """GLOBAL mean class cross-entropy (labels [B] sharded over dp)."""
    logits = transformer_forward(cfg, params, tokens, axes)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    num = jnp.sum(nll)
    den = jnp.asarray(nll.shape[0], jnp.float32)
    if axes.dp:
        num = jax.lax.psum(num, axes.dp)
        den = jax.lax.psum(den, axes.dp)
    return num / den
