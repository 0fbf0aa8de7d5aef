"""A looped decoder: ONE stack of ``num_hidden_layers`` layers applied
``total_ut_steps`` times with the same parameters, rotary positions,
sandwich norms, an exit gate, and the gate's expected loss as the training
objective.

``h = E[tokens]``. For loop step ``t = 1..T``, for layer ``l = 1..N`` (the
same parameters at every ``t``)::

    a = h + Norm2_l(Attn_l(Norm1_l(h)))
    h = a + Norm4_l(W_down(silu(W_gate u) * (W_up u))),   u = Norm3_l(a)

``Attn``: ``q, k, v = W_q x, W_k x, W_v x`` in ``num_attention_heads`` heads
of ``head_dim`` (no bias, as many key-value heads), rotary embedding over the
whole head width on ``q`` and ``k`` (``rope_theta``; the pairs are ``(j, j +
head_dim / 2)``), causal softmax at ``head_dim^-1/2`` through
:func:`omldm_tpu.ops.attention.attention`, ``W_o``. After the ``N`` layers
``h_t = Norm_f(h)``: the input of loop step ``t + 1``, of the head (``logits_t
= W_head h_t``) and of the gate (``lambda_t = sigmoid(w_g . h_t + b_g)``).
A position leaves at step ``t`` with probability ``p_t = lambda_t prod_{j<t}
(1 - lambda_j)`` for ``t < T`` and ``p_T = prod_{j<T} (1 - lambda_j)``.

- Objective a position: ``sum_t p_t CE(logits_t, target) - ENTROPY_WEIGHT
  H(p)``; the loss of step ``t`` goes through
  :func:`omldm_tpu.models.transformer._lm_nll_fused` with ``p_t`` as its
  mask, so the gate is trained through it.
- Forecast: the logits of the first step whose cumulative ``p`` reaches
  ``early_exit_threshold`` (at 1.0 the last step).

Precision: parameters, gradients, norms, rotary angles, softmax, the gate
and the loss are float32; matrix products read ``operand_dtype`` operands
(bfloat16) and accumulate in float32.

The backward pass of the loop is written out (:func:`loop_steps`): the
forward keeps the input of every layer application (``T x N`` arrays of
``[L, hidden]``) and nothing else of a layer; the backward walks the
applications from the last to the first, recomputes each layer, and adds its
gradient into ONE accumulator a weight (a row of a stacked array, updated in
place). Left to ``jax.grad`` over two ``lax.scan``s, each loop step would
first build a whole stacked gradient of its own beside the accumulator.
``jax.named_scope`` names the parts (``omldm.lm.*``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

from omldm_tpu.models.blocks import LOSS_CHUNK, dense, normal_matrix, rms_norm, swiglu_ffn
from omldm_tpu.models.transformer import _lm_nll_fused
from omldm_tpu.ops.attention import attention
from omldm_tpu.utils import tracing

ENTROPY_WEIGHT = 0.1  # beta: the weight of the exit distribution's entropy
# what a Create request's ``dataStructure`` may say of the model: the keys of
# a published ``config.json``, and nothing of how the program computes it
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "total_ut_steps", "early_exit_threshold",
)


@dataclass(frozen=True)
class OuroConfig:
    """The defaults are a model a test holds."""

    vocab_size: int = 64
    hidden_size: int = 32
    intermediate_size: int = 88
    num_hidden_layers: int = 2
    num_attention_heads: int = 2
    num_key_value_heads: int = 2
    head_dim: int = 16
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    # what the matrix products read: the precision the program states, which
    # no request changes (a test compares in float32 by ``dataclasses.replace``)
    operand_dtype: str = "bfloat16"

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "OuroConfig":
        """From the keys a published ``config.json`` has
        (``PUBLISHED_KEYS``); every other key is ignored."""
        cfg = cls(**{k: m[k] for k in PUBLISHED_KEYS if k in m})
        if cfg.num_key_value_heads != cfg.num_attention_heads:
            raise ValueError("key-value heads shared between query heads are not implemented")
        if cfg.head_dim % 2:
            raise ValueError("rotary positions pair the two halves of a head: head_dim must be even")
        if cfg.total_ut_steps < 1 or cfg.num_hidden_layers < 1:
            raise ValueError("a looped decoder has at least one layer and one loop step")
        return cfg


Config = OuroConfig


def init_params(cfg: OuroConfig, rng: jax.Array) -> Dict[str, Any]:
    """Float32 parameters, the layers STACKED (a leaf of ``layers`` holds
    all ``num_hidden_layers`` of its kind): matrices normal(0, 0.02), norm
    gains 1, the gate's bias 0 (the published config gives no initial
    scale)."""
    f32 = jnp.float32
    n, d, f = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    width = cfg.num_attention_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 10))
    mat = lambda *shape: normal_matrix(next(keys), shape)
    gain = lambda: jnp.ones((n, d), f32)
    layers = {
        "attn_in_norm": gain(), "attn_out_norm": gain(), "ffn_in_norm": gain(), "ffn_out_norm": gain(),
        "wq": mat(n, d, width), "wk": mat(n, d, width), "wv": mat(n, d, width), "wo": mat(n, width, d),
        "w_gate": mat(n, d, f), "w_up": mat(n, d, f), "w_down": mat(n, f, d),
    }
    return {
        "embed": mat(cfg.vocab_size, d), "layers": layers, "norm": jnp.ones((d,), f32),
        "head": mat(d, cfg.vocab_size),
        "gate": {"w": mat(d), "b": jnp.zeros((1,), f32)},
    }


# --- a layer -------------------------------------------------------------------


def rope_angles(cfg: OuroConfig, length: int):
    """``cos, sin [length, head_dim / 2]`` of ``position * rope_theta^(-j /
    (head_dim / 2))``, float32."""
    half = cfg.head_dim // 2
    freq = jnp.float32(cfg.rope_theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, cos, sin):
    """Rotary embedding of ``x [B, L, H, head_dim]``: the pair ``(x_j, x_{j +
    head_dim / 2})`` turned by the position's ``j``-th angle."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(cfg: OuroConfig, rope, layer, x):
    dt = jnp.dtype(cfg.operand_dtype)
    b, l, _ = x.shape
    heads = (b, l, cfg.num_attention_heads, cfg.head_dim)
    with jax.named_scope("omldm.lm.attn_proj"):
        q, k, v = (dense(x, layer[w], dt).reshape(heads) for w in ("wq", "wk", "wv"))
    with jax.named_scope("omldm.lm.rope"):
        q, k = rotate(q, *rope), rotate(k, *rope)
    with jax.named_scope("omldm.lm.flash_attn"):
        o = attention(q.astype(dt), k.astype(dt), v.astype(dt), causal=True)
    with jax.named_scope("omldm.lm.attn_proj"):
        return dense(o.reshape(b, l, -1), layer["wo"], dt)


def _layer(cfg: OuroConfig, rope, layer, h):
    eps = cfg.rms_norm_eps
    attn = _attention(cfg, rope, layer, rms_norm(h, layer["attn_in_norm"], eps))
    a = h + rms_norm(attn, layer["attn_out_norm"], eps)
    ffn = swiglu_ffn(layer, rms_norm(a, layer["ffn_in_norm"], eps), jnp.dtype(cfg.operand_dtype))
    return a + rms_norm(ffn, layer["ffn_out_norm"], eps)


# --- the loop, forward and backward ---------------------------------------------


def _forward(cfg: OuroConfig, layers, norm, h0, keep: bool):
    """``hs [T, B, L, hidden]``, the normed output of every loop step; with
    ``keep`` also the input of every layer application ``[T * N, B, L,
    hidden]`` (written where it is made: no loop step's share stands beside
    it) and every loop step's output before ``Norm_f``."""
    rope = rope_angles(cfg, h0.shape[1])
    n, steps = cfg.num_hidden_layers, cfg.total_ut_steps

    def layer_step(carry, at):
        h, kept = carry
        i, layer = at
        if keep:
            kept = jax.lax.dynamic_update_index_in_dim(kept, h, i, 0)
        return (_layer(cfg, rope, layer, h), kept), None

    def loop_step(carry, t):
        (pre, kept), _ = jax.lax.scan(layer_step, carry, (t * n + jnp.arange(n), layers))
        h = rms_norm(pre, norm, cfg.rms_norm_eps)
        return (h, kept), (h, pre if keep else None)

    kept = jnp.broadcast_to(_zeros_like(h0), (steps * n,) + h0.shape) if keep else None
    (_, kept), (hs, pres) = jax.lax.scan(loop_step, (h0, kept), jnp.arange(steps))
    return hs, kept, pres


def _zeros_like(x):
    """Zeros of ``x``'s shape that vary over the mesh axes ``x`` varies over
    (inside ``shard_map`` a plain constant does not, and a loop refuses a
    carry whose type changes)."""
    zeros = jnp.zeros(x.shape, x.dtype)
    varying = tuple(jax.typeof(x).vma)
    return jax.lax.pcast(zeros, varying, to="varying") if varying else zeros


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def loop_steps(cfg: OuroConfig, layers, norm, h0):
    """The stack ``layers`` (stacked leaves) applied ``total_ut_steps`` times
    to ``h0 [B, L, hidden]``, ``Norm_f`` (``norm``) after each: ``[T, B, L,
    hidden]``."""
    return _forward(cfg, layers, norm, h0, keep=False)[0]


def _loop_steps_fwd(cfg, layers, norm, h0):
    hs, inputs, pres = _forward(cfg, layers, norm, h0, keep=True)
    return hs, (layers, norm, inputs, pres)


def _loop_steps_bwd(cfg, kept, g_hs):
    layers, norm, inputs, pres = kept
    rope = rope_angles(cfg, pres.shape[2])
    n, steps = cfg.num_hidden_layers, cfg.total_ut_steps

    def layer_step(carry, at):
        g, acc = carry
        i, layer = at
        x = jax.lax.dynamic_index_in_dim(inputs, i, 0, keepdims=False)
        _, pull = jax.vjp(functools.partial(_layer, cfg, rope), layer, x)
        g_layer, g = pull(g)
        # one accumulator a weight: row ``i mod N`` of the stacked gradient, in place
        acc = jax.tree_util.tree_map(
            lambda a, d: jax.lax.dynamic_update_index_in_dim(
                a, jax.lax.dynamic_index_in_dim(a, i % n, 0, keepdims=False) + d, i % n, 0),
            acc, g_layer)
        return (g, acc), None

    def loop_step(carry, at):
        g_next, acc, g_norm = carry
        t, g_h, pre = at
        # a step's output feeds its head and gate (``g_h``) and the next step
        _, pull = jax.vjp(lambda gain, x: rms_norm(x, gain, cfg.rms_norm_eps), norm, pre)
        d_norm, g = pull(g_h + g_next)
        (g, acc), _ = jax.lax.scan(layer_step, (g, acc), (t * n + jnp.arange(n), layers), reverse=True)
        return (g, acc, g_norm + d_norm), None

    start = (_zeros_like(g_hs[0]), jax.tree_util.tree_map(_zeros_like, layers), _zeros_like(norm))
    (g_h0, g_layers, g_norm), _ = jax.lax.scan(
        loop_step, start, (jnp.arange(steps), g_hs, pres), reverse=True)
    return g_layers, g_norm, g_h0


loop_steps.defvjp(_loop_steps_fwd, _loop_steps_bwd)


def hidden_states(cfg: OuroConfig, params, tokens):
    """``tokens [B, L]`` int -> the normed output of every loop step, ``[T,
    B, L, hidden]`` float32. Ids outside the vocabulary are clipped."""
    tracing.RECORDER.add_counts("lm_loop", ut_steps=cfg.total_ut_steps, layers=cfg.num_hidden_layers)
    with jax.named_scope("omldm.lm.embed"):
        h0 = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0, mode="clip")
    return loop_steps(cfg, params["layers"], params["norm"], h0)


# --- the exit gate ---------------------------------------------------------------


def exit_log_probs(gate, hs):
    """``log p_t`` of leaving at each loop step, ``[T, ...]`` float32, from
    the steps' outputs ``hs [T, ..., hidden]``: the gate reads the first ``T
    - 1`` (the last step takes what is left)."""
    z = jnp.sum(hs[:-1] * gate["w"], axis=-1) + gate["b"][0]  # float32 on the vector unit
    log_exit, log_stay = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    stayed = jnp.cumsum(log_stay, axis=0)  # log prod_{j <= t} (1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(hs[:1, ..., 0]), stayed], axis=0)  # ... prod_{j < t}
    return jnp.concatenate([log_exit + before[:-1], before[-1:]], axis=0)


def _exit_logits(cfg: OuroConfig, params, hs):
    """The head over, of ``hs [T, ..., hidden]``, the output of the first
    loop step whose cumulative exit probability reaches
    ``early_exit_threshold`` (the last step's cumulative probability is 1)."""
    with jax.named_scope("omldm.lm.exit_gate"):
        reached = jnp.cumsum(jnp.exp(exit_log_probs(params["gate"], hs)), axis=0) >= cfg.early_exit_threshold
        step = jnp.argmax(reached.at[-1].set(True), axis=0)
        x = jnp.take_along_axis(hs, step[None, ..., None], axis=0)[0]
    with jax.named_scope("omldm.lm.head_loss"):
        return dense(x, params["head"], jnp.dtype(cfg.operand_dtype))


# --- what the learner calls ---------------------------------------------------------


def objective_sum(cfg: OuroConfig, params, tokens, targets, mask):
    """Sum over positions of ``mask * (sum_t p_t CE(logits_t, target) -
    ENTROPY_WEIGHT H(p))``; ``targets`` and ``mask`` are ``[B, L]``."""
    hs = hidden_states(cfg, params, tokens)
    dt = jnp.dtype(cfg.operand_dtype)
    with jax.named_scope("omldm.lm.exit_gate"):
        log_p = exit_log_probs(params["gate"], hs)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
    with jax.named_scope("omldm.lm.head_loss"):
        expected = _lm_nll_fused(
            params["head"].astype(dt), hs.astype(dt), jnp.broadcast_to(targets, p.shape),
            mask * p, LOSS_CHUNK)
    return expected - ENTROPY_WEIGHT * jnp.sum(mask * entropy)


def last_logits(cfg: OuroConfig, params, tokens):
    """Logits of the position after the row: ``tokens [B, L]`` -> ``[B, V]``."""
    return _exit_logits(cfg, params, hidden_states(cfg, params, tokens)[:, :, -1])


def all_logits(cfg: OuroConfig, params, tokens):
    """``[B, L, V]`` logits: small sizes only (tests, a holdout's score)."""
    return _exit_logits(cfg, params, hidden_states(cfg, params, tokens))
