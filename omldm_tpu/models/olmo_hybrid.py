"""A hybrid decoder: gated delta-rule (linear attention) layers and full
softmax-attention layers in a fixed pattern, as ``layer_types`` names them
(``linear_attention`` / ``full_attention``), with SwiGLU feed-forwards and
RMSNorm on every sublayer's OUTPUT.

Per layer ``x <- x + Norm(Mixer(x))``, ``x <- x + Norm(FFN(x))`` and
``FFN(x) = W_down(silu(W_gate x) * (W_up x))``.

- Full layer: ``q, k, v = W_q x, W_k x, W_v x`` (no bias), RMSNorm over the
  whole of ``q`` and of ``k``, causal softmax attention at ``1/sqrt(head)``
  through :func:`omldm_tpu.ops.attention.attention`, ``W_o``. No rotary
  embedding (the published ``rope_theta`` is null).
- Linear layer, per head of key width ``dk`` and value width ``dv``:
  ``q_t, k_t = l2norm(silu(conv(W_q x)_t)), l2norm(silu(conv(W_k x)_t))``,
  ``v_t = silu(conv(W_v x)_t)`` (causal depthwise conv), ``beta_t = 2
  sigmoid(w_b x_t)`` (``linear_allow_neg_eigval``; ``sigmoid`` without),
  ``log alpha_t = -exp(A_log) softplus(w_a x_t + dt_bias)``, the gated delta
  rule of :mod:`omldm_tpu.ops.delta_rule`, and the output ``W_o(RMSNorm(o_t)
  * silu(W_g x_t))``.
- Output norm, then the head over the vocabulary; the loss goes through
  :func:`omldm_tpu.models.transformer._lm_nll_fused` (chunked, no ``[L, V]``
  logits).

Precision: parameters, gradients, the recurrent state, gates, norms, softmax
and loss are float32; matrix products read ``operand_dtype`` operands
(bfloat16) and accumulate in float32. Every layer is recomputed in the
backward pass (``jax.checkpoint``), so a step keeps one ``[L, hidden]``
input a layer. ``jax.named_scope`` names the parts (``omldm.lm.*``) so that
a device trace can be attributed.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from omldm_tpu.models.blocks import LOSS_CHUNK, dense, normal_matrix, rms_norm, swiglu_ffn
from omldm_tpu.models.transformer import _lm_nll_fused
from omldm_tpu.ops.attention import attention
from omldm_tpu.ops.delta_rule import RESIDUALS, gated_delta_rule

LINEAR, FULL = "linear_attention", "full_attention"
# what a Create request's ``dataStructure`` may say of the model: the keys of
# a published ``config.json``, and nothing of how the program computes it
PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_attention_heads",
    "layer_types", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "rms_norm_eps",
)


@dataclass(frozen=True)
class OlmoHybridConfig:
    """The defaults are a model a test holds, in the published ratios (three
    linear layers to one full, keys half as wide as values)."""

    vocab_size: int = 64
    hidden_size: int = 32
    intermediate_size: int = 64
    num_attention_heads: int = 2
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 2
    linear_key_head_dim: int = 8
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    # what the matrix products read: the precision the program states, which
    # no request changes (a test compares in float32 by ``dataclasses.replace``)
    operand_dtype: str = "bfloat16"

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "OlmoHybridConfig":
        """From the keys a published ``config.json`` has
        (``PUBLISHED_KEYS``); every other key is ignored."""
        kw = {k: m[k] for k in PUBLISHED_KEYS if k in m}
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        bad = set(cfg.layer_types) - {LINEAR, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if cfg.linear_num_key_heads != cfg.linear_num_value_heads:
            raise ValueError("key heads shared between value heads are not implemented")
        if cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError("hidden_size must divide into the attention heads")
        return cfg

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


Config = OlmoHybridConfig


def init_params(cfg: OlmoHybridConfig, rng: jax.Array) -> Dict[str, Any]:
    """Float32 parameters. Matrices are normal(0, 0.02), norm gains 1, conv
    taps uniform in +-1/sqrt(taps), ``A_log = log(uniform(1, 16))`` and
    ``dt_bias`` the inverse softplus of a step drawn log-uniformly from
    [0.001, 0.1] (the conventions of the gated delta-rule mixer; the
    published config gives no initial scale)."""
    f32 = jnp.float32
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    taps = cfg.linear_conv_kernel_dim
    keys = iter(jax.random.split(rng, 16 * len(cfg.layer_types) + 2))

    def mat(n_in, n_out):
        return normal_matrix(next(keys), (n_in, n_out))

    def conv(width):
        return jax.random.uniform(next(keys), (taps, width), f32, -1.0, 1.0) / (taps ** 0.5)

    layers = []
    for kind in cfg.layer_types:
        layer = {
            "mixer_norm": jnp.ones((d,), f32), "ffn_norm": jnp.ones((d,), f32),
            "w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d),
        }
        if kind == FULL:
            layer.update(
                wq=mat(d, d), wk=mat(d, d), wv=mat(d, d), wo=mat(d, d),
                q_norm=jnp.ones((d,), f32), k_norm=jnp.ones((d,), f32),
            )
        else:
            dt = jnp.exp(jax.random.uniform(
                next(keys), (h,), f32, jnp.log(0.001), jnp.log(0.1)))
            layer.update(
                wq=mat(d, h * dk), wk=mat(d, h * dk), wv=mat(d, h * dv),
                wg=mat(d, h * dv), wo=mat(h * dv, d), wa=mat(d, h), wb=mat(d, h),
                conv_q=conv(h * dk), conv_k=conv(h * dk), conv_v=conv(h * dv),
                A_log=jnp.log(jax.random.uniform(next(keys), (h,), f32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                o_norm=jnp.ones((dv,), f32),
            )
        layers.append(layer)
    return {
        "embed": mat(cfg.vocab_size, d), "layers": layers,
        "norm": jnp.ones((d,), f32), "head": mat(d, cfg.vocab_size),
    }


# --- the pieces --------------------------------------------------------------


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def causal_conv(x, taps):
    """Depthwise causal conv: ``y_t = sum_j taps[j] x_{t - (K - 1) + j}``
    (``taps[K - 1]`` weighs the current position). x: [B, L, C]."""
    k, l = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * xp[:, j : j + l] for j in range(k))


def _full_mixer(cfg: OlmoHybridConfig, layer, x):
    dt = jnp.dtype(cfg.operand_dtype)
    b, l, d = x.shape
    heads = (b, l, cfg.num_attention_heads, cfg.head_dim)
    with jax.named_scope("omldm.lm.full_proj"):
        q = rms_norm(dense(x, layer["wq"], dt), layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(dense(x, layer["wk"], dt), layer["k_norm"], cfg.rms_norm_eps)
        v = dense(x, layer["wv"], dt)
    with jax.named_scope("omldm.lm.flash_attn"):
        o = attention(
            q.reshape(heads).astype(dt), k.reshape(heads).astype(dt),
            v.reshape(heads).astype(dt), causal=True,
        )
    with jax.named_scope("omldm.lm.full_proj"):
        return dense(o.reshape(b, l, d), layer["wo"], dt)


def _linear_mixer(cfg: OlmoHybridConfig, layer, x):
    dt = jnp.dtype(cfg.operand_dtype)
    b, l, _ = x.shape
    h, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    with jax.named_scope("omldm.lm.linear_proj"):
        q = jax.nn.silu(causal_conv(dense(x, layer["wq"], dt), layer["conv_q"]))
        k = jax.nn.silu(causal_conv(dense(x, layer["wk"], dt), layer["conv_k"]))
        v = jax.nn.silu(causal_conv(dense(x, layer["wv"], dt), layer["conv_v"]))
        q = l2_norm(q.reshape(b, l, h, dk))
        k = l2_norm(k.reshape(b, l, h, dk))
        v = v.reshape(b, l, h, dv)
        beta = jax.nn.sigmoid(dense(x, layer["wb"], dt))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
            dense(x, layer["wa"], dt) + layer["dt_bias"])
        gate = jax.nn.silu(dense(x, layer["wg"], dt)).reshape(b, l, h, dv)
    with jax.named_scope("omldm.lm.delta_rule"):
        o = gated_delta_rule(q, k, v, beta, g, operand_dtype=dt)
    with jax.named_scope("omldm.lm.linear_proj"):
        o = rms_norm(o, layer["o_norm"], cfg.rms_norm_eps) * gate
        return dense(o.reshape(b, l, h * dv), layer["wo"], dt)


def _layer(cfg: OlmoHybridConfig, kind: str, layer, x):
    mixer = _full_mixer if kind == FULL else _linear_mixer
    x = x + rms_norm(mixer(cfg, layer, x), layer["mixer_norm"], cfg.rms_norm_eps)
    ffn = swiglu_ffn(layer, x, jnp.dtype(cfg.operand_dtype))
    return x + rms_norm(ffn, layer["ffn_norm"], cfg.rms_norm_eps)


def hidden_states(cfg: OlmoHybridConfig, params, tokens):
    """``tokens [B, L]`` int -> the normed output of the last layer,
    ``[B, L, hidden]`` float32. Ids outside the vocabulary are clipped."""
    with jax.named_scope("omldm.lm.embed"):
        x = jnp.take(params["embed"], tokens.astype(jnp.int32), axis=0, mode="clip")
    # a layer is recomputed in the backward pass but for what the delta rule's
    # kernels name as theirs to keep (on a TPU; elsewhere nothing has the name)
    keep = jax.checkpoint_policies.save_only_these_names(RESIDUALS)
    for kind, layer in zip(cfg.layer_types, params["layers"]):
        x = jax.checkpoint(functools.partial(_layer, cfg, kind), policy=keep)(layer, x)
    return rms_norm(x, params["norm"], cfg.rms_norm_eps)


def objective_sum(cfg: OlmoHybridConfig, params, tokens, targets, mask):
    """Sum over positions of ``mask * -log p(target)``; ``targets`` and
    ``mask`` are ``[B, L]``."""
    x = hidden_states(cfg, params, tokens)
    dt = jnp.dtype(cfg.operand_dtype)
    with jax.named_scope("omldm.lm.head_loss"):
        return _lm_nll_fused(
            params["head"].astype(dt), x.astype(dt), targets, mask, LOSS_CHUNK
        )


def last_logits(cfg: OlmoHybridConfig, params, tokens):
    """Logits of the position after the row: ``tokens [B, L]`` -> ``[B, V]``."""
    x = hidden_states(cfg, params, tokens)[:, -1]
    with jax.named_scope("omldm.lm.head_loss"):
        return dense(x, params["head"], jnp.dtype(cfg.operand_dtype))


def all_logits(cfg: OlmoHybridConfig, params, tokens):
    """``[B, L, V]`` logits: small sizes only (tests, a holdout's score)."""
    x = hidden_states(cfg, params, tokens)
    with jax.named_scope("omldm.lm.head_loss"):
        return dense(x, params["head"], jnp.dtype(cfg.operand_dtype))
