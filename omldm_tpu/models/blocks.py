"""What the language models of the ``LM`` learner share
(:mod:`omldm_tpu.models.olmo_hybrid`, :mod:`omldm_tpu.models.ouro`): the
matrix product at the models' precision, RMSNorm, the SwiGLU feed-forward,
the law matrices are drawn from and the block of the fused loss."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LOSS_CHUNK = 1024  # positions a block of logits holds in the fused loss


def normal_matrix(key: jax.Array, shape) -> jax.Array:
    """A float32 matrix (or a stack of them) drawn normal(0, 0.02)."""
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dense(x, w, dtype):
    """``x [..., K] @ w [K, N]`` with operands read in ``dtype`` and a
    float32 result; the backward products read the cotangent in ``dtype``
    too (jax's own transpose would hand them a float32 operand)."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def _dense_fwd(x, w, dtype):
    x, w = x.astype(dtype), w.astype(dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32), (x, w)


def _dense_bwd(dtype, res, g):
    x, w = res
    g = g.astype(dtype)
    dx = jnp.dot(g, w.T, preferred_element_type=jnp.float32)
    k, n = w.shape
    dw = jnp.dot(x.reshape(-1, k).T, g.reshape(-1, n), preferred_element_type=jnp.float32)
    return dx, dw


dense.defvjp(_dense_fwd, _dense_bwd)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu_ffn(layer, x, dtype):
    """``W_down(silu(W_gate x) * (W_up x))`` of a layer's ``w_gate``,
    ``w_up``, ``w_down``."""
    with jax.named_scope("omldm.lm.ffn"):
        hidden = jax.nn.silu(dense(x, layer["w_gate"], dtype)) * dense(x, layer["w_up"], dtype)
        return dense(hidden, layer["w_down"], dtype)
