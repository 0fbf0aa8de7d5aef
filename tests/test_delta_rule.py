"""``ops/delta_rule.py``: the chunked gated delta rule against the recurrence
over single positions, values and gradients.

Tolerances. With float32 operands the two differ only by the order of float32
sums (a chunk's triangular solve against 64 sequential rank-1 updates): 2e-5
of the result's norm. With bfloat16 operands (the model's precision) every
product reads operands rounded to 8 bits of mantissa, 2^-9 relative each,
through a handful of products in sequence: 2e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omldm_tpu.ops.delta_rule import (
    gated_delta_rule, gated_delta_rule_recurrent, unit_lower_inverse,
)

TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def draw(seed, l, b=2, h=3, dk=8, dv=16, beta_scale=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, l, h, dk)))
    k = unit(jax.random.normal(ks[1], (b, l, h, dk)))
    v = jax.random.normal(ks[2], (b, l, h, dv))
    # beta_scale 2: write strengths up to 2 (negative eigenvalues of I - beta k k^T)
    beta = beta_scale * jax.nn.sigmoid(jax.random.normal(ks[3], (b, l, h)) + 1.0)
    g = -jnp.exp(jax.random.normal(ks[4], (b, l, h)) - 1.0)
    return q, k, v, beta, g


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("beta_scale", [1.0, 2.0], ids=["beta<1", "beta<2"])
@pytest.mark.parametrize("length", [128, 150], ids=["whole-chunks", "ragged"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_equals_the_recurrence(chunk, length, beta_scale, dtype):
    args = draw(chunk + length, length, beta_scale=beta_scale)
    if beta_scale == 2.0:
        assert float(args[3].max()) > 1.5
    fn = lambda *a: gated_delta_rule(*a, chunk=chunk, operand_dtype=jnp.dtype(dtype))
    assert rel(fn(*args), gated_delta_rule_recurrent(*args)) < TOL[dtype]
    # gradients of a scalar that weighs every output differently
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(weigh * fn(*a)), argnums=range(5))(*args)
    want = jax.grad(
        lambda *a: jnp.sum(weigh * gated_delta_rule_recurrent(*a)), argnums=range(5)
    )(*args)
    for name, a, b in zip("q k v beta g".split(), got, want):
        assert rel(a, b) < 2 * TOL[dtype], name


def test_a_chunk_of_the_whole_row_and_a_chunk_of_one_agree():
    args = draw(4, 48)
    one = gated_delta_rule(*args, chunk=1, operand_dtype=jnp.float32)
    whole = gated_delta_rule(*args, chunk=48, operand_dtype=jnp.float32)
    assert rel(one, whole) < TOL["float32"]


def test_unit_lower_inverse_and_its_derivative():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 16, 16)), -1) * 0.7
    eye = jnp.eye(16)
    t = unit_lower_inverse(a)
    np.testing.assert_allclose(np.asarray((eye + a) @ t), np.broadcast_to(eye, a.shape), atol=2e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda x: jnp.sum(weigh * unit_lower_inverse(x)))(a)
    want = jax.grad(lambda x: jnp.sum(weigh * jnp.linalg.inv(eye + x)))(a)
    assert rel(got, want) < 1e-4
