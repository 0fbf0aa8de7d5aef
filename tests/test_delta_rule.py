"""``ops/delta_rule.py``: the chunked gated delta rule against the recurrence
over single positions, values and gradients, on both paths: the ``jax.numpy``
form (``jnp``) and the Pallas kernels (``pallas``, interpreted on the CPU).

Tolerances. With float32 operands the two differ only by the order of float32
sums (a chunk's triangular solve against 64 sequential rank-1 updates): 2e-5
of the result's norm. With bfloat16 operands (the model's precision) every
product reads operands rounded to 8 bits of mantissa, 2^-9 relative each,
through a handful of products in sequence: 2e-2."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from omldm_tpu.ops import delta_rule
from omldm_tpu.ops.delta_rule import (
    RESIDUALS, gated_delta_rule, gated_delta_rule_pallas, gated_delta_rule_recurrent,
    gated_delta_rule_scan, unit_lower_inverse, unit_lower_inverse_blocked,
)
from omldm_tpu.utils import tracing

TOL = {"float32": 2e-5, "bfloat16": 2e-2}
IMPLS = {  # on the CPU the function itself takes the ``jax.numpy`` path; the kernels run interpreted
    "jnp": lambda chunk, dtype: lambda *a: gated_delta_rule(*a, chunk=chunk, operand_dtype=dtype),
    "pallas": lambda chunk, dtype: lambda *a: gated_delta_rule_pallas(*a, chunk, dtype, True),
}


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


def check_against_the_recurrence(fn, args, dtype):
    assert rel(fn(*args), gated_delta_rule_recurrent(*args)) < TOL[dtype]
    # gradients of a scalar that weighs every output differently
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(lambda *a: jnp.sum(weigh * fn(*a)), argnums=range(5))(*args)
    want = jax.grad(
        lambda *a: jnp.sum(weigh * gated_delta_rule_recurrent(*a)), argnums=range(5)
    )(*args)
    for name, a, b in zip("q k v beta g".split(), got, want):
        assert rel(a, b) < 2 * TOL[dtype], name


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("beta_scale", [1.0, 2.0], ids=["beta<1", "beta<2"])
@pytest.mark.parametrize("length", [128, 150], ids=["whole-chunks", "ragged"])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_equals_the_recurrence(chunk, length, beta_scale, dtype, impl):
    args = draw(chunk + length, length, beta_scale=beta_scale)
    if beta_scale == 2.0:
        assert float(args[3].max()) > 1.5
    check_against_the_recurrence(IMPLS[impl](chunk, jnp.dtype(dtype)), args, dtype)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_head_widths_that_are_no_multiple_of_128(impl):
    """The published widths (keys 96, values 192), one row, a ragged tail."""
    args = draw(7, 100, b=1, h=2, dk=96, dv=192)
    check_against_the_recurrence(IMPLS[impl](64, jnp.dtype("bfloat16")), args, "bfloat16")


def test_the_kernels_against_the_jax_numpy_path():
    """The oracle beside the recurrence: same chunks, same products, so the
    two paths differ by the order of float32 sums alone."""
    args = draw(11, 150, beta_scale=2.0)
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    for dtype in ("float32", "bfloat16"):
        out, grads = {}, {}
        for impl in IMPLS:
            fn = IMPLS[impl](64, jnp.dtype(dtype))
            out[impl] = fn(*args)
            grads[impl] = jax.grad(lambda *a: jnp.sum(weigh * fn(*a)), argnums=range(5))(*args)
        assert rel(out["pallas"], out["jnp"]) < TOL[dtype]
        for name, a, b in zip("q k v beta g".split(), grads["pallas"], grads["jnp"]):
            assert rel(a, b) < 2 * TOL[dtype], (dtype, name)


def test_the_kernels_under_shard_map():
    """Inside the SPMD step the kernels' outputs inherit the inputs' varying
    axes: traced, forward and backward, with shard_map's check of them on
    (as the step is; the compile for a described chip in
    ``test_tpu_compile.py`` goes the same way). Run with the check off (the
    Pallas interpreter cannot slice a varying block at an unvarying index):
    rows split over ``dp``, values and gradients as on one device."""
    args = draw(8, 100)
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    fn = IMPLS["pallas"](64, jnp.dtype("float32"))
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    grad = lambda f: jax.grad(lambda *a: jnp.sum(weigh * f(*a)), argnums=range(5))
    rows = P("dp")
    sharded = lambda check: jax.shard_map(
        fn, mesh=mesh, in_specs=(rows,) * 5, out_specs=rows, check_vma=check)
    assert str(jax.make_jaxpr(grad(sharded(True)))(*args)).count("pallas_call[") == 4
    assert rel(jax.jit(sharded(False))(*args), fn(*args)) < 1e-6
    for name, a, b in zip("q k v beta g".split(), grad(sharded(False))(*args), grad(fn)(*args)):
        assert rel(a, b) < 1e-6, name


def path_counts(fn, *args):
    """What one trace of ``fn`` adds under ``delta_rule_path`` (a function
    of its own each time: jax traces one function once a shape)."""
    before = tracing.RECORDER.counts("delta_rule_path")
    jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    after = tracing.RECORDER.counts("delta_rule_path")
    return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}, jaxpr


def test_which_path_runs_is_read_from_the_backend_and_recorded(monkeypatch):
    args = draw(5, 150)
    assert jax.default_backend() == "cpu"
    counts, jaxpr = path_counts(gated_delta_rule, *args)
    assert counts == {"jnp": 1, "chunks": 3, "heads": 6} and "pallas_call" not in jaxpr
    np.testing.assert_array_equal(
        np.asarray(gated_delta_rule(*args)), np.asarray(gated_delta_rule_scan(*args)))
    # on a TPU (here: said to be one, the kernels interpreted) the kernels,
    # but for a chunk they do not tile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(delta_rule, "gated_delta_rule_pallas",
                        functools.partial(gated_delta_rule_pallas, interpret=True))
    counts, jaxpr = path_counts(gated_delta_rule, *args)
    assert counts == {"pallas": 1, "chunks": 3, "heads": 6} and "pallas_call" in jaxpr
    counts, jaxpr = path_counts(functools.partial(gated_delta_rule, chunk=48), *args)
    assert counts == {"jnp": 1, "chunks": 4, "heads": 6} and "pallas_call" not in jaxpr


def test_a_checkpoint_that_saves_the_residuals_runs_no_forward_kernel_twice():
    """What the Pallas forward keeps carries a name: under a
    ``jax.checkpoint`` that saves it (the model's), the backward pass
    starts from the kept arrays; under one that saves nothing, both forward
    kernels run again. The gradients are the same either way."""
    args = draw(6, 128)
    inner = lambda *a: jnp.tanh(gated_delta_rule_pallas(*a, 64, jnp.dtype("bfloat16"), True))
    grads, kernels = {}, {}
    for policy in (None, jax.checkpoint_policies.save_only_these_names(RESIDUALS)):
        f = jax.checkpoint(inner, policy=policy)
        grad = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=range(5))
        kernels[policy is None] = str(jax.make_jaxpr(grad)(*args)).count("pallas_call[")
        grads[policy is None] = grad(*args)
    assert kernels == {True: 6, False: 4}
    for a, b in zip(grads[True], grads[False]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_chunk_of_the_whole_row_and_a_chunk_of_one_agree():
    args = draw(4, 48)
    one = gated_delta_rule(*args, chunk=1, operand_dtype=jnp.float32)
    whole = gated_delta_rule(*args, chunk=48, operand_dtype=jnp.float32)
    assert rel(one, whole) < TOL["float32"]


@jax.custom_vjp
def blocked_inverse(a):
    """The kernels' inverse with the closed-form derivative they rest on
    (``dA = -T^T dT T^T``, which the backward kernel folds into its products)."""
    return unit_lower_inverse_blocked(a)


blocked_inverse.defvjp(
    lambda a: (unit_lower_inverse_blocked(a),) * 2,
    lambda t, ct: (-jnp.matmul(jnp.matmul(t.T, ct, precision="highest"), t.T, precision="highest"),),
)
INVERSES = {
    "rows": unit_lower_inverse,
    "blocked": lambda a: jax.vmap(jax.vmap(blocked_inverse))(a),
}


@pytest.mark.parametrize("size", [16, 64])
@pytest.mark.parametrize("impl", sorted(INVERSES))
def test_unit_lower_inverse_and_its_derivative(impl, size):
    inverse = INVERSES[impl]
    # entries as a chunk's A has them: beta (k_r . k_i), under 2 and mostly small
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, size, size)), -1) * 0.7 * (16 / size) ** 0.5
    eye = jnp.eye(size)
    t = inverse(a)
    np.testing.assert_allclose(np.asarray((eye + a) @ t), np.broadcast_to(eye, a.shape), atol=2e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda x: jnp.sum(weigh * inverse(x)))(a)
    want = jax.grad(lambda x: jnp.sum(weigh * jnp.linalg.inv(eye + x)))(a)
    assert rel(got, want) < 1e-4
