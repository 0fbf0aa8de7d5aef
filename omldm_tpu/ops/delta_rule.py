"""Gated delta rule: the linear-attention mixer's recurrence, in chunks.

Per head, with a key ``k_t`` (unit length, width ``dk``), a value ``v_t``
(width ``dv``), a write strength ``beta_t`` in (0, 2) and a decay
``alpha_t = exp(g_t)`` in (0, 1], the state ``S`` (``dv x dk``, float32,
``S_0 = 0``) and the output are

    S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t / sqrt(dk)

:func:`gated_delta_rule_recurrent` is that, one position at a time (the
ground truth of the tests). :func:`gated_delta_rule` computes the same in
chunks of ``chunk`` positions. Inside a chunk, with ``G_r`` the decay from
the chunk's start to position ``r`` and ``u_r = beta_r (v_r - alpha_r
S_{r-1} k_r)``, the state is ``S_r = G_r S_0 + sum_{i<=r} (G_r / G_i) u_i
k_i^T``, so the ``u`` solve a unit lower-triangular system

    (I + A) U = diag(beta) (V - diag(G) K S_0^T),
    A[r, i] = beta_r (G_r / G_i) (k_r . k_i)  for i < r,

whose inverse ``T`` depends on no state: every chunk's ``T``, ``T diag(beta)
V`` and ``T diag(beta G) K`` are computed at once, and the only sequential
part is a ``lax.scan`` over chunks that carries ``S`` (three small matrix
products a head a chunk). Differentiable: the scan by jax's own transpose,
the triangular inverse by its closed-form derivative (``dA = -T^T dT T^T``),
so a backward pass keeps one state per chunk and no substitution step.

Precision: matrix products take bfloat16 operands and accumulate in
float32, as everywhere in the model; the state, the decays, the triangular
inverse (forward substitution in float32 on the vector unit, and its two
products at ``highest``) are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 64


def _mm_in(dtype):
    """A batched product with operands in ``dtype``, accumulated in float32."""

    def mm(spec: str, a, b):
        return jnp.einsum(
            spec, a.astype(dtype), b.astype(dtype),
            preferred_element_type=jnp.float32,
        )

    return mm


def _mm_exact(spec: str, *operands):
    return jnp.einsum(
        spec, *operands, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``A [..., C, C]`` by
    forward substitution, row by row, in float32 sums (no matrix unit: a
    product's rounding would feed every later row)."""
    c = a.shape[-1]
    # (plus 0 * a: under shard_map the carry varies over the axes a does)
    eye = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape) + 0.0 * a

    def row(i, t):
        # row i of the inverse: e_i - A[i, :i] @ T[:i, :] (A[i, j] = 0 for
        # j >= i, and the rows of T below i are still the identity's)
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = jax.lax.dynamic_index_in_dim(eye, i, axis=-2, keepdims=False) - jnp.sum(
            a_i[..., :, None] * t, axis=-2
        )
        return jax.lax.dynamic_update_index_in_dim(t, new, i, axis=-2)

    return jax.lax.fori_loop(1, c, row, eye)


def _unit_lower_inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, ct):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm_exact("...ij,...jk,...kl->...il", tt, ct, tt),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, beta, g, chunk: int = DEFAULT_CHUNK,
                     operand_dtype=jnp.bfloat16):
    """``q, k: [B, L, H, dk]`` (``k`` of unit length), ``v: [B, L, H, dv]``,
    ``beta, g: [B, L, H]`` (``g = log alpha <= 0``) -> ``o: [B, L, H, dv]``
    float32. ``L`` need not be a multiple of ``chunk``: the tail is padded
    with positions that write nothing (``beta = 0``, ``g = 0``).
    ``operand_dtype`` is what the matrix products read (float32 in the
    tests that check the algebra alone)."""
    f32 = jnp.float32
    _mm = _mm_in(operand_dtype)
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-l // chunk)
    pad = n * chunk - l

    def chunks(x):  # [B, L, H, ...] -> [N, B, H, C, ...]
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, beta, g = (chunks(x) for x in (q, k, v, beta, g))
    gc = jnp.cumsum(g, axis=-1)                          # log G_r
    # decay from position i to position r >= i; the exponent is masked
    # before it is taken, so no entry above the diagonal overflows
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, 0.0))
    # exact: the triangular inverse below multiplies what A's rounding adds
    kk = _mm_exact("...rd,...id->...ri", k, k)
    a = jnp.where(strict, beta[..., :, None] * decay * kk, 0.0)
    t = unit_lower_inverse(a)
    u0 = _mm_exact("...ri,...id->...rd", t, beta[..., None] * v)                       # T diag(beta) V
    w = _mm_exact("...ri,...id->...rd", t, (beta * jnp.exp(gc))[..., None] * k)        # T diag(beta G) K
    p = jnp.where(lower, decay * _mm("...rd,...id->...ri", q, k), 0.0) * (dk ** -0.5)
    qg = q * jnp.exp(gc)[..., None] * (dk ** -0.5)       # diag(G) Q / sqrt(dk)
    g_end = gc[..., -1]                                  # log G_C
    k_end = k * jnp.exp(g_end[..., None] - gc)[..., None]  # diag(G_C / G) K

    def step(s, c):
        u0_c, w_c, p_c, qg_c, k_end_c, g_end_c = c
        u = u0_c - _mm("...rk,...vk->...rv", w_c, s)     # [B, H, C, dv]
        o = _mm("...rk,...vk->...rv", qg_c, s) + _mm("...ri,...iv->...rv", p_c, u)
        s = jnp.exp(g_end_c)[..., None, None] * s + _mm("...rv,...rk->...vk", u, k_end_c)
        return s, o

    s0 = jnp.zeros((b, h, dv, dk), f32) + 0.0 * g_end[0][..., None, None]
    _, o = jax.lax.scan(step, s0, (u0, w, p, qg, k_end, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)        # [B, N, C, H, dv]
    return o.reshape(b, n * chunk, h, dv)[:, :l]


def gated_delta_rule_recurrent(q, k, v, beta, g):
    """The recurrence over single positions, float32 at ``highest``: what
    :func:`gated_delta_rule` has to equal."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, c):
        q_t, k_t, v_t, beta_t, g_t = c                   # [B, H, ...]
        sk = jnp.einsum("bhvk,bhk->bhv", s, k_t, precision=hi)
        s = jnp.exp(g_t)[..., None, None] * (
            s - beta_t[..., None, None] * sk[..., :, None] * k_t[..., None, :]
        ) + beta_t[..., None, None] * v_t[..., :, None] * k_t[..., None, :]
        return s, jnp.einsum("bhvk,bhk->bhv", s, q_t, precision=hi) * (dk ** -0.5)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, beta, g))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dv, dk), f32), xs)
    return jnp.moveaxis(o, 0, 1)
