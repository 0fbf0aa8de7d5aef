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

whose inverse ``T`` depends on no state: a chunk's ``T``, ``u0 = T
diag(beta) V`` and ``w = T diag(beta G) K`` need nothing of any other chunk,
and the only sequential part carries ``S`` from chunk to chunk (``u = u0 - w
S^T``, ``o = diag(G) Q S^T + P u``, ``S <- G_C S + u^T diag(G_C / G) K``).

**Which path runs** is read from the input, never set
(:func:`gated_delta_rule`, as ``ops/attention.py::attention`` decides): the
Pallas kernels where the backend is a TPU and the chunk is one the kernels
tile (``INVERSE_BLOCK`` times a power of two), the ``jax.numpy`` form
everywhere else. At trace time the choice leaves one record in
``tracing.RECORDER`` (``delta_rule_path``: ``pallas`` or ``jnp``, with
``chunks`` and ``heads``), so a launch compiled with the fallback shows in
the program's own tracing.

- **Pallas, on a TPU** (:func:`gated_delta_rule_pallas`, one
  ``jax.custom_vjp``): four kernels, each over a grid of (a block of heads:
  ``parallel``; a chunk), over head-major arrays ``[B * H, L, width]`` at
  the published widths (keys 96, values 192: a block spans the whole width,
  the lanes are padded in VMEM, not in HBM and not in the model; the
  transposes into head-major stay ``jax.numpy``). A grid step reads its
  chunk's blocks from HBM once and writes its outputs once; what is between
  stays on the chip.
  ``_prepare_kernel`` (no order): the cumulative decays, both Gram matrices,
  ``A``, ``T`` by a blocked inverse held in VMEM (16 x 16 diagonal blocks by
  forward substitution, the rest by products), ``u0``, ``w``, ``P``,
  ``diag(G) Q`` and ``diag(G_C / G) K``: none larger than the chunk's inputs.
  ``_state_kernel`` (chunks ``arbitrary``, in order): ``S`` lives in VMEM
  scratch from the first chunk (where it is zeroed) to the last; it writes
  ``o`` and, where a backward pass follows, the state each chunk starts from.
  ``_state_bwd_kernel`` (chunks from last to first): the cotangent of ``S`` in
  VMEM scratch, ``u`` made again from the saved state.
  ``_prepare_bwd_kernel`` (no order): the cotangents of ``q, k, v, beta, g``,
  the inverse's by its closed form (``dA = -T^T dT T^T``, folded into the
  products with ``u0`` and ``w``).
  **What the backward pass keeps, a chunk a head:** the state the chunk
  starts from (``[dk, dv]`` float32, written by the forward pass: what the
  scan's transpose kept before; recomputing it would run the state kernel a
  second time) and what the prepare kernel wrote (``T``, ``u0`` and ``w``
  float32; ``P`` and the two scaled copies of ``q`` and ``k`` in the
  products' dtype), so it inverts nothing again: 0.75 GB a layer at the
  published widths and 8,192 positions, beside the head-major ``q, k, v``
  (with them 138,480 bytes a position, growing with batch x positions).
  They carry the name ``RESIDUALS``: a caller's ``jax.checkpoint`` that saves
  that name (the model's does) runs neither forward kernel a second time.
- **``jax.numpy``, elsewhere** (:func:`gated_delta_rule_scan`, bit for bit
  what this module computed before it had kernels): every chunk's ``T``,
  ``u0`` and ``w`` at once by batched products, then a ``lax.scan`` over
  chunks; differentiable by jax's own transpose of the scan (which keeps one
  state a chunk) and the closed form of :func:`unit_lower_inverse`. It is
  also the kernels' oracle, beside :func:`gated_delta_rule_recurrent`.

Precision, both paths, product by product. ``operand_dtype`` operands
(bfloat16 in the model, float32 in the algebra tests) with float32 sums:
``Q K^T`` (for ``P``), the three products with the state (``w S^T``, ``diag(G)
Q S^T``, ``u^T diag(G_C / G) K``), ``P u``, and their transposes in the
backward pass. Float32 operands at ``highest``, no bfloat16 anywhere:
``K K^T`` (for ``A``), the inverse (forward substitution on the vector unit;
the kernels' block merges are products at ``highest``), ``T diag(beta) V``,
``T diag(beta G) K``, and in the backward pass ``T^T du0``, ``T^T dw``, the
two products that make ``dA`` and ``(dKK + dKK^T) K``: a product's rounding
there would feed every later row of the chunk. The state, the decays and the
gates are float32 throughout.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from omldm_tpu.ops.attention import _vma_struct_factory
from omldm_tpu.utils import tracing

DEFAULT_CHUNK = 64
RESIDUALS = "omldm.delta_rule.residuals"  # the name of what the Pallas forward keeps


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


def gated_delta_rule_scan(q, k, v, beta, g, chunk: int = DEFAULT_CHUNK,
                          operand_dtype=jnp.bfloat16):
    """The ``jax.numpy`` lowering of :func:`gated_delta_rule`: batched
    products over all chunks, then a ``lax.scan`` that carries ``S``."""
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


# ---------------------------------------------------------------------------
# The Pallas TPU lowering: a chunk's working set stays on the chip
# ---------------------------------------------------------------------------

# Both measured on a TPU v5e at one layer of Olmo-Hybrid-7B (30 heads, 8,192
# positions, keys 96, values 192, chunk 64), forward + backward (PERF.md
# section 6, PR 35, call D).
# Diagonal blocks of T by substitution, the rest by products: 16, 32, 64 read
# 16.59, 16.79, 18.25 ms (the difference is all in the forward's inverse).
INVERSE_BLOCK = 16
# Heads a grid step holds at most (it takes the largest divisor of B * H): 1,
# 2, 3, 5, 6, 10 read 20.61, 18.22, 17.21, 16.59, 16.53, 16.28 ms. 5 and not
# 10: the kernels unroll over the block's heads, and at 10 the backward
# prepare kernel's double-buffered blocks come to 11.5 of 16 MiB of scoped VMEM
# (counted from the block shapes) for 0.3 ms a layer.
HEAD_BLOCK = 5
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _kernels_tile(chunk: int) -> bool:
    """A chunk the kernels take: ``INVERSE_BLOCK`` times a power of two
    (what :func:`unit_lower_inverse_blocked` merges, and whole tiles of
    sublanes in either operand dtype)."""
    blocks = chunk // INVERSE_BLOCK
    return chunk % INVERSE_BLOCK == 0 and blocks & (blocks - 1) == 0


def _dot(a, b, dims, dtype=None):
    """A product inside a kernel: float32 operands at ``highest`` (what
    ``_mm_exact`` is outside), or operands read in ``dtype`` (``_mm_in``);
    float32 sums either way."""
    if dtype is None:
        return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims, preferred_element_type=jnp.float32)


def _masks(c):
    """(eye, lower, strict) of a ``[c, c]`` tile, and its two iotas."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row == col, row >= col, row > col, row, col


def _to_col(x_row, eye):  # [1, C] -> [C, 1]
    return jnp.sum(jnp.where(eye, x_row, 0.0), axis=1, keepdims=True)


def _to_row(x_col, eye):  # [C, 1] -> [1, C]
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def unit_lower_inverse_blocked(a):
    """``(I + A)^-1`` for one strictly lower-triangular ``A [C, C]`` held on
    the chip: the ``INVERSE_BLOCK``-wide diagonal blocks by forward
    substitution in float32 on the vector unit (all of them at once, a
    column a step), then pairs of blocks merged, ``[[T1, 0], [-T2 A21 T1,
    T2]]``, by products at ``highest``, doubling the block until it is the
    chunk (a ``C`` that :func:`_kernels_tile`)."""
    c = a.shape[0]
    if not _kernels_tile(c):
        raise ValueError(f"a chunk of {c} is not {INVERSE_BLOCK} times a power of two")
    eye, _, _, row, col = _masks(c)
    bs = INVERSE_BLOCK
    x = eye.astype(jnp.float32)
    for j in range(bs - 1):
        # rows below j of every diagonal block lose A[., j] times row j,
        # which is final by now (A[i, j] = 0 for i <= j)
        a_j = jnp.concatenate(
            [a[b : b + bs, b + j : b + j + 1] for b in range(0, c, bs)], axis=0)
        x_j = jnp.concatenate(
            [jnp.broadcast_to(x[b + j : b + j + 1, :], (bs, c)) for b in range(0, c, bs)], axis=0)
        x = x - a_j * x_j
    while bs < c:
        shift = bs.bit_length() - 1
        pair = (row >> (shift + 1) == col >> (shift + 1)) & (row >> shift != col >> shift)
        x = x - _dot(_dot(x, jnp.where(pair, a, 0.0), _NN), x, _NN)
        bs *= 2
    return x


def _chunk_terms(q, k, g_row, beta_row, dtype):
    """What a chunk's kernels share, from one head's ``q, k [C, dk]`` and
    its ``g, beta [1, C]``: the decays as columns and as a tile, the two
    Gram matrices, ``beta`` as a column."""
    c, dk = q.shape
    eye, lower, strict, _, _ = _masks(c)
    gc = jnp.sum(jnp.where(lower, g_row, 0.0), axis=1, keepdims=True)  # log G_r, [C, 1]
    # the exponent is masked before it is taken: nothing above the diagonal overflows
    decay = jnp.exp(jnp.where(lower, gc - _to_row(gc, eye), 0.0))
    g_end = jnp.sum(g_row, axis=1, keepdims=True)                      # log G_C, [1, 1]
    return SimpleNamespace(
        eye=eye, lower=lower, strict=strict, decay=decay, beta=_to_col(beta_row, eye),
        eg=jnp.exp(gc), end=jnp.exp(g_end - gc), scale=dk ** -0.5,
        kk=_dot(k, k, _NT), qk=_dot(q, k, _NT, dtype),
    )


def _prepare_kernel(q_ref, k_ref, v_ref, gb_ref, t_ref, u0_ref, w_ref, qg_ref, kend_ref, p_ref,
                    *, dtype):
    """Grid (head blocks, chunks), no order: what of a chunk depends on no
    state. ``T`` never leaves the chip while it is made."""
    for h in range(q_ref.shape[0]):
        q, k, v, gb = q_ref[h], k_ref[h], v_ref[h], gb_ref[h, 0]
        m = _chunk_terms(q, k, gb[0:1], gb[1:2], dtype)
        t = unit_lower_inverse_blocked(
            jnp.where(m.strict, m.beta * m.decay * m.kk, 0.0))
        t_ref[h] = t
        u0_ref[h] = _dot(t, m.beta * v, _NN)                        # T diag(beta) V
        w_ref[h] = _dot(t, (m.beta * m.eg) * k, _NN)                # T diag(beta G) K
        qg_ref[h] = (q * (m.eg * m.scale)).astype(qg_ref.dtype)     # diag(G) Q / sqrt(dk)
        kend_ref[h] = (k * m.end).astype(kend_ref.dtype)            # diag(G_C / G) K
        p_ref[h] = (jnp.where(m.lower, m.decay * m.qk, 0.0) * m.scale).astype(p_ref.dtype)


def _state_kernel(u0_ref, w_ref, qg_ref, kend_ref, p_ref, gb_ref, o_ref, *rest, dtype, save):
    """Grid (head blocks, chunks), the chunks in order: ``S^T [dk, dv]`` of
    every head of the block stays in VMEM scratch from the first chunk to
    the last. ``save``: the state each chunk starts from goes out too."""
    st_ref = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _first():
        st_ref[...] = jnp.zeros_like(st_ref)

    for h in range(u0_ref.shape[0]):
        st = st_ref[h]
        if save:
            rest[0][h, 0] = st
        u = u0_ref[h] - _dot(w_ref[h], st, _NN, dtype)
        o_ref[h] = _dot(qg_ref[h], st, _NN, dtype) + _dot(p_ref[h], u, _NN, dtype)
        alpha = jnp.exp(jnp.sum(gb_ref[h, 0][0:1], axis=1, keepdims=True))  # G_C, [1, 1]
        st_ref[h] = alpha * st + _dot(kend_ref[h], u, _TN, dtype)


def _state_bwd_kernel(do_ref, s_ref, u0_ref, w_ref, qg_ref, kend_ref, p_ref, gb_ref,
                      du_ref, dw_ref, dqg_ref, dkend_ref, dp_ref, dgend_ref, dst_ref, *, dtype):
    """Grid (head blocks, chunks), the chunks from last to first (the index
    maps turn them round): the cotangent of ``S^T`` stays in VMEM scratch.
    A chunk's ``u`` is made again from its saved starting state."""

    @pl.when(pl.program_id(1) == 0)
    def _last():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    for h in range(do_ref.shape[0]):
        do, st, w, qg, kend, p = do_ref[h], s_ref[h, 0], w_ref[h], qg_ref[h], kend_ref[h], p_ref[h]
        dsn = dst_ref[h]                                   # of the state this chunk leaves
        u = u0_ref[h] - _dot(w, st, _NN, dtype)
        alpha = jnp.exp(jnp.sum(gb_ref[h, 0][0:1], axis=1, keepdims=True))
        # S' = alpha S + k_end^T u
        dgend = alpha * jnp.sum(jnp.sum(dsn * st, axis=1, keepdims=True), axis=0, keepdims=True)
        dgend_ref[h, 0] = jnp.broadcast_to(dgend, dgend_ref.shape[2:])
        dkend_ref[h] = _dot(u, dsn, _NT, dtype)
        # o = qg S + p u
        du = _dot(kend, dsn, _NN, dtype) + _dot(p, do, _TN, dtype)
        dqg_ref[h] = _dot(do, st, _NT, dtype)
        dp_ref[h] = _dot(do, u, _NT, dtype)
        # u = u0 - w S
        du_ref[h] = du
        dw_ref[h] = -_dot(du, st, _NT, dtype)
        dst_ref[h] = alpha * dsn + _dot(qg, do, _TN, dtype) - _dot(w, du, _TN, dtype)


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, gb_ref, t_ref, u0_ref, w_ref, du0_ref, dw_ref, dqg_ref,
                        dkend_ref, dp_ref, dgend_ref, dq_ref, dk_ref, dv_ref, dgb_ref, *, dtype):
    """Grid (head blocks, chunks), no order: the cotangents of a chunk's
    inputs from those of what :func:`_prepare_kernel` made of them."""
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)
    for h in range(q_ref.shape[0]):
        q, k, v, gb, t = q_ref[h], k_ref[h], v_ref[h], gb_ref[h, 0], t_ref[h]
        du0, dw, dqg, dkend = du0_ref[h], dw_ref[h], dqg_ref[h], dkend_ref[h]
        m = _chunk_terms(q, k, gb[0:1], gb[1:2], dtype)
        eye, lower, decay, beta, eg, end, scale = m.eye, m.lower, m.decay, m.beta, m.eg, m.end, m.scale
        # u0 = T bv, w = T bgk, T = (I + A)^-1, A = strict(beta decay kk): the inverse's
        # closed form -T^T dT T^T with dT = du0 bv^T + dw bgk^T, which is
        # -(T^T du0) (T bv)^T - (T^T dw) (T bgk)^T
        dbv, dbgk = _dot(t, du0, _TN), _dot(t, dw, _TN)
        da = jnp.where(m.strict, -(_dot(dbv, u0_ref[h], _NT) + _dot(dbgk, w_ref[h], _NT)), 0.0)
        dkk = da * beta * decay
        # p = lower(decay qk) / sqrt(dk)
        dpm = jnp.where(lower, dp_ref[h], 0.0) * scale
        dqk = dpm * decay
        ddecay = (da * beta * m.kk + dpm * m.qk) * decay  # times decay: of the exponent
        dq_ref[h] = _dot(dqk, k, _NN, dtype) + dqg * (eg * scale)
        dk_ref[h] = (_dot(dkk + dkk.T, k, _NN) + _dot(dqk, q, _TN, dtype)
                     + dkend * end + dbgk * (beta * eg))
        dv_ref[h] = dbv * beta
        dbeta = rows(da * decay * m.kk) + rows(dbgk * k) * eg + rows(dbv * v)
        of_end = rows(dkend * k) * end                       # rows of dk_end . k_end
        dgc = (rows(dqg * q) * (eg * scale) - of_end + rows(dbgk * k) * (beta * eg)
               + rows(ddecay) - _to_col(jnp.sum(ddecay, axis=0, keepdims=True), eye))
        # log G_C = gc[C - 1], of alpha and of k_end; gc = cumsum(g)
        c = q.shape[0]
        dg_end = dgend_ref[h, 0][:, 0:1] + jnp.sum(of_end, axis=0, keepdims=True)
        last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
        dgc = dgc + jnp.where(last, dg_end, 0.0)
        dgb_ref[h, 0] = jnp.concatenate(
            [jnp.sum(jnp.where(lower, dgc, 0.0), axis=0, keepdims=True), _to_row(dbeta, eye)], axis=0)


class _Pass:
    """One kernel's pass over head-major arrays: the grid of (head blocks,
    chunks), the block specs and shapes of a chunk's rows (``[B * H, Lp,
    width]``) and of what a chunk has one of (``[B * H, N, ...]``), and the
    call. ``backward`` walks the chunks from last to first."""

    def __init__(self, like, bh, n, chunk, interpret, backward=False):
        self.bh, self.n, self.chunk, self.interpret = bh, n, chunk, interpret
        self.hb = max(d for d in range(1, HEAD_BLOCK + 1) if bh % d == 0)
        self.struct = _vma_struct_factory(like)  # under shard_map: the inputs' varying axes
        self.at = (lambda c: n - 1 - c) if backward else (lambda c: c)

    def rows(self, width):
        return pl.BlockSpec((self.hb, self.chunk, width), lambda i, c: (i, self.at(c), 0))

    def rows_shape(self, width, dtype=jnp.float32):
        return self.struct((self.bh, self.n * self.chunk, width), dtype)

    def one(self, *dims):
        return pl.BlockSpec((self.hb, 1) + dims, lambda i, c: (i, self.at(c)) + (0,) * len(dims))

    def one_shape(self, *dims):
        return self.struct((self.bh, self.n) + dims, jnp.float32)

    def __call__(self, kernel, name, order, in_specs, out_specs, out_shape, scratch=()):
        params = {} if self.interpret else {
            "compiler_params": pltpu.CompilerParams(dimension_semantics=("parallel", order))}
        return pl.pallas_call(
            kernel, name=name, grid=(self.bh // self.hb, self.n), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=list(scratch),
            interpret=self.interpret, **params)


# (jitted, so that a model's layers of one shape trace each kernel once: a
# kernel's body is some thousand operations a head, unrolled over the block.
# Three layers' forward and backward trace and lower in 0.83 s so, 2.00 s
# otherwise: PERF.md section 6, PR 35, call D)
@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret", "save"))
def _pallas_forward(q, k, v, beta, g, chunk, dtype, interpret, save):
    """The two forward kernels over head-major arrays; ``save``: the
    residuals of the backward pass beside the output."""
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-l // chunk)
    lp, bh = n * chunk, b * h

    def heads(x):  # [B, L, H, ...] -> [B * H, Lp, ...]
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, lp - l)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 2, 1).reshape((bh, lp) + x.shape[3:])

    q, k, v = heads(q), heads(k), heads(v)
    gb = jnp.stack([heads(g).reshape(bh, n, chunk), heads(beta).reshape(bh, n, chunk)], axis=2)
    run = _Pass(q, bh, n, chunk, interpret)
    made = run(
        functools.partial(_prepare_kernel, dtype=dtype), "delta_rule_prepare", "parallel",
        [run.rows(dk), run.rows(dk), run.rows(dv), run.one(2, chunk)],
        [run.rows(chunk), run.rows(dv), run.rows(dk), run.rows(dk), run.rows(dk), run.rows(chunk)],
        [run.rows_shape(chunk), run.rows_shape(dv), run.rows_shape(dk), run.rows_shape(dk, dtype),
         run.rows_shape(dk, dtype), run.rows_shape(chunk, dtype)],
    )(q, k, v, gb)
    _, u0, w, qg, kend, p = made
    out = run(
        functools.partial(_state_kernel, dtype=dtype, save=save), "delta_rule_state", "arbitrary",
        [run.rows(dv), run.rows(dk), run.rows(dk), run.rows(dk), run.rows(chunk), run.one(2, chunk)],
        [run.rows(dv)] + [run.one(dk, dv)] * save,
        [run.rows_shape(dv)] + [run.one_shape(dk, dv)] * save,
        scratch=[pltpu.VMEM((run.hb, dk, dv), jnp.float32)],
    )(u0, w, qg, kend, p, gb)
    o = jnp.moveaxis(out[0].reshape(b, h, lp, dv), 1, 2)[:, :l]
    return o, ((q, k, v, gb, *made, out[1]) if save else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gated_delta_rule_pallas(q, k, v, beta, g, chunk: int = DEFAULT_CHUNK,
                            operand_dtype=jnp.bfloat16, interpret: bool = False):
    """The Pallas TPU lowering of :func:`gated_delta_rule`, forward and
    backward (``interpret=True`` runs it on the CPU, for the tests)."""
    return _pallas_forward(q, k, v, beta, g, chunk, jnp.dtype(operand_dtype), interpret, save=False)[0]


def _pallas_fwd(q, k, v, beta, g, chunk, operand_dtype, interpret):
    # named, so that a caller's ``jax.checkpoint`` can keep them (``RESIDUALS``)
    # and run neither forward kernel a second time (imported here: a process
    # that differentiates no delta rule loads the modules it always did)
    from jax.ad_checkpoint import checkpoint_name

    return jax.tree_util.tree_map(
        lambda x: checkpoint_name(x, RESIDUALS),
        _pallas_forward(q, k, v, beta, g, chunk, jnp.dtype(operand_dtype), interpret, save=True))


@functools.partial(jax.jit, static_argnames=("chunk", "dtype", "interpret"))
def _pallas_backward(res, do, chunk, dtype, interpret):
    """The two backward kernels, from the forward's residuals and the
    output's cotangent to those of ``q, k, v, beta, g``."""
    q, k, v, gb, t, u0, w, qg, kend, p, s = res
    bh, lp, dk = q.shape
    b, l, h, dv = do.shape
    n = lp // chunk
    do = jnp.pad(do.astype(jnp.float32), ((0, 0), (0, lp - l), (0, 0), (0, 0)))
    do = jnp.moveaxis(do, 2, 1).reshape(bh, lp, dv)
    run = _Pass(do, bh, n, chunk, interpret, backward=True)
    of_state = run(
        functools.partial(_state_bwd_kernel, dtype=dtype), "delta_rule_state_bwd", "arbitrary",
        [run.rows(dv), run.one(dk, dv), run.rows(dv), run.rows(dk), run.rows(dk), run.rows(dk),
         run.rows(chunk), run.one(2, chunk)],
        [run.rows(dv), run.rows(dk), run.rows(dk), run.rows(dk), run.rows(chunk), run.one(1, chunk)],
        [run.rows_shape(dv), run.rows_shape(dk), run.rows_shape(dk), run.rows_shape(dk),
         run.rows_shape(chunk), run.one_shape(1, chunk)],
        scratch=[pltpu.VMEM((run.hb, dk, dv), jnp.float32)],
    )(do, s, u0, w, qg, kend, p, gb)  # du, dw, dqg, dkend, dp, dgend
    run = _Pass(do, bh, n, chunk, interpret)
    dq, dk_, dv_, dgb = run(
        functools.partial(_prepare_bwd_kernel, dtype=dtype), "delta_rule_prepare_bwd", "parallel",
        [run.rows(dk), run.rows(dk), run.rows(dv), run.one(2, chunk), run.rows(chunk), run.rows(dv),
         run.rows(dk), run.rows(dv), run.rows(dk), run.rows(dk), run.rows(dk), run.rows(chunk),
         run.one(1, chunk)],
        [run.rows(dk), run.rows(dk), run.rows(dv), run.one(2, chunk)],
        [run.rows_shape(dk), run.rows_shape(dk), run.rows_shape(dv), run.one_shape(2, chunk)],
    )(q, k, v, gb, t, u0, w, *of_state)

    def rows(x):  # [B * H, Lp, ...] -> [B, L, H, ...]
        return jnp.moveaxis(x.reshape((b, h, lp) + x.shape[2:]), 1, 2)[:, :l]

    return (rows(dq), rows(dk_), rows(dv_),
            rows(dgb[:, :, 1].reshape(bh, lp)), rows(dgb[:, :, 0].reshape(bh, lp)))


gated_delta_rule_pallas.defvjp(
    _pallas_fwd, lambda chunk, dtype, interpret, res, do: _pallas_backward(res, do, chunk, dtype, interpret))


def gated_delta_rule(q, k, v, beta, g, chunk: int = DEFAULT_CHUNK,
                     operand_dtype=jnp.bfloat16):
    """``q, k: [B, L, H, dk]`` (``k`` of unit length), ``v: [B, L, H, dv]``,
    ``beta, g: [B, L, H]`` (``g = log alpha <= 0``) -> ``o: [B, L, H, dv]``
    float32. ``L`` need not be a multiple of ``chunk``: the tail is padded
    with positions that write nothing (``beta = 0``, ``g = 0``).
    ``operand_dtype`` is what the matrix products read (float32 in the
    tests that check the algebra alone). One algorithm, two paths: the
    Pallas kernels where the backend is a TPU and they tile the chunk, the
    ``jax.numpy`` form otherwise; which one was traced is counted under
    ``delta_rule_path`` in ``tracing.RECORDER``."""
    pallas = jax.default_backend() == "tpu" and _kernels_tile(chunk)
    tracing.RECORDER.add_counts(
        "delta_rule_path", **{"pallas" if pallas else "jnp": 1},
        chunks=-(-q.shape[1] // chunk), heads=q.shape[0] * q.shape[2])
    if pallas:
        return gated_delta_rule_pallas(q, k, v, beta, g, chunk, jnp.dtype(operand_dtype))
    return gated_delta_rule_scan(q, k, v, beta, g, chunk, operand_dtype)


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
