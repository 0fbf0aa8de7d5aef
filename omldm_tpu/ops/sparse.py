"""Sparse (padded-COO) feature ops for high-dimensional linear learners.

Reference counterpart: ``mlAPI.math.SparseVector`` — a first-class input
type in the reference's parse path (reference:
src/main/scala/omldm/utils/parsers/dataStream/DataPointParser.scala:4,20-47).
Criteo-class streams (13 numeric + 26 categoricals hashed into 2^18+) and
Avazu-class hashed streams must not densify through a fixed width: the
model weight vector stays dense on device (HBM is fine with a few MB), but
each record touches only its K active features.

TPU-first layout: a batch is ``(idx[B, K] int32, val[B, K] float32)`` with
FIXED K (max nnz per record, padded with idx=0/val=0 — a zero value
contributes nothing to either the gather-dot or the scatter-add, so pad
slots are harmless without sentinel bookkeeping). Static shapes keep XLA
happy; gathers/scatters lower to efficient dynamic-(update-)slice loops on
TPU and the surrounding elementwise work fuses.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

SparseBatch = tuple  # (idx[B, K] int32, val[B, K] float32)


def sparse_matvec(w: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray) -> jnp.ndarray:
    """margins[b] = sum_k w[idx[b, k]] * val[b, k]  (gather-dot)."""
    return jnp.sum(jnp.take(w, idx, axis=0) * val, axis=1)


def sparse_matmat(W: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray) -> jnp.ndarray:
    """logits[b, c] = sum_k W[idx[b, k], c] * val[b, k] for W[D, C]."""
    rows = jnp.take(W, idx, axis=0)            # [B, K, C]
    return jnp.einsum("bkc,bk->bc", rows, val)


def sparse_scatter_add(
    w: jnp.ndarray, idx: jnp.ndarray, coef: jnp.ndarray, val: jnp.ndarray
) -> jnp.ndarray:
    """w[idx[b, k]] += coef[b] * val[b, k] over the whole batch (duplicate
    indices accumulate, including the idx=0 pad slots whose val is 0)."""
    upd = (coef[:, None] * val).reshape(-1)
    return w.at[idx.reshape(-1)].add(upd)


# lane width of the kron factorization below: the TPU register/MXU lane
# count, so the one-hot matmul operands tile exactly
MXU_LANES = 512


def sparse_scatter_add_mxu(
    w: jnp.ndarray, idx: jnp.ndarray, coef: jnp.ndarray, val: jnp.ndarray
) -> jnp.ndarray:
    """The SAME scatter-add as :func:`sparse_scatter_add`, reformulated as
    ONE MXU contraction — XLA's TPU scatter serializes randomly-indexed
    updates while the systolic array is idle; this trades FLOPs for that
    serialization.

    Factor the index space D <= R*C as (hi, lo) = divmod(idx, C) with
    C = 512 lanes. The scattered delta, viewed as a [R, C] matrix, is a
    sum of rank-1 one-hot outer products — i.e. one matmul over the
    update dimension n:

        delta[hi, lo] = sum_n u_n * e(hi_n) (x) e(lo_n)
                      = OneHotHi[n, R]^T @ (OneHotLo[n, C] * u_n)

    Numerics: one-hot entries are exact in bf16; u is split
    u = bf16(u) + bf16(u - bf16(u)) and the two halves are CONCATENATED
    along the contraction dim. The high half's products are exact; the
    low-half residual is itself rounded to bf16, leaving a bounded
    ~2^-17 relative error per update ON TOP of the f32 accumulation
    reorder — close to, but not exactly, scatter-bit-equivalence (pinned
    to 2e-5 against the scatter by tests/test_sparse.py).

    Cost: 2 * 2 * R*C FLOPs per update — at D = 2^18 that is ~1 MFLOP
    per scattered update, so the MXU formulation pays for itself exactly
    when the chip's matmul rate beats 66M * 2^20 FLOP/s; see the
    experiment's roofline section for where the crossover lands.

    Reference counterpart: SparseVector updates in the reference's data
    model (DataPointParser.scala:4,20-47) — the reference applies them
    element-by-element on the JVM; this is the TPU-native form.
    """
    d = w.shape[0]
    c = MXU_LANES
    r = -(-d // c)
    n = idx.size
    flat_idx = idx.reshape(n)
    u = (coef[:, None] * val).reshape(n).astype(jnp.float32)
    hi = flat_idx // c
    lo = flat_idx % c
    one_hi = jax.nn.one_hot(hi, r, dtype=jnp.bfloat16)            # [n, R]
    lo_oh = jax.nn.one_hot(lo, c, dtype=jnp.float32)              # [n, C]
    u_hi = u.astype(jnp.bfloat16).astype(jnp.float32)
    u_lo = u - u_hi
    rhs = jnp.concatenate(
        [
            (lo_oh * u_hi[:, None]).astype(jnp.bfloat16),
            (lo_oh * u_lo[:, None]).astype(jnp.bfloat16),
        ],
        axis=0,
    )                                                              # [2n, C]
    lhs = jnp.concatenate([one_hi, one_hi], axis=0)                # [2n, R]
    delta = jax.lax.dot_general(
        lhs, rhs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                              # [R, C]
    flat = delta.reshape(-1)
    return w + (flat[:d] if r * c != d else flat)


def sparse_scatter_add_segsum(
    w: jnp.ndarray, idx: jnp.ndarray, coef: jnp.ndarray, val: jnp.ndarray
) -> jnp.ndarray:
    """The SAME scatter-add with duplicate indices PRE-COMBINED by a sort +
    segmented sum before the scatter touches ``w``.

    Hashed categorical batches are duplicate-heavy: popular category values
    repeat across most records of a batch, so the B*K raw updates collapse
    onto far fewer distinct rows. XLA's TPU scatter serializes per update
    row; this formulation moves the duplicate work into a bitonic sort and
    a segment sum (both fully vectorized on TPU), leaving the scatter with
    one combined update per distinct index and inert (idx 0, val 0) pads
    for the rest — the module's standard padding convention.

    Shapes stay static: with R <= n distinct indices, run totals land
    compactly in the first R slots of an [n] array via sorted segment ids,
    and slots >= R scatter a zero onto row 0. Numerics: per-row totals are
    plain f32 sums of the row's updates (no prefix-difference
    cancellation); only the accumulation ORDER differs from the direct
    scatter, the same 2e-5 envelope as the MXU twin
    (tests/test_sparse.py).

    Reference counterpart: SparseVector updates applied element-by-element
    on the JVM (DataPointParser.scala:4,20-47); this is the dedup-first
    TPU-native form.
    """
    n = idx.size
    flat_idx = idx.reshape(n)
    u = (coef[:, None] * val).reshape(n).astype(jnp.float32)
    si, su = jax.lax.sort_key_val(flat_idx, u)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), si[1:] != si[:-1]]
    )
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1       # run id, sorted
    run_total = jax.ops.segment_sum(
        su, seg, num_segments=n, indices_are_sorted=True
    )                                                      # [n], first R real
    pos = jnp.arange(n, dtype=jnp.int32)
    # run start positions compacted to the front (pads sort to the tail)
    start_pos = jnp.sort(jnp.where(is_start, pos, n))
    real = start_pos < n
    run_idx = jnp.where(real, si[jnp.minimum(start_pos, n - 1)], 0)
    return w.at[run_idx].add(jnp.where(real, run_total, 0.0))


# ---------------------------------------------------------------------------
# scatter dispatch: calibration table + env/config override
# ---------------------------------------------------------------------------

SCATTER_IMPLS = {
    "scatter": sparse_scatter_add,
    "mxu": sparse_scatter_add_mxu,
    "segsum": sparse_scatter_add_segsum,
}

# env knob: OMLDM_SPARSE_SCATTER = scatter | mxu | segsum | auto ("auto" or
# unset reads the calibration table); config twin: dataStructure
# {"scatterImpl": "..."} on the sparse learner spec (learners pass impl=).
_ENV_KNOB = "OMLDM_SPARSE_SCATTER"


def _resolve_impl(d: int, n_updates: int, impl=None) -> str:
    """Trace-time dispatch decision, in precedence order: explicit config
    (``impl`` argument, from dataStructure.scatterImpl), the
    OMLDM_SPARSE_SCATTER env var, the persisted calibration table
    (ops/sparse_dispatch.json, nearest (D, updates) grid point for this
    backend), and only then the uncalibrated fallback: ``scatter``.

    The round-5 ``D >= 2^16 -> mxu`` TPU guess is RETIRED: the crossover
    was never measured, and ops/sparse_dispatch.json has no ``tpu``
    section. An uncalibrated backend gets the plain scatter, the only
    formulation with a measured record on every backend we have touched;
    the first ``python -m omldm_tpu.ops.sparse_calibrate`` run on the chip
    writes the table section that makes the mxu/segsum formulations
    eligible there. The physics behind the old guess still stands as a
    hypothesis (XLA's TPU scatter serializing randomly-indexed updates
    regardless of D, while the MXU reformulation costs ~2*2*D FLOPs per
    update; no rate has been measured on the present machine), but a
    hypothesis is what the calibration table exists to test, not to
    hardcode. On
    CPU the committed table measures the plain scatter fastest through
    D = 2^18 (12-17M updates/s); at D = 2^20 the scatter drops to ~8M as
    the target array falls out of cache and the segsum pre-combine
    (~10M, D-independent) wins 3 of 4 grid points; the MXU formulation
    never wins off-TPU.
    """
    if impl:
        name = str(impl)
        if name not in SCATTER_IMPLS:
            raise ValueError(
                f"unknown sparse scatter impl {name!r}; "
                f"expected one of {sorted(SCATTER_IMPLS)} "
            )
        return name
    env = os.environ.get(_ENV_KNOB, "").strip().lower()
    if env and env != "auto":
        if env not in SCATTER_IMPLS:
            raise ValueError(
                f"{_ENV_KNOB}={env!r}: expected "
                f"{sorted(SCATTER_IMPLS) + ['auto']}"
            )
        return env
    from omldm_tpu.ops.sparse_calibrate import lookup_winner

    winner = lookup_winner(jax.default_backend(), d, n_updates)
    if winner is not None:
        return winner
    # uncalibrated backend: plain scatter until a real calibration run
    # writes this backend's table section (the round-5 D>=2^16 mxu guess
    # is retired — see the docstring)
    return "scatter"


def sparse_scatter_add_auto(
    w: jnp.ndarray,
    idx: jnp.ndarray,
    coef: jnp.ndarray,
    val: jnp.ndarray,
    impl: str = None,
) -> jnp.ndarray:
    """Calibrated dispatch (resolved at trace time) between the three
    scatter formulations; see :func:`_resolve_impl` for the precedence
    chain and the measured record behind the fallback guess."""
    name = _resolve_impl(int(w.shape[0]), int(idx.size), impl)
    return SCATTER_IMPLS[name](w, idx, coef, val)


def sparse_scatter_add_outer(
    W: jnp.ndarray, idx: jnp.ndarray, coef: jnp.ndarray, val: jnp.ndarray
) -> jnp.ndarray:
    """W[idx[b, k], :] += val[b, k] * coef[b, :] for W[D, C] (the rank-1
    per-record outer product of a multiclass gradient)."""
    b, k = idx.shape
    upd = val[:, :, None] * coef[:, None, :]   # [B, K, C]
    return W.at[idx.reshape(-1)].add(upd.reshape(b * k, -1))


def sparse_sq_norm(val: jnp.ndarray) -> jnp.ndarray:
    """||x_b||^2 per record (pad slots contribute 0)."""
    return jnp.sum(val * val, axis=1)


def append_bias_sparse(idx: jnp.ndarray, val: jnp.ndarray, bias_index: int):
    """Append the constant-1 bias slot (weight row ``bias_index``) to every
    record — the sparse analogue of learners.base.append_bias."""
    b = idx.shape[0]
    bias_idx = jnp.full((b, 1), bias_index, idx.dtype)
    bias_val = jnp.ones((b, 1), val.dtype)
    return (
        jnp.concatenate([idx, bias_idx], axis=1),
        jnp.concatenate([val, bias_val], axis=1),
    )
