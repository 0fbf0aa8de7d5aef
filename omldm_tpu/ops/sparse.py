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
happy and the surrounding elementwise work fuses; at the weight vector the
TPU pays by the slot (gather) and by the distinct address or a pass over
the whole operand (scatter), which is what the index plan below is for.
"""

from __future__ import annotations

from typing import NamedTuple

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


class IndexPlan(NamedTuple):
    """What one launch knows about its indices: built once by
    :func:`index_plan`, shared by the margin's gather and the update's
    scatter, so that the weight vector is addressed once per DISTINCT index.
    ``n`` slots hold ``U`` distinct addresses."""

    idx: jnp.ndarray          # the indices as given
    sidx: jnp.ndarray         # [n] the addresses, ascending
    perm: jnp.ndarray         # [n] slot (row-major in idx) at each sorted place
    is_start: jnp.ndarray     # [n] bool: first of its run of equal addresses
    distinct: jnp.ndarray     # [] int32, U
    overflow: jnp.ndarray     # [] bool, U > plan_capacity(n)


def plan_capacity(n: int) -> int:
    """Distinct addresses up to which a launch of ``n`` slots goes through
    the plan: a quarter of its slots. Hashed click logs repeat (the
    numerics, the bias and the small vocabularies in every row, Zipf on the
    large ones): the benchmark's launches hold 0.19 n distinct addresses
    (PERF.md section 6, PR 30), so a quarter leaves a third of headroom and
    still quarters the slots at the weight vector. A launch with more
    (overflow) runs the plain pair on its indices as given, behind one
    ``lax.cond`` in each half: exact, and one sort dearer than the plain
    pair alone."""
    return max(n // 4, 1)


def plan_fits(d: int, n: int, dtype=jnp.float32) -> bool:
    """The spare addresses ``d + j`` have to stay int32, and the copy down
    a run (:func:`plan_gather`) moves the weights as int32 bits."""
    return (
        d + n <= jnp.iinfo(jnp.int32).max and jnp.dtype(dtype).itemsize == 4
    )


def _permute(keys: jnp.ndarray, *payloads):
    """Sort by keys that are all DISTINCT, payloads riding along: the way
    the plan moves data (a sort of 167,936 pairs costs 0.17 ms on the chip,
    a gather or scatter of as many slots 1.2 ms or more). With no ties a
    stable sort is the same permutation, and asking for one makes the TPU's
    compiler carry one more operand through the sort (0.235 against
    0.172 ms; PERF.md section 6, PR 30)."""
    return jax.lax.sort((keys,) + payloads, num_keys=1, is_stable=False)


def index_plan(idx: jnp.ndarray) -> IndexPlan:
    """Sort the launch's addresses once. ``idx`` holds addresses in
    ``[0, d)`` (what the vectorizers emit; a pad is address 0)."""
    n = idx.size
    sidx, perm = jax.lax.sort(
        (idx.reshape(n).astype(jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=1,
    )
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sidx[1:] != sidx[:-1]]
    )
    distinct = jnp.sum(is_start.astype(jnp.int32))
    return IndexPlan(
        idx, sidx, perm, is_start, distinct, distinct > plan_capacity(n)
    )


def _compact(keep: jnp.ndarray, sidx: jnp.ndarray, d: int, payload):
    """The addresses ``sidx[keep]`` to the front, ascending, ``payload``
    riding along; behind them distinct out-of-range addresses ``d + j``, so
    that the whole is sorted and unique and the spare ones are inert under
    ``mode="fill"`` / ``mode="drop"``."""
    pos = jnp.arange(sidx.shape[0], dtype=jnp.int32)
    return _permute(jnp.where(keep, sidx, d + pos), payload)


def _run_sums(values: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive sums that restart at every run start (the total of a run
    stands at its end): log2(n) shifted adds, each one elementwise pass.
    Plain f32 sums of a run's own values, no prefix differences."""
    n, step = values.shape[0], 1
    while step < n:
        shifted = jnp.concatenate(
            [jnp.zeros((step,), values.dtype), values[:-step]]
        )
        reached = jnp.concatenate([jnp.ones((step,), bool), is_start[:-step]])
        values = jnp.where(is_start, values, values + shifted)
        is_start = is_start | reached
        step *= 2
    return values


def plan_gather(w: jnp.ndarray, plan: IndexPlan):
    """``jnp.take(w, idx)`` in idx's shape, bit for bit, with ``w`` addressed
    once per distinct index: a gather at the distinct addresses (``cap``
    slots), then two sorts and a copy down each run bring every value to
    its slots. Also returns the sorted place of every slot (the inverse of
    ``perm``), which the second sort yields for nothing and
    :func:`plan_scatter_add` needs. An overflowing launch takes
    ``jnp.take`` itself."""
    n = plan.sidx.shape[0]
    cap = plan_capacity(n)
    pos = jnp.arange(n, dtype=jnp.int32)

    def combined(w):
        # upos: the sorted place of each run's start, in uidx's order (the
        # spare slots hold the other places)
        uidx, upos = _compact(plan.is_start, plan.sidx, w.shape[0], pos)
        head = w.at[uidx[:cap]].get(
            mode="fill", fill_value=0, indices_are_sorted=True,
            unique_indices=True,
        )
        # the copy down a run is a cumulative sum of int32 differences
        # between consecutive distinct values' BITS, set at the run starts:
        # integer sums wrap, so every slot gets its value's exact bits (and
        # a cumsum is one cheap window reduction where a segmented scan is
        # 2 log2(n) passes)
        bits = jax.lax.bitcast_convert_type(head, jnp.int32)
        before = jnp.concatenate([jnp.zeros((1,), jnp.int32), bits[:-1]])
        diff = jnp.where(pos[:cap] < plan.distinct, bits - before, 0)
        _, diff_sorted = _permute(
            upos, jnp.concatenate([diff, jnp.zeros((n - cap,), jnp.int32)])
        )
        g_sorted = jax.lax.bitcast_convert_type(
            jnp.cumsum(diff_sorted), w.dtype
        )
        _, g, place = _permute(plan.perm, g_sorted, pos)
        return g, place

    def plain(w):
        # no place to hand on: zeros that carry perm's type (inside
        # shard_map: its varying axes)
        return jnp.take(w, plan.idx.reshape(n), axis=0), plan.perm * 0

    g, place = jax.lax.cond(plan.overflow, plain, combined, w)
    return g.reshape(plan.idx.shape), place


def plan_scatter_add(
    w: jnp.ndarray, plan: IndexPlan, upd: jnp.ndarray, place: jnp.ndarray
) -> jnp.ndarray:
    """``w.at[idx].add(upd)`` with each address's updates summed first (in
    sorted order, :func:`_run_sums`) and ``w`` addressed once per distinct
    index, in place. Only the order in which one address's duplicates are
    summed differs from the plain scatter, which an overflowing launch
    takes itself. ``place`` is what :func:`plan_gather` returned beside the
    values."""
    n = plan.sidx.shape[0]
    cap = plan_capacity(n)
    upd = upd.reshape(n)

    def combined(v):
        _, upd_sorted = _permute(place, upd)
        total = _run_sums(upd_sorted, plan.is_start)
        # a run's total stands at its END
        is_end = jnp.concatenate([plan.is_start[1:], jnp.ones((1,), bool)])
        uidx, tot = _compact(is_end, plan.sidx, v.shape[0], total)
        # told "sorted" only: told "unique" too, the TPU's compiler copies
        # the whole operand (3.2 ms at 2^28 weights, whatever the number of
        # updates; PERF.md section 6, PR 30)
        return v.at[uidx[:cap]].add(
            tot[:cap], mode="drop", indices_are_sorted=True
        )

    return jax.lax.cond(
        plan.overflow,
        lambda v: v.at[plan.idx.reshape(n)].add(upd),
        combined,
        w,
    )


# ---------------------------------------------------------------------------
# dispatch: explicit impl, else the calibration table, else the plain pair
# ---------------------------------------------------------------------------

# the update's formulations by name: the plain pair (``jnp.take`` and
# XLA's scatter-add) and the plan, which is both halves (sparse_update).
# Both are exact in f32 up to the order of one address's sums.
IMPLS = ("scatter", "plan")


def _resolve_impl(d: int, n_updates: int, impl=None, dtype=jnp.float32) -> str:
    """Trace-time dispatch decision: the explicit ``impl`` argument (from
    dataStructure.scatterImpl: how a test or a twin run pins a side), else
    the calibration table's section for this backend
    (ops/sparse_dispatch.json, nearest (D, updates) grid point), else the
    plain pair.

    The table holds what ``python -m omldm_tpu.ops.sparse_calibrate``
    measured of the whole update (margin and scatter of one formulation
    together) on that backend and names the faster of the two; a backend
    or a table without a section, and a plan that does not fit, get the
    plain pair, the only formulation with a record everywhere. On the TPU
    (PR 30's section, measured at the benchmark's shapes) the plan wins at
    a launch of 4096 x 41 slots over 2^28 weights and the plain pair at
    256 x 41 and 16 x 41; the CPU has no section (a sort costs more than
    the scatter there, over the whole grid).
    """
    if impl:
        name = str(impl)
        if name not in IMPLS:
            raise ValueError(
                f"unknown sparse scatter impl {name!r}; "
                f"expected one of {sorted(IMPLS)} "
            )
        if name == "plan" and not plan_fits(d, n_updates, dtype):
            raise ValueError(
                f"sparse impl 'plan' needs d + updates < 2^31 and 4-byte "
                f"weights, got {d} + {n_updates} of {jnp.dtype(dtype).name}"
            )
        return name
    from omldm_tpu.ops.sparse_calibrate import lookup_winner

    winner = lookup_winner(jax.default_backend(), d, n_updates)
    if winner == "plan" and plan_fits(d, n_updates, dtype):
        return "plan"
    return "scatter"


def sparse_update(
    w: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray, impl: str = None
):
    """Both halves of a linear learner's update in ONE formulation,
    resolved at trace time (:func:`_resolve_impl`): the margins
    ``sum_k w[idx[b, k]] * val[b, k]``, a function ``add(w2, coef)`` giving
    ``w2[idx[b, k]] += coef[b] * val[b, k]`` (``w2`` is ``w`` or a vector
    derived from it, as pegasos' decayed one), and the launch's counters:
    ``[distinct addresses, 1 if the launch overflowed, slots]`` (int32)
    under the plan, ``None`` under the plain pair. The plan's margins are
    those of :func:`sparse_matvec` over the same gathered bits."""
    d, n = int(w.shape[0]), int(idx.size)
    name = _resolve_impl(d, n, impl, w.dtype)
    if name != "plan":
        return (
            sparse_matvec(w, idx, val),
            lambda w2, coef: sparse_scatter_add(w2, idx, coef, val),
            None,
        )
    plan = index_plan(idx)
    g, place = plan_gather(w, plan)
    counters = jnp.stack([
        plan.distinct,
        plan.overflow.astype(jnp.int32),
        jnp.full((), n, jnp.int32),
    ])
    return (
        jnp.sum(g * val, axis=1),
        lambda w2, coef: plan_scatter_add(
            w2, plan, coef[:, None] * val, place
        ),
        counters,
    )


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
