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

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from omldm_tpu.ops.attention import _vma_struct_factory
from omldm_tpu.utils import tracing

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
    values.

    The final add at the distinct addresses is read from the backend: on a
    TPU :func:`tile_scatter_add`, which reads and writes only the touched
    tiles of ``v``; elsewhere XLA's scatter. Which one was traced is counted
    under ``sparse_scatter_path`` (``pallas`` or ``xla``) in
    ``tracing.RECORDER``, the kernel's tiles a batch beside ``pallas``
    (``tiles_per_batch``, added at each trace like ``pallas``)."""
    n = plan.sidx.shape[0]
    cap = plan_capacity(n)
    upd = upd.reshape(n)
    pallas = jax.default_backend() == "tpu"

    def combined(v):
        _, upd_sorted = _permute(place, upd)
        total = _run_sums(upd_sorted, plan.is_start)
        # a run's total stands at its END
        is_end = jnp.concatenate([plan.is_start[1:], jnp.ones((1,), bool)])
        uidx, tot = _compact(is_end, plan.sidx, v.shape[0], total)
        if pallas:
            tracing.RECORDER.add_counts(
                "sparse_scatter_path", pallas=1,
                tiles_per_batch=min(CHUNK, v.shape[0] // TILE),
            )
            return tile_scatter_add(v, uidx[:cap], tot[:cap], plan.distinct)
        tracing.RECORDER.add_counts("sparse_scatter_path", xla=1)
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
# the plan's final add on a TPU: a Pallas kernel over the touched tiles
# ---------------------------------------------------------------------------

# a 1-D float32 vector lies in HBM in tiles of 1,024 elements (layout
# T(1024)), the least a DMA may move of it: Mosaic refuses a slice of 128
# ("must be aligned to tiling (1024)"). So a tile is the kernel's window.
TILE = 1024
# tiles a batch holds: the kernel reads a batch's tiles, adds into them and
# writes them back in phases, two batches in VMEM (4 MiB, inside the 16 MiB
# a v5e's kernel may use by default)
CHUNK = 512
# DMAs issued, and addresses added, a loop step: the scalar core that
# issues them is what bounds the kernel, and a loop step costs it more than
# a DMA (PERF.md section 6)
UNROLL = 8
# addresses a grid step holds in SMEM (1 MiB on a v5e): three int32 /
# float32 arrays, double-buffered, 384 KiB at most (the cell's launch of
# 4096 x 41 slots, 41,984 addresses, is three steps)
BLOCK = 16384


def _tile_kernel(chunk, unroll, chunks, groups_ref, bounds_ref, off_ref,
                 tot_ref, tiles_ref, v_in, v_ref, rows, sems):
    """One grid step: its block's live addresses (before ``distinct`` and
    in one of the whole tiles; a prefix of the block) in groups that share
    a tile, the groups in batches of ``chunk``. Batch ``c``'s tiles are read
    into VMEM buffer ``c % 2`` while batch ``c - 1`` is added, get each
    address's total at its lane (``row + t``; every other lane keeps its
    bits) and are written back; a buffer is read into again only once its
    writes have landed. No loop branches: a batch's reads (or writes) are
    issued in a loop of their own, ``unroll`` a step, and waited for at
    once (``wait``); its addresses are added ``unroll`` at a time, each
    one's place in the buffers made beforehand (``off``). The addresses
    are sorted, so a
    tile is one group, in one batch, with one read-modify-write; a tile
    that two blocks share is two groups (a block's first address starts
    one), the first written before the second is read, since a step waits
    for all of its writes."""
    step = pl.program_id(0)
    groups = groups_ref[step]
    # a tile as the vector unit holds it, 8 x 128
    lanes = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0) * 128
             + jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1))

    def count(c):
        """Batch ``c``'s tiles; none before the first or past the last."""
        left = jnp.minimum(groups - c * chunk, (c + 1) * chunk)
        return jax.lax.clamp(0, left, chunk)

    def copy(hbm, vmem, read, slot):
        sem = sems.at[0 if read else 1, slot]
        if read:
            return pltpu.make_async_copy(hbm, vmem, sem)
        return pltpu.make_async_copy(vmem, hbm, sem)

    def unrolled(lo, hi, body):
        """``body(j, size)`` over ``[lo, hi)``: ``unroll`` at a time, then
        one at a time."""
        whole = lo + jax.lax.div(hi - lo, unroll) * unroll
        pl.loop(lo, whole, step=unroll)(lambda j: body(j, unroll))
        pl.loop(whole, hi)(lambda j: body(j, 1))

    def issue(c, read):
        """Start batch ``c``'s reads (or writes); group ``g``'s tile lies at
        ``g * TILE + shift`` in VMEM."""
        slot = c & 1
        shift = (slot - c) * chunk * TILE

        def start(g, size):
            at = g * TILE + shift
            for i in range(size):
                hbm = pl.multiple_of(tiles_ref[g + i] * TILE, TILE)
                vmem = pl.multiple_of(at + i * TILE, TILE)
                copy(v_ref.at[pl.ds(hbm, TILE)], rows.at[pl.ds(vmem, TILE)],
                     read, slot).start()

        unrolled(c * chunk, c * chunk + count(c), start)

    def wait(c, read):
        """Batch ``c``'s reads (or writes) landed, exactly the bytes issued
        on the buffer's semaphore: a whole batch by one wait, the partial
        last batch of a step by one wait a tile."""
        n, slot = count(c), c & 1
        at = pl.multiple_of(slot * chunk * TILE, TILE)

        def tiles(size):
            return copy(v_ref.at[pl.ds(0, size * TILE)],
                        rows.at[pl.ds(at, size * TILE)], read, slot)

        pl.when(n == chunk)(lambda: tiles(chunk).wait())
        pl.loop(0, jax.lax.select(n == chunk, 0, n))(
            lambda k: tiles(1).wait())

    def add(j, size):
        """Addresses ``j`` to ``j + size``: their tiles loaded together, an
        address that shares its predecessor's tile adding into that
        address's result, the results stored in order (the last store to a
        tile holds every add)."""
        offs = [off_ref[j + i] for i in range(size)]
        ats = [pl.multiple_of(o & -TILE, TILE) for o in offs]
        rows_in = [rows[pl.ds(at, TILE)].reshape(8, 128) for at in ats]
        out = []
        for i, (o, row) in enumerate(zip(offs, rows_in)):
            if i:
                row = jax.lax.select(
                    jnp.full((8, 128), ats[i] == ats[i - 1]), out[-1], row
                )
            out.append(jax.lax.select(
                lanes == jnp.full((8, 128), o & (TILE - 1)),
                row + tot_ref[j + i], row,
            ))
        for at, row in zip(ats, out):
            rows[pl.ds(at, TILE)] = row.reshape(TILE)

    # from batch -1, which only reads batch 0 (its count is 0)
    batches = jax.lax.div(groups + chunk - 1, chunk)

    @pl.loop(-1, batches)
    def _(c):
        wait(c - 1, False)  # the writes out of batch c + 1's buffer
        issue(c + 1, True)
        wait(c, True)
        at = step * (chunks + 1)
        unrolled(bounds_ref[at + jnp.maximum(c, 0)], bounds_ref[at + c + 1],
                 add)
        issue(c, False)

    wait(batches - 1, False)


def _tail_add(v, n0, uidx, tot):
    """The addresses past the last whole tile, ``[n0, N)``, by a small
    in-place update (no DMA may move their tile, which runs past ``N``). In
    the benchmark's vector they are the 13 numerics and the bias, which
    every row touches. ``uidx`` is sorted and unique, so they are the
    ``N - n0`` entries at most from the first at or past ``n0`` on."""
    n, cap = v.shape[0], uidx.shape[0]
    m = min(n - n0, cap)
    at = jnp.minimum(jnp.sum(uidx < n0), cap - m)
    near, t = (jax.lax.dynamic_slice(a, (at,), (m,)) for a in (uidx, tot))
    hits = near[:, None] == n0 + jnp.arange(n - n0, dtype=near.dtype)
    old = v[n0:]
    new = jnp.where(jnp.any(hits, 0), old + t[jnp.argmax(hits, 0)], old)
    return jax.lax.dynamic_update_slice(v, new, (n0,))


@functools.partial(jax.jit, static_argnames=("chunk", "unroll", "interpret"))
def tile_scatter_add(v, uidx, tot, distinct, chunk: int = CHUNK,
                     unroll: int = UNROLL, interpret: bool = False):
    """``v.at[uidx].add(tot, mode="drop", indices_are_sorted=True)``, bit
    for bit, in place: ``uidx`` ascending and unique, its first
    ``distinct`` entries addresses in ``[0, N)`` (non-negative, as
    :func:`index_plan` takes them) and the rest at or past ``N``, which
    are dropped. Each touched tile of ``v`` is read once and written once
    (:func:`_tile_kernel`, batches of ``chunk`` tiles), the partial tail by
    :func:`_tail_add`; nothing else of ``v`` is read, written, copied or
    laid out anew. What the kernel reads is made here over ``cap`` entries,
    a block a grid step: each address's place in its batch's buffer, the
    block's tiles in group order (one sort of the block), and each batch's
    first address. Jitted, so that the programs of a process that hold it
    at the same shapes trace and lower the kernel once (a few tenths of a
    second each time)."""
    n, cap = v.shape[0], uidx.shape[0]
    n0 = n // TILE * TILE
    if n0 == 0:
        return _tail_add(v, n0, uidx, tot)
    # a wait's descriptor spans up to a batch of whole tiles
    chunk = min(chunk, n0 // TILE)
    steps = -(-cap // BLOCK)
    # a block of a 1-D array is whole or a number of its tiles
    block = cap if steps == 1 else -(-cap // (steps * TILE)) * TILE
    if steps * block > cap:
        pad = steps * block - cap
        uidx_b = jnp.concatenate([uidx, jnp.full((pad,), n, uidx.dtype)])
        tot_b = jnp.concatenate([tot, jnp.zeros((pad,), tot.dtype)])
    else:
        uidx_b, tot_b = uidx, tot
    pos = jnp.arange(steps * block, dtype=jnp.int32)
    live = (pos < distinct) & (uidx_b < n0)
    tile = jax.lax.shift_right_logical(uidx_b, TILE.bit_length() - 1)
    starts = live & ((pos % block == 0) | (tile != jnp.roll(tile, 1)))
    starts, live, tile = (
        a.reshape(steps, block) for a in (starts, live, tile)
    )
    group = jnp.cumsum(starts.astype(jnp.int32), axis=1) - 1
    off = (group // chunk % 2 * chunk + group % chunk) * TILE + (
        uidx_b.reshape(steps, block) & (TILE - 1))
    # each block's group starts to its front, in order: its tile list
    place = pos[:block]
    _, tiles = jax.lax.sort(
        (jnp.where(starts, place, block + place), tile), dimension=1,
        num_keys=1, is_stable=False,
    )
    chunks = -(-block // chunk)
    # bounds[s, c]: step s's first address of batch c (its live count at
    # the end)
    first = (jnp.arange(chunks + 1) * chunk)[:, None]
    bounds = jnp.sum(live[:, None] & (group[:, None] < first), axis=2,
                     dtype=jnp.int32)
    smem = pl.BlockSpec((block,), lambda b, g, e: (b,),
                        memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    }
    out = pl.pallas_call(
        functools.partial(_tile_kernel, chunk, unroll, chunks),
        name="sparse_tile_scatter_add",
        out_shape=_vma_struct_factory(v)(v.shape, v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[smem, smem, smem, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2 * chunk * TILE,), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        input_output_aliases={5: 0},
        interpret=interpret,
        **params,
    )(
        jnp.sum(starts, axis=1, dtype=jnp.int32), bounds.reshape(-1),
        off.reshape(-1), tot_b, tiles.reshape(-1), v,
    )
    if n0 == n:
        return out
    return _tail_add(out, n0, uidx, tot)


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
    ``[distinct addresses, 1 if the launch overflowed, slots, touched
    whole tiles]`` (int32; a TPU's final add reads and writes each such
    tile once, :func:`tile_scatter_add`) under the plan, ``None`` under
    the plain pair. The plan's margins are
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
    tile = jax.lax.shift_right_logical(plan.sidx, TILE.bit_length() - 1)
    new_tile = jnp.concatenate([jnp.ones((1,), bool), tile[1:] != tile[:-1]])
    counters = jnp.stack([
        plan.distinct,
        plan.overflow.astype(jnp.int32),
        jnp.full((), n, jnp.int32),
        jnp.sum(new_tile & (plan.sidx < d // TILE * TILE), dtype=jnp.int32),
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
