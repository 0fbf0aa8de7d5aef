"""Attention kernels: reference, blockwise (flash-style), and Pallas TPU.

The reference has no attention anywhere (SURVEY.md section 2.4 — its models
are per-record online learners over feature vectors), but long-context
sequence models are first-class in this framework: the transformer family
(omldm_tpu.models.transformer) and sequence/context parallelism
(omldm_tpu.ops.ring_attention) are built on the kernels here.

Three implementations, one contract ``[B, L, H, Dh] -> [B, L, H, Dh]``:

- ``mha_reference``      — materializes the full [L, L] score matrix; O(L^2)
                           memory; ground truth for tests.
- ``blockwise_attention``— flash-style online-softmax over K/V blocks via
                           ``lax.scan``: O(L * block) memory, numerically
                           identical (up to fp assoc.) to the reference.
                           Works on every backend; this is also the
                           per-device inner loop of ring attention.
- ``flash_attention_pallas`` — hand-tiled Pallas TPU kernel keeping the
                           Q block + online-softmax accumulators in VMEM;
                           ``interpret=True`` runs it on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from omldm_tpu.utils import tracing

NEG_INF = -1e30

# Pallas tile sizes, read on a TPU v5e at bf16, causal, L=8192, head width
# 128, 16 heads, under jax 0.9 (PR 37; ms of kernel an application, the
# parent's 1,024 x 1,024 forward 2.568 and its two backward kernels 7.137):
#   forward, K tile in one piece: 1024x1024 2.339, 1024x512 2.295, 512x512
#     2.433, 2048x1024 2.525, 512x1024 2.538, 256x1024 3.058; in sub-blocks
#     (see _flash_kernel): 1024x1024 by 512 2.092, by 256 2.137, by 128 2.990,
#     2048x1024 by 256 2.096, 1024x2048 by 256 2.161, 2048x2048 by 256 2.006
#     (7.6 s to compile against 1.6);
#   one-pass backward: 1024x1024 4.333, 512x1024 4.544, 1024x512 4.544,
#     512x512 4.844, 2048x1024 4.637, 1024x2048 4.662, 256x1024 5.064.
# What is computed above the diagonal follows the LARGER tile side (12.5% of
# the triangle at 1,024) but a grid step costs 0.35 us whether skipped or
# not, and below 512 the sweeps' fixed costs win. The two-kernel backward
# keeps the tiles it was given at width 64 under an earlier jax.
FWD_BLOCK_Q = 1024
FWD_BLOCK_K = 1024
BWD_BLOCK_Q = 1024
BWD_BLOCK_K = 1024
TWO_PASS_BLOCK_Q = 512
TWO_PASS_BLOCK_K = 1024
# The backward is ONE kernel where what it keeps resident for a head's dQ
# (_one_pass_fits) is within this much VMEM, the dq and dk/dv kernels past
# it: 8 MiB is a bf16 row of 8,192 positions at head width 128, the longest
# measured. The kernel's scoped VMEM (default 16 MiB) is raised for the
# float32 rows that fit
ONE_PASS_DQ_BYTES = 8 << 20
ONE_PASS_VMEM_LIMIT = 32 << 20


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Plain softmax attention. q,k,v: [B, L, H, Dh].

    ``q_offset``/``kv_offset`` give the absolute positions of the first query
    / key row — used by the blockwise and ring variants to apply a causal
    mask across chunk boundaries."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    if causal:
        qi = q_offset + jnp.arange(q.shape[1])[:, None]
        ki = kv_offset + jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(qi >= ki, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def online_softmax_sweep(
    q32: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    carry,
    q_pos: jnp.ndarray,
    kv_pos_start,
    causal: bool = False,
    block_k: int = 256,
):
    """Sweep ONE K/V chunk in key blocks, updating an online-softmax carry.

    q32: [B, Lq, H, Dh] float32; k/v: [B, Lk, H, Dh]; carry is
    ``(o [B,H,Lq,Dh], m [B,H,Lq], l [B,H,Lq])``. ``q_pos`` are absolute
    query positions [Lq]; ``kv_pos_start`` the absolute position of key row
    0 (may be a traced scalar — ring attention passes the rotating chunk's
    origin). Never materializes more than [.., Lq, block_k] scores —
    shared by :func:`blockwise_attention` and the per-hop accumulate of
    ring attention."""
    b, lq, h, dh = q32.shape
    lk = k.shape[1]
    block_k = min(block_k, lk)
    pad = (-lk) % block_k
    if pad:
        # padded keys are masked out via an explicit finite bias so that a
        # fully-masked block still produces well-defined (zero) weights
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (lk + pad) // block_k
    kb = k.reshape(b, n_blocks, block_k, h, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block_k, h, dh).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / jnp.sqrt(float(dh))

    def scan_step(c, kv):
        o, m, l, step = c
        kb_i, vb_i = kv
        ki_local = step * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kb_i.astype(jnp.float32)) * scale
        if pad:
            # mask pad rows of the (only) ragged final block; the NEG_INF
            # bias alone suffices — p is exactly 0 for padded keys
            s = jnp.where(ki_local[None, None, None, :] < lk, s, NEG_INF)
        if causal:
            ki = kv_pos_start + ki_local[None, :]
            s = jnp.where(q_pos[:, None] >= ki, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard: a row with every key masked so far (m_new still -inf) must
        # produce zero weights, not exp(0)=1 per masked key
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l_new = alpha * l + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb_i.astype(jnp.float32)
        )
        return (o_new, m_new, l_new, step + 1), None

    o0, m0, l0 = carry
    (o, m, l, _), _ = jax.lax.scan(
        scan_step, (o0, m0, l0, jnp.int32(0)), (kb, vb)
    )
    return o, m, l


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_k: int = 256,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Flash-style attention: scan over K/V blocks with online softmax.

    q,k,v: [B, L, H, Dh] (Lk may differ from Lq). Never materializes the
    [Lq, Lk] matrix; peak memory is O(Lq * block_k) per head."""
    q32 = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(q.shape[1])
    # derive accumulators from q so that, under shard_map, they inherit its
    # varying-axis type (scan requires matching carry types)
    zq = jnp.transpose(q32 * 0.0, (0, 2, 1, 3))  # [B,H,Lq,Dh]
    carry = (zq, zq[..., 0] + NEG_INF, zq[..., 0])
    o, m, l = online_softmax_sweep(
        q32, k, v, carry, q_pos, kv_offset, causal=causal, block_k=block_k
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Lq, H, Dh]


# ---------------------------------------------------------------------------
# Pallas TPU flash-attention kernels
# ---------------------------------------------------------------------------


def _masked_scores(q_ref, k_ref, qi, ki, *, causal, q_offset, kv_offset, lk,
                   k_padded, k_major=False):
    """Scaled QK^T for one (Q tile, K tile) pair with the K-padding and
    causal masks applied — the ONE implementation every kernel (forward,
    one-pass backward, dq, dk/dv) shares so their masking can never diverge.
    ``[block_q, block_k]``, or ``K Q^T`` as ``[block_k, block_q]`` with
    ``k_major``. ``ki`` counts K tiles of ``k_ref``'s own length.

    The K-padding mask is STATICALLY skipped when ``k_padded`` is false (Lk
    divides the K tile evenly: no padded keys exist). The causal mask stays
    unconditional. Masking only the pairs the diagonal crosses (two
    ``pl.when`` bodies, interior and diagonal) was read at head width 128,
    L=8192 on a v5e (PR 37): the forward rose from 2.339 to 2.396 ms, the
    one-pass backward fell from 4.333 to 4.300 ms; not kept. (A ``lax.cond``
    on a per-block scalar had cost 40% at width 64 under an earlier jax.)"""
    block_q, dh = q_ref.shape
    block_k = k_ref.shape[0]
    # operands keep their storage dtype: bf16 x bf16 -> f32 runs the MXU at
    # full rate (casting to f32 first halves/quarters it); accumulation is
    # always f32 via preferred_element_type
    q = q_ref[...]
    k = k_ref[...]
    scale = 1.0 / jnp.sqrt(float(dh))
    lhs, rhs = (k, q) if k_major else (q, k)
    s = jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale

    if k_padded or causal:
        q_axis, k_axis = (1, 0) if k_major else (0, 1)
        ki_local = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, k_axis
        )
        if k_padded:
            s = jnp.where(ki_local < lk, s, NEG_INF)
        if causal:
            q_pos = (
                q_offset + qi * block_q
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
            )
            s = jnp.where(q_pos >= kv_offset + ki_local, s, NEG_INF)
    return s, scale


def _block_needed(qi, ki, block_q, block_k, *, causal, q_offset, kv_offset,
                  **_):
    """Whether a (Q tile, K tile) pair holds a score that is not masked:
    every pair without a causal mask, with one all but those entirely above
    the diagonal (the last query row of the Q tile attends to nothing in
    them), which the kernels skip."""
    if not causal:
        return qi >= 0  # always
    return (q_offset + qi * block_q + block_q - 1) >= (
        kv_offset + ki * block_k
    )


def _needs_masked_row_guard(causal, q_offset, kv_offset):
    """Whether a query row can have every key so far masked, so that
    ``exp(s - m)`` of its masked scores would read ``exp(0)`` and the
    kernels must zero them by hand (a compare and a select a score). Not
    with ``q_offset >= kv_offset``, and never without a causal mask: key 0
    is visible to every row in the first block, ``m`` and ``lse`` are finite
    from there on, and ``exp(-1e30 - m)`` is exactly 0 in float32. Read at
    head width 128 (PR 37): 8.9% of the forward, 7.3% of the one-pass
    backward."""
    return causal and q_offset < kv_offset


def _causal_kv_index(block_q, block_k, q_offset, kv_offset):
    """K/V BlockSpec index_map for causal grids (B*H, q tile j, k step kk):
    clamp the k index to the LAST needed tile for this Q tile. Skipped
    steps (kk past the diagonal) then map to the same block as the step
    before, and Mosaic elides the repeat DMA — pl.when alone skips the
    compute but still paid the HBM->VMEM copy for every masked block
    (~2x the needed K/V traffic at long context)."""

    def index_map(i, j, kk):
        last = (q_offset + (j + 1) * block_q - 1 - kv_offset) // block_k
        return (i, jnp.minimum(kk, jnp.maximum(last, 0)), 0)

    return index_map


def _causal_q_index(block_q, block_k, q_offset, kv_offset, n_q, rows=True):
    """Q-side BlockSpec index_map for the grids that sweep Q inside a K tile
    (B*H, k tile a, q step b_): clamp to the FIRST needed Q tile for this K
    tile (the skipped steps sit at the sweep's start), same DMA-elision
    trick as above. ``rows=False``: the tile index is the block's LAST axis
    (a ``[1, Lq]`` row of per-query scalars)."""

    def index_map(i, a, b_):
        first = (kv_offset + a * block_k - q_offset) // block_q
        first = jnp.minimum(jnp.maximum(first, 0), n_q - 1)
        tile = jnp.maximum(b_, first)
        return (i, tile, 0) if rows else (i, 0, tile)

    return index_map


def _vma_struct_factory(ref_array):
    """ShapeDtypeStruct builder inheriting ``ref_array``'s varying-axis type
    (required for pallas_call outputs under shard_map's vma checking)."""
    vma = jax.typeof(ref_array).vma

    def _struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    return _struct


def _tpu_compiler_kwargs(interpret: bool, tile_axis: str = "parallel",
                         vmem_limit_bytes: Optional[int] = None) -> dict:
    """dimension_semantics for the flash grids: B*H ``parallel``, the tile
    axis ``parallel`` unless the kernel carries an accumulator across it
    too, the innermost sweep ``arbitrary``; the interpreter takes no
    compiler parameters."""
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", tile_axis, "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        )
    }


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, n_k: int, sub_k: int, guard: bool, **mask):
    """Grid: (B*H, Lq/block_q, Lk/block_k) with the K axis innermost
    (sequential). Each program sees ONE Q tile and ONE K/V tile; the
    online-softmax accumulators live in VMEM scratch and carry across the
    K sweep, so VMEM holds O(block_q * (dh + block_k)) regardless of Lk —
    the whole-K/V-per-program staging this replaces blew VMEM exactly in
    the long-context regime the module exists for.

    The K/V tile is taken in sub-blocks of ``sub_k`` keys, one online-softmax
    update each, unrolled: ``exp(s - m)`` waits for the row maximum of the
    whole score block, so within ONE block the matrix unit idles through the
    softmax and the vector unit through the product; with two sub-blocks the
    next one's ``Q K^T`` has nothing to wait for."""
    block_q, dh = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    needed = _block_needed(qi, ki, block_q, block_k, **mask)

    def _sub_block(j):
        keys = pl.ds(j * sub_k, sub_k)
        s, _ = _masked_scores(
            q_ref, k_ref.at[keys, :], qi, ki * (block_k // sub_k) + j, **mask)
        # m/l scratch is LANES wide with every lane identical: subtracting
        # a [bq, 1] vector from the [bq, bk] scores broadcasts from lane 0,
        # which the VPU does poorly — pltpu.repeat of a full vreg is cheap
        # (the jax reference flash kernel's MIN_BLOCK_SIZE trick)
        m_prev = m_ref[...]  # [bq, LANES]
        l_prev = l_ref[...]
        lanes = m_prev.shape[-1]
        m_curr = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_curr)          # [bq, LANES]
        if sub_k % lanes == 0 and sub_k > lanes:
            m_rep = pltpu.repeat(m_new, sub_k // lanes, axis=1)
        elif sub_k <= lanes:
            m_rep = m_new[:, :sub_k]
        else:  # ragged sub_k (< full tiles): lane-0 broadcast fallback
            m_rep = jnp.broadcast_to(m_new[:, :1], s.shape)
        p = jnp.exp(s - m_rep)
        if guard:  # the fully-masked-row guard of the blockwise/ring variants
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))  # [bq, LANES]
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        if dh > lanes and dh % lanes == 0:
            alpha_dh = pltpu.repeat(alpha, dh // lanes, axis=1)
        elif dh <= lanes:
            alpha_dh = alpha[:, :dh]
        else:  # ragged dh: lane-0 broadcast fallback
            alpha_dh = jnp.broadcast_to(alpha[:, :1], acc_ref.shape)
        # P quantizes to the value dtype for the PV matmul (bf16 MXU rate;
        # identity for f32 inputs) — the accumulator stays f32
        acc_ref[...] = acc_ref[...] * alpha_dh + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[keys, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(needed)
    def _block():
        for j in range(block_k // sub_k):
            _sub_block(j)

    @pl.when(ki == n_k - 1)
    def _finish():
        lanes_f = l_ref.shape[-1]
        if dh > lanes_f and dh % lanes_f == 0:
            l_dh = pltpu.repeat(l_ref[...], dh // lanes_f, axis=1)
        elif dh <= lanes_f:
            l_dh = l_ref[:, :dh]
        else:
            l_dh = jnp.broadcast_to(l_ref[:, :1], acc_ref.shape)
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_dh, 1e-30)
        ).astype(o_ref.dtype)
        # per-row logsumexp: the backward kernels recompute P from S - lse
        lse_ref[...] = (
            m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30))
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "q_offset", "kv_offset",
                     "interpret", "return_lse"),
)
def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    interpret: bool = False,
    return_lse: bool = False,
):
    """Pallas flash attention. q,k,v: [B, L, H, Dh] -> [B, Lq, H, Dh].

    The grid is (B*H, ceil(Lq/block_q), ceil(Lk/block_k)) with the K axis
    sequential: VMEM holds one Q tile, one K/V tile and the online-softmax
    accumulators — O(block_q * (dh + block_k)) regardless of context
    length. Causal runs skip K tiles above the diagonal. Tiles of 1,024 x
    1,024 taken in two sub-blocks of 512 keys read fastest at head width 128
    on a TPU v5e (the table at the module's tile sizes). Use
    ``interpret=True`` on CPU."""
    b, lq, h, dh = q.shape
    lk = k.shape[1]
    block_q = min(block_q or FWD_BLOCK_Q, lq)
    block_k = min(block_k or FWD_BLOCK_K, lk)
    # half a K tile where that is whole lanes, else the tile in one piece
    sub_k = block_k // 2 if block_k % 256 == 0 else block_k
    pad_q = (-lq) % block_q
    pad_k = (-lk) % block_k

    # flatten (B, H) into the leading grid axis; pallas BlockSpec tiles Lq
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, dh)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    # under shard_map's vma typing the kernel output must declare which mesh
    # axes it varies over — inherit the query's
    _struct = _vma_struct_factory(qf)
    out_struct = (
        _struct((b * h, lq + pad_q, dh), q.dtype),
        _struct((b * h, lq + pad_q, 1), jnp.float32),  # logsumexp rows
    )
    n_k = (lk + pad_k) // block_k
    grid = (b * h, (lq + pad_q) // block_q, n_k)
    # m/l scratch is a full 128-lane vreg wide (every lane identical): the
    # kernel expands it over the score block with pltpu.repeat instead of
    # a slow lane-0 broadcast
    lanes = 128
    scratch = [
        pltpu.VMEM((block_q, dh), jnp.float32),     # acc
        pltpu.VMEM((block_q, lanes), jnp.float32),  # m (running max)
        pltpu.VMEM((block_q, lanes), jnp.float32),  # l (running denom)
    ]
    # the K axis carries the accumulators: sequential ("arbitrary");
    # B*H and the Q tiles are embarrassingly parallel
    kwargs = _tpu_compiler_kwargs(interpret)
    kv_index = (
        _causal_kv_index(block_q, block_k, q_offset, kv_offset)
        if causal else (lambda i, j, kk: (i, kk, 0))
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            n_k=n_k,
            sub_k=sub_k,
            guard=_needs_masked_row_guard(causal, q_offset, kv_offset),
            causal=causal,
            q_offset=q_offset,
            kv_offset=kv_offset,
            lk=lk,
            k_padded=pad_k != 0,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((None, block_k, dh), kv_index),
            pl.BlockSpec((None, block_k, dh), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((None, block_q, dh), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j, kk: (i, j, 0)),
        ),
        out_shape=out_struct,
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(qf, kf, vf)
    out, lse = out
    out = out[:, :lq].reshape(b, h, lq, dh).transpose(0, 2, 1, 3)
    if return_lse:
        return out, lse  # lse stays in the flattened [B*H, Lq+pad, 1] layout
    return out


def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *,
               guard, k_major=False, **mask):
    """``P``, ``dS`` and the scale of one (Q tile, K tile) pair, recomputed
    from the saved per-row logsumexp (flash backward never materializes P):
    float32 ``[block_q, block_k]`` with ``lse_ref`` and ``delta_ref``
    ``[block_q, 1]`` columns, or, ``k_major``, ``[block_k, block_q]`` with
    ``[1, block_q]`` rows."""
    s, scale = _masked_scores(q_ref, k_ref, qi, ki, k_major=k_major, **mask)
    p = jnp.exp(s - lse_ref[...])
    if guard:
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    # storage-dtype operands, f32 accumulators (see _masked_scores)
    lhs, rhs = (v_ref, do_ref) if k_major else (do_ref, v_ref)
    dp = jax.lax.dot_general(
        lhs[...], rhs[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta_ref[...]), scale


def _contract(a, b, a_axis):
    """``a^T b`` (``a_axis`` 0) or ``a b`` (``a_axis`` 1) with ``a`` cast to
    ``b``'s storage dtype, float32 out."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((a_axis,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      n_q, n_k, guard, **mask):
    """The backward pass in ONE kernel: grid (B*H, Lk/bk, Lq/bq), both tile
    axes sequential, Q innermost. ``S``, ``P``, ``dP`` and ``dS`` of a pair
    are made once and all three gradients take from them: ``dK`` / ``dV`` of
    the K tile accumulate over the Q sweep in VMEM scratch (written at its
    end), ``dQ`` of the head's WHOLE length accumulates in a float32 VMEM
    scratch over the K tiles, and the head's ``dq`` block stays resident
    (its index depends on the head alone) until the head's last step
    writes it: no partial ``dQ`` goes to HBM.

    The scores are made key-major (``S^T = K Q^T``, ``[block_k, block_q]``):
    ``lse`` and ``delta`` are then ``[1, block_q]`` rows that broadcast down
    the sublanes, ``dV += P^T dO`` and ``dK += dS^T Q`` are plain products
    and only ``dQ`` contracts a transposed block."""
    block_q, dh = q_ref.shape
    block_k = k_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = _block_needed(qi, ki, block_q, block_k, **mask)

    @pl.when(needed)
    def _block():
        pt, dst, scale = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            guard=guard, k_major=True, **mask)
        dv_acc[...] = dv_acc[...] + _contract(pt, do_ref[...], 1)
        dk_acc[...] = dk_acc[...] + _contract(dst, q_ref[...], 1) * scale
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[rows, :] = dq_acc[rows, :] + _contract(
            dst, k_ref[...], 0) * scale

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == n_k - 1, qi == n_q - 1))
    def _finish_head():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, n_k, guard, **mask):
    """dQ pass of the two-kernel backward: grid (B*H, Lq/bq, Lk/bk), K
    sequential; accumulates dQ in VMEM scratch."""
    block_q, dh = q_ref.shape
    block_k = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = _block_needed(qi, ki, block_q, block_k, **mask)

    @pl.when(needed)
    def _block():
        _, ds, scale = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            guard=guard, **mask)
        acc_ref[...] = acc_ref[...] + _contract(ds, k_ref[...], 1) * scale

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, n_q, guard,
                           **mask):
    """dK/dV pass of the two-kernel backward: grid (B*H, Lk/bk, Lq/bq), Q
    sequential. One K/V tile's gradients accumulate across the whole Q sweep
    in VMEM scratch."""
    block_q, dh = q_ref.shape
    block_k = k_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    needed = _block_needed(qi, ki, block_q, block_k, **mask)

    @pl.when(needed)
    def _block():
        p, ds, scale = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            guard=guard, **mask)
        # dV += P^T dO; dK += dS^T Q * scale
        dv_acc[...] = dv_acc[...] + _contract(p, do_ref[...], 0)
        dk_acc[...] = dk_acc[...] + _contract(ds, q_ref[...], 0) * scale

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal: bool, q_offset: int, kv_offset: int,
                interpret: bool = False):
    """Differentiable Pallas flash attention: Pallas forward AND backward
    (:func:`flash_backward`, which recomputes the scores from the saved
    logsumexp)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        interpret=interpret,
    )


def _flash_diff_fwd(q, k, v, causal, q_offset, kv_offset, interpret=False):
    out, lse = flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        interpret=interpret, return_lse=True,
    )
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, q_offset, kv_offset, interpret, res, g):
    return flash_backward(*res, g, causal=causal, q_offset=q_offset,
                          kv_offset=kv_offset, interpret=interpret)


def _one_pass_fits(lq: int, dh: int, dtype) -> bool:
    """Whether the one-pass backward can hold a head's ``dQ`` in VMEM: the
    float32 ``[Lq, dh]`` accumulator and the two buffers of the resident
    ``dq`` block in the storage dtype, ``dh`` as the lanes pad it, against
    ``ONE_PASS_DQ_BYTES``."""
    lanes = -(-dh // 128) * 128
    return lq * lanes * (4 + 2 * jnp.dtype(dtype).itemsize) <= ONE_PASS_DQ_BYTES


def flash_backward(q, k, v, out, lse, g, *, causal, q_offset, kv_offset,
                   interpret=False, block_q=None, block_k=None):
    """``(dq, dk, dv)`` from the forward's residuals (``lse`` as
    :func:`flash_attention_pallas` returns it) and the cotangent ``g`` of
    ``out``. ONE kernel where a head's whole-length ``dQ`` fits VMEM
    (:func:`_one_pass_fits`), the dq and dk/dv kernels for longer rows: the
    same sums, staged by a size read from the shapes. Which was traced is
    counted under ``flash_bwd_path`` in ``tracing.RECORDER``. ``block_q``,
    ``block_k``: the tests' tiles; callers leave them to the module."""
    b, lq, h, dh = q.shape
    lk = k.shape[1]
    one_pass = _one_pass_fits(lq, dh, q.dtype)
    tracing.RECORDER.add_counts(
        "flash_bwd_path", **{"one_pass" if one_pass else "two_pass": 1})
    tiles = (BWD_BLOCK_Q, BWD_BLOCK_K) if one_pass else (
        TWO_PASS_BLOCK_Q, TWO_PASS_BLOCK_K)
    block_q = min(block_q or tiles[0], lq)
    block_k = min(block_k or tiles[1], lk)
    pad_q = (-lq) % block_q
    pad_k = (-lk) % block_k
    n_q = (lq + pad_q) // block_q
    n_k = (lk + pad_k) // block_k

    def flat(a, pad):
        f = a.transpose(0, 2, 1, 3).reshape(b * h, a.shape[1], dh)
        if pad:
            f = jnp.pad(f, ((0, 0), (0, pad), (0, 0)))
        return f

    qf, kf, vf = flat(q, pad_q), flat(k, pad_k), flat(v, pad_k)
    dof, of = flat(g, pad_q), flat(out, pad_q)
    # the forward saved lse under ITS q padding (fwd/bwd tile sizes may
    # differ); re-pad to this pass's layout. Zero pad rows are inert: the
    # cotangent is zero there, so every pad contribution cancels.
    lse = lse[:, :lq]
    if pad_q:
        lse = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0)))
    # delta_i = rowsum(dO * O) per query row — tiny elementwise op, fused
    # by XLA around the kernels
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, lq+pad, 1]

    # under shard_map's vma typing the kernel outputs must declare which
    # mesh axes they vary over — inherit the cotangent's (same as forward)
    _struct = _vma_struct_factory(dof)
    where = dict(causal=causal, q_offset=q_offset, kv_offset=kv_offset, lk=lk,
                 k_padded=pad_k != 0,
                 guard=_needs_masked_row_guard(causal, q_offset, kv_offset))
    dq_shape = _struct((b * h, lq + pad_q, dh), q.dtype)
    dkv_shape = (_struct((b * h, lk + pad_k, dh), k.dtype),
                 _struct((b * h, lk + pad_k, dh), v.dtype))
    dkv_scratch = [pltpu.VMEM((block_k, dh), jnp.float32),
                   pltpu.VMEM((block_k, dh), jnp.float32)]

    # the grids that sweep Q inside a K tile, axes (k tile a, q step b_).
    # causal: the skipped q steps sit at the sweep's start; clamp their
    # index to the first needed tile (DMA elision, see _causal_kv_index)
    def q_map(rows=True):
        if causal:
            return _causal_q_index(block_q, block_k, q_offset, kv_offset, n_q,
                                   rows=rows)
        return (lambda i, a, b_: (i, b_, 0)) if rows else (
            lambda i, a, b_: (i, 0, b_))

    q_spec2 = pl.BlockSpec((None, block_q, dh), q_map())
    kv_spec2 = pl.BlockSpec((None, block_k, dh), lambda i, a, b_: (i, a, 0))

    def unflat(a, l):
        return a[:, :l].reshape(b, h, l, dh).transpose(0, 2, 1, 3)

    if one_pass:
        # per-query scalars as [1, Lq] rows: on the lanes, where a
        # [Lq, 1] column is padded to 128 of them in HBM and in VMEM
        lse, delta = (a.reshape(b * h, 1, lq + pad_q) for a in (lse, delta))
        row_spec2 = pl.BlockSpec((None, 1, block_q), q_map(rows=False))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_kernel, n_q=n_q, n_k=n_k, **where),
            grid=(b * h, n_k, n_q),
            in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                      row_spec2],
            out_specs=(
                pl.BlockSpec((None, lq + pad_q, dh), lambda i, a, b_: (i, 0, 0)),
                kv_spec2, kv_spec2,
            ),
            out_shape=(dq_shape,) + dkv_shape,
            scratch_shapes=[pltpu.VMEM((lq + pad_q, dh), jnp.float32)]
            + dkv_scratch,
            interpret=interpret,
            **_tpu_compiler_kwargs(interpret, tile_axis="arbitrary",
                                   vmem_limit_bytes=ONE_PASS_VMEM_LIMIT),
        )(qf, kf, vf, dof, lse, delta)
        return unflat(dq, lq), unflat(dk, lk), unflat(dv, lk)

    kwargs = _tpu_compiler_kwargs(interpret)
    q_spec = pl.BlockSpec((None, block_q, dh), lambda i, a, b_: (i, a, 0))
    row_spec = pl.BlockSpec((None, block_q, 1), lambda i, a, b_: (i, a, 0))
    # causal: clamp skipped K steps to the last needed tile so their DMA
    # is elided (see _causal_kv_index)
    kv_map = (
        _causal_kv_index(block_q, block_k, q_offset, kv_offset)
        if causal else (lambda i, a, b_: (i, b_, 0))
    )
    kv_spec = pl.BlockSpec((None, block_k, dh), kv_map)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_k=n_k, **where),
        grid=(b * h, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(qf, kf, vf, dof, lse, delta)
    row_spec2 = pl.BlockSpec((None, block_q, 1), q_map())
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, n_q=n_q, **where),
        grid=(b * h, n_k, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=(kv_spec2, kv_spec2),
        out_shape=dkv_shape,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
        **kwargs,
    )(qf, kf, vf, dof, lse, delta)
    return unflat(dq, lq), unflat(dk, lk), unflat(dv, lk)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    q_offset: int = 0,
    kv_offset: int = 0,
    block_k: int = 256,
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Backend-dispatching attention entry point: the Pallas kernel on TPU
    (differentiable end to end — Pallas forward AND backward, the latter
    recomputing P from the saved logsumexp in one kernel, or two for rows
    past its VMEM budget: :func:`flash_backward`), blockwise scan
    elsewhere."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return _flash_diff(q, k, v, causal, q_offset, kv_offset)
    return blockwise_attention(
        q, k, v, causal=causal, block_k=block_k,
        q_offset=q_offset, kv_offset=kv_offset,
    )
