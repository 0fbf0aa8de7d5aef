"""Ring attention: exact long-context attention over an ``"sp"`` mesh axis.

Sequence/context parallelism has no counterpart in the reference (no
sequence dimension exists there, SURVEY.md section 5 "long-context"), but it
is first-class here: sequences longer than one chip's HBM are sharded over
the ``"sp"`` mesh axis, each device holds one contiguous chunk of Q/K/V, and
K/V chunks rotate around the ring via ``ppermute`` (one hop per step, riding
ICI) while every device accumulates its queries' attention with the online
softmax — compute overlaps communication, memory stays O(L / sp) per device,
and the result is bit-for-bit softmax attention (up to fp reassociation).

Call :func:`ring_attention` INSIDE ``shard_map`` with the sequence axis
sharded over ``axis_name``; :func:`ring_attention_sharded` wraps a whole
[B, L, H, Dh] batch for convenience/testing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from omldm_tpu.ops.attention import NEG_INF, online_softmax_sweep


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    block_k: int = 512,
) -> jnp.ndarray:
    """Per-shard ring attention. q,k,v: the LOCAL chunk [B, Lc, H, Dh];
    shard i owns absolute positions [i*Lc, (i+1)*Lc). Must run inside
    ``shard_map`` with the sequence dim sharded over ``axis_name``."""
    b, lc, h, dh = q.shape
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    q32 = q.astype(jnp.float32)
    q_pos = idx * lc + jnp.arange(lc)  # absolute query positions [Lc]

    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(acc, kc, vc, src):
        """Online-softmax update of (o, m, l) against the chunk whose origin
        shard is ``src`` (absolute key positions src*Lc + [0, Lc)). The
        chunk is swept in block_k-sized key blocks — peak score memory is
        [B, H, Lc, block_k], not the full O(Lc^2) chunk pair."""
        return online_softmax_sweep(
            q32, kc, vc, acc, q_pos, src * lc, causal=causal,
            block_k=block_k,
        )

    # derive the zero accumulators from q so they inherit its device-varying
    # type (shard_map's vma checking requires the scan carry types to match)
    zq = jnp.transpose(q32 * 0.0, (0, 2, 1, 3))  # [B, H, Lc, Dh]
    acc0 = (zq, zq[..., 0] + NEG_INF, zq[..., 0])

    # step 0: the local chunk, no communication
    acc = accumulate(acc0, k, v, idx)

    def step(carry, t):
        acc, kc, vc = carry
        # rotate K/V one hop around the ring, then accumulate — exactly n-1
        # hops total, so no chunk travels back to its origin unused
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        src = jax.lax.rem(idx - t + n, n)  # origin shard of this chunk
        acc = accumulate(acc, kc, vc, src)
        return (acc, kc, vc), None

    if n > 1:
        (acc, _, _), _ = jax.lax.scan(step, (acc, k, v), jnp.arange(1, n))
    o, m, l = acc
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Lc, H, Dh]


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = False,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Whole-array convenience wrapper: shards the sequence dim of
    [B, L, H, Dh] inputs over ``axis_name`` of ``mesh`` and runs the ring."""
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
