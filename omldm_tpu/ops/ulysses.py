"""Ulysses-style sequence parallelism: all_to_all head/sequence re-shard.

The second of the two standard long-context strategies (alongside ring
attention, omldm_tpu.ops.ring_attention): instead of rotating K/V chunks
around the ring, ONE ``all_to_all`` re-shards the activations from
sequence-sharded ``[B, L/sp, H, Dh]`` to head-sharded ``[B, L, H/sp, Dh]``,
each device runs ordinary (flash/blockwise) attention over the FULL
sequence for its head group, and a second ``all_to_all`` restores sequence
sharding. Two collectives total per attention call — cheaper than ring's
sp-1 hops when heads divide evenly and the full-sequence activations fit —
while ring keeps O(L/sp) memory. ``TransformerConfig.seq_parallel`` picks
the strategy per model.

Requires ``n_heads % sp == 0``. Runs INSIDE ``shard_map`` with the
sequence dim sharded over ``axis_name``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from omldm_tpu.ops.attention import attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
) -> jnp.ndarray:
    """Per-shard Ulysses attention. q,k,v: the LOCAL chunk [B, Lc, H, Dh];
    returns the local chunk of the attention output [B, Lc, H, Dh]."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return attention(q, k, v, causal=causal)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"n_heads {h} not divisible by sp axis size {n}")

    def scatter_heads(x):
        # [B, Lc, H, Dh] -> [B, L, H/n, Dh]: split the head dim across the
        # axis, gather all sequence chunks (source-shard order = seq order)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def gather_heads(x):
        # [B, L, H/n, Dh] -> [B, Lc, H, Dh]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = attention(qg, kg, vg, causal=causal)
    return gather_heads(out)


def ulysses_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = False,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Whole-array convenience wrapper (testing): shards the sequence dim of
    [B, L, H, Dh] inputs over ``axis_name`` and runs Ulysses."""
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
