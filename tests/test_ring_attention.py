"""Ring attention over an sp mesh axis matches full attention exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from omldm_tpu.ops.attention import mha_reference
from omldm_tpu.ops.ring_attention import ring_attention, ring_attention_sharded


def _qkv(b=2, l=64, h=2, dh=8, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, l, h, dh), jnp.float32),
        jax.random.normal(k2, (b, l, h, dh), jnp.float32),
        jax.random.normal(k3, (b, l, h, dh), jnp.float32),
    )


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(sp, causal):
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_grad_flows():
    """Autodiff through the ring (ppermute inside scan) works — required by
    the sequence-parallel training step."""
    sp = 4
    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    q, k, v = _qkv(b=1, l=32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-4)


def test_ring_inside_shard_map_2d_mesh():
    """Ring composes with a dp axis (batch sharded) on a 2D mesh."""
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "sp"))
    q, k, v = _qkv(b=4, l=32)
    ref = mha_reference(q, k, v, causal=True)

    spec = P("dp", "sp", None, None)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
