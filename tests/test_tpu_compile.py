"""Compiles for a DESCRIBED chip (no chip attached, nothing runs): what the
TPU's own compiler makes of the main path's programs at real widths.

One file on purpose: the worker that is handed it loads the TPU's library
once, inside the fixture; a second file could land on another worker, whose
fixture would then skip. A compile that passes here is not a chip run.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from omldm_tpu.ops import sparse as sp


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or its library is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_plan_update_at_2e28_weights_passes_over_them_once(one_chip):
    """The sparse PA-II update through the index plan at the benchmark's
    width (2^28 + 14 weights, a tail step's 256 x 41 slots, which compiles
    in seconds), with a sync branch beside it as the SPMD step has: the
    donated vector is updated in place, and outside the branches taken when
    their predicate holds (the sync; an overflowing launch's plain pair) no
    operation passes over the model's width but the scatter. Neither
    conditional around the plan's halves copies the vector."""
    n_weights, batch, slots = (1 << 28) + 14, 256, 41

    def step(w, est, idx, val, y, k):
        margins, add, counters = sp.sparse_update(w, idx, val, impl="plan")
        ys = 2.0 * y - 1.0
        hinge = jnp.maximum(0.0, 1.0 - ys * margins)
        tau = hinge / (jnp.sum(val * val, axis=1) + 5.0)
        w = add(w, tau * ys / batch)
        w, est = jax.lax.cond(
            k % 4 == 0, lambda f, e: (f, f), lambda f, e: (f, e), w, est
        )
        return w, est, jnp.mean(hinge), counters

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        shape((n_weights,), jnp.float32), shape((n_weights,), jnp.float32),
        shape((batch, slots), jnp.int32), shape((batch, slots), jnp.float32),
        shape((batch,), jnp.float32), shape((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert chip_smoke.hlo_wide_passes(text, n_weights) == []
    assert chip_smoke.hlo_aliased_parameters(text) == [0, 1]
    # two vectors in, the same two out, and nothing model-sized between
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
