"""Compiles for a DESCRIBED chip (no chip attached, nothing runs): what the
TPU's own compiler makes of the main path's programs at real widths.

One file on purpose: the worker that is handed it loads the TPU's library
once, inside the fixture; a second file could land on another worker, whose
fixture would then skip. A compile that passes here is not a chip run.
"""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke
from omldm_tpu.ops import sparse as sp
from omldm_tpu.parallel.spmd import SPMDTrainer


@pytest.fixture(scope="module")
def one_chip_mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or its library is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "hub"))


def test_plan_update_at_2e28_weights_passes_over_them_once(one_chip_mesh):
    """The sparse PA-II update through the index plan at the benchmark's
    width (2^28 + 14 weights, a tail step's 256 x 41 slots, which compiles
    in seconds), in the shape the SPMD step has under Synchronous on one
    chip: a ``shard_map`` over a 1 x 1 mesh, the state's two vector leaves
    donated, and a sync branch that returns the trainer's own
    ``_ps_allreduce`` of the weights (with no ``est`` beside it to copy
    them into). The vectors are updated in place, and NO computation of the
    module, the sync branch and an overflowing launch's plain pair
    included, passes over the model's width but the scatter: no copy, no
    other fusion of N elements."""
    n_weights, batch, slots = (1 << 28) + 14, 256, 41
    mesh = one_chip_mesh
    ps = SimpleNamespace(shard_size=n_weights)  # hub = 1: one bucket

    def step(state, idx, val, y):
        w, center = state["w"], state["center"]
        k, syncs = state["step"][0, 0] + 1, state["syncs"][0, 0]
        idx, val, y = (
            jax.lax.pcast(a[0], "hub", to="varying") for a in (idx, val, y)
        )
        margins, add, counters = sp.sparse_update(w, idx, val, impl="plan")
        ys = 2.0 * y - 1.0
        hinge = jnp.maximum(0.0, 1.0 - ys * margins)
        tau = hinge / (jnp.sum(val * val, axis=1) + 5.0)
        w = add(w, tau * ys / batch)
        w, center, syncs = jax.lax.cond(
            k % 4 == 0,
            lambda f, c, s: (SPMDTrainer._ps_allreduce(ps, f), c, s + 1),
            lambda f, c, s: (f, c, s),
            w, center, syncs,
        )
        state = {"w": w, "center": center, "step": k[None, None],
                 "syncs": syncs[None, None]}
        return state, (jnp.mean(hinge)[None, None], counters[None, None])

    vec, stack, rows = P(("dp", "hub")), P("dp", "hub"), P("dp")
    specs = {"w": vec, "center": vec, "step": stack, "syncs": stack}

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=NamedSharding(mesh, spec)
        )

    compiled = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(specs, rows, rows, rows),
            out_specs=(specs, (stack, stack)),
        ),
        donate_argnums=0,
    ).lower(
        {"w": shape((n_weights,), jnp.float32, vec),
         "center": shape((n_weights,), jnp.float32, vec),
         "step": shape((1, 1), jnp.int32, stack),
         "syncs": shape((1, 1), jnp.int32, stack)},
        shape((1, batch, slots), jnp.int32, rows),
        shape((1, batch, slots), jnp.float32, rows),
        shape((1, batch), jnp.float32, rows),
    ).compile()
    text = compiled.as_text()
    assert chip_smoke.hlo_wide_passes(text, n_weights, every_branch=True) == []
    # the four state leaves, both vectors among them, are donated
    assert chip_smoke.hlo_aliased_parameters(text) == [0, 1, 2, 3]
    # two vectors in, the same two out, and nothing model-sized between
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
