"""Compiles for a DESCRIBED chip (no chip attached, nothing runs): what the
TPU's own compiler makes of the main path's programs at real widths.

One file on purpose: the worker that is handed it loads the TPU's library
once, inside the fixture; a second file could land on another worker, whose
fixture would then skip. A compile that passes here is not a chip run.
"""

import os
import re
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


def compiled_plan_update(mesh, batch: int, n_weights: int = (1 << 28) + 14):
    """The sparse PA-II update through the index plan at the benchmark's
    width, ``batch`` x 41 slots, in the shape the SPMD step has under
    Synchronous on one chip: a ``shard_map`` over a 1 x 1 mesh, the state's
    vector leaf donated (the weights: Synchronous holds no ``est`` and no
    ``center`` beside them), and a sync branch that returns the trainer's
    own ``_ps_allreduce`` of the weights (the form a mesh of more shards
    compiles). -> the compiled program."""
    slots = 41
    ps = SimpleNamespace(shard_size=n_weights)  # hub = 1: one bucket

    def step(state, idx, val, y):
        w = state["w"]
        k, syncs = state["step"][0, 0] + 1, state["syncs"][0, 0]
        idx, val, y = (
            jax.lax.pcast(a[0], "hub", to="varying") for a in (idx, val, y)
        )
        margins, add, counters = sp.sparse_update(w, idx, val, impl="plan")
        ys = 2.0 * y - 1.0
        hinge = jnp.maximum(0.0, 1.0 - ys * margins)
        tau = hinge / (jnp.sum(val * val, axis=1) + 5.0)
        w = add(w, tau * ys / batch)
        w, syncs = jax.lax.cond(
            k % 4 == 0,
            lambda f, s: (SPMDTrainer._ps_allreduce(ps, f), s + 1),
            lambda f, s: (f, s),
            w, syncs,
        )
        state = {"w": w, "step": k[None, None], "syncs": syncs[None, None]}
        return state, (jnp.mean(hinge)[None, None], counters[None, None])

    vec, stack, rows = P(("dp", "hub")), P("dp", "hub"), P("dp")
    specs = {"w": vec, "step": stack, "syncs": stack}

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(
            dims, dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(specs, rows, rows, rows),
            out_specs=(specs, (stack, stack)),
        ),
        donate_argnums=0,
    ).lower(
        {"w": shape((n_weights,), jnp.float32, vec),
         "step": shape((1, 1), jnp.int32, stack),
         "syncs": shape((1, 1), jnp.int32, stack)},
        shape((1, batch, slots), jnp.int32, rows),
        shape((1, batch, slots), jnp.float32, rows),
        shape((1, batch), jnp.float32, rows),
    ).compile()


def test_plan_update_at_2e28_weights_passes_over_them_once(one_chip_mesh):
    """:func:`compiled_plan_update` at a tail step's 256 x 41 slots (which
    compiles in seconds), the backend's own choice of the final add (the
    CPU's here: XLA's scatter). The vector is updated in place, and NO
    computation of the module, the sync branch and an overflowing launch's
    plain pair included, passes over the model's width but the scatter: no
    copy, no other fusion of N elements."""
    n_weights = (1 << 28) + 14
    compiled = compiled_plan_update(one_chip_mesh, 256, n_weights)
    text = compiled.as_text()
    assert chip_smoke.hlo_wide_passes(text, n_weights, every_branch=True) == []
    # the three state leaves, the vector among them, are donated
    assert chip_smoke.hlo_aliased_parameters(text) == [0, 1, 2]
    # one vector in, the same out, and nothing model-sized between
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_plan_update_on_a_tpu_adds_by_tiles_in_place(one_chip_mesh, monkeypatch):
    """The benchmark's launch, 4,096 x 41 slots over 2^28 + 14 weights,
    with the final add a TPU takes (the tile kernel: the backend is said to
    be one, the compile is for the chip). The branch every launch that does
    not overflow takes holds the kernel's custom call and no scatter; the
    only other instruction there with a result of 2^28 elements is the
    in-place update of the 14 weights past the last whole tile (a fusion
    whose root is a ``dynamic-update-slice`` of 14 elements): no copy, no
    relayout, no other fusion of N. The state stays donated, and the
    kernel's VMEM (its two batches of tiles) fits the 16 MiB a v5e's
    kernel may use by default."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_weights = (1 << 28) + 14
    compiled = compiled_plan_update(one_chip_mesh, 4096, n_weights)
    text = compiled.as_text()
    comps = chip_smoke.hlo_computations(text)

    def walk(comp, seen):
        if comp not in seen:
            seen.add(comp)
            for _, _, _, line in comps.get(comp, ()):
                for c in chip_smoke._hlo_called(line):
                    walk(c, seen)
        return seen

    def ops(comp):
        return [(name, op, shape, line) for c in walk(comp, set())
                for name, op, shape, line in comps.get(c, ())]

    kernel_lines = [l for _, op, _, l in ops("ENTRY") if op == "custom-call"
                    and "sparse_tile_scatter_add" in l]
    assert len(kernel_lines) == 1 and "tpu_custom_call" in kernel_lines[0]
    vmem = [int(size) for space, size in re.findall(
        r'"memory_space":"(\d+)","offset":"\d+","size":"(\d+)"',
        kernel_lines[0]) if space == "1"]
    assert vmem == [2 * sp.CHUNK * sp.TILE * 4] and vmem[0] <= 16 << 20
    # the conditional whose branch holds the kernel: the plan's scatter half
    [(branches, taken)] = {
        (tuple(branches), b)
        for _, op, _, line in ops("ENTRY") if op == "conditional"
        for branches in [chip_smoke._hlo_called(line)] for b in branches
        if kernel_lines[0] in [l for _, _, _, l in ops(b)]
    }
    assert not any(op == "scatter" for _, op, _, _ in ops(taken))
    # what the branch itself computes at the model's width (its argument
    # and result tuples aside)
    wide = [(name, op, line) for name, op, shape, line in comps[taken]
            if chip_smoke._hlo_wide(shape, n_weights)
            and op not in ("parameter", "get-tuple-element", "tuple")]
    assert sorted(op for _, op, _ in wide) == ["custom-call", "fusion"]
    [fusion] = [line for _, op, line in wide if op == "fusion"]
    [root] = [line for c in chip_smoke._hlo_called(fusion)
              for _, op, _, line in comps[c] if line.lstrip().startswith("ROOT ")]
    assert " dynamic-update-slice(" in root
    assert "f32[14]" in " ".join(
        line for c in chip_smoke._hlo_called(fusion)
        for _, op, shape, line in comps[c] if op == "select")
    # the other branch (an overflowing launch) keeps XLA's plain scatter
    assert any(op == "scatter" for b in branches if b != taken
               for _, op, _, _ in ops(b))
    assert chip_smoke.hlo_wide_passes(text, n_weights, every_branch=True) == []
    assert chip_smoke.hlo_aliased_parameters(text) == [0, 1, 2]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def compiled_lm_launch(mesh, cell: str):
    """A language-model cell's launch (its configuration's Create request at
    the cell's ``tokens_per_row``, one row, one SGD step in float32) as the
    trainer compiles it under Synchronous on one chip: the trainer's own
    ``step_many_dense`` body over the state tree it builds, donated.
    ``(compiled, trainer, tokens_per_row, n_params)``. Both cells' rows fit
    the one-pass flash backward, and the trace says so (``flash_bwd_path``)."""
    from omldm_tpu.api.requests import LearnerSpec, TrainingConfiguration
    from omldm_tpu.parallel import spmd
    from omldm_tpu.utils import tracing

    from perfbench import harness

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = harness.load_cell(cell, root)
    config, dim = spec["config"], int(spec["cell"]["traffic"]["tokens_per_row"])
    learner = spec["kind"].create_request(config, dim, 0)["learner"]

    tr = spmd.SPMDTrainer.__new__(spmd.SPMDTrainer)
    tr.mesh, tr.dp, tr.hub, tr.protocol = mesh, 1, 1, "Synchronous"
    tr.tc = TrainingConfiguration(protocol="Synchronous")
    tr.learner = spmd.make_learner(LearnerSpec(
        learner["name"], hyper_parameters=learner["hyperParameters"],
        data_structure=learner["dataStructure"],
    ))
    tr.preps, tr.sync_every, tr.threshold, tr.alpha = [], 4, 0.5, 0.5
    tr.staleness, tr._qdq, tr.pad = 3, None, 0
    step_fn = tr._build_step()

    template = jax.eval_shape(lambda: tr.learner.init(dim, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(template))

    def stored_shape(leaf_shape, dtype):
        dims = leaf_shape if len(leaf_shape) == 1 else (1, 1) + tuple(leaf_shape)
        spec = P(("dp", "hub")) if len(dims) == 1 else P("dp", "hub")
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    scalar = lambda dtype: stored_shape((), dtype)
    state = {
        "params": jax.tree_util.tree_map(
            lambda l: stored_shape(l.shape, l.dtype), template),
        "preps": [],
        "step": scalar(jnp.int32), "syncs": scalar(jnp.int32),
        "cum_loss": scalar(jnp.float32), "clock": scalar(jnp.int32),
        "accepted": scalar(jnp.float32), "fold_rounds": scalar(jnp.int32),
    }
    specs = jax.tree_util.tree_map(lambda s: s.sharding.spec, state)

    def launch(state, xs, ys):  # SPMDTrainer.step_many_dense's body
        def body(st, b):
            x, y = b
            return step_fn(st, x, y, y.astype(jnp.float32) * 0.0 + 1.0)

        return jax.lax.scan(body, state, (xs, ys))

    rows = P(None, "dp")
    paths = tracing.RECORDER.counts("flash_bwd_path")
    compiled = jax.jit(
        jax.shard_map(launch, mesh=mesh, in_specs=(specs, rows, rows),
                      out_specs=(specs, (P(None, "dp", "hub"), ()))),
        donate_argnums=0,
    ).lower(
        state,
        jax.ShapeDtypeStruct((1, 1, 1, dim), jnp.float32, sharding=NamedSharding(mesh, rows)),
        jax.ShapeDtypeStruct((1, 1, 1), jnp.float32, sharding=NamedSharding(mesh, rows)),
    ).compile()
    traced = tracing.RECORDER.counts("flash_bwd_path")
    assert traced.get("one_pass", 0) > paths.get("one_pass", 0)
    assert traced.get("two_pass", 0) == paths.get("two_pass", 0)
    return compiled, tr, dim, n_params


def flash_attn_calls(text: str):
    """The Pallas calls under ``omldm.lm.flash_attn`` in a compiled launch's
    text, ``(forward, backward)``: the forward kernel, run again by a layer's
    recomputation too, is called through ``jit(flash_attention_pallas)``,
    the backward's kernels straight from the ``custom_vjp``'s rule. A loop's
    body is counted once however often it runs."""
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and "omldm.lm.flash_attn" in l]
    forward = sum("jit(flash_attention_pallas)" in l for l in calls)
    return forward, len(calls) - forward


def test_lm_step_at_published_widths_fits_one_chip(one_chip_mesh, monkeypatch):
    """The benchmark's language-model launch (one period of Olmo-Hybrid-7B at
    its published widths, one row of 8,192 tokens, one SGD step in float32)
    as the trainer compiles it (:func:`compiled_lm_launch`). The
    program holds the weights once (arguments) and their gradients (among
    the temporaries) and no further float32 copy of all parameters: no
    ``center``, no flat concatenation, no split. A compile, not a chip run."""
    # the step picks its attention kernel by the default backend, which is
    # the CPU here: the compile is for the chip, so is the choice
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, tr, dim, n_params = compiled_lm_launch(one_chip_mesh, "olmo_hybrid_7b_l4.train_sat")
    mem = compiled.memory_analysis()
    weights = 4 * n_params
    assert n_params > 900e6
    assert mem.argument_size_in_bytes < weights + (1 << 20)
    # the new weights are written over the donated ones
    assert mem.alias_size_in_bytes >= weights
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16 << 30
    # (the temporaries are the gradients and one layer's recomputed
    # activations: one more float32 copy of the model, a ``center`` or the
    # flat form, would not fit beside them)
    assert mem.temp_size_in_bytes > weights
    # not above what the parent of PR 37 compiles to here (two backward
    # flash kernels and their padded copies)
    assert mem.temp_size_in_bytes <= 8_801_004_544
    text = compiled.as_text()
    # the flash kernels are in the program, at 30 heads of 128 over 8,192
    # positions (Mosaic took the one-pass backward's VMEM: the compile is the
    # check): the full layer's forward, its recomputation, and ONE backward
    # kernel where PR 36 held two
    assert flash_attn_calls(text) == (2, 1)
    # so are the delta rule's, for three linear layers: two forward (run
    # once: the layer's recomputation keeps what they made) and two
    # backward; no loop over chunks is left there
    in_delta_rule = [l for l in text.splitlines() if "omldm.lm.delta_rule" in l]
    assert sum("tpu_custom_call" in l for l in in_delta_rule) == 3 * (2 + 2)
    assert not any(" while(" in l for l in in_delta_rule)
    # what the model's checkpoint keeps of them (``RESIDUALS``) stays for the
    # whole backward pass and grows with batchSize x tokens: 138,480 bytes a
    # token a linear layer at these widths, 3.4 GB a launch. The temporaries
    # hold it beside the gradients, and the launch leaves a GiB of the chip
    # for what lives outside it (0.2 GiB of staged rows and the predict
    # program on the chip) and the runtime: a longer row or a larger batch
    # fails HERE, before the chip's allocator refuses it
    from omldm_tpu.ops import delta_rule

    cfg = tr.learner.cfg
    h, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    row = lambda *width: jax.ShapeDtypeStruct((1, dim, h) + width, jnp.float32)
    kept_a_layer = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(jax.eval_shape(
            lambda *a: delta_rule._pallas_fwd(
                *a, delta_rule.DEFAULT_CHUNK, cfg.operand_dtype, False)[1],
            row(dk), row(dk), row(dv), row(), row())))
    assert kept_a_layer == 138_480 * dim
    kept = kept_a_layer * sum(kind == "linear_attention" for kind in cfg.layer_types)
    assert mem.temp_size_in_bytes > weights + kept
    assert total + (1 << 30) < 16 << 30
    # no operation of the program yields all the parameters in one array
    import re

    widest = max(
        int(np.prod([int(d) for d in dims.split(",") if d]))
        for dims in re.findall(r"= f32\[([\d,]*)\]", text)
    )
    assert widest < n_params // 2


def test_looped_lm_step_at_published_widths_fits_one_chip(one_chip_mesh, monkeypatch):
    """The looped decoder's launch (12 layers of Ouro-2.6B at the published
    widths applied 4 times, one row of 8,192 tokens, one SGD step in float32)
    as the trainer compiles it. The arguments are the weights, 3.27 GB; the
    temporaries hold ONE float32 gradient a weight, the input of each of the
    48 layer applications, and what is left stays under 4.5 GB, so the launch
    leaves a GiB of the chip. A compile, not a chip run."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, tr, dim, n_params = compiled_lm_launch(one_chip_mesh, "ouro_2_6b_l12.train_sat")
    cfg = tr.learner.cfg
    assert n_params == 817_991_681
    mem = compiled.memory_analysis()
    weights = 4 * n_params
    assert mem.argument_size_in_bytes < weights + (1 << 20)
    assert mem.alias_size_in_bytes >= weights  # the new weights are written over the donated ones
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total + (1 << 30) < 16 << 30
    kept = 4 * cfg.total_ut_steps * cfg.num_hidden_layers * dim * cfg.hidden_size
    assert weights + kept < mem.temp_size_in_bytes < weights + kept + 4.5e9
    text = compiled.as_text()
    # no loop carries a second float32 copy of a stacked weight: beside the
    # parameters themselves (arguments, read in bfloat16 inside the loops)
    # there is one accumulator for each leaf of a shape, where two scans left
    # to ``jax.grad`` would carry the accumulator and a loop step's own
    # stacked gradient
    n, d, f = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
    leaves_of = {f"f32[{n},{d},{f}]": 2, f"f32[{n},{f},{d}]": 1, f"f32[{n},{d},{d}]": 4}
    loops = [l for l in text.splitlines() if " while(" in l]
    assert len(loops) >= 4  # forward and backward, over loop steps and over layers
    carried = 0
    for line in loops:
        for shape, leaves in leaves_of.items():
            count = len(re.findall(re.escape(shape) + r"\{", line.split(" while(")[0]))
            assert count <= leaves, (shape, count)
            carried += count
    assert carried >= 2 * 7  # both backward loops carry the accumulators
    # every application's input is kept in one array, written where it is made
    assert f"f32[{cfg.total_ut_steps * n},1,{dim},{d}]" in text
    # the flash kernels and the model's scopes are in the program
    scopes = set(re.findall(r"omldm\.lm\.([a-z_]+)", text))
    assert {"embed", "attn_proj", "rope", "flash_attn", "ffn", "head_loss", "exit_gate", "sgd"} <= scopes
    # the flash kernels at 16 heads of 128: the forward loop's body holds
    # the forward, the backward loop's body its recomputation and ONE
    # backward kernel where PR 36 held two
    assert flash_attn_calls(text) == (2, 1)
    # not above what the parent of PR 37 compiles to here
    assert mem.temp_size_in_bytes <= 10_738_496_000
