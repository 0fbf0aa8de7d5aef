"""The kind ``token_stream_looped`` (a looped decoder trained online on token
rows) through the harness on the CPU at its ``TINY`` size: the sound run
reads ``correct``; the reference in the program's place with parameters in
bfloat16, and with each of the seven faults planted (each leaves out a part
of the looped model), does not; a launch that returns its state unchanged
does not; a program that does not build the request's model is refused at
once; the program's initial weights follow the reference's laws; the readers'
operation counts on hand-worked shapes and the three new readers on a
hand-made trace."""

import time

import numpy as np
import pytest

import conftest  # noqa: F401  (the CPU backend and the path)
from perfbench import harness
from test_token_stream import failed, state_unchanged

CELL = "ouro_2_6b_l12.train_sat"
KIND = harness.load_cell(CELL)["kind"]


def test_the_kind_keeps_the_token_streams_comparison():
    """Records, job, comparison and the traced run's table are the ones of
    ``kinds/token_stream.py``; only the model's keys, the test size and the
    stand-ins are this kind's."""
    base = harness.load_module(harness.HERE + "/kinds", "token_stream")
    for name in ("Rows", "Pool", "System", "Readings", "draw_rows", "render", "scaled", "distance",
                 "leaf_update_gaps", "initial_gaps", "launch_scopes", "LEAF_CHANGE_FLOOR"):
        assert getattr(KIND, name) is getattr(base, name), name
    assert issubclass(KIND.Kind, base.Kind) and KIND.Kind.compare is base.Kind.compare
    assert not set(KIND.ARCH_KEYS) & {k for k in base.ARCH_KEYS if k.startswith("linear_")}
    spec = harness.load_cell(CELL)
    request = KIND.create_request(spec["config"], 8192, 7)["learner"]["dataStructure"]
    assert set(request) == set(KIND.ARCH_KEYS) | {"nFeatures"} and request["model_type"] == "ouro"
    assert (request["num_hidden_layers"], request["total_ut_steps"], request["head_dim"]) == (12, 4, 128)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    seen = []
    result = harness.run_cell(CELL, 2**31 + 36, 1.0, trace, time.perf_counter(), need_chip=False,
                              scale=KIND.TINY, hooks={"finished": lambda run, result: seen.append(run)})
    assert result["correct"] is True and result["failed"] == 0 and failed(result["checks"]) == []
    assert list(result["checks"]) == ["rows_lost", "forecasts_bad", "answers_wrong", "loss_gap",
                                      "first_update_norm_gap", "update_diff_rel", "leaf_update_diff_rel",
                                      "initial_stat_z", "initial_outside"]
    # the probe: 4 launches of one row, 2 forecasts answered
    assert result["counters"]["probe_answers"] == 2
    assert result["counters"]["fitted"] == 4 + result["counters"]["window_rows"]
    assert result["counters"]["leaves"] == 16
    if trace:
        # the compiled launch names the model's parts
        [run] = seen
        assert {"omldm.lm.embed", "omldm.lm.attn_proj", "omldm.lm.rope", "omldm.lm.flash_attn", "omldm.lm.ffn",
                "omldm.lm.head_loss", "omldm.lm.exit_gate", "omldm.lm.sgd"} <= set(run.kind.scope_of.values())
        assert 0.0 < result["metrics"]["dense_parse_stage_busy_share.train"]["value"] <= 100.0
        assert result["counters"]["launch_argument_bytes"] > 0
    else:
        assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}


@pytest.fixture(scope="module")
def probe():
    return harness.probe_only_run(CELL, 2**31 + 5, KIND.TINY)


def test_float32_reference_passes_itself(probe):
    assert failed(probe.control("float32")) == []


@pytest.mark.parametrize("precision,fault", KIND.STAND_INS, ids=[f or p for p, f in KIND.STAND_INS])
def test_stand_in_is_not_correct(probe, precision, fault):
    assert failed(probe.control(precision, fault)) != []


def test_the_stand_ins_are_the_references_faults():
    ref = harness.load_module(harness.HERE + "/reference", "ouro")
    assert [f for _, f in KIND.STAND_INS if f] == list(ref.FAULTS)
    assert KIND.STAND_INS[0] == ("bfloat16", None)


def test_the_timed_path_broken_reads_not_correct():
    result = harness.run_cell(CELL, 2**31 + 21, 1.0, False, time.perf_counter(), need_chip=False,
                              scale=KIND.TINY, hooks={"after_build": state_unchanged})
    assert result["correct"] is False
    assert result["checks"]["update_diff_rel"]["value"] == pytest.approx(1.0, abs=1e-6)


def test_a_program_without_the_model_is_refused_at_once():
    """A program from before the looped model ignores ``model_type`` and
    builds its one model from the widths: the kind refuses it before a job is
    built (here: a request that names the other model)."""
    spec = harness.load_cell(CELL)
    learner = KIND.create_request(spec["config"], 150, 1)["learner"]
    KIND.require_model(learner)
    learner["dataStructure"]["model_type"] = "olmo_hybrid"
    with pytest.raises(RuntimeError, match="does not build model_type"):
        KIND.require_model(learner)


# a size at which every leaf has the 16 elements its mean and spread are judged from
MID = {"model_type": "ouro", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 176, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "rms_norm_eps": 1e-6,
       "rope_theta": 1000000, "total_ut_steps": 4, "early_exit_threshold": 1.0}


def program_initial(seed: int) -> dict:
    import jax

    from omldm_tpu.models import ouro as model

    return jax.tree_util.tree_map(np.asarray, model.init_params(
        model.OuroConfig.from_mapping(MID), jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(harness.HERE + "/reference", "ouro")


@pytest.mark.parametrize("seed", [0, 1])
def test_the_programs_initial_weights_follow_the_references_laws(ref, seed):
    z, outside = KIND.initial_gaps(program_initial(seed), ref, MID, seed + 100)
    assert z < 4.5 and outside == 0


INITIAL_FAULTS = {
    "a_stack_of_zeros": lambda p: p["layers"].update(wv=np.zeros_like(p["layers"]["wv"])),
    "a_key_used_twice": lambda p: p["layers"].update(wk=p["layers"]["wq"].copy()),
    "a_gain_that_is_not_one": lambda p: p["layers"].update(ffn_out_norm=p["layers"]["ffn_out_norm"] * 0.5),
    "a_gate_with_a_bias": lambda p: p["gate"].update(b=p["gate"]["b"] + 1.0),
    "a_gate_ten_times_too_wide": lambda p: p["gate"].update(w=10.0 * p["gate"]["w"]),
    "the_layers_not_stacked": lambda p: p["layers"].update(wo=p["layers"]["wo"][0]),
}


@pytest.mark.parametrize("fault", sorted(INITIAL_FAULTS))
def test_initial_weights_of_another_law_are_not_correct(ref, fault):
    got = program_initial(5)
    INITIAL_FAULTS[fault](got)
    z, outside = KIND.initial_gaps(got, ref, MID, 105)
    limits = harness.load_cell(CELL)["cell"]["limits"]
    assert z > limits["initial_stat_z"] or outside > limits["initial_outside"]


def test_operation_counts_on_hand_worked_shapes():
    km = harness.load_module(harness.HERE + "/kernel_models", "ouro")
    arch = {"vocab_size": 5, "hidden_size": 4, "intermediate_size": 6, "num_hidden_layers": 2,
            "num_attention_heads": 3, "head_dim": 2, "total_ut_steps": 3}
    # a layer: q, k, v, o 4 x (4 x 6); ffn 3 x (4 x 6) = 96 + 72; the head 20; the gate 4, at two of three steps
    assert km.matmul_parameters(arch) == 3 * (2 * 168 + 20) + 2 * 4
    counts = km.launch_counts(arch, 1, 10)
    assert counts["matmul_flops"] == 6 * 1076 * 10
    assert counts["head_flops"] == 6 * 20 * 3 * 10
    # six layer applications of heads x head_dim = 6 columns
    assert counts["flash_attn_flops"] == 6 * 100 * 6 * 6
    assert counts["model_flops"] == counts["matmul_flops"] + counts["flash_attn_flops"]
    # at the cell's size: 180.6 TFLOP a launch, 39.6 of them attention, 19.8 the head's four passes
    cell = harness.load_cell(CELL)
    request = KIND.create_request(cell["config"], 8192, 0)["learner"]["dataStructure"]
    counts = km.launch_counts(request, 1, 8192)
    assert round(counts["model_flops"] / 1e12, 1) == 180.6
    assert round(counts["flash_attn_flops"] / 1e12, 1) == 39.6 and round(counts["head_flops"] / 1e12, 1) == 19.8


def test_readers_on_a_hand_made_trace():
    """Two launches of 10 ms: a flash kernel of 2 ms a launch, the head's
    loop of 1 ms holding a 0.5 ms operation of its body (the union counts the
    loop once), a rotary fusion of 0.25 ms. Arithmetic on a CPU, on made-up
    times: what the readers compute, not what a chip does."""
    from types import SimpleNamespace

    from perfbench import trace_reduce

    run = harness.probe_only_run(CELL, 1, KIND.TINY)
    kind = run.kind
    kind.scope_of = {"flash.3": "omldm.lm.flash_attn", "while.1": "omldm.lm.head_loss",
                     "fusion.2": "omldm.lm.head_loss", "fusion.5": "omldm.lm.rope"}
    ms = 1e6
    mods = [("jit_many_dense_impl(1)", 0.0, 10 * ms), ("jit_many_dense_impl(1)", 20 * ms, 10 * ms)]
    ops = []
    for start in (0.0, 20 * ms):
        ops += [("%flash.3 = bf16[2]{0} custom-call(...)", start + 1 * ms, 2 * ms),
                ("%while.1 = (f32[2]) while(...)", start + 4 * ms, 1 * ms),
                ("%fusion.2 = f32[2]{0} fusion(...)", start + 4.25 * ms, 0.5 * ms),
                ("%fusion.5 = f32[2]{0} fusion(...)", start + 6 * ms, 0.25 * ms),
                ("%other.4 = f32[2]{0} fusion(...)", start + 7 * ms, 2 * ms)]
    trace = trace_reduce.Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": mods})
    ctx = SimpleNamespace(kind=kind, trace=trace, window_ns=(0.0, 40 * ms), counters={},
                          peaks={"bf16_tflops": 100.0, "hbm_gbps": 1000.0})
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("lm_step_ms") == pytest.approx(10.0)
    assert read("flash_attn_step_share") == pytest.approx(20.0)
    assert read("head_loss_step_share") == pytest.approx(10.0)
    assert read("rope_step_share") == pytest.approx(2.5)
    counts = kind.flops
    assert read("lm_step_mfu") == pytest.approx(100 * counts["model_flops"] / (0.010 * 100e12))
    assert read("flash_attn_roofline") == pytest.approx(100 * counts["flash_attn_flops"] / 100e12 / 0.002)
    assert ctx.counters["lm_scope_ms.head_loss"] == pytest.approx(1.0)
    # a program that names no scope, as the parent's: the shares are left out
    kind.scope_of, kind._scope_ms = {}, None
    assert read("flash_attn_roofline") is None and read("head_loss_step_share") is None
    assert read("rope_step_share") is None and read("lm_step_ms") == pytest.approx(10.0)
    # and no peak for the device: no roofline
    kind.scope_of, kind._scope_ms, ctx.peaks = {"flash.3": "omldm.lm.flash_attn"}, None, {}
    assert read("flash_attn_roofline") is None
