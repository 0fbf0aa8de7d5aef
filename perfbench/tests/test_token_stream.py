"""The kind ``token_stream`` (a language model trained online on token rows)
through the harness on the CPU at its ``TINY`` size: the sound run reads
``correct``; the reference in the program's place with parameters and
recurrent state in bfloat16, and with each of the six faults planted, does
not; a small leaf left unmoved or moved the wrong way fails the leaf's own
limit beside a whole-model number that passes; initial weights drawn from
another law than the reference's fail theirs; the readers' operation counts
on hand-worked shapes."""

import copy
import time

import numpy as np
import pytest

import conftest  # noqa: F401  (the CPU backend and the path)
from perfbench import harness

CELL = "olmo_hybrid_7b_l4.train_sat"
KIND = harness.load_cell(CELL)["kind"]


def failed(checks: dict) -> list:
    return [name for name, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    seen = []
    result = harness.run_cell(CELL, 2**31 + 33, 1.0, trace, time.perf_counter(), need_chip=False,
                              scale=KIND.TINY, hooks={"finished": lambda run, result: seen.append(run)})
    assert result["correct"] is True and result["failed"] == 0 and failed(result["checks"]) == []
    assert list(result["checks"]) == ["rows_lost", "forecasts_bad", "answers_wrong", "loss_gap",
                                      "first_update_norm_gap", "update_diff_rel", "leaf_update_diff_rel",
                                      "initial_stat_z", "initial_outside"]
    # the probe: 8 launches of one row, 4 forecasts answered
    assert result["counters"]["probe_answers"] == 4
    assert result["counters"]["fitted"] == 8 + result["counters"]["window_rows"]
    if trace:
        # the compiled launch names the model's parts
        [run] = seen
        assert {"omldm.lm.delta_rule", "omldm.lm.flash_attn", "omldm.lm.ffn", "omldm.lm.linear_proj",
                "omldm.lm.head_loss", "omldm.lm.sgd"} <= set(run.kind.scope_of.values())
        # the dense route's producer wrote its span inside the window
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


@pytest.mark.parametrize("leaf", [(0, "conv_q"), (1, "conv_v"), (3, "w_gate")])
@pytest.mark.parametrize("moved,reads", [(0.0, 1.0), (-1.0, 2.0)], ids=["unmoved", "wrong_way"])
def test_one_leaf_at_fault_fails_the_leaf_limit_alone(probe, leaf, moved, reads):
    """The sound reference's final weights with ONE leaf left at its initial
    value, or moved by the negated update: the whole-model number stays under
    its limit (the leaf is a small part of the update), the leaf's own reads
    1 or 2."""
    probe.control("float32")
    sound = probe.kind._sound
    layer, name = leaf
    got = copy.deepcopy(sound.final)
    first, last = sound.initial["layers"][layer][name], sound.final["layers"][layer][name]
    got["layers"][layer][name] = first + moved * (last - first)
    limits = probe.kind.cell["limits"]
    assert KIND.distance(got, sound.final) / KIND.distance(sound.final, sound.initial) < limits["update_diff_rel"]
    gaps = KIND.leaf_update_gaps(got, sound.final, sound.initial)
    gap, share = gaps[f"['layers'][{layer}]['{name}']"]
    assert share >= KIND.LEAF_CHANGE_FLOOR and gap == pytest.approx(reads, rel=1e-4) and gap > limits["leaf_update_diff_rel"]
    assert sum(g > limits["leaf_update_diff_rel"] for g, _ in gaps.values()) == 1


# a size at which every leaf has the 16 elements its mean and spread are judged from
MID = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
       "layer_types": ["linear_attention"] * 3 + ["full_attention"], "linear_num_key_heads": 16,
       "linear_num_value_heads": 16, "linear_key_head_dim": 8, "linear_value_head_dim": 16,
       "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6}


def program_initial(seed: int) -> dict:
    import jax

    from omldm_tpu.models import olmo_hybrid as model

    return jax.tree_util.tree_map(np.asarray, model.init_params(
        model.OlmoHybridConfig.from_mapping(MID), jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def ref():
    return harness.load_module(harness.HERE + "/reference", "olmo_hybrid")


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_programs_initial_weights_follow_the_references_laws(ref, seed):
    z, outside = KIND.initial_gaps(program_initial(seed), ref, MID, seed + 100)
    assert z < 4.5 and outside == 0


def zero(leaf):
    return np.zeros_like(leaf)


def with_nan(leaf):
    leaf = leaf.copy()
    leaf[3, 5] = np.nan
    return leaf


INITIAL_FAULTS = {
    "a_matrix_of_zeros": lambda p: p["layers"][1].update(wv=zero(p["layers"][1]["wv"])),
    "a_small_leaf_of_zeros": lambda p: p["layers"][0].update(wa=zero(p["layers"][0]["wa"])),
    "twice_the_scale": lambda p: p["layers"][2].update(w_up=2 * p["layers"][2]["w_up"]),
    "a_tenth_more_scale": lambda p: p.update(embed=1.1 * p["embed"]),
    "a_key_used_twice": lambda p: p["layers"][3].update(wk=p["layers"][3]["wq"].copy()),
    "one_law_for_two_leaves": lambda p: p["layers"][0].update(wg=0.5 * p["layers"][0]["wv"] + 0.01),
    "A_log_in_the_wrong_range": lambda p: p["layers"][0].update(A_log=p["layers"][0]["A_log"] + np.log(2.0)),
    "dt_bias_as_the_step_itself": lambda p: p["layers"][1].update(dt_bias=np.log1p(np.exp(p["layers"][1]["dt_bias"]))),
    "conv_taps_unscaled": lambda p: p["layers"][2].update(conv_k=2 * p["layers"][2]["conv_k"]),
    "a_gain_that_is_not_one": lambda p: p["layers"][3].update(q_norm=p["layers"][3]["q_norm"] * 0.0),
    "another_shape": lambda p: p.update(head=p["head"][:, :-1]),
    "not_a_number": lambda p: p["layers"][1].update(w_down=with_nan(p["layers"][1]["w_down"])),
}


@pytest.mark.parametrize("fault", sorted(INITIAL_FAULTS))
def test_initial_weights_of_another_law_are_not_correct(ref, fault):
    got = program_initial(5)
    INITIAL_FAULTS[fault](got)
    z, outside = KIND.initial_gaps(got, ref, MID, 105)
    limits = harness.load_cell(CELL)["cell"]["limits"]
    assert z > limits["initial_stat_z"] or outside > limits["initial_outside"]


def test_a_run_on_faulty_initial_weights_is_not_correct(monkeypatch):
    """Through the harness: the model's own ``init_params`` broken underneath
    (every ``A_log`` nought: a decay as if ``uniform(1, 16)`` were 1), the
    rest of the run sound, the reference starting from those same weights."""
    from omldm_tpu.models import olmo_hybrid as model

    real = model.init_params

    def broken(cfg, rng):
        params = real(cfg, rng)
        for layer in params["layers"]:
            if "A_log" in layer:
                layer["A_log"] = layer["A_log"] * 0.0 - 1.0
        return params

    monkeypatch.setattr(model, "init_params", broken)
    result = harness.run_cell(CELL, 2**31 + 9, 1.0, False, time.perf_counter(), need_chip=False, scale=KIND.TINY)
    assert result["correct"] is False
    assert [k for k, c in result["checks"].items() if c["value"] > c["limit"]] == ["initial_outside"]


def state_unchanged(system):
    import jax

    trainer = system.bridge.trainer
    real = trainer.step_many_dense

    def step(xs, ys):
        kept = jax.tree_util.tree_map(lambda leaf: leaf + 0, trainer.state)  # the real step donates
        out = real(xs, ys)
        trainer.state = kept
        return out

    trainer.step_many_dense = step


def test_the_timed_path_broken_reads_not_correct():
    result = harness.run_cell(CELL, 2**31 + 21, 1.0, False, time.perf_counter(), need_chip=False,
                              scale=KIND.TINY, hooks={"after_build": state_unchanged})
    assert result["correct"] is False
    assert result["checks"]["update_diff_rel"]["value"] == pytest.approx(1.0, abs=1e-6)


def test_operation_counts_on_hand_worked_shapes():
    km = harness.load_module(harness.HERE + "/kernel_models", "olmo_hybrid")
    arch = {"vocab_size": 5, "hidden_size": 4, "intermediate_size": 6, "layer_types": ["linear_attention", "full_attention"],
            "linear_num_value_heads": 2, "linear_key_head_dim": 1, "linear_value_head_dim": 2}
    # linear: q, k 2 x 4 x 2; v, g, o 3 x 4 x 4; a, b 2 x 4 x 2; ffn 3 x 4 x 6 = 16 + 48 + 16 + 72
    # full: 4 x 16 + 72; head 20
    assert km.matmul_parameters(arch) == 152 + 136 + 20
    counts = km.launch_counts(arch, 1, 10)
    assert counts["matmul_flops"] == 6 * 308 * 10
    assert counts["delta_rule_flops"] == 3 * 7 * 2 * 1 * 2 * 10
    assert counts["delta_rule_bytes"] == 3 * (2 + 4 + 2) * 4 * 2 * 10
    assert counts["flash_attn_flops"] == 6 * 100 * 4
    assert counts["model_flops"] == counts["matmul_flops"] + counts["delta_rule_flops"] + counts["flash_attn_flops"]


def test_readers_on_a_hand_made_trace():
    """Two launches of 10 ms; under the delta-rule scope a loop of 4 ms that
    holds a 1 ms operation of its body (the union counts the loop once) and,
    in the second launch only, 2 ms more; a flash kernel of 0.5 ms a launch.
    Arithmetic on a CPU, on made-up times: what the readers compute, not what
    a chip does."""
    from types import SimpleNamespace

    from perfbench import trace_reduce

    run = harness.probe_only_run(CELL, 1, KIND.TINY)
    kind = run.kind
    kind.scope_of = {"while.1": "omldm.lm.delta_rule", "fusion.2": "omldm.lm.delta_rule",
                     "fusion.9": "omldm.lm.delta_rule", "flash.3": "omldm.lm.flash_attn"}
    ms = 1e6
    mods = [("jit_many_dense_impl(1)", 0.0, 10 * ms), ("jit_predict_fn(2)", 11 * ms, 1 * ms),
            ("jit_many_dense_impl(1)", 20 * ms, 10 * ms)]
    ops = [("%while.1 = (f32[2]) while(...)", 1 * ms, 4 * ms), ("%fusion.2 = f32[2]{0} fusion(...)", 2 * ms, 1 * ms),
           ("%flash.3 = bf16[2]{0} custom-call(...)", 6 * ms, 0.5 * ms), ("%other.4 = f32[2]{0} fusion(...)", 7 * ms, 2 * ms),
           ("%while.1 = (f32[2]) while(...)", 21 * ms, 4 * ms), ("%fusion.9 = f32[2]{0} fusion(...)", 26 * ms, 2 * ms),
           ("%flash.3 = bf16[2]{0} custom-call(...)", 28 * ms, 0.5 * ms)]
    trace = trace_reduce.Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": mods})
    ctx = SimpleNamespace(kind=kind, trace=trace, window_ns=(0.0, 40 * ms), counters={},
                          peaks={"bf16_tflops": 100.0, "hbm_gbps": 1000.0})
    read = lambda name: harness.load_reader(name)(ctx)
    assert read("lm_step_ms") == pytest.approx(10.0)
    assert read("delta_rule_ms") == pytest.approx((4 + 6) / 2)
    assert read("delta_rule_step_share") == pytest.approx(50.0)
    assert read("flash_attn_step_share") == pytest.approx(5.0)
    counts = kind.flops
    assert read("lm_step_mfu") == pytest.approx(100 * counts["model_flops"] / (0.010 * 100e12))
    least = max(counts["delta_rule_bytes"] / 1000e9, counts["delta_rule_flops"] / 100e12)
    assert read("delta_rule_roofline") == pytest.approx(100 * least / 0.005)
    assert ctx.counters["lm_scope_ms.delta_rule"] == pytest.approx(5.0)
    # a program that names no scope, as the parent's: the shares are left out
    kind.scope_of, kind._scope_ms = {}, None
    assert read("delta_rule_ms") is None and read("delta_rule_roofline") is None
    assert read("flash_attn_step_share") is None and read("lm_step_ms") == pytest.approx(10.0)
    # and one without the recorder, or whose producer wrote no ``parse_stage``: left out
    ctx.t0, ctx.t1 = time.perf_counter(), time.perf_counter() + 1.0
    assert read("dense_parse_stage_busy_share.train") is None
