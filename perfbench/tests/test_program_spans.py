"""The readers of the program's spans on a hand-built trace and a hand-filled
recorder: one poll of ``serve_paced`` worked out by hand. Host clock 100 s is
trace clock 5e9 ns."""

import types

import pytest

from omldm_tpu.utils import tracing
from perfbench import harness
from perfbench import program_spans as ps
from perfbench import trace_reduce

P, D = 1, 2  # producer and dispatch thread idents
T0, T1, LO, HI = 100.0, 101.0, 5.0e9, 6.0e9


def ns(t: float) -> float:
    return LO + (t - T0) * 1e9


def fill(rec, rows) -> None:
    names = {}
    for rid, name, start, end, thread, parent, key, attrs, self_s in rows:
        names[rid] = name
        rec._note(tracing.Record(rid, name, start, end, end - start if self_s is None else self_s,
                                 thread, parent, names.get(parent, ""), key, attrs), None)


POLL = [
    # id, name, start, end, thread, parent, key, attrs, self seconds (None: all of it)
    (90, "build_state", 50.0, 63.8, P, 0, None, None, 0.3),
    (1, "ingest_file", 100.100, 100.160, P, 0, None, None, 0.0091),
    (2, "parse", 100.100, 100.103, P, 1, None, None, None),
    (3, "stage", 100.103, 100.104, P, 1, None, None, None),
    (4, "forecast", 100.104, 100.112, P, 1, 77, None, 0.0005),
    (5, "decode", 100.1040, 100.1044, P, 4, None, None, None),
    (6, "quiesce", 100.1045, 100.1046, P, 4, None, None, None),
    (7, "serve", 100.1050, 100.1115, P, 4, None, None, None),
    (8, "emit", 100.1115, 100.1119, P, 4, None, None, None),
    (9, "launch", 100.120, 100.155, D, 1, None, None, 0.021),
    (10, "fit", 100.121, 100.123, D, 9, 40, {"tail": False}, None),
    (11, "fit", 100.140, 100.152, D, 9, 41, {"tail": True}, None),
    (12, "dispatcher_close", 100.130, 100.160, P, 1, None, None, None),
    (13, "compile", 100.500, 100.600, P, 0, "late", {"stage": "backend_compile_duration"}, None),
]
STEP_A = ("jit_step_fn(111)", ns(100.122), 25.0e6)
STEP_B = ("jit_step_fn(222)", ns(100.150), 19.5e6)
PREDICT = ("jit_predict_fn(333)", ns(100.106), 6.0e6)


def make_ctx(window_ns=(LO, HI), steps=(STEP_A, STEP_B)):
    # a step of the probe: begun before the window, busy until just before the poll
    before = ("jit_step_fn(111)", LO - 1e6, 0.099e9 + 1e6)
    events = sorted([before, PREDICT, *steps], key=lambda e: e[1])
    trace = trace_reduce.Trace(
        ops={"/device:TPU:0": list(events)}, modules={"/device:TPU:0": list(events)},
        spans=[("perfbench.window", LO, HI - LO), ("perfbench.sleep", LO + 1e3, 0.099e9),
               ("perfbench.handover", ns(100.0995), 0.0615e9), ("perfbench.sleep", ns(100.162), 0.8e9)],
    )
    return types.SimpleNamespace(t0=T0, t1=T1, window_ns=window_ns, trace=trace)


@pytest.fixture
def rec(monkeypatch):
    rec = tracing.Recorder()
    fill(rec, POLL)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    return rec


def read(metric, ctx):
    return harness.load_reader(metric)(ctx)


def test_recorder_only_readers(rec):
    ctx = make_ctx()
    assert read("ingest_host_busy_share.train", ctx) == pytest.approx(6.0)
    assert read("ingest_parse_busy_share.train", ctx) == pytest.approx(0.4)
    assert read("forecast_quiesce_ms", ctx) == pytest.approx(0.1)
    assert read("predict_call_ms", ctx) == pytest.approx(6.5)
    assert read("forecast_in_handover_ms", ctx) == pytest.approx(11.9)
    assert read("state_build_s", ctx) == pytest.approx(13.8)
    assert read("window_compiles.serve", ctx) == read("window_compiles.train", ctx) == 1.0
    # a quiesce that no forecast asked for is not a forecast's wait
    fill(rec, [(20, "quiesce", 100.2, 100.9, P, 0, None, None, None)])
    assert read("forecast_quiesce_ms", ctx) == pytest.approx(0.1)


def test_clock_mapping_and_ordinal_join(rec):
    ctx = make_ctx()
    cm = ps.clock_map(ctx)
    assert cm.to_ns(100.25) == pytest.approx(5.25e9) and cm.to_s(5.75e9) == pytest.approx(100.75)
    pairs = ps.joined_fits(ctx)
    assert [(fit.key, step[0]) for fit, step in pairs] == [(40, STEP_A[0]), (41, STEP_B[0])]
    assert read("tail_step_device_ms", ctx) == pytest.approx(19.5)
    # device end of each step minus the host start of its fit
    assert ps.completion_lags_ms(ctx) == pytest.approx([26.0, 29.5])


def test_handover_idle_by_program_span(rec):
    ctx = make_ctx()
    gaps = ps.handover_idle(ctx)
    # the three gaps whose middle lies in the hand-over: before the predict
    # (the producer parses), between the predict and the launch (the file's
    # own glue: no child covers it), between the two steps (the producer
    # waits in close, the dispatch thread dispatches the tail step)
    assert [(round(s, 6), label, named) for s, label, named in gaps] == [
        (0.007, "parse", True), (0.010, "ingest_file", False), (0.003, "dispatcher_close>fit", True),
    ]
    assert ps.by_label(gaps) == pytest.approx({"ingest_file": 0.010, "parse": 0.007, "dispatcher_close>fit": 0.003})
    assert read("handover_idle_unattributed_share.serve", ctx) == pytest.approx(50.0)
    # the same seconds as the breakdown of the result line gives the span
    listed = dict(map(tuple, trace_reduce.breakdown(ctx.trace, LO, HI)["idle_gaps"]))
    assert sum(s for s, _, _ in gaps) == pytest.approx(listed["perfbench.handover"])


def test_clock_map_takes_the_drains_end_for_t1(rec):
    # the open loop: the window span closes 0.8 ms after t1 was read, the
    # drain span just before it
    ctx = make_ctx(window_ns=(LO, HI + 0.8e6))
    assert ps.clock_map(ctx) is None
    ctx.trace.spans.append(("perfbench.drain", HI - 2e6, 2e6 - 5e3))
    assert ps.clock_disagreement_ns(ctx) == pytest.approx(-5e3)
    assert ps.clock_map(ctx).to_ns(100.5) == pytest.approx(5.5e9, abs=3e3)
    assert read("tail_step_device_ms", ctx) == pytest.approx(19.5)


def test_refusal_when_the_windows_ends_disagree(rec):
    ctx = make_ctx(window_ns=(LO, HI + 0.6e6))
    assert ps.clock_map(ctx) is None
    assert read("tail_step_device_ms", ctx) is None
    assert read("handover_idle_unattributed_share.serve", ctx) is None
    assert ps.completion_lags_ms(ctx) is None
    assert ps.clock_map(make_ctx(window_ns=(LO, HI + 0.4e6))) is not None
    # the recorder-only readers need no trace clock
    assert read("predict_call_ms", ctx) == pytest.approx(6.5)


def test_refusal_when_a_ring_wrapped_inside_the_window(monkeypatch):
    rec = tracing.Recorder(cap=2)
    fill(rec, POLL)
    fill(rec, [(30, "fit", 100.70, 100.71, D, 0, 42, {"tail": False}, None)])
    monkeypatch.setattr(tracing, "RECORDER", rec)
    ctx = make_ctx()
    assert rec.dropped("fit") == 1 and ps.in_window(ctx, "fit") is None
    assert read("tail_step_device_ms", ctx) is None
    assert read("handover_idle_unattributed_share.serve", ctx) is None
    # a ring whose oldest record began before the window has dropped
    # nothing of the window
    late = types.SimpleNamespace(t0=100.145, t1=T1, window_ns=(ns(100.145), HI), trace=ctx.trace)
    assert [r.key for r in ps.in_window(late, "fit")] == [42]


def test_refusal_when_fits_and_step_programs_differ_in_number(rec):
    ctx = make_ctx(steps=(STEP_A,))
    assert ps.joined_fits(ctx) is None
    assert read("tail_step_device_ms", ctx) is None
    # a trace with no device in it (the CPU self-check) joins nothing either
    ctx.trace = trace_reduce.Trace(spans=ctx.trace.spans)
    assert read("tail_step_device_ms", ctx) is None
    assert read("handover_idle_unattributed_share.serve", ctx) is None


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    monkeypatch.delattr(tracing, "RECORDER")
    ctx = make_ctx()
    with open(harness.os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = harness.json.load(f)
    # the readers that take their number from the program's spans
    def reads_spans(name):
        with open(harness.os.path.join(harness.HERE, "metrics", name + ".py")) as f:
            return "program_spans" in f.read()

    new = [m["name"] for m in bench["per_layer"] if reads_spans(m["name"])]
    assert len(new) == 10
    for metric in new:
        assert read(metric, ctx) is None, metric
