"""``trace_reduce.load`` keeps the program's ``omldm.*`` host spans beside the
harness's ``perfbench.*``, so ``breakdown.idle_gaps`` names the program's span
where one covers a gap; the window is still the harness's own span."""

import time

from conftest import SCALE
from perfbench import harness
from perfbench import trace_reduce as tr

DEV = "/device:TPU:0"


def trace_of(spans):
    # the device works 0-10 and 50-100 of a window of 100: one gap, 10-50
    ops = {DEV: [("%a = f32[1]{0} add(x)", 0.0, 10.0), ("%b = f32[1]{0} add(y)", 50.0, 50.0)]}
    return tr.Trace(ops=ops, modules={DEV: []}, spans=sorted(spans, key=lambda e: e[1]))


def test_a_gap_is_named_by_the_innermost_span_the_programs_where_one_covers_it():
    harness_only = trace_of([("perfbench.window", 0.0, 100.0), ("perfbench.handover", 5.0, 60.0)])
    assert tr.breakdown(harness_only, 0.0, 100.0)["idle_gaps"] == [["perfbench.handover", 40e-9]]
    both = trace_of([("perfbench.window", 0.0, 100.0), ("perfbench.handover", 5.0, 60.0),
                     ("omldm.ingest_file", 6.0, 58.0), ("omldm.parse", 20.0, 15.0)])
    assert tr.breakdown(both, 0.0, 100.0)["idle_gaps"] == [["omldm.parse", 40e-9]]
    assert tr.host_activity(both, 45.0) == "omldm.ingest_file"
    assert tr.host_activity(both, 99.0) == "host.outside_harness_spans"


def test_the_window_is_the_harness_own_span():
    spans = [("omldm.build_state", 0.0, 500.0), ("perfbench.handover", 600.0, 50.0), ("perfbench.drain", 700.0, 10.0)]
    assert tr.window_of(trace_of(spans)) == (600.0, 710.0)
    assert tr.window_of(trace_of(spans + [("perfbench.window", 590.0, 130.0)])) == (590.0, 720.0)


def test_a_traced_run_keeps_both_families_of_spans():
    seen = []
    harness.run_cell("criteo_pa_2e28.serve_paced", 2**31 + 41, 1.0, True, time.perf_counter(), need_chip=False,
                     scale=SCALE, hooks={"finished": lambda run, result: seen.append((run, result))})
    [(run, result)] = seen
    names = {name for name, _start, _dur in run.trace.spans}
    assert {"perfbench.window", "perfbench.handover", "perfbench.drain"} <= names
    assert {"omldm.ingest_file", "omldm.parse", "omldm.forecast", "omldm.fit"} <= names
    assert all(name.startswith(("perfbench.", "omldm.")) for name in names)
    # on the CPU no operation runs on a device plane: the whole window is one gap
    assert len(result["breakdown"]["idle_gaps"]) == 1
