"""The four readers of ``setup_s``'s parts (``setup_compile_s``,
``setup_compile_misses``, ``deploy_s``, ``setup_serve_s``) on a hand-filled
recorder whose window opens at 100 s, and in a traced run of every cell at
its kind's tiny size on the CPU."""

import time

import pytest

from omldm_tpu.utils import tracing
from perfbench import harness
from test_program_spans import fill

P, D = 1, 2  # producer and dispatch thread idents
T0 = 100.0
TRACE, LOWER, BACKEND = "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration", "backend_compile_duration"
READERS = ("setup_compile_s", "setup_compile_misses", "deploy_s", "setup_serve_s")
CELLS = ("criteo_pa_2e28.train_sat", "criteo_pa_2e28.serve_paced",
         "olmo_hybrid_7b_l4.train_sat", "ouro_2_6b_l12.train_sat")


def compile_row(rid, start, end, parent, stage, cache=None, thread=P):
    attrs = {"stage": stage}
    if cache is not None:
        attrs["cache"] = cache
    return (rid, "compile", start, end, thread, parent, "jit(f)", attrs, None)


SETUP = [
    # id, name, start, end, thread, parent, key, attrs, self seconds (None: all of it)
    (1, "deploy", 10.0, 16.0, P, 0, 0, None, None),
    (2, "build_state", 11.0, 15.0, P, 1, None, None, None),
    # the harness's own marker: no program span open
    compile_row(3, 5.0, 7.0, 0, BACKEND, "miss"),
    # under deploy: a trace with a program traced inside it, then the rest
    compile_row(4, 12.0, 13.0, 1, TRACE),
    compile_row(5, 12.2, 12.6, 1, TRACE),
    compile_row(6, 13.0, 13.5, 1, LOWER),
    compile_row(7, 13.5, 14.5, 1, BACKEND, "miss"),
    (8, "forecast", 40.0, 45.0, P, 0, None, None, None),
    (9, "serve", 41.0, 44.0, P, 8, None, None, None),
    compile_row(10, 41.5, 43.5, 9, BACKEND, "hit"),
    (11, "forecast", 50.0, 50.5, P, 0, None, None, None),
    (12, "serve", 50.1, 50.35, P, 11, None, None, None),
    # the dispatch thread, under a span it adopted
    compile_row(13, 60.0, 61.0, 8, BACKEND, "off", thread=D),
    # the window: left out
    (14, "deploy", 100.5, 101.0, P, 0, 1, None, None),
    (15, "serve", 100.2, 100.3, P, 0, None, None, None),
    compile_row(16, 100.4, 100.9, 1, BACKEND, "miss"),
]


@pytest.fixture
def rec(monkeypatch):
    rec = tracing.Recorder()
    fill(rec, SETUP)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    return rec


class Ctx:
    t0 = T0
    n_probe_forecasts = 2


def read(metric, ctx=Ctx):
    return harness.load_reader(metric)(ctx)


def test_readers_take_the_programs_set_up_alone(rec):
    # 12 to 14.5 under deploy (the inner trace counted once), 41.5 to 43.5
    # under serve, 60 to 61 on the dispatch thread; the harness's 2 s and
    # the window's 0.5 s left out
    assert read("setup_compile_s") == pytest.approx(2.5 + 2.0 + 1.0)
    assert read("setup_compile_misses") == 1.0
    assert read("deploy_s") == pytest.approx(6.0)
    # the serves less the 2 s compile under the first
    assert read("setup_serve_s") == pytest.approx(1.0 + 0.25)


def test_a_wrapped_ring_reads_nothing(monkeypatch):
    rec = tracing.Recorder(cap=4)
    fill(rec, SETUP)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    assert rec.dropped("compile") and not rec.dropped("deploy")
    assert read("setup_compile_s") is None and read("setup_compile_misses") is None
    assert read("deploy_s") == pytest.approx(6.0)


def test_a_program_without_the_cache_answer_or_the_spans(monkeypatch):
    """The parent of PR 38: compile records with no ``cache``, no
    ``deploy`` span, no ``serve`` span on the dense route."""
    rec = tracing.Recorder()
    fill(rec, [r[:7] + ({"stage": r[7]["stage"]},) + r[8:] for r in SETUP if r[1] == "compile"])
    monkeypatch.setattr(tracing, "RECORDER", rec)
    assert read("setup_compile_s") == pytest.approx(5.5)
    assert read("setup_compile_misses") is None
    assert read("deploy_s") is None and read("setup_serve_s") is None

    class Unasked(Ctx):
        n_probe_forecasts = 0

    assert read("setup_serve_s", Unasked) == 0.0  # true of any program
    monkeypatch.setattr(tracing, "RECORDER", None)
    assert all(read(m) is None for m in READERS)


def test_another_runs_records_are_left_out(monkeypatch):
    """A process of several runs: what began before this run's deploy (at
    49 s) is another run's, and a ring that wrapped before then does not
    matter."""
    rec = tracing.Recorder(cap=4)
    fill(rec, SETUP + [(20, "deploy", 49.0, 49.5, P, 0, 2, None, None)])
    monkeypatch.setattr(tracing, "RECORDER", rec)
    assert rec.dropped("compile") and rec.records("compile")[0].start < 49.0
    assert read("setup_compile_s") == pytest.approx(1.0)  # the dispatch thread's, at 60
    assert read("setup_compile_misses") == 0.0
    assert read("setup_serve_s") == pytest.approx(0.25)
    assert read("deploy_s") == pytest.approx(0.5)


def test_no_backend_compile_reads_no_miss(monkeypatch):
    rec = tracing.Recorder()
    fill(rec, [r for r in SETUP if r[1] == "compile" and r[7]["stage"] != BACKEND])
    monkeypatch.setattr(tracing, "RECORDER", rec)
    assert read("setup_compile_misses") == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_its_set_up(cell, monkeypatch):
    """A traced run at the tiny size, on a recorder of its own (one run a
    process, as the driver runs it): every reader reads a number, and the
    parts nest as they must."""
    fresh = tracing.Recorder(caps=tracing.RECORDER.caps)
    for name, value in (("RECORDER", fresh), ("span", fresh.span),
                        ("current", fresh.current), ("adopt", fresh.adopt)):
        monkeypatch.setattr(tracing, name, value)
    spec = harness.load_cell(cell)
    result = harness.run_cell(cell, 2**31 + 38, 1.0, True, time.perf_counter(), need_chip=False,
                              scale=spec["kind"].TINY)
    got = {m: result["metrics"][m]["value"] for m in READERS + ("state_build_s",)}
    assert got["setup_compile_misses"] >= 0.0
    asked = result["counters"]["probe_answers"]
    assert (got["setup_serve_s"] > 0.0) == (asked > 0) and fresh.count("serve") >= asked
    assert got["state_build_s"] <= got["deploy_s"] <= result["setup_split"]["build_job_s"]
    assert got["setup_compile_s"] + got["setup_serve_s"] < result["end_to_end_in_traced_run"]["setup_s"]
