"""One run of one cell: set-up, the measured window, the comparison, the line.

Driven by data: the cell, its configuration, the configuration's kind and the
cell's per-layer metrics are found by the names in ``BENCHMARK.json``
(``perfbench/workloads/<cell>.json``, the configuration's ``file``,
``perfbench/kinds/<kind>.py`` for the ``kind`` that file names,
``perfbench/metrics/<metric>.py``). Adding one of them never edits this file.
See ``perfbench/README.md``.

What this file holds is what every kind of deployment shares: the look for
the chip, the set-up split and its clock, the closed-loop and the open-loop
window, pacing and lateness, tracing, the latency of a forecast from its
creation time on the schedule, ``attempted`` and ``failed``, the result line.
What a record is, how the system under test is built and driven, what is kept
of a prediction and of the probe, and the comparison that decides ``correct``
are the kind's. The generator's schedule, the clock, the trace reduction and
the peaks are the benchmark's own.
"""

from __future__ import annotations

import argparse
import copy
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional

import numpy as np

from perfbench import generator as gen
from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NO_CHIP_EXIT = 3


class NoChip(RuntimeError):
    pass


# --- data files --------------------------------------------------------------


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """``<directory>/<name>.py``, loaded by its file name, once a process."""
    path = os.path.abspath(os.path.join(directory, name + ".py"))
    module_name = "perfbench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    if module_name in sys.modules:
        return sys.modules[module_name]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {name}.py in {directory}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module  # dataclasses look their module up there
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[module_name]
        raise
    return module


def load_kind(config: dict, here: str = HERE):
    """The module of the kind a configuration's file names. No default: a
    configuration that names none, or one that has no file, is an error."""
    kind = config.get("kind")
    if not kind:
        raise ValueError(f"configuration {config.get('name')!r} names no kind")
    return load_module(os.path.join(here, "kinds"), kind)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """BENCHMARK.json's entry for ``workload`` with its configuration, its
    kind, its cell file and the metrics that list it. ``root`` holds
    ``BENCHMARK.json``; the first of its ``paths`` holds ``workloads/``,
    ``kinds/``, ``metrics/`` and ``reference/``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    here = os.path.normpath(os.path.join(root, bench["paths"][0]))
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[0]
    [cfg_entry] = [c for c in bench["configs"] if c["name"] == entry["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    cell = _load_json(os.path.join(here, "workloads", workload + ".json"))

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config": config,
        "cell": cell,
        "here": here,
        "kind": load_kind(config, here),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


def scaled(spec: dict, scale: dict) -> dict:
    """``spec`` at a size a CPU test can hold (the self-check and the tests
    under ``perfbench/tests``; never a chip run): the configuration as its
    kind scales it, the traffic's parameters as ``scale["traffic"]`` has
    them."""
    spec = dict(spec, config=spec["kind"].scaled(spec["config"], scale),
                cell=copy.deepcopy(spec["cell"]))
    spec["cell"]["traffic"].update(scale.get("traffic", {}))
    return spec


def load_reader(metric: str, here: str = HERE) -> Callable:
    return load_module(os.path.join(here, "metrics"), metric).read


# --- the run -----------------------------------------------------------------


class Stamps:
    """The prediction sink: what the kind keeps of every answer (its
    forecast's id and a value) with the host clock at emission."""

    def __init__(self, keep: Callable):
        self.keep = keep
        self.rows: List[tuple] = []  # (id, value, t)

    def __call__(self, pred) -> None:
        self.rows.append((*self.keep(pred), time.perf_counter()))


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def require_chip(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform}"
        )
    return devices


class Run:
    """State of one run, handed to the per-layer readers as ``ctx``. A name
    the run does not hold is looked up on its kind (``ctx.kind``), so a kind
    gives its readers whatever numbers they need (a batch, the operations of
    one row) without an edit here."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool):
        self.spec, self.seed, self.seconds, self.tracing = spec, seed, seconds, trace
        self.config, self.cell = spec["config"], spec["cell"]
        self.traffic = self.cell["traffic"]
        self.setup_split: Dict[str, float] = {}
        self.counters: Dict[str, float] = {}
        self.extras: Dict[str, float] = {}
        self.trace: Optional[trace_reduce.Trace] = None
        self.window_ns = (0.0, 0.0)
        self.device_kind = ""
        self.peaks: dict = {}
        self.memory_peak_bytes = 0
        self.late_s: List[float] = []
        self.handover_s: List[float] = []
        self.kind = spec["kind"].Kind(self.config, self.cell, seed, spec["here"])
        self.stamps = Stamps(self.kind.keep)

    def __getattr__(self, name: str):
        kind = self.__dict__.get("kind")
        if kind is None:
            raise AttributeError(name)
        return getattr(kind, name)

    # -- set-up ---------------------------------------------------------------

    def make_probe(self) -> None:
        """The probe files' event lists, from the cell's file."""
        files = self.cell["probe"]["files"]
        self.n_probe_rows = sum(f["train_rows"] for f in files)
        self.n_probe_forecasts = sum(f["forecasts"] for f in files)
        self.probe_plans, row, fc = [], 0, 0
        for f in files:
            self.probe_plans.append(gen.spread_forecasts(f["train_rows"], f["forecasts"], fc, row))
            row += f["train_rows"]
            fc += f["forecasts"]

    def make_records(self, n_window_forecasts: int) -> None:
        """The run's forecast records (the probe's first) and the probe's
        training records, drawn and rendered by the kind."""
        self.forecast_lines = self.kind.forecast_records(self.n_probe_forecasts + n_window_forecasts)
        self.probe_lines = self.kind.training_records(self.n_probe_rows)

    def make_files(self) -> None:
        """Every file of the run, rendered into memory during set-up."""
        t = self.traffic
        if t["kind"] == "closed_loop":
            self.make_records(0)
            part_rows = int(t["part_rows"])
            if not self.n_pool or self.n_pool % part_rows:
                raise ValueError("part_rows must divide the pool")

            def part(k: int) -> gen.MemFile:
                plan = gen.train_plan(k * part_rows, part_rows, self.n_pool)
                return gen.MemFile(gen.file_pieces(plan, self.kind.pool(), None), f"part{k}")

            with ThreadPoolExecutor(4) as ex:
                self.part_files = list(ex.map(part, range(self.n_pool // part_rows)))
            self.part_rows = part_rows
            self.window_plans = []
        elif t["kind"] == "open_loop":
            rate_fc = float(t["forecasts_per_s"])
            times = gen.arrival_times(self.seed, rate_fc, self.seconds, int(t["arrivals_seed"]),
                                      float(t["poll_ms"]) / 1e3)
            self.make_records(len(times))
            # a cell with no training rows names no rate and its kind no pool
            self.window_plans = gen.paced_plans(
                float(t.get("train_rows_per_s", 0)), times, self.seconds,
                float(t["poll_ms"]) / 1e3, self.n_pool,
                first_forecast=self.n_probe_forecasts,
            )
            pool = self.kind.pool() if self.n_pool else None
            self.slice_files = [
                gen.MemFile(gen.file_pieces(p, pool, self.forecast_lines), f"slice{k}")
                for k, p in enumerate(self.window_plans)
            ]
        else:
            raise ValueError(f"unknown traffic kind {t['kind']!r}")
        self.probe_files = [
            gen.MemFile(gen.file_pieces(p, self.probe_lines, self.forecast_lines), f"probe{k}")
            for k, p in enumerate(self.probe_plans)
        ]

    def drive_probe(self, system) -> None:
        """The first files of the run, through the window's own call. They
        compile every shape the window uses (set-up time), and what the kind
        keeps after each is what its reference is compared with."""
        import jax

        for mf in self.probe_files:
            system.hand_over(mf.path)
            system.wait()
            self.kind.after_probe_file(system)
        self.n_probe_answers = len(self.stamps.rows)
        # the closed loop's back-pressure marker, compiled here and not in
        # the window
        jax.block_until_ready(system.marker())
        self.counters["peak_bytes_after_probe"] = peak_bytes(jax.devices()[0])

    # -- windows --------------------------------------------------------------

    def window_closed_loop(self, system) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        in_flight: List = []
        ahead = int(self.traffic["files_in_flight"])
        offered = k = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.seconds:
            with TraceAnnotation("perfbench.handover"):
                system.hand_over(self.part_files[k % len(self.part_files)].path)
            offered += self.part_rows
            k += 1
            # back-pressure: a value that exists once the device has finished
            # this file; wait for the file before the previous one
            in_flight.append(system.marker())
            if len(in_flight) >= ahead:
                with TraceAnnotation("perfbench.wait_device"):
                    jax.block_until_ready(in_flight.pop(0))
        with TraceAnnotation("perfbench.drain"):
            system.wait()
        self.t0, self.t1 = t0, time.perf_counter()
        self.counters.update(window_rows=offered, window_forecasts=0, handovers=k)

    def window_open_loop(self, system) -> None:
        from jax.profiler import TraceAnnotation

        offered = 0
        t0 = time.perf_counter()
        for plan, mf in zip(self.window_plans, self.slice_files):
            wait = t0 + plan.due - time.perf_counter()
            if wait > 0:
                with TraceAnnotation("perfbench.sleep"):
                    time.sleep(wait)
            start = time.perf_counter()
            self.late_s.append(start - (t0 + plan.due))
            with TraceAnnotation("perfbench.handover"):
                system.hand_over(mf.path)
            self.handover_s.append(time.perf_counter() - start)
            offered += plan.n_train
        with TraceAnnotation("perfbench.drain"):
            system.wait()
        self.t0, self.t1 = t0, time.perf_counter()
        n_fc = sum(p.n_forecast for p in self.window_plans)
        self.counters.update(
            window_rows=offered, window_forecasts=n_fc, handovers=len(self.window_plans),
            handover_p50_ms=percentile(self.handover_s, 50) * 1e3,
            handover_p95_ms=percentile(self.handover_s, 95) * 1e3,
            handover_max_ms=max(self.handover_s) * 1e3,
            late_max_ms=max(self.late_s) * 1e3,
            late_slices=sum(x > 1e-3 for x in self.late_s),
        )

    def run_window(self, system) -> None:
        from jax.profiler import TraceAnnotation

        window = (self.window_closed_loop if self.traffic["kind"] == "closed_loop"
                  else self.window_open_loop)
        if not self.tracing:
            window(system)
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with TraceAnnotation(trace_reduce.WINDOW_SPAN):
                window(system)
        finally:
            jax.profiler.stop_trace()

    def read_trace(self) -> None:
        import glob

        try:
            [path] = glob.glob(os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            self.trace = trace_reduce.load(path)
            self.window_ns = trace_reduce.window_of(self.trace)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # -- the comparison -------------------------------------------------------

    def control(self, precision: str = "float32", fault: Optional[str] = None) -> Dict[str, dict]:
        """The kind's control over this run's probe files."""
        return self.kind.control(self.probe_plans, precision, fault)

    def latencies_ms(self, answers: List[tuple]) -> List[float]:
        """Of every forecast of the window: the host clock at which its first
        answer reached the sink, minus its creation time on the schedule. An
        answer that never came waits until the window closed."""
        first = {}
        for fid, _value, t in answers[self.n_probe_answers:]:
            first.setdefault(fid, t)
        out = []
        for plan in self.window_plans:
            for i in np.nonzero(plan.kind == gen.FORECAST)[0]:
                t = first.get(int(plan.index[i]), self.t1)
                out.append((t - (self.t0 + float(plan.created[i]))) * 1e3)
        return out


def probe_only_run(workload: str, seed: int, scale: Optional[dict] = None, root: str = ROOT) -> Run:
    """A run's probe records and nothing else: no job, no device, no window.
    What ``Run.control`` needs (the control script and the tests)."""
    spec = load_cell(workload, root)
    if scale:
        spec = scaled(spec, scale)
    run = Run(spec, seed, 1.0, False)
    run.make_probe()
    run.make_records(0)
    return run


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all values (q in 0..100)."""
    v = sorted(values)
    k = max(int(np.ceil(q / 100.0 * len(v))) - 1, 0)
    return v[k]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t_process: float,
             need_chip: bool = True, scale: Optional[dict] = None,
             hooks: Optional[dict] = None, root: str = ROOT) -> dict:
    """One run. ``need_chip=False``, ``scale`` and ``root`` are for the CPU
    self-check and the tests under ``perfbench/tests``. ``hooks``:
    ``after_build(system)`` lets those tests break the timed path underneath;
    ``finished(run, result)`` hands a report the run the readers saw."""
    hooks = hooks or {}
    spec = load_cell(workload, root)
    if scale:
        spec = scaled(spec, scale)
    run = Run(spec, seed, seconds, trace)

    import jax

    from omldm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache("on")
    # store the sub-second programs too: a warm run should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = require_chip(spec["chips"]) if need_chip else jax.devices()
    run.device_kind = devices[0].device_kind
    peaks = _load_json(os.path.join(HERE, "peaks.json"))
    if need_chip and run.device_kind not in peaks:
        raise RuntimeError(f"no published peaks for device kind {run.device_kind!r}")
    run.peaks = peaks.get(run.device_kind, {})
    mark = lambda name: run.setup_split.__setitem__(name, time.perf_counter() - t_process)
    mark("reach_chip_s")

    system = run.kind.build(run.stamps)
    system.wait()
    mark("build_job_s")
    if "after_build" in hooks:
        hooks["after_build"](system)

    run.make_probe()
    run.make_files()
    mark("generate_s")
    if trace:
        run.extras.update(run.kind.traced_extras())
        mark("traced_extras_s")
    run.drive_probe(system)
    mark("warm_probe_s")

    # the harness's own heap (plans, rows, rendered lines) is out of the
    # collector's way during the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    run.run_window(system)
    window_s = run.t1 - run.t0

    # where every row offered went, as the system says when it is closed
    # (which may use the device: the peak is read on both sides)
    run.counters["peak_bytes_after_window"] = peak_bytes(devices[0])
    done = system.close()
    run.memory_peak_bytes = peak_bytes(devices[0])
    answers = list(run.stamps.rows)
    window_rows = int(run.counters["window_rows"])
    counts = {
        "offered_rows": run.n_probe_rows + window_rows, **done,
        "offered_forecasts": run.n_probe_forecasts + int(run.counters["window_forecasts"]),
        "probe_answers": run.n_probe_answers,
    }
    run.counters.update(counts)
    # the program's state is freed before the reference runs, so that a
    # reference may use the device
    del system
    gc.collect()
    if trace:
        run.read_trace()

    checks = run.kind.checks(run.probe_plans, answers, counts)
    run.counters.update(run.kind.counters)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # end-to-end metrics: a rate over all the rows and all the time of the
    # window, a tail of all its forecasts; a cell without training rows has
    # no rate, one without forecasts no latency
    metrics: Dict[str, dict] = {}
    latencies = run.latencies_ms(answers)
    values = {"setup_s": setup_s}
    if window_rows:
        values["train_rows_per_s"] = window_rows / window_s
    if latencies:
        values["predict_p50_ms"] = percentile(latencies, 50)
        values["predict_p95_ms"] = percentile(latencies, 95)
    if not trace:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            value = load_reader(m["name"], spec["here"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # failed: rows that went nowhere, forecasts unanswered or answered twice
    ids = [a[0] for a in answers]
    forecasts_bad = (counts["offered_forecasts"] - len(set(ids))) + (len(ids) - len(set(ids)))
    failed = int(counts["offered_rows"] - sum(done.values()) + forecasts_bad)
    device = {
        "platform": devices[0].platform, "kind": run.device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {
        "correct": bool(correct),
        "attempted": int(run.counters["window_rows"] + run.counters["window_forecasts"]),
        "failed": max(failed, 0),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        lo, hi = run.window_ns
        device["busy_s"] = trace_reduce.busy_seconds(run.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = trace_reduce.breakdown(run.trace, lo, hi)
    result["window_s"] = window_s
    result["setup_split"] = run.setup_split
    result["counters"] = {k: float(v) for k, v in run.counters.items()}
    if trace:
        result["end_to_end_in_traced_run"] = values
    result["checks"] = checks
    if "finished" in hooks:
        hooks["finished"](run, result)
    return result


def print_result(result: dict) -> None:
    checks = result["checks"]
    lines = [f"perfbench check {name}: value {c['value']:.6g} limit {c['limit']:.6g} "
             f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}" for name, c in checks.items()]
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: List[str], t_process: float) -> int:
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_process)
    except NoChip as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return NO_CHIP_EXIT
    print_result(result)
    return 0
