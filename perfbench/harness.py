"""One run of one cell: set-up, the measured window, the comparison, the line.

Driven by data: the cell, its configuration and its per-layer metrics are
found by the names in ``BENCHMARK.json`` (``perfbench/workloads/<cell>.json``,
the configuration's ``file``, ``perfbench/metrics/<metric>.py``). Adding one
of them never edits this file. See ``perfbench/README.md``.

From the program the harness takes the system under test (a ``StreamJob``
built as ``python -m omldm_tpu`` builds it, fed through
``StreamJob.run_file_fused``), its counters (fitted, holdout), its per-step
losses, and its weight vector for the comparison. The generator, the clock,
the schedule, the reference, the trace reduction and the peaks are the
benchmark's own.
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


def load_cell(workload: str) -> dict:
    """BENCHMARK.json's entry for ``workload`` with its configuration, its
    cell file and the metrics that list it."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[0]
    [cfg_entry] = [c for c in bench["configs"] if c["name"] == entry["config"]]
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    cell = _load_json(os.path.join(HERE, "workloads", workload + ".json"))

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config": config,
        "cell": cell,
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": [m for m in bench["per_layer"] if listed(m)],
    }


def scaled(config: dict, hash_space: int, rows: int) -> dict:
    """The configuration at a hash space and pool a CPU test can hold (the
    self-check and the tests under ``perfbench/tests``; never a chip run)."""
    config = copy.deepcopy(config)
    ds = config["create"]["learner"]["dataStructure"]
    ds["hashSpace"] = hash_space
    ds["nFeatures"] = config["schema"]["numeric_fields"] + hash_space
    config["rows"] = rows
    return config


def load_reader(metric: str) -> Callable:
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_metric_" + metric.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --- the system under test ---------------------------------------------------


class Stamps:
    """The prediction sink: every answer with the host clock at emission."""

    def __init__(self):
        self.rows: List[tuple] = []  # (id, value, t)

    def __call__(self, pred) -> None:
        self.rows.append((pred.data_instance.id, float(pred.value), time.perf_counter()))


def build_job(config: dict, stamps: Stamps):
    """The job as ``python -m omldm_tpu`` builds it from the configuration's
    flags, its Create request through the normal entry."""
    from omldm_tpu.__main__ import build_job as cli_build_job

    job, _sinks = cli_build_job(dict(config["job_flags"]))
    job.set_sinks(on_prediction=stamps, on_response=lambda r: None,
                  on_performance=lambda r: None)
    job.process_event("requests", json.dumps(config["create"]))
    job.ensure_deployed(int(config["create"]["learner"]["dataStructure"]["nFeatures"]))
    bridge = job.fused_file_bridge()
    if bridge is None or not bridge.supports_overlapped_ingest():
        raise RuntimeError("the job does not qualify for the fused, overlapped file route")
    return job, bridge


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


class DenseWeights:
    """A weight vector as the program holds it, copied to the host."""

    def __init__(self, w: np.ndarray):
        self.w = w

    def take(self, idx: np.ndarray) -> np.ndarray:
        return self.w[idx]

    def sumsq(self) -> float:
        return float(np.sum(np.square(self.w, dtype=np.float64)))


class SparseWeights:
    """A weight vector that is zero outside ``idx`` (sorted): how a stand-in
    reference hands its weights over without a copy of the whole vector."""

    def __init__(self, idx: np.ndarray, val: np.ndarray):
        self.idx, self.val = idx, val

    def take(self, idx: np.ndarray) -> np.ndarray:
        if not len(self.idx):
            return np.zeros(len(idx), np.float32)
        at = np.minimum(np.searchsorted(self.idx, idx), len(self.idx) - 1)
        return np.where(self.idx[at] == idx, self.val[at], np.float32(0))

    def sumsq(self) -> float:
        return float(np.sum(np.square(self.val, dtype=np.float64)))


# --- the run -----------------------------------------------------------------


class Run:
    """State of one run, handed to the per-layer readers as ``ctx``."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool):
        self.spec, self.seed, self.seconds, self.tracing = spec, seed, seconds, trace
        self.config, self.cell = spec["config"], spec["cell"]
        self.traffic = self.cell["traffic"]
        self.schema = gen.Schema.from_config(self.config)
        ds = self.config["create"]["learner"]["dataStructure"]
        self.hash_space = int(ds["hashSpace"])
        self.max_nnz = int(ds["maxNnz"])
        self.batch = int(self.config["job_flags"]["batchSize"])
        self.n_pool = int(self.config["rows"])
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
        self.stamps = Stamps()

    # -- set-up ---------------------------------------------------------------

    def make_probe(self) -> None:
        files = self.cell["probe"]["files"]
        n_train = sum(f["train_rows"] for f in files)
        n_fc = sum(f["forecasts"] for f in files)
        self.probe_rows = gen.draw_rows(gen.rng_for(self.seed, gen.STREAM_PROBE), n_train, self.schema)
        self.n_probe_forecasts = n_fc
        self.probe_plans, row, fc = [], 0, 0
        for f in files:
            self.probe_plans.append(gen.spread_forecasts(f["train_rows"], f["forecasts"], fc, row))
            row += f["train_rows"]
            fc += f["forecasts"]

    def make_forecasts(self, n_window: int) -> None:
        n = self.n_probe_forecasts + n_window
        self.forecast_rows = gen.draw_rows(gen.rng_for(self.seed, gen.STREAM_FORECAST), max(n, 1), self.schema)
        self.forecast_lines = gen.render(self.forecast_rows, ids=np.arange(max(n, 1)))

    def make_files(self) -> None:
        """Every file of the run, rendered into memory during set-up."""
        t = self.traffic
        if t["kind"] == "closed_loop":
            self.make_forecasts(0)
            part_rows = int(t["part_rows"])
            if self.n_pool % part_rows or part_rows % self.batch:
                raise ValueError("part_rows must divide the pool and hold whole launches")

            def part(k: int) -> gen.MemFile:
                pool = gen.Pool(self.seed, self.schema, self.n_pool)
                plan = gen.train_plan(k * part_rows, part_rows, self.n_pool)
                return gen.MemFile(gen.file_pieces(plan, pool, None), f"part{k}")

            with ThreadPoolExecutor(4) as ex:
                self.part_files = list(ex.map(part, range(self.n_pool // part_rows)))
            self.part_rows = part_rows
            self.window_plans = []
        elif t["kind"] == "open_loop":
            rate_fc = float(t["forecasts_per_s"])
            times = gen.arrival_times(self.seed, rate_fc, self.seconds, int(t["arrivals_seed"]),
                                      float(t["poll_ms"]) / 1e3)
            self.make_forecasts(len(times))
            self.window_plans = gen.paced_plans(
                float(t["train_rows_per_s"]), times, self.seconds,
                float(t["poll_ms"]) / 1e3, self.n_pool,
                first_forecast=self.n_probe_forecasts,
            )
            pool = gen.Pool(self.seed, self.schema, self.n_pool)
            self.slice_files = [
                gen.MemFile(gen.file_pieces(p, pool, self.forecast_lines), f"slice{k}")
                for k, p in enumerate(self.window_plans)
            ]
        else:
            raise ValueError(f"unknown traffic kind {t['kind']!r}")
        probe_lines = gen.render(self.probe_rows)
        self.probe_files = [
            gen.MemFile(gen.file_pieces(p, probe_lines, self.forecast_lines), f"probe{k}")
            for k, p in enumerate(self.probe_plans)
        ]

    def drive_probe(self, job, bridge) -> None:
        """The first files of the run, through the window's own call. They
        compile every shape the window uses (set-up time), and what they
        leave behind is what the reference is compared with: per-step
        losses, the answers, and the weight vector after each file."""
        import jax

        self.probe_w: List[DenseWeights] = []
        self.probe_losses: List[float] = []
        for mf in self.probe_files:
            job.run_file_fused(mf.path)
            jax.block_until_ready(bridge.trainer.state)
            self.probe_losses += [l for l, _ in bridge.trainer.curve_slice()]
            w = np.asarray(bridge.trainer.state["params"]["w"]).reshape(-1)
            self.probe_w.append(DenseWeights(w))
        self.n_probe_answers = len(self.stamps.rows)
        # the closed loop's back-pressure marker, compiled here and not in
        # the window
        self.marker = jax.jit(lambda s: s + 0)
        jax.block_until_ready(self.marker(bridge.trainer.state["step"]))
        self.counters["peak_bytes_after_probe"] = peak_bytes(jax.devices()[0])

    # -- windows --------------------------------------------------------------

    def window_closed_loop(self, job, bridge) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        in_flight: List = []
        ahead = int(self.traffic["files_in_flight"])
        offered = k = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < self.seconds:
            with TraceAnnotation("perfbench.handover"):
                job.run_file_fused(self.part_files[k % len(self.part_files)].path)
            offered += self.part_rows
            k += 1
            # back-pressure: a value that exists once the device has finished
            # this file; wait for the file before the previous one
            in_flight.append(self.marker(bridge.trainer.state["step"]))
            if len(in_flight) >= ahead:
                with TraceAnnotation("perfbench.wait_device"):
                    jax.block_until_ready(in_flight.pop(0))
        with TraceAnnotation("perfbench.drain"):
            jax.block_until_ready(bridge.trainer.state)
        self.t0, self.t1 = t0, time.perf_counter()
        self.counters.update(window_rows=offered, window_forecasts=0, handovers=k)

    def window_open_loop(self, job, bridge) -> None:
        import jax
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
                job.run_file_fused(mf.path)
            self.handover_s.append(time.perf_counter() - start)
            offered += plan.n_train
        with TraceAnnotation("perfbench.drain"):
            jax.block_until_ready(bridge.trainer.state)
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

    def run_window(self, job, bridge) -> None:
        from jax.profiler import TraceAnnotation

        window = (self.window_closed_loop if self.traffic["kind"] == "closed_loop"
                  else self.window_open_loop)
        if not self.tracing:
            window(job, bridge)
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with TraceAnnotation(trace_reduce.WINDOW_SPAN):
                window(job, bridge)
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

    def reference(self, precision: str = "float32", fault: Optional[str] = None):
        """The plain reference over the probe files (nothing of the program).
        After each file it keeps the weights it has touched so far (it is zero
        everywhere else)."""
        path = os.path.join(HERE, "reference", self.config["reference"] + ".py")
        spec = importlib.util.spec_from_file_location("perfbench_reference_" + self.config["reference"], path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        ref = module.build(self.config, precision=precision, fault=fault)
        ref.w_after, ref.touched_after = [], []
        for plan in self.probe_plans:
            ref.feed_file(plan.kind, plan.index, self.probe_rows, self.forecast_rows)
            touched = ref.touched_indices()
            ref.touched_after.append(touched)
            ref.w_after.append(ref.w[touched].copy())
        return ref

    def control(self, precision: str = "float32", fault: Optional[str] = None) -> Dict[str, dict]:
        """The numbers compared when the reference, in a lower precision or
        with a fault planted, stands in the program's place (no program, no
        window: the probe files alone)."""
        if getattr(self, "_sound", None) is None:
            self._sound = self.reference()
        sound = self._sound
        stand_in = self.reference(precision=precision, fault=fault)
        weights = [SparseWeights(t, w) for t, w in zip(stand_in.touched_after, stand_in.w_after)]
        answers = [(fid, value, 0.0) for fid, value, _m, _s in stand_in.answers]
        counts = {
            "offered_rows": len(self.probe_rows), "fitted": stand_in.fitted,
            "holdout": stand_in.holdout, "offered_forecasts": self.n_probe_forecasts,
            "probe_answers": len(answers),
        }
        return self.compare(sound, weights, stand_in.losses, answers, counts)

    def compare(self, ref, got_w: list, got_losses: List[float],
                got_answers: List[tuple], counts: dict) -> Dict[str, dict]:
        """Every number compared, beside its limit."""
        limits = self.cell["limits"]
        out: Dict[str, float] = {}
        out["rows_lost"] = counts["offered_rows"] - counts["fitted"] - counts["holdout"]
        # forecasts: every id exactly once (probe and window)
        ids = [a[0] for a in got_answers]
        expected = counts["offered_forecasts"]
        out["forecasts_bad"] = (expected - len(set(ids))) + (len(ids) - len(set(ids)))
        # the probe's answers, where the reference's margin is not a rounding
        # of zero (1e-4 of the sum of its terms' sizes)
        ref_by_id = {a[0]: a for a in ref.answers}
        mismatch = judged = 0
        for fid, value, _t in got_answers[: counts["probe_answers"]]:
            r = ref_by_id.get(fid)
            if r is None:
                mismatch += 1
                continue
            _, answer, margin, scale = r
            if abs(margin) <= 1e-4 * scale and scale > 0:
                continue
            judged += 1
            mismatch += value != answer
        mismatch += max(len(ref.answers) - counts["probe_answers"], 0)
        out["probe_pred_mismatch"] = mismatch
        self.counters["probe_answers_judged"] = judged
        # per-step loss
        n = max(len(got_losses), len(ref.losses))
        gaps = [1.0] * n
        for i in range(min(len(got_losses), len(ref.losses))):
            gaps[i] = abs(got_losses[i] - ref.losses[i]) / max(abs(ref.losses[i]), 1e-3)
        out["loss_gap"] = max(gaps) if gaps else 1.0
        # the update after the first file, the change after the last
        norm = lambda v: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
        for name, k in (("first_update_norm_gap", 0), ("change_norm_gap", len(ref.w_after) - 1)):
            want = norm(ref.w_after[k])
            have = norm(got_w[k].take(ref.touched_after[k]))
            out[name] = abs(have - want) / max(want, 1e-30)
        last = len(ref.w_after) - 1
        touched = ref.touched_after[last]
        want = ref.w_after[last]
        have = got_w[last].take(touched)
        out["w_diff_rel"] = norm(have - want) / max(norm(want), 1e-30)
        total = got_w[last].sumsq()
        inside = float(np.sum(np.square(have, dtype=np.float64)))
        out["w_stray_share"] = max(total - inside, 0.0) / max(float(np.sum(np.square(want, dtype=np.float64))), 1e-30)
        return {k: {"value": float(v), "limit": float(limits[k])} for k, v in out.items()}


def probe_only_run(workload: str, seed: int, scale: Optional[dict] = None) -> Run:
    """A run's probe files and nothing else: no job, no device, no window.
    What ``Run.control`` needs (the control script and the tests)."""
    spec = load_cell(workload)
    if scale:
        spec["config"] = scaled(spec["config"], scale["hash_space"], scale["rows"])
    run = Run(spec, seed, 1.0, False)
    run.make_probe()
    run.make_forecasts(0)
    return run


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all values (q in 0..100)."""
    v = sorted(values)
    k = max(int(np.ceil(q / 100.0 * len(v))) - 1, 0)
    return v[k]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, t_process: float,
             need_chip: bool = True, scale: Optional[dict] = None,
             hooks: Optional[dict] = None) -> dict:
    """One run. ``need_chip=False`` and ``scale`` are for the CPU self-check
    and the tests under ``perfbench/tests``; ``hooks`` lets those tests break
    the timed path underneath (``after_build(job, bridge)``)."""
    hooks = hooks or {}
    spec = load_cell(workload)
    if scale:
        spec["config"] = scaled(spec["config"], scale["hash_space"], scale["rows"])
        spec["cell"] = copy.deepcopy(spec["cell"])
        spec["cell"]["traffic"].update(scale.get("traffic", {}))
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

    job, bridge = build_job(run.config, run.stamps)
    jax.block_until_ready(bridge.trainer.state)
    mark("build_job_s")
    if "after_build" in hooks:
        hooks["after_build"](job, bridge)

    run.make_probe()
    run.make_files()
    mark("generate_s")
    if trace:
        run.extras.update(time_parser(run))
        mark("parser_timing_s")
    run.drive_probe(job, bridge)
    mark("warm_probe_s")

    # the harness's own heap (plans, rows, rendered lines) is out of the
    # collector's way during the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    run.run_window(job, bridge)
    window_s = run.t1 - run.t0

    # counters, through the job's normal termination report (which also
    # evaluates the holdout on the device: the peak is read on both sides)
    run.counters["peak_bytes_after_window"] = peak_bytes(devices[0])
    report = job.terminate()
    stats = report.to_dict()["statistics"][0] if report is not None else {}
    fitted = int(stats.get("fitted", bridge.trainer.fitted))
    holdout = len(bridge.test_set)
    run.memory_peak_bytes = peak_bytes(devices[0])
    answers = list(run.stamps.rows)
    probe_rows = len(run.probe_rows)
    counts = {
        "offered_rows": probe_rows + int(run.counters["window_rows"]),
        "fitted": fitted, "holdout": holdout,
        "offered_forecasts": run.n_probe_forecasts + int(run.counters["window_forecasts"]),
        "probe_answers": run.n_probe_answers,
    }
    run.counters.update(counts)
    # the program's state is freed before the reference runs
    got_w, got_losses = run.probe_w, run.probe_losses
    del job, bridge, report
    gc.collect()
    if trace:
        run.read_trace()

    checks = run.compare(run.reference(), got_w, got_losses, answers, counts)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # end-to-end metrics
    metrics: Dict[str, dict] = {}
    window_answers = {}
    for fid, _value, t in answers[run.n_probe_answers:]:
        window_answers.setdefault(fid, t)
    latencies = []
    for plan in run.window_plans:
        for i in np.nonzero(plan.kind == gen.FORECAST)[0]:
            t = window_answers.get(int(plan.index[i]))
            created = run.t0 + float(plan.created[i])
            # an answer that never came waits until the window closed
            latencies.append(((t if t is not None else run.t1) - created) * 1e3)
    values = {
        "setup_s": setup_s,
        "train_rows_per_s": run.counters["window_rows"] / window_s,
    }
    if latencies:
        values["predict_p50_ms"] = percentile(latencies, 50)
        values["predict_p95_ms"] = percentile(latencies, 95)
    if not trace:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    failed = int(checks["rows_lost"]["value"] + checks["forecasts_bad"]["value"])
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
    return result


def time_parser(run: Run) -> Dict[str, float]:
    """The repo's sparse parser alone over pool bytes (host-only code, host
    clock), for the parse layer's metric. Traced runs only, before the
    window."""
    from omldm_tpu.ops.native import SparseFastParser

    pool = gen.Pool(run.seed, run.schema, run.n_pool)
    blocks = [bytes(pool.block(b).data) for b in range(min(run.n_pool // gen.BLOCK, 16))]
    blob = b"".join(blocks)
    n_rows = len(blocks) * gen.BLOCK
    parser = SparseFastParser(run.schema.n_num, run.hash_space, run.max_nnz, n_threads=0)
    parser.parse(blocks[0])
    t = time.perf_counter()
    reps = 0
    while time.perf_counter() - t < 0.5:
        parser.parse(blob)
        reps += 1
    return {"parser_rows": n_rows * reps, "parser_s": time.perf_counter() - t,
            "parser_threads": parser.n_threads}


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
