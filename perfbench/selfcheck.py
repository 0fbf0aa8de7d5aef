"""``python3 perfbench/selfcheck.py``: the benchmark checked on the CPU, no chip.

- the harness end to end, every cell, at its kind's tiny scale on the CPU
  backend: paths, the shape of the last line, ``correct`` true;
- the byte-count function behind ``sparse_step_hbm_roofline`` on hand-worked
  shapes;
- the trace reduction on the recorded TPU trace against numbers worked out
  beside it;
- the reference's own CRC-32 against zlib, and the generator against
  ``json.loads``.

It prints counts only. A rate, a time or a share from a CPU run is never
printed under a device metric's name.
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import json
import zlib

import numpy as np

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def need(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"selfcheck FAILED: {what}")
    print(f"ok  {what}")


def check_kernel_model() -> None:
    from perfbench import kernel_model as km

    # one row, one feature: index 4 + value 4 + target 4, two touched weights
    # (the feature and the bias): 2 x 4 gathered, 2 x 8 read and written
    need(km.pa2_step_bytes(1, 1) == 36, "pa2_step_bytes(1, 1) == 36")
    # the cell's shape: 4096 x (40 x 8 + 4 + 41 x 4 + 41 x 8) = 4096 x 816
    need(km.pa2_step_bytes(4096, 40) == 3_342_336, "pa2_step_bytes(4096, 40) == 3,342,336")
    t, bound = km.roofline_seconds(km.pa2_step_flops(4096, 40), 3_342_336, 197e12, 819e9)
    need(bound == "memory" and abs(t - 3_342_336 / 819e9) < 1e-15, "the PA-II step is memory-bound at the cell's shape")


def check_trace_reduction() -> None:
    from perfbench import trace_reduce as tr

    with open(os.path.join(HERE, "testdata", "scout_v5e.expected.json")) as f:
        want = json.load(f)
    trace = tr.load(os.path.join(HERE, "testdata", "scout_v5e.xplane.pb.gz"))
    lo, hi = tr.window_of(trace)
    near = lambda a, b: abs(a - b) <= 1e-9 * max(abs(a), abs(b), 1.0)
    need(near((hi - lo) / 1e9, want["window_s"]), "recorded trace: window length")
    need(near(tr.busy_seconds(trace, lo, hi), want["busy_s"]), "recorded trace: device busy seconds (union of ops)")
    steps = tr.modules_in(trace, lo, hi, "jit_step_fn")
    need(len(steps) == want["step_programs_in_window"], "recorded trace: step programs in the window")
    sized = tr.launch_sized(steps)
    need(len(sized) == want["launch_sized_steps"], "recorded trace: launch-sized steps told from the tail step")
    need(near(tr.median([e[2] for e in sized]), want["launch_sized_median_ns"]), "recorded trace: median launch-sized step")
    preds = tr.modules_in(trace, lo, hi, "jit_predict_fn")
    need(len(preds) == want["predict_programs"], "recorded trace: predict programs")
    need(near(tr.median([e[2] for e in preds]), want["predict_median_ns"]), "recorded trace: median predict")
    need(near(sum(e[2] for e in preds), want["predict_total_ns"]), "recorded trace: predict total")
    bd = tr.breakdown(trace, lo, hi)
    need(bd["device_ops"][0][0] == want["top_device_op"], "recorded trace: the operation with most device time")
    need(near(dict(map(tuple, bd["idle_gaps"]))["perfbench.handover"], want["idle_s_attributed_to_handover"]),
         "recorded trace: idle seconds attributed to the hand-over span")
    need(len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10, "breakdown lists hold at most 10 entries")


def check_generator_and_reference() -> None:
    from perfbench import generator as gen
    from perfbench import harness
    from perfbench.reference import pa2

    with open(os.path.join(HERE, "configs", "criteo_pa_2e28.json")) as f:
        config = json.load(f)
    kind = harness.load_kind(config)
    schema = kind.Schema.from_config(config)
    big_seed = 2**31 + 1234567
    rows = kind.draw_rows(gen.rng_for(big_seed, gen.STREAM_PROBE), 3000, schema)
    again = kind.draw_rows(gen.rng_for(big_seed, gen.STREAM_PROBE), 3000, schema)
    need(np.array_equal(rows.cats, again.cats) and np.array_equal(rows.nums, again.nums), "the same seed gives the same rows")
    lines = kind.render(rows)
    for i in (0, 1, 1499, 2999):
        d = json.loads(bytes(lines.span(i, i + 1)))
        ok = (d["numericalFeatures"] == rows.nums[i].tolist()
              and d["categoricalFeatures"] == ["%08x" % c for c in rows.cats[i]]
              and d["target"] == rows.target[i] and d["operation"] == "training")
        if not ok:
            raise SystemExit(f"selfcheck FAILED: rendered line {i} does not parse back to its row")
    print("ok  rendered lines parse back to their rows")
    for field in (0, 9, 10, 25):
        h = pa2.hash_field(field, rows.cats[:500, field])
        if any(int(h[i]) != zlib.crc32(f"{field}={rows.cats[i, field]:08x}".encode()) for i in range(500)):
            raise SystemExit("selfcheck FAILED: the reference's CRC-32 differs from zlib")
    print("ok  the reference's CRC-32 equals zlib's on 2,000 strings")
    times = gen.arrival_times(big_seed, 50.0, 10.0, 7919, 0.1)
    other = gen.arrival_times(big_seed + 1, 50.0, 10.0, 7919, 0.1)
    per_slice = lambda t: sorted(np.bincount((t / 0.1).astype(int), minlength=100).tolist())
    need(len(times) == len(other) == 500 and per_slice(times) == per_slice(other)
         and not np.allclose(times, other) and np.allclose(np.sort(times % 0.1), np.sort(other % 0.1)),
         "two seeds give the same slices of forecast arrivals, in another order")
    plans = gen.paced_plans(42240.0, times, 10.0, 0.1, 1 << 20)
    need(all(p.n_train == 4224 for p in plans) and sum(p.n_forecast for p in plans) == 500, "every slice holds 4,224 rows; all 500 forecasts are dealt")


def check_harness() -> None:
    from perfbench import harness

    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for k, cell in enumerate(w["name"] for w in bench["workloads"]):
        tiny = harness.load_cell(cell)["kind"].TINY
        for trace in (False, True):
            result = harness.run_cell(cell, 2**31 + 17 + k, 1.5, trace, time.perf_counter(),
                                      need_chip=False, scale=tiny)
            line = json.loads(json.dumps(result))
            need(RESULT_KEYS <= set(line) and list(line)[-1] == "checks", f"{cell} trace={int(trace)}: the line has its keys, checks last")
            need(line["correct"] is True and line["failed"] == 0, f"{cell} trace={int(trace)}: correct, nothing failed")
            need(line["device"]["platform"] == "cpu", f"{cell} trace={int(trace)}: the line names the device it ran on (cpu)")
            print(f"    counted: attempted {line['attempted']}, rows fitted {int(line['counters']['fitted'])}, "
                  f"holdout {int(line['counters']['holdout'])}, forecasts answered "
                  f"{int(line['counters']['offered_forecasts'])}, probe answers judged "
                  f"{int(line['counters']['probe_answers_judged'])}, metrics named {sorted(line['metrics'])}")


def main() -> int:
    check_kernel_model()
    check_trace_reduction()
    check_generator_and_reference()
    check_harness()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
