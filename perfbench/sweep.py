"""``python3 perfbench/sweep.py --workload <open-loop cell> --rates 21120,42240,... --seconds 8``

The sweep behind an open-loop cell's rate: one process, one job, and for each
offered training rate a window of the cell's own mix (same forecasts, same
poll). A rate is sustained when the hand-overs do not fall behind their
schedule: lateness at the end of the window no larger than a poll. One JSON
line a rate. Not a benchmark run: nothing here is compared across PRs; the
readings go into PERF.md when the cell's rate is set.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from perfbench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--forecasts", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    forecasts = [float(f) for f in args.forecasts.split(",")] if args.forecasts else [None]

    from omldm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache("on")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = harness.load_cell(args.workload)
    devices = harness.require_chip(spec["chips"])
    system = stamps = None
    for fc in forecasts:
        for rate in rates:
            cell = json.loads(json.dumps(spec["cell"]))
            cell["traffic"]["train_rows_per_s"] = rate
            if fc is not None:
                cell["traffic"]["forecasts_per_s"] = fc
            run = harness.Run({**spec, "cell": cell}, args.seed, args.seconds, False)
            run.make_probe()
            run.make_files()
            if system is None:
                stamps = run.stamps
                system = run.kind.build(stamps)
                run.drive_probe(system)  # compiles every shape once
            # one job, one sink: this run's answers are those after the mark
            run.stamps, run.n_probe_answers = stamps, len(stamps.rows)
            run.window_open_loop(system)
            lat = run.latencies_ms(stamps.rows)
            late = [max(x, 0.0) * 1e3 for x in run.late_s]
            tail = late[-max(len(late) // 4, 1):]
            poll_ms = float(cell["traffic"]["poll_ms"])
            print(json.dumps({
                "train_rows_per_s": rate, "forecasts_per_s": cell["traffic"]["forecasts_per_s"],
                "rows_per_poll": run.window_plans[0].n_train,
                "sustained": max(tail) <= poll_ms,
                "late_last_quarter_max_ms": max(tail), "late_p95_ms": harness.percentile(late, 95),
                "predict_p50_ms": harness.percentile(lat, 50) if lat else None,
                "predict_p95_ms": harness.percentile(lat, 95) if lat else None,
                "window_s": run.t1 - run.t0, "asked_s": args.seconds, "forecasts": len(lat),
                "device": devices[0].device_kind,
            }), flush=True)
            for mf in run.slice_files + run.probe_files:
                mf.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
