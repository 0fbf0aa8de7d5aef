"""``python3 perfbench/span_report.py --workload <cell> --seed <n>``: one traced
run of a cell on the chip, then what the result line's ``breakdown`` cannot
hold, from the program's own spans (``program_spans.py``):

- the device-idle seconds inside ``perfbench.handover`` by the innermost
  program span covering each gap on the producer thread, beside the
  breakdown's own list for the whole window;
- count, total, self time and median of every span that starts in the window;
- the completion lag of the step programs: device end of the k-th step minus
  the host start of its ``fit`` span.

The last line of standard output is one JSON object with all of it.
"""

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness
from perfbench import program_spans as ps
from perfbench import trace_reduce


def traced_run(workload: str, seed: int, seconds: float, **kwargs):
    """``harness.run_cell`` with the trace on; the result and the run (what
    the readers are handed as ``ctx``), which the ``finished`` hook is
    handed."""
    kept = []
    hooks = dict(kwargs.pop("hooks", None) or {}, finished=lambda run, result: kept.append(run))
    result = harness.run_cell(workload, seed, seconds, True, T_PROCESS, hooks=hooks, **kwargs)
    return result, kept[-1]


def report(ctx, result: dict) -> dict:
    rec = ps.recorder()
    spans = {}
    for name in rec.names():
        got = ps.in_window(ctx, name)
        if got:
            spans[name] = {
                "count": len(got),
                "total_s": sum(r.end - r.start for r in got),
                "self_s": sum(r.self_s for r in got),
                "median_ms": trace_reduce.median(ps.durations_ms(got)),
            }
    gaps = ps.handover_idle(ctx)
    lags = ps.completion_lags_ms(ctx)
    tails = [bool((fit.attrs or {}).get("tail")) for fit, _ in ps.joined_fits(ctx) or []]
    pct = lambda v, q: harness.percentile(v, q) if v else None
    fits, off = ps.in_window(ctx, "fit"), ps.clock_disagreement_ns(ctx)
    return {
        "window_s": ps.window_s(ctx),
        "clock_disagreement_ms": off and off / 1e6,
        "fit_spans": fits and len(fits),
        "step_programs": len(trace_reduce.modules_in(ctx.trace, *ctx.window_ns, ps.STEP_PROGRAM)),
        "handover_idle_s": ps.by_label(gaps) if gaps is not None else None,
        "handover_idle_total_s": sum(s for s, _, _ in gaps) if gaps is not None else None,
        "breakdown_idle_gaps_s": dict(map(tuple, result["breakdown"]["idle_gaps"])),
        "spans": dict(sorted(spans.items(), key=lambda kv: -kv[1]["self_s"])),
        "completion_lag_ms": lags and {
            "steps": len(lags), "p50": pct(lags, 50), "p95": pct(lags, 95), "max": max(lags),
            "tail_p50": pct([l for l, t in zip(lags, tails) if t], 50),
            "full_p50": pct([l for l, t in zip(lags, tails) if not t], 50),
        },
    }


def print_report(out: dict) -> None:
    print(f"window {out['window_s']:.3f} s; the clocks' two points disagree by {out['clock_disagreement_ms']} ms; "
          f"{out['fit_spans']} fit spans, {out['step_programs']} step programs on the device")
    print("device-idle seconds inside perfbench.handover, by the program span covering each gap:")
    for label, s in (out["handover_idle_s"] or {}).items():
        print(f"  {label:32s} {s:9.4f}")
    print(f"  {'sum':32s} {out['handover_idle_total_s']}")
    print("the result line's breakdown.idle_gaps (whole window, innermost span of either thread):")
    for label, s in out["breakdown_idle_gaps_s"].items():
        print(f"  {label:32s} {s:9.4f}")
    print(f"{'span':18s} {'count':>7s} {'total_s':>9s} {'self_s':>9s} {'median_ms':>10s}")
    for name, row in out["spans"].items():
        print(f"{name:18s} {row['count']:7d} {row['total_s']:9.4f} {row['self_s']:9.4f} {row['median_ms']:10.3f}")
    print("completion lag (device end of a step minus host start of its fit), ms:", out["completion_lag_ms"])
    print(json.dumps(out))


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="perfbench/span_report.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        result, ctx = traced_run(args.workload, args.seed, args.seconds)
    except harness.NoChip as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return harness.NO_CHIP_EXIT
    harness.print_result(result)
    print_report(report(ctx, result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
