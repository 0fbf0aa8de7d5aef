"""Benchmark: end-to-end streaming training throughput (BASELINE.md config 1).

JSON bytes -> trained parameters through the CLI's ingest route (C++ block
parse -> packed holdout/staging -> chained SPMD device steps), the path
``python -m omldm_tpu --trainingData file.jsonl`` takes. This maps to the
reference's whole-job throughput (Job.scala:42-70 -> FlinkSpoke.scala:92-107
per-record hot loop, which it drives at parallelism 16 on a 4C/8T
workstation, hs_err_pid77107.log:21).

``benchmarks/run_benchmarks.py:bench_e2e_stream`` takes several runs of the
same stream: the full run with the device in the loop (serial and
overlapped, reported as ``raw_*`` fields), the host pipeline with the device
stubbed, and the chained launches on device-resident stages. ``value`` is
still the wall clock of the overlapped run whose device step is a stub
calibrated to the separately measured device time; replacing it with a
device-in-the-loop figure is ROADMAP A1, not done here.

The run needs the chip: with no TPU, or without the native parser, it fails
before it measures and prints no ``value``.

The reference publishes no numbers (BASELINE.md); ``vs_baseline`` is
computed against a 100k examples/sec proxy — a generous estimate of the
reference's whole-job throughput at parallelism 16 on its workstation —
i.e. vs_baseline = value / 100_000.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))


def main() -> int:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(
            f"bench.py needs a TPU; jax found platform={device['platform']!r} "
            f"({device['kind']!r} x{device['count']}). No measurement taken.",
            file=sys.stderr,
        )
        return 1
    from omldm_tpu.ops.native import fast_parser_available

    if not fast_parser_available():
        print(
            "bench.py needs the native parser (g++ build failed); the "
            "Python parser is not the route this benchmark times.",
            file=sys.stderr,
        )
        return 1
    from omldm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from run_benchmarks import bench_e2e_stream

    _, measured, extra = bench_e2e_stream(n_records=1_000_000)
    print(
        json.dumps(
            {
                "metric": (
                    "e2e streaming train throughput, JSON bytes -> trained "
                    "params (double-buffered overlapped run, device step "
                    "stubbed at its measured time)"
                ),
                "value": round(measured, 1),
                "unit": "examples/sec",
                "vs_baseline": round(measured / 100_000.0, 3),
                "device": device,
                **extra,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
