"""Bytes the PA-II update needs for one batch (perfbench/kernel_model.py),
over the chip's published HBM bandwidth, over the measured step time. The
update is memory-bound at every shape (a handful of operations per byte)."""
from perfbench import kernel_model, trace_reduce


def read(ctx):
    if ctx.trace is None or "hbm_gbps" not in ctx.peaks:
        return None
    lo, hi = ctx.window_ns
    steps = trace_reduce.launch_sized(trace_reduce.modules_in(ctx.trace, lo, hi, "jit_step_fn"))
    if not steps:
        return None
    step_s = trace_reduce.median([e[2] for e in steps]) / 1e9
    least_s, _bound = kernel_model.roofline_seconds(
        kernel_model.pa2_step_flops(ctx.batch, ctx.max_nnz),
        kernel_model.pa2_step_bytes(ctx.batch, ctx.max_nnz),
        ctx.peaks["bf16_tflops"] * 1e12, ctx.peaks["hbm_gbps"] * 1e9,
    )
    return 100.0 * least_s / step_s
