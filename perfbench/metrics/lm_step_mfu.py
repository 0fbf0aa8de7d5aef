"""The launch's model operations (forward and backward of one row, no
recomputation counted: ``kernel_models/olmo_hybrid.py``) over the median
launch's device time times the chip's published bfloat16 peak."""
from perfbench import trace_reduce


def read(ctx):
    launches = ctx.kind.launches(ctx)
    if not launches or "bf16_tflops" not in ctx.peaks:
        return None
    step_s = trace_reduce.median([e[2] for e in launches]) / 1e9
    return 100.0 * ctx.kind.flops["model_flops"] / (step_s * ctx.peaks["bf16_tflops"] * 1e12)
