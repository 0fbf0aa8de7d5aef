"""The least time the chip could take for the causal attention of one launch
(its operations over the published bfloat16 peak: the kernel model's
``flash_attn_flops``, forward and backward, no recomputation counted) over the
time the launch spends under ``omldm.lm.flash_attn`` (the Pallas flash
kernels: forward, recomputed forward and backward)."""


def read(ctx):
    ms = ctx.kind.scope_ms(ctx, "omldm.lm.flash_attn")
    if not ms or "bf16_tflops" not in ctx.peaks:
        return None
    least_s = ctx.kind.flops["flash_attn_flops"] / (ctx.peaks["bf16_tflops"] * 1e12)
    return 100.0 * least_s / (ms / 1e3)
