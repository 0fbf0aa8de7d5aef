"""The least time the chip could take for the delta rule of one launch (the
larger of its bytes over the published HBM bandwidth and its operations over
the bfloat16 peak: ``kernel_models/olmo_hybrid.py``) over the time the launch
spends under ``omldm.lm.delta_rule``."""


def read(ctx):
    ms = ctx.kind.scope_ms(ctx, "omldm.lm.delta_rule")
    if not ms or "hbm_gbps" not in ctx.peaks:
        return None
    counts = ctx.kind.flops
    least_s = max(counts["delta_rule_bytes"] / (ctx.peaks["hbm_gbps"] * 1e9),
                  counts["delta_rule_flops"] / (ctx.peaks["bf16_tflops"] * 1e12))
    return 100.0 * least_s / (ms / 1e3)
