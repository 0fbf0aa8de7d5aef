"""Share of a launch's device time spent under ``omldm.lm.flash_attn`` (the
Pallas flash kernels, forward, recomputation and backward)."""


def read(ctx):
    return ctx.kind.scope_share(ctx, "omldm.lm.flash_attn")
