"""Share of a launch's device time spent under ``omldm.lm.head_loss`` (the
head's product and the fused loss: in a looped decoder at every loop step,
forward, recomputation and backward)."""


def read(ctx):
    return ctx.kind.scope_share(ctx, "omldm.lm.head_loss")
