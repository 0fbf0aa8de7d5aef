"""Share of a launch's device time spent under ``omldm.lm.rope`` (the rotary
embedding of ``q`` and ``k``, forward, recomputation and backward)."""


def read(ctx):
    return ctx.kind.scope_share(ctx, "omldm.lm.rope")
