"""Share of a launch's device time spent under ``omldm.lm.delta_rule``."""


def read(ctx):
    return ctx.kind.scope_share(ctx, "omldm.lm.delta_rule")
