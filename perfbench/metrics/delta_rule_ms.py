"""Device time a launch spends under the scope ``omldm.lm.delta_rule``
(forward, recomputation and backward together): the union of those
operations' intervals over the window's launches, a launch."""


def read(ctx):
    return ctx.kind.scope_ms(ctx, "omldm.lm.delta_rule")
