"""Floating-point operations of one full step, from what the kind says of one
row: what a later cell's ``*_mfu`` reader does with ``ctx.kind``."""


def read(ctx):
    return ctx.kind.flops_per_row * ctx.batch
