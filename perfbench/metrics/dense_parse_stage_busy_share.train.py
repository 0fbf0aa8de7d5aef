"""Share of the window the dense route's producer thread spends reading and
parsing lines into their stage slots (``kinds/token_stream.py``
``producer_busy_share``: self time of the ``read`` and ``parse_stage`` spans
over the window)."""


def read(ctx):
    return ctx.kind.producer_busy_share(ctx)
