"""Peak bytes in use on the fullest chip, ``memory_stats()`` after the window."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 2**30
