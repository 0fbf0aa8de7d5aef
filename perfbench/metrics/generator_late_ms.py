"""How late a slice was handed over against its schedule, 95th percentile
over the window's slices (0 when the previous hand-over returned in time)."""


def read(ctx):
    if not ctx.late_s:
        return None
    v = sorted(max(x, 0.0) for x in ctx.late_s)
    k = max(-(-95 * len(v) // 100) - 1, 0)
    return v[k] * 1e3
