"""Mean of the middle half of the window's forecast latencies (from the 25th
to the 75th percentile), by the same clock and from the same creation times
as ``predict_p50_ms``. The median of a window is one forecast's latency, and
which forecast holds the middle rank changes from run to run; the midmean
moves with half of the forecasts and, unlike the plain mean, not with the few
that a stalled host holds back, so it repeats about four times more closely
(PERF.md section 2) and shows a small loss that the median's bound lets
pass."""


def read(ctx):
    latencies = sorted(ctx.latencies_ms(list(ctx.stamps.rows)))
    n = len(latencies)
    if n < 4:
        return None
    middle = latencies[n // 4: 3 * n // 4]
    return sum(middle) / len(middle)
