"""Device busy milliseconds per ``bench.step`` span (one solve call)."""


def read(readings):
    r = readings.reduction
    if r is None or "bench.step" not in r.span_busy:
        return None
    count, busy_s = r.span_busy["bench.step"]
    return 1e3 * busy_s / count if count and busy_s > 0 else None
