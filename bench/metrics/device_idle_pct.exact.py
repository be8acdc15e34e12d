"""Share of the exact cell's traced window in which no op ran on the chip."""


def read(readings):
    r = readings.reduction
    return None if r is None else r.idle_pct
