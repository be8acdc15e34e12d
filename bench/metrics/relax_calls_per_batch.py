"""CSR relax calls (forward plus backward iterations) per batch, from the
executor's ``occupancy_summary`` counters over the window."""


def read(readings):
    c = readings.counters
    calls, batches = c.get("relax_calls", 0), c.get("batches", 0)
    return calls / batches if calls and batches else None
