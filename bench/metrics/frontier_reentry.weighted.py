"""MFBF frontier entries per reached entry over the window: how often a
(source, vertex) entry joins the forward frontier on a weighted graph
(1.0 on unit weights), from the executor's ``entries_bf`` and
``reached_bf`` counters."""


def read(readings):
    c = readings.counters
    entries, reached = c.get("entries_bf"), c.get("reached_bf")
    return entries / reached if entries and reached else None
