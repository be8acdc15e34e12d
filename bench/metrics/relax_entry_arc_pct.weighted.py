"""Share of the chosen relax branches' candidate slots (n_b × arc slots)
that belong to active (source, vertex) entries, over the window: the
work an entry-level relax would do against the work the union-column
relax did, from the executor's ``entry_arcs`` and ``arc_slots``."""


def read(readings):
    c = readings.counters
    entry_arcs, slots, n_b = (c.get("entry_arcs"), c.get("arc_slots"),
                              c.get("n_b"))
    if entry_arcs is None or not slots or not n_b:
        return None
    return 100.0 * entry_arcs / (n_b * slots)
