"""Percent of dispatched batch columns that carry a real query (the
program's ``columns_real`` over ``columns_total`` in the window)."""


def read(run):
    c = run.counters
    if run.kind != "open_loop" or not c.get("columns_total"):
        return None
    return 100.0 * c["columns_real"] / c["columns_total"]
