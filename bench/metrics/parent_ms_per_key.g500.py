"""Device milliseconds of the parent transform's programs (the batched
``dp_transform`` of every batch, which ``sssp_parents`` runs too) in the
traced window, per key completed in it."""
from bench.trace import parent_transform_s


def read(run):
    t, done = run.device_trace, run.window.get("completed")
    if run.kind != "batches" or not t or not done:
        return None
    seconds = parent_transform_s(t["programs"])
    return 1e3 * seconds / done if seconds > 0 else None
