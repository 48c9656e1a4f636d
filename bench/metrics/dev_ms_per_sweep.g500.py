"""Device busy milliseconds of the traced window (union of the device's
operation intervals), less the parent transform's programs, per engine
sweep run in it: the batched fixpoint's time per sweep."""
from bench.trace import parent_transform_s


def read(run):
    t, sweeps = run.device_trace, run.counters.get("sweeps_total")
    if run.kind != "batches" or not t or not sweeps:
        return None
    return 1e3 * (t["busy_s"] - parent_transform_s(t["programs"])) / sweeps
