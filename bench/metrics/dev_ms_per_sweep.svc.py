"""Device busy milliseconds of the traced window (union of the device's
operation intervals) per engine sweep run in it."""


def read(run):
    t, sweeps = run.device_trace, run.counters.get("sweeps_total")
    if run.kind != "open_loop" or not t or not sweeps:
        return None
    return 1e3 * t["busy_s"] / sweeps
