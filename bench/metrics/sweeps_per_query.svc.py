"""Engine sweeps (the program's ``sweeps_total`` counter) per query
answered in the window."""


def read(run):
    if run.kind != "open_loop" or not run.window["completed"]:
        return None
    return run.counters["sweeps_total"] / run.window["completed"]
