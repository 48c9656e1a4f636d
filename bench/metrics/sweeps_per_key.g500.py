"""Engine sweeps (the program's ``sweeps_total`` counter) per key completed
in the window."""


def read(run):
    if run.kind != "batches" or not run.window["completed"]:
        return None
    return run.counters["sweeps_total"] / run.window["completed"]
