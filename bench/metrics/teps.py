"""Graph500 traversed edges per second: the edges of every key completed
in the window over the window's time, first submit to last completion."""


def read(run):
    w = run.window
    if run.kind != "batches" or not w["completed"]:
        return None
    return w["edges"] / w["elapsed_s"]
