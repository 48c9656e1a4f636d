"""Directed nnz as a percent of the slots of the resident SlimSell layout:
the share of a full sweep's reads that are real edges."""


def read(run):
    if run.kind != "batches":
        return None
    return 100.0 * run.layout["nnz"] / run.layout["slots"]
