"""Rows per dispatched batch: ``ServingStats``' exact row and batch
counts over the window."""


def read(summary, counters, readings, peaks):
    if not counters.get("n_batches"):
        return None
    return counters["n_queries"] / counters["n_batches"]
