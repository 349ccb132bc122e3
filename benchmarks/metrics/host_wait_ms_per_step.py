"""Milliseconds a step's loop waited for its batch: the program's
`host_wait` span (`nn/graph.py`, round `next(batches)` in `fit()`), summed
from the start of the window and divided by the steps since then."""


def read(run):
    waits = [e - s for name, s, e in run.spans if name == "host_wait"]
    if not waits:
        return None
    return sum(waits) / run.attempted * 1e3
