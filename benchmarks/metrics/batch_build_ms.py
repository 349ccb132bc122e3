"""Median milliseconds the producer thread spent in the family's
`make_batch` (the benchmark's own span round it), over the window."""

import statistics


def read(run):
    built = run.counters.get("batch_build_s")
    return statistics.median(built) * 1e3 if built else None
