"""Median duration of the train step's events on the `XLA Modules` line of
the first device, over the traced slice."""

import statistics


def read(run):
    if run.device_trace is None:
        return None
    return statistics.median(run.device_trace.first.step_seconds()) * 1e3
