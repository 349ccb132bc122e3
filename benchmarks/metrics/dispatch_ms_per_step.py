"""Median milliseconds of the program's `step_dispatch` span (`_fit_batch`,
round the call of the jitted step) from the start of the window: the host's
own cost of one step, which bounds the rate once the step is short."""

import statistics


def read(run):
    spans = [e - s for name, s, e in run.spans if name == "step_dispatch"]
    return statistics.median(spans) * 1e3 if spans else None
