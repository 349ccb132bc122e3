"""Share of the window's drained wall time that steps slower than the
median took beyond it: 1 - steps * median time between steps / window. The
host is held to a few steps ahead of the device, or waits for batches, so
the listener's calls come at the pace at which steps complete. What a user
loses to stalls (a page-fault storm in the producer, a neighbour on the
host) beyond the pace the run mostly keeps."""

import statistics


def read(run):
    if not run.step_interval_s or not run.window_s:
        return None
    paced = run.steps * statistics.median(run.step_interval_s)
    return 100.0 * (1.0 - paced / run.window_s)
