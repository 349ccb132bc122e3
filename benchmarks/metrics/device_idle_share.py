"""Share of the traced slice's steady window in which no operation ran on
the device: 1 minus the union of the busy intervals on the `XLA Ops` line
over the window, averaged over the chips."""


def read(run):
    trace = run.device_trace
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
