"""Tokens trained per second of drained wall time over the window, all
chips together: the steps dispatched after `t0` times the step's tokens,
over `t1 - t0`, both taken with the device drained."""


def read(run):
    return run.items / run.window_s
