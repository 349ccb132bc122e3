"""Milliseconds per step in which the first device ran nothing while no
`host_wait` span was open on the host: what the training loop itself
(dispatch, listeners, Python) leaves the device waiting for."""


def read(run):
    if run.device_trace is None:
        return None
    loop = sum(s for names, s in run.idle_by_host_span.items()
               if "host_wait" not in names.split("+"))
    return loop / len(run.device_trace.first.steps) * 1e3
