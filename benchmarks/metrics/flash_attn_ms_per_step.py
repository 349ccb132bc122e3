"""Device milliseconds per step in the attention kernels, on the `XLA Ops`
line of the first device: the stock flash kernels (forward, dK/dV, dQ) or
the splash kernels (forward, and one fused backward), whichever the trace
holds (`harness/kernel_costs.py:FLASH_ATTENTION_OPS`)."""

from benchmarks.harness import kernel_costs


def read(run):
    trace = run.device_trace
    if trace is None:
        return None
    seconds = trace.first.op_seconds(kernel_costs.FLASH_ATTENTION_OPS)
    return seconds / len(trace.first.steps) * 1e3 if seconds else None
