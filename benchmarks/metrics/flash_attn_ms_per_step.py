"""Device milliseconds per step in the three kernels of
`jax.experimental.pallas.ops.tpu.flash_attention` (forward, dK/dV, dQ), on
the `XLA Ops` line of the first device."""

from benchmarks.harness import kernel_costs


def read(run):
    trace = run.device_trace
    if trace is None:
        return None
    seconds = trace.first.op_seconds(kernel_costs.FLASH_ATTENTION_OPS)
    return seconds / len(trace.first.steps) * 1e3 if seconds else None
