"""Seconds of the first `step_dispatch` that jax spent tracing the step in
Python (`jax_trace`) and lowering it to StableHLO (`jax_lowering`, the
Pallas kernels to Mosaic among it): the cost of a first step that no
compile cache saves. The length of the union of those spans' intervals
below the first `step_dispatch`: jax reports an inner `jit`'s trace inside
the outer one's, and lowering traces too, so their sum would count twice.
The line before the value gives the step's span and, for each kind of
span jax reported below it, how many there were and their own union. None where the program opens
no `step_dispatch` span."""

from benchmarks.harness import setup_spans


def read(run):
    setup = setup_spans.collect(run)
    if setup is None or setup.first_step is None:
        return None
    step = setup.first_step
    traces = setup.under(step, "jax_trace")
    lowerings = setup.under(step, "jax_lowering")
    setup_spans.say("step_trace_s",
                    setup_spans.describe(setup, step, below=True))
    return setup.union_seconds(traces + lowerings)
