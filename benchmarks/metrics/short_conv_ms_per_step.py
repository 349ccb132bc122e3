"""Device milliseconds per step in the forward and backward passes of the
gated short convolutions (class `GatedShortConvLayer`): both projections,
the gates and the depthwise convolution (`harness/step_scopes.py`). None
where the step names no such layer."""

from benchmarks.harness import step_scopes


def read(run):
    return step_scopes.class_ms(step_scopes.table(run),
                                lambda cls: cls == "GatedShortConvLayer",
                                ("forward", "backward"))
