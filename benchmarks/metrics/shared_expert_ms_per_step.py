"""Device milliseconds per step in the forward and backward passes of the
shared expert, the gated feed-forward that every token passes through
beside the routed experts: its three products by their vertex names,
`DenseLayer:block<l>-shared1|shared3|shared2` (`harness/window_costs.py`
over `harness/step_scopes.py`'s labels). None where the step names no such
vertex."""

from benchmarks.harness import window_costs


def read(run):
    return window_costs.layer_ms(run, window_costs.SHARED_EXPERT)
