"""Device milliseconds per step in the forward and backward passes of the
attention layers that see a sliding window: the layers whose scope is
`<a class that holds Attention>:block<l>-swa` (projections, the output
gate, QK-norm, rotation, the repeat of K and V, the windowed kernels and the
copies round them) (`harness/window_costs.py`, over
`harness/step_scopes.py`'s labels). None where the step names no such
layer."""

from benchmarks.harness import window_costs


def read(run):
    return window_costs.layer_ms(run, window_costs.WINDOW_LAYER)
