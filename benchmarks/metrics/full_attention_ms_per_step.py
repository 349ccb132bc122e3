"""`window_attention_ms_per_step` for the attention layers of the same model
that see every earlier key: the layers whose scope is `<a class that holds
Attention>:block<l>-att` (`harness/window_costs.py`). None where the step
names no such layer."""

from benchmarks.harness import window_costs


def read(run):
    return window_costs.layer_ms(run, window_costs.FULL_LAYER)
