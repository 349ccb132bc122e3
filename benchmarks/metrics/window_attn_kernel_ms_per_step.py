"""Device milliseconds per step in the attention kernels of the window
layers, forward and backward: the trace's events named as the custom calls
of the compiled step that carry an attention kernel's name
(`kernel_costs.FLASH_ATTENTION_OPS`) and whose `op_name` lies under a
`...Attention...:block<l>-swa` scope (`harness/window_costs.py`)."""

from benchmarks.harness import window_costs


def read(run):
    return window_costs.kernel_ms(run, window_costs.WINDOW_LAYER)
