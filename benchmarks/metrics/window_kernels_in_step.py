"""How many attention kernel calls of the compiled train step belong to a
window layer (an exact count from its HLO: the custom calls under an
attention kernel's name, as `flash_kernels_in_step` counts them, whose
`op_name` lies under a `...Attention...:block<l>-swa` scope;
`harness/window_costs.py`). The splash kernels carry the same names under
either mask, so the scope is what tells them apart."""

from benchmarks.harness import window_costs


def read(run):
    names = window_costs.kernel_names(run, window_costs.WINDOW_LAYER)
    return None if names is None else len(names)
