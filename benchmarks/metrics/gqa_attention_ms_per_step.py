"""`attention_ms_per_step` for a configuration with grouped-query heads:
device milliseconds per step in the forward and backward passes of the
layers whose class holds `Attention` (projections, QK-norm, rotation, the
repeat of K and V, kernels or einsums and the copies round them)
(`harness/step_scopes.py`). None where the step names no such layer."""

from benchmarks.harness import step_scopes


def read(run):
    return step_scopes.class_ms(step_scopes.table(run),
                                lambda cls: "Attention" in cls,
                                ("forward", "backward"))
