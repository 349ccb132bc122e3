"""Device milliseconds per step in the forward and backward passes of the
sparse expert layers (class `MixtureOfExpertsLayer`): router, dispatch,
grouped products and combine together (`harness/step_scopes.py`). None where
the step names no such layer."""

from benchmarks.harness import expert_costs, step_scopes


def read(run):
    return step_scopes.class_ms(step_scopes.table(run),
                                lambda cls: cls == expert_costs.EXPERT_CLASS,
                                ("forward", "backward"))
