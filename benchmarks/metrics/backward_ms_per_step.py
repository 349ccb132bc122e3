"""Device milliseconds per step in operations of the backward pass: those
the compiled step names under `transpose(jvp(...))`
(`harness/step_scopes.py`). A fusion takes the phase of the matmul in it, so
a weight gradient with the optimizer's update fused into it counts here, and
`step_scope_coverage` says how much of the step is fused across phases."""

from benchmarks.harness import step_scopes


def read(run):
    return step_scopes.phase_ms(run, "backward")
