"""Device milliseconds per step in operations of the forward pass: those the
compiled step names under `jvp(...)` (`harness/step_scopes.py`), the
mixed-precision cast of the parameters (`cast_params`) with them."""

from benchmarks.harness import step_scopes


def read(run):
    return step_scopes.phase_ms(run, "forward")
