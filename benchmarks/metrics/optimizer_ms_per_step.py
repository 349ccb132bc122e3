"""Device milliseconds per step in operations the compiled step names under
the program's `optimizer` scope (`harness/step_scopes.py`): what of
`_apply_updates` the compiler left outside the weight gradients' fusions."""

from benchmarks.harness import step_scopes


def read(run):
    return step_scopes.phase_ms(run, "optimizer")
