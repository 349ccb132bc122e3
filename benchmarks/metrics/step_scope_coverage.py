"""Share of the traced step's device time that `harness/step_scopes.py`
gives to one phase without doubt: its operation carries a phase, and is no
fusion that holds more than one. The guard on the phase and layer metrics: a
PR that drops a scope, or a compiler that fuses across phases, shows here
first."""

from benchmarks.harness import step_scopes


def read(run):
    table = step_scopes.table(run)
    return None if table is None else table["coverage_percent"]
