"""How many collective operations the compiled train step holds, all kinds
together (an exact count from its HLO)."""

from benchmarks.harness import hlo_text


def read(run):
    if run.step_text is None:
        return None
    return sum(hlo_text.collective_counts(run.step_text).values())
