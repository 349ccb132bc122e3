"""(Token, expert) pairs the held experts computed in the last step, all
expert layers together: the layers' own count (`expert_rows` in their
state), exact. None where the family counts none."""

from benchmarks.harness import expert_costs


def read(run):
    return expert_costs.counted_rows(run)
