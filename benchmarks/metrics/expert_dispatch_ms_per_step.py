"""Device milliseconds per step, forward and backward, in what an expert
layer does around its products: its `route`, `dispatch` and `combine` scopes
(the router, the sort, the gathers into expert order and back, the weighted
sum) (`harness/expert_costs.py`)."""

from benchmarks.harness import expert_costs


def read(run):
    return expert_costs.scope_ms(
        run, expert_costs.in_scopes("route", "dispatch", "combine"))
