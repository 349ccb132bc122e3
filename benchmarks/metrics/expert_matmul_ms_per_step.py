"""Device milliseconds per step in the grouped matrix products of the expert
layers, forward and backward: the operations under the layers' `experts`
scope that `ragged_dot` or the megablox kernels made
(`harness/expert_costs.py`)."""

from benchmarks.harness import expert_costs


def read(run):
    return expert_costs.scope_ms(run, expert_costs.grouped_products) or None
