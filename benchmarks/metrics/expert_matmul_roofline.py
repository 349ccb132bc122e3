"""The grouped products' share of their roofline: the least time the chip
could take for the (token, expert) pairs the held experts were counted to
compute in a step (`harness/expert_costs.py:gated_expert_ffn`, from the
layers' `expert_rows`; the same whatever implements the products) over the
device time the trace shows for them. At 1,024 rows an expert the bound is
compute."""

from benchmarks.harness import expert_costs, kernel_costs


def read(run):
    rows = expert_costs.counted_rows(run)
    ms = expert_costs.scope_ms(run, expert_costs.grouped_products)
    if not rows or not ms or run.peaks is None:
        return None
    config = run.cell.config
    cost = expert_costs.gated_expert_ffn(
        rows, config["hidden_size"], config["moe_intermediate_size"],
        config["num_experts_held"] * len(run.counters["expert_rows"]))
    least, _bound = kernel_costs.min_seconds(cost, run.peaks)
    return 100.0 * least * 1e3 / ms
