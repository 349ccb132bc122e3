"""The attention kernels' share of their roofline: the least time the chip
could take for what the kernels need in every layer of one step
(`harness/kernel_costs.py`, from the shapes: nine products in three kernels
for the stock flash split, seven in two for the splash kernels with a fused
backward, by the kernel names the trace holds) over the device time the
trace shows for them. At these shapes the bound is compute."""

from benchmarks.harness import kernel_costs


def read(run):
    trace = run.device_trace
    if trace is None or run.peaks is None:
        return None
    seconds = trace.first.op_seconds(kernel_costs.FLASH_ATTENTION_OPS)
    if not seconds:
        return None
    config, mix = run.cell.config, run.cell.traffic
    cost = kernel_costs.attention_causal(
        trace.first.ops.names, mix["batch"], config["n_head"],
        mix["seq_len"], config["n_embd"] // config["n_head"])
    least, _bound = kernel_costs.min_seconds(cost, run.peaks)
    return 100.0 * config["n_layer"] * least * len(trace.first.steps) / seconds
