"""The windowed attention kernels' share of their roofline: the least time
the chip could take for the pairs inside the window in every window layer
of one step (`harness/window_costs.py:windowed_attention`, from the shapes
and the configuration's `sliding_window`; the same whatever implements the
kernel) over the device time the trace shows for the kernels under those
layers (`window_attn_kernel_ms_per_step`)."""

from benchmarks.harness import kernel_costs, window_costs


def read(run):
    ms = window_costs.kernel_ms(run, window_costs.WINDOW_LAYER)
    if not ms or run.peaks is None:
        return None
    config, mix = run.cell.config, run.cell.traffic
    cost = window_costs.windowed_attention(
        window_costs.kernel_names(run, window_costs.WINDOW_LAYER),
        mix["batch"], config["num_attention_heads"], mix["seq_len"],
        config["head_dim"], config["sliding_window"])
    least, _bound = kernel_costs.min_seconds(cost, run.peaks)
    layers = sum(kind == "sliding_attention"
                 for kind in config["layer_types"])
    return 100.0 * layers * least * 1e3 / ms
