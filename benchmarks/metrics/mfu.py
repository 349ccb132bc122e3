"""Model FLOP/s utilisation: the operations the forward and backward
passes need for an item (the family's `required_flops_per_item`; nothing
recomputed is counted) times the items per second of drained wall time
(over the untraced part of a traced run's window), over chips times the
published bf16 peak."""


def read(run):
    if run.peaks is None:
        return None
    needed = run.cell.family.required_flops_per_item(run.cell.config,
                                                     run.cell.traffic)
    peak = run.cell.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * needed * run.items / run.window_s / peak
