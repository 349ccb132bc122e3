"""GB of batch payload per step, from `net.transfer_bytes` (exact). The
program counts a batch's bytes whether or not they moved, so this is read
only where every batch comes from the host."""


def read(run):
    moved = run.counters.get("transfer_bytes_per_step")
    return None if moved is None else moved / 1e9
