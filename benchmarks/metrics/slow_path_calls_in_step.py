"""Calls on the slow side of the program's gates while the first step was
traced (an exact count): under the first `step_dispatch`, the sum of
`attention.einsum_calls`, `attention.window_einsum_calls`,
`loss.one_hot_calls`, `moe.held_rows_plain_calls` and
`activation.gelu_erfc_calls`, which the program's tracer counts on the span
that was open when the choice was made. 0 where every layer took its kernel,
its class ids, its held rows and its one-branch GELU; a cell on the einsum
side of the attention gate by design reads its layer count. The line before
the value holds every count of that span, the fast sides and
`attention.sharded_kernel_calls` among them. None where the program opens no
`step_dispatch` span or its spans carry no counts."""

from benchmarks.harness import setup_spans

SLOW = ("attention.einsum_calls", "attention.window_einsum_calls",
        "loss.one_hot_calls", "moe.held_rows_plain_calls",
        "activation.gelu_erfc_calls")


def read(run):
    setup = setup_spans.collect(run)
    if setup is None or setup.first_step is None \
            or not hasattr(setup.first_step, "counts"):
        return None
    setup_spans.say("slow_path_calls_in_step",
                    setup_spans.describe(setup, setup.first_step))
    counts = setup_spans.counts_of(setup.first_step)
    return sum(counts.get(name, 0) for name in SLOW)
