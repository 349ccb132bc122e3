"""Attention under a sliding window, under the yardstick: what a windowed
kernel needs for one call, computed from its shapes, and which device
operations of a traced step belong to a window layer and which to a full
one.

The program names an attention layer's scope `Class:vertex`
(`observe/scope.py`), and `zoo.models.gated_window_moe_block` names the
vertex of a `sliding_attention` layer `block<l>-swa` and that of a
`full_attention` layer `block<l>-att`; both are of a class that holds
`Attention`. The splash kernels carry the same names under either mask
(`kernel_costs.FLASH_ATTENTION_OPS`), so a kernel is told apart by the
`op_name` of its custom call in the compiled step's text, which holds the
layer's scope. `harness/step_scopes.py` joins that text to the trace's
events. A program without such layers has nothing to read here, and every
function returns None.
"""

from __future__ import annotations

import re

from benchmarks.harness import kernel_costs, step_scopes

WINDOW_LAYER = re.compile(r"Attention\w*:[^/()]*-swa\b")
FULL_LAYER = re.compile(r"Attention\w*:[^/()]*-att\b")
SHARED_EXPERT = re.compile(r"DenseLayer:[^/()]*-shared[123]\b")


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs with `0 <= q - k < window` in a sequence of
    `seq`: `window * (window + 1) / 2` while the window fills, `window` for
    each query after; every causal pair where the window covers the
    sequence."""
    window = min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def windowed_attention(names, batch: int, heads: int, seq: int,
                       head_dim: int, window: int,
                       bytes_per_element: int = 2):
    """Operations and bytes of one layer's attention under a window, forward
    and backward, the same whatever implements it: the products of
    `kernel_costs.attention_causal` for the kernels whose `names` are found
    (seven with a fused backward, nine in three kernels), each over the
    pairs inside the window and not over the causal half; the bytes are the
    causal kernels' (every row of q, k, v and of the gradients is read and
    written once whatever the mask). None where no name is an attention
    kernel's."""
    causal = kernel_costs.attention_causal(names, batch, heads, seq, head_dim,
                                           bytes_per_element)
    if causal is None:
        return None
    # `attention_causal` counts each product over seq * seq / 2 pairs
    return {"flops": causal["flops"] * window_pairs(seq, window)
            / (seq * seq / 2), "bytes": causal["bytes"]}


def _labels(run):
    """`step_scopes.Labels` of the run's compiled step, parsed once a run
    (`expert_costs.scope_ms` keeps it under the same name); None where the
    run has no step text."""
    if run.step_text is None:
        return None
    if getattr(run, "step_labels", None) is None:
        run.step_labels = step_scopes.Labels(run.step_text)
    return run.step_labels


def _kernel_calls(run, layer):
    """The attention kernels' custom calls of the compiled step whose
    `op_name` lies under a layer that `layer` finds; None where there is no
    step text."""
    labels = _labels(run)
    if labels is None:
        return None
    return [i for i in labels.instructions.values()
            if i.opcode == "custom-call"
            and kernel_costs.FLASH_ATTENTION_OPS.search(i.name)
            and layer.search(i.op_name)]


def kernel_names(run, layer):
    """Names of those kernels, or None."""
    calls = _kernel_calls(run, layer)
    return None if calls is None else [i.name for i in calls]


def _ms_per_step(run, wanted):
    """Device milliseconds per step, on the first device, of the traced
    events that `wanted(event, labels)` accepts; None where there is no
    trace or no step text, or nothing is accepted."""
    labels = _labels(run)
    if labels is None or run.device_trace is None:
        return None
    plane = run.device_trace.first
    found = [seconds for event, seconds in plane.ops.seconds_by_name().items()
             if wanted(event, labels)]
    return sum(found) / len(plane.steps) * 1e3 if found else None


def layer_ms(run, layer, phases=("forward", "backward")):
    """Device milliseconds per step of the traced operations that
    `step_scopes` labels with a layer whose `Class:name` the pattern `layer`
    finds, in `phases`; None where the step has no such layer."""
    def wanted(event, labels):
        label = labels.of_event(event)
        return label.phase in phases and bool(layer.search(label.name))
    return _ms_per_step(run, wanted)


def kernel_ms(run, layer):
    """Device milliseconds per step in the attention kernels under the
    layers that `layer` finds; None where the trace holds none."""
    names = set(kernel_names(run, layer) or ())
    return _ms_per_step(run, lambda event, labels: event in names)
