"""The sparse expert layer under the yardstick: what its grouped matrix
products need for the rows that were counted, and which device operations
of a traced step belong to which part of the layer.

The program names the parts of an expert layer with `jax.named_scope`s inside
the layer's own scope (`nn/layers/moe.py`): `route`, `dispatch`, `experts`,
`combine`. They reach the compiled step's text as
`.../jvp(MixtureOfExpertsLayer:block1-moe)/experts/ragged_dot_general`, and
`harness/step_scopes.py` joins that text to the trace's events. A program
without such a layer or such scopes has nothing to read here, and every
function returns None.
"""

from __future__ import annotations

import re

from benchmarks.harness import step_scopes

EXPERT_CLASS = "MixtureOfExpertsLayer"
#: the grouped products, whatever implements them: `jax.lax.ragged_dot`
#: (`ragged_dot_general`) or the megablox kernels (`gmm`, `tgmm`)
GROUPED_PRODUCT = re.compile(r"ragged_dot|gmm")


def gated_expert_ffn(rows: int, d_model: int, d_hidden: int,
                     experts_held: int, bytes_per_element: int = 2) -> dict:
    """Operations and bytes of the grouped products of gated experts
    (`W2(act(W1 x) * W3 x)`) over `rows` (token, expert) pairs spread over
    the held experts, forward and backward, the same whatever implements
    them. Each of the three forward products (`rows x d_model x d_hidden`,
    2 operations a multiply-add) has two backward ones of its size: for its
    input and for its weights.

    Bytes: a product that multiplies by the weights reads those of every
    held expert once (3 forward, 3 backward) and a weight gradient writes
    as much (3); activations and their gradients move by the row, an input
    and an output for each product."""
    product = 2.0 * rows * d_model * d_hidden
    weights = float(experts_held * d_model * d_hidden * bytes_per_element)
    by_row = float(rows * (d_model + d_hidden) * bytes_per_element)
    return {"flops": 9 * product, "bytes": 9 * weights + 9 * by_row}


def counted_rows(run):
    """(Token, expert) pairs the held experts computed in the last step, all
    expert layers together, or None where the family counts none."""
    by_layer = run.counters.get("expert_rows")
    if not by_layer:
        return None
    return sum(sum(rows) for rows in by_layer.values())


def _deciding_op_name(labels: step_scopes.Labels, instruction) -> str:
    """The `op_name` that labels an instruction, as `step_scopes` rule 2 has
    it: a fusion's matmul where it holds one, else its own, else the first
    that an instruction inside it carries."""
    if instruction.opcode == "fusion" and instruction.calls:
        inner = list(labels._body(instruction.calls))
        matmul = next((i for i in inner if i.op_name and i.opcode in
                       ("convolution", "dot", "ragged-dot", "custom-call")),
                      None)
        if matmul is not None:
            return matmul.op_name
        return instruction.op_name or next(
            (i.op_name for i in inner if i.op_name), "")
    return instruction.op_name


def scope_ms(run, wanted):
    """Device milliseconds per step, on the first device, of the traced
    operations inside an expert layer's scope whose deciding `op_name`
    `wanted(op_name)` accepts; None where the step has no expert layer."""
    if run.device_trace is None or run.step_text is None:
        return None
    if getattr(run, "step_labels", None) is None:
        run.step_labels = step_scopes.Labels(run.step_text)   # parsed once
    labels = run.step_labels
    plane = run.device_trace.first
    seen, seconds = False, 0.0
    for event, s in plane.ops.seconds_by_name().items():
        instruction = labels.instructions.get(event)
        if instruction is None:     # an event that is no instruction of it
            continue
        op_name = _deciding_op_name(labels, instruction)
        if EXPERT_CLASS + ":" not in op_name:
            continue
        seen = True
        if wanted(op_name):
            seconds += s
    return seconds / len(plane.steps) * 1e3 if seen else None


def in_scopes(*scopes):
    """A `wanted` for `scope_ms`: the operation was traced under one of the
    layer's own scopes `scopes` (the first after the layer's name)."""
    part = re.compile(EXPERT_CLASS + r":[^/()]+\)*/(\w+)")

    def wanted(op_name: str) -> bool:
        found = part.search(op_name)
        return bool(found) and found.group(1) in scopes
    return wanted


def grouped_products(op_name: str) -> bool:
    return in_scopes("experts")(op_name) and \
        bool(GROUPED_PRODUCT.search(op_name))
