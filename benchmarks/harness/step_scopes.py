"""Which layer of the model the device is running: the traced slice's device
time by phase (forward, backward, optimizer) and by layer.

The program names its train step's parts with `jax.named_scope`
(`deeplearning4j_tpu/observe/scope.py`): a layer is `Class:name`, and there
are `loss`, `regularization`, `optimizer` and `cast_params`. `jax.grad` adds
`jvp(...)` round a forward scope and `transpose(jvp(...))` round a backward
one. The names reach the compiled step's text (`run.step_text`) as
`metadata={op_name="jit(train_step)/jvp(DenseLayer:fc)/dot_general"}`, and
an event on the trace's `XLA Ops` line is named by its instruction
(`fusion.12`). The join is a dictionary: no new parsing of the profiler's
file, and no event statistics.

Rules, in the order they are applied to an event:

1. Its instruction's `op_name` gives a phase (`optimizer` if it holds
   `/optimizer/`, else `backward` if `transpose(`, else `forward` if `jvp(`,
   else `other`) and a layer (the innermost `Class:name`; class `-` where
   there is none, or the bare scope `cast_params` / `regularization`).
   Several names joined by `;` count as several, and the first that has a
   phase labels the instruction.
2. A fusion takes the label of the `convolution` / `dot` inside the
   computation it calls (or a fusion nested in it), where there is one
   (the matmul is what costs), else its own `op_name`. It is *mixed* where
   the instructions of that computation carry more than one phase, `other`
   among them: an operation of the step that no scope names, fused into a
   named one, is how a dropped scope shows.
   Instructions that compute nothing (`FREE`: a constant keeps the name of
   whoever made it first) are not asked. Nor is, in a fusion that holds
   backward instructions, a forward one that works element by element or
   only moves data (any but `COMBINING`): that is the backward pass
   recomputing what it did not keep (the compiler duplicates a GELU, or a
   softmax's exponential, into the matmul that needs it), not a mix.
3. An instruction without an `op_name` (a layout `copy`, `copy-start` /
   `slice-start` and their `-done`, a `bitcast`) is the compiler's way of
   feeding another: it takes the label of the first instruction that uses
   its result and has one, looked for through at most `HOPS` such users.
4. An event whose name is no instruction (a Pallas kernel may be named by
   its kernel and not by its instruction) is looked up among the
   `custom-call`s whose name or `op_name` contains it; it takes their label
   where they agree on phase and class, else `other`.

Every event's self seconds go to exactly one label, so the table's total is
the line's, nothing counted twice and nothing dropped.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

from benchmarks.harness import xplane

PHASES = ("forward", "backward", "optimizer", "other")
BARE_SCOPES = ("cast_params", "regularization")
HOPS = 4
FREE = frozenset(("parameter", "constant", "broadcast", "bitcast", "iota",
                  "tuple", "get-tuple-element", "reshape"))
COMBINING = frozenset((
    "dot", "convolution", "reduce", "reduce-window", "select-and-scatter",
    "scatter", "gather", "sort", "custom-call", "fft", "cholesky",
    "triangular-solve", "rng", "rng-bit-generator", "all-reduce",
    "all-gather", "reduce-scatter", "all-to-all", "collective-permute"))
TOP_NAMES = 20

_DEFINITION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OPCODE = re.compile(r"([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_LAYER = re.compile(r"([A-Za-z_]\w*):([^/()]+)")
_NOT_WORD = re.compile(r"\W")      # an instruction's name has `_` for each


@dataclasses.dataclass(frozen=True)
class Label:
    phase: str          # one of PHASES
    cls: str            # layer class, a bare scope, a collective's kind or '-'
    name: str           # 'Class:name', with '/loss' for an output's loss
    mixed: str = ""     # 'backward+optimizer' for a fusion that holds both


OTHER = Label("other", "-", "-")


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    op_name: str        # '' where the compiler gave none
    calls: str          # the computation a fusion calls, or ''
    operands: tuple


# ------------------------------------------------------------------ parsing
def _after_shape(rest: str) -> str:
    """What follows an instruction's shape, which is one token or, for a
    tuple, a parenthesis that holds spaces and layouts with parentheses."""
    if not rest.startswith("("):
        return rest.partition(" ")[2]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[i + 1:].lstrip()
    return ""


def _operands(after_opcode: str) -> tuple:
    """Names in the balanced parenthesis that opens `after_opcode`."""
    depth = 0
    for i, ch in enumerate(after_opcode):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return tuple(_OPERAND.findall(after_opcode[:i]))
    return ()


def _whole_lines(text: str):
    """The text's lines, an instruction that the text breaks over several
    joined into one. jax 0.9.0 writes a Pallas kernel's metadata as JSON
    with a newline after every brace and comma, so its custom call ends on
    a line of its own that starts with `}}`: a line belongs to the one
    before it for as long as that one's braces are open. The line that
    opens a computation ends in its open brace and stands alone."""
    pending, depth = [], 0
    for line in text.splitlines():
        if not pending and _COMPUTATION.match(line):
            yield line
            continue
        pending.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            yield " ".join(pending)
            pending, depth = [], 0
    if pending:
        yield " ".join(pending)


def parse(text: str):
    """`(instructions, computations)`: `{instruction name: Instruction}` and
    `{computation name: [its Instructions]}` of a compiled module's
    text. Where a name is used in two computations (a fused computation's
    parameters are), the one outside a fused computation is kept: only such
    instructions run as events of their own."""
    computations, current = {}, None
    for line in _whole_lines(text):
        opened = _COMPUTATION.match(line)
        if opened:
            current = computations.setdefault(opened.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        definition = _DEFINITION.match(line) if current is not None else None
        if not definition:
            continue
        after = _after_shape(definition.group(2))
        opcode = _OPCODE.match(after)
        if not opcode:
            continue
        op_name = _OP_NAME.search(after)
        calls = _CALLS.search(after)
        current.append(Instruction(
            definition.group(1), opcode.group(1),
            op_name.group(1) if op_name else "",
            calls.group(1) if calls else "",
            _operands(after[opcode.end() - 1:])))
    fused = {i.calls for body in computations.values() for i in body
             if i.opcode == "fusion"}
    instructions = {}
    for inside_fusion in (True, False):
        for name, body in computations.items():
            if (name in fused) == inside_fusion:
                instructions.update((i.name, i) for i in body)
    return instructions, computations


# ----------------------------------------------------------------- labelling
def _label_of_one(one: str) -> Label:
    if "/optimizer/" in one:
        phase = "optimizer"
    elif "transpose(" in one:
        phase = "backward"
    elif "jvp(" in one:
        phase = "forward"
    else:
        phase = "other"
    layers = _LAYER.findall(one)
    if layers:
        cls, name = layers[-1]
        tail = one[one.rindex(f"{cls}:{name}"):]
        name = f"{cls}:{name}" + ("/loss" if "/loss" in tail else "")
    else:
        cls = name = next((s for s in BARE_SCOPES if s in one), "-")
    return Label(phase, cls, name)


def label_of(op_name: str) -> Label:
    """Rule 1, for an `op_name` that may hold several, joined by `;`."""
    labels = [_label_of_one(one) for one in op_name.split(";")]
    return next((l for l in labels if l.phase != "other"), labels[0])


def _phases(op_name: str) -> set:
    """The phases of the operations that `op_name` names. One that the step
    traced (`jit(train_step)/mul`) outside every scope counts as `other`; an
    argument's name (`labels[0]`, which a layout copy of it keeps) as none."""
    return {phase for one in op_name.split(";")
            for phase in (_label_of_one(one).phase,)
            if phase != "other" or one.startswith("jit(")}


class Labels:
    """The label of every instruction of one compiled step, and of an event
    by its name."""

    def __init__(self, text: str):
        self.instructions, self.computations = parse(text)
        self._users = {}
        for instruction in self.instructions.values():
            for operand in instruction.operands:
                self._users.setdefault(operand, []).append(instruction.name)
        self._labels = {}
        self._kernels = [i for i in self.instructions.values()
                         if i.opcode == "custom-call" and i.op_name]

    def _body(self, computation: str):
        """The instructions of a fused computation, with those of the
        fusions nested in it in place of them."""
        for instruction in self.computations.get(computation, ()):
            if instruction.opcode == "fusion" and instruction.calls:
                yield from self._body(instruction.calls)
            else:
                yield instruction

    def _own(self, instruction: Instruction):
        """Rules 1 and 2: a label from the instruction itself, or None where
        it carries no name."""
        if instruction.opcode == "fusion" and instruction.calls:
            inner = list(self._body(instruction.calls))
            working = [(i, _phases(i.op_name)) for i in inner
                       if i.opcode not in FREE]
            phases = set().union(*(p for _, p in working))
            if "backward" in phases:
                phases = set().union(*(
                    p for i, p in working
                    if p != {"forward"} or i.opcode in COMBINING))
            matmul = next((i for i in inner if i.opcode in
                           ("convolution", "dot") and i.op_name), None)
            source = matmul.op_name if matmul else instruction.op_name
            if not source:
                source = next((i.op_name for i in inner if i.op_name), "")
            if not source:
                return None
            mixed = "+".join(p for p in PHASES if p in phases)
            return dataclasses.replace(
                label_of(source), mixed=mixed if len(phases) > 1 else "")
        return label_of(instruction.op_name) if instruction.op_name else None

    def of_instruction(self, name: str, hops: int = HOPS) -> Label:
        if name in self._labels:
            return self._labels[name]
        instruction = self.instructions[name]
        label = self._own(instruction)
        if label is None:                   # rule 3
            label = OTHER
            if hops:
                for user in self._users.get(name, ()):
                    label = self.of_instruction(user, hops - 1)
                    if label is not OTHER:
                        break
        if hops == HOPS:
            self._labels[name] = label
        return label

    def of_event(self, name: str) -> Label:
        if name in self.instructions:
            label = self.of_instruction(name)
        else:                               # rule 4
            kind = xplane.kind_of(name)
            found = {dataclasses.replace(self.of_instruction(k.name),
                                         name="-")
                     for k in self._kernels
                     if kind in k.name or kind in _NOT_WORD.sub("_", k.op_name)}
            if len(found) != 1:
                return dataclasses.replace(OTHER, name=kind)
            label = dataclasses.replace(found.pop(), name=kind)
        collective = xplane.COLLECTIVE.match(name)
        if collective:
            # apart from the layers: whose operation it was made for says
            # its phase, but its time is the interconnect's
            return dataclasses.replace(label, cls=collective.group(1))
        return label


# ---------------------------------------------------------------- accounting
def account(labels: Labels, ops, steps: int) -> dict:
    """The table of one `xplane.Line` of operations over `steps` steps.
    Milliseconds are per step; every event's self time is in exactly one
    cell of `by_phase_and_class`, so `total_ms` is their sum."""
    cells, names, unlabelled, mixed = {}, {}, {}, {}
    total = covered = 0.0
    for event, seconds in ops.seconds_by_name().items():
        label = labels.of_event(event)
        total += seconds
        if label.mixed:
            mixed[label.mixed] = mixed.get(label.mixed, 0.0) + seconds
        if label.phase != "other" and not label.mixed:
            covered += seconds
        else:
            kind = xplane.kind_of(event) + (" (mixed)" if label.mixed else "")
            unlabelled[kind] = unlabelled.get(kind, 0.0) + seconds
        key = (label.phase, label.cls)
        cells[key] = cells.get(key, 0.0) + seconds
        key = (label.phase, label.name)
        names[key] = names.get(key, 0.0) + seconds

    def ms(seconds: float) -> float:
        return seconds / steps * 1e3

    def ranked(table: dict, top=None) -> list:
        return [[*key, ms(s)] for key, s in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    by_phase = {p: ms(sum(s for (phase, _), s in cells.items() if phase == p))
                for p in PHASES}
    return {"steps": steps, "total_ms": ms(total), "by_phase_ms": by_phase,
            "mixed_ms": {k: ms(s) for k, s in sorted(mixed.items())},
            "coverage_percent": 100.0 * covered / total if total else None,
            "by_phase_and_class": ranked(cells),
            "costliest_names": ranked(names, TOP_NAMES),
            "other_or_mixed_kinds": ranked(
                {(k,): s for k, s in unlabelled.items()}, TOP_NAMES)}


_TABLES = {}        # a run's xplane_path -> its table


def table(run):
    """The table of `run`'s traced slice on its first device, made once per
    run, written to `<out_dir>/step_scopes.json` and printed; None where
    there is no device trace or no compiled step to read."""
    if run.device_trace is None or run.step_text is None:
        return None
    if run.xplane_path not in _TABLES:
        plane = run.device_trace.first
        made = account(Labels(run.step_text), plane.ops, len(plane.steps))
        with open(os.path.join(run.out_dir, "step_scopes.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(made, fh, indent=1)
        print("step_scopes " + json.dumps(made), flush=True)
        _TABLES[run.xplane_path] = made
    return _TABLES[run.xplane_path]


def phase_ms(run, phase: str):
    """Milliseconds per step of `phase` in `run`'s table, or None."""
    made = table(run)
    return None if made is None else made["by_phase_ms"][phase]


def class_ms(made, wanted, phases=PHASES):
    """Milliseconds per step of the cells of a table (None for none) whose
    class `wanted(cls)` accepts, in `phases`; None where the step has no
    such class."""
    found = [ms for phase, cls, ms in (made or {}).get(
        "by_phase_and_class", ()) if phase in phases and wanted(cls)]
    return sum(found) if found else None
