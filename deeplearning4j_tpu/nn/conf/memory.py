"""Memory estimation: analytic per-layer forecasts + compiled-HLO analysis.

Parity with the reference's ``nn/conf/memory/`` package
(`MemoryReport.java:70`, `LayerMemoryReport.java`, `NetworkMemoryReport.java`,
`MemoryType.java`, `MemoryUseMode.java`): analytic, pre-run forecasts of
parameter / gradient / updater-state / activation memory per layer and per
network, JSON-serialisable.

TPU addition the reference cannot offer: :func:`compiled_memory_analysis` asks
XLA for the *actual* buffer assignment of the jitted training step
(``lowered.compile().memory_analysis()``) — exact HBM numbers (arguments,
outputs, temps, generated code) instead of an estimate.
"""

from __future__ import annotations

import enum
import json
from typing import Dict, List, Optional

import numpy as np


class MemoryType(enum.Enum):
    """What a block of memory is used for (``MemoryType.java``)."""

    PARAMETERS = "parameters"
    PARAMETER_GRADIENTS = "parameter_gradients"
    ACTIVATIONS = "activations"
    ACTIVATION_GRADIENTS = "activation_gradients"
    UPDATER_STATE = "updater_state"
    WORKING_MEMORY_FIXED = "working_memory_fixed"
    WORKING_MEMORY_VARIABLE = "working_memory_variable"

    def is_inference(self) -> bool:
        """Types that exist during inference as well as training
        (``MemoryType.java:16-25``)."""
        return self in (MemoryType.PARAMETERS, MemoryType.ACTIVATIONS,
                        MemoryType.WORKING_MEMORY_FIXED,
                        MemoryType.WORKING_MEMORY_VARIABLE)


class MemoryUseMode(enum.Enum):
    TRAINING = "training"
    INFERENCE = "inference"


# updater classname -> number of state copies of the params it keeps
_UPDATER_STATE_MULT = {
    "Sgd": 0, "NoOp": 0,
    "Nesterovs": 1, "AdaGrad": 1, "RmsProp": 1,
    "Adam": 2, "AdaMax": 2, "AdaDelta": 2, "Nadam": 2,
    "AMSGrad": 3,
}


def updater_state_multiplier(updater) -> int:
    return _UPDATER_STATE_MULT.get(type(updater).__name__, 2)


class LayerMemoryReport:
    """Per-layer memory forecast (``LayerMemoryReport.java``): fixed counts
    (params, updater state) and per-example counts (activations, working
    memory), in *elements*; byte totals computed against a minibatch size and
    dtype width."""

    def __init__(self, layer_name: str, layer_type: str, *, parameters: int = 0,
                 updater_state: int = 0, activations_per_ex: int = 0,
                 working_mem_fixed: int = 0, working_mem_per_ex: int = 0):
        self.layer_name = layer_name
        self.layer_type = layer_type
        self.parameters = int(parameters)
        self.updater_state = int(updater_state)
        self.activations_per_ex = int(activations_per_ex)
        self.working_mem_fixed = int(working_mem_fixed)
        self.working_mem_per_ex = int(working_mem_per_ex)

    def get_memory_elements(self, memory_type: MemoryType, minibatch: int,
                            mode: MemoryUseMode = MemoryUseMode.TRAINING) -> int:
        training = mode is MemoryUseMode.TRAINING
        if memory_type is MemoryType.PARAMETERS:
            return self.parameters
        if memory_type is MemoryType.PARAMETER_GRADIENTS:
            return self.parameters if training else 0
        if memory_type is MemoryType.ACTIVATIONS:
            return self.activations_per_ex * minibatch
        if memory_type is MemoryType.ACTIVATION_GRADIENTS:
            return self.activations_per_ex * minibatch if training else 0
        if memory_type is MemoryType.UPDATER_STATE:
            return self.updater_state if training else 0
        if memory_type is MemoryType.WORKING_MEMORY_FIXED:
            return self.working_mem_fixed
        if memory_type is MemoryType.WORKING_MEMORY_VARIABLE:
            return self.working_mem_per_ex * minibatch
        return 0

    def get_total_memory_bytes(self, minibatch: int,
                               mode: MemoryUseMode = MemoryUseMode.TRAINING,
                               bytes_per_element: int = 4) -> int:
        return sum(self.get_memory_elements(t, minibatch, mode)
                   for t in MemoryType) * bytes_per_element

    def to_dict(self) -> dict:
        return {"layer_name": self.layer_name, "layer_type": self.layer_type,
                "parameters": self.parameters,
                "updater_state": self.updater_state,
                "activations_per_ex": self.activations_per_ex,
                "working_mem_fixed": self.working_mem_fixed,
                "working_mem_per_ex": self.working_mem_per_ex}

    @staticmethod
    def from_dict(d: dict) -> "LayerMemoryReport":
        return LayerMemoryReport(d["layer_name"], d["layer_type"],
                                 parameters=d["parameters"],
                                 updater_state=d["updater_state"],
                                 activations_per_ex=d["activations_per_ex"],
                                 working_mem_fixed=d.get("working_mem_fixed", 0),
                                 working_mem_per_ex=d.get("working_mem_per_ex", 0))


class NetworkMemoryReport:
    """Whole-network forecast: aggregates layer reports
    (``NetworkMemoryReport.java:26``)."""

    def __init__(self, layer_reports: List[LayerMemoryReport], model_name: str,
                 input_elements_per_ex: int = 0, bytes_per_element: int = 4):
        self.layer_reports = list(layer_reports)
        self.model_name = model_name
        self.input_elements_per_ex = int(input_elements_per_ex)
        self.bytes_per_element = bytes_per_element

    def get_name(self) -> str:
        return self.model_name

    def get_memory_bytes(self, memory_type: MemoryType, minibatch: int,
                         mode: MemoryUseMode = MemoryUseMode.TRAINING) -> int:
        total = sum(r.get_memory_elements(memory_type, minibatch, mode)
                    for r in self.layer_reports)
        if memory_type is MemoryType.ACTIVATIONS:
            total += self.input_elements_per_ex * minibatch
        return total * self.bytes_per_element

    def get_total_memory_bytes(self, minibatch: int,
                               mode: MemoryUseMode = MemoryUseMode.TRAINING) -> int:
        return sum(self.get_memory_bytes(t, minibatch, mode) for t in MemoryType)

    def to_json(self) -> str:
        return json.dumps({
            "model_name": self.model_name,
            "bytes_per_element": self.bytes_per_element,
            "input_elements_per_ex": self.input_elements_per_ex,
            "layers": [r.to_dict() for r in self.layer_reports],
        })

    @staticmethod
    def from_json(s: str) -> "NetworkMemoryReport":
        d = json.loads(s)
        return NetworkMemoryReport(
            [LayerMemoryReport.from_dict(r) for r in d["layers"]],
            d["model_name"], d.get("input_elements_per_ex", 0),
            d.get("bytes_per_element", 4))

    def __str__(self) -> str:
        lines = [f"NetworkMemoryReport: {self.model_name} "
                 f"({len(self.layer_reports)} layers)"]
        header = f"  {'layer':<24}{'type':<26}{'params':>12}{'act/ex':>10}"
        lines.append(header)
        for r in self.layer_reports:
            lines.append(f"  {r.layer_name:<24}{r.layer_type:<26}"
                         f"{r.parameters:>12}{r.activations_per_ex:>10}")
        for mb in (1, 32):
            tot = self.get_total_memory_bytes(mb)
            lines.append(f"  total training memory @ batch {mb}: "
                         f"{tot / (1 << 20):.2f} MiB")
        return "\n".join(lines)


def network_memory_report(conf, model_name: str = "MultiLayerNetwork") -> NetworkMemoryReport:
    """Build a NetworkMemoryReport from a finalized MultiLayerConfiguration
    (the reference builds these via ``getMemoryReport(InputType)``)."""
    import math

    bytes_per = 4 if conf.global_conf.dtype in ("float32",) else (
        8 if conf.global_conf.dtype == "float64" else 2)
    reports = []
    for i, l in enumerate(conf.layers):
        n_params = l.num_params()
        act = 0
        if conf.input_type is not None and conf.layer_input_types[i] is not None:
            out = l.output_type(conf.layer_input_types[i])
            act = int(math.prod(out.batch_shape(1)))
        upd = getattr(l, "updater", None) or conf.global_conf.updater
        mult = updater_state_multiplier(upd) if upd is not None else 0
        reports.append(LayerMemoryReport(
            l.name or f"layer{i}", type(l).__name__,
            parameters=n_params, updater_state=n_params * mult,
            activations_per_ex=act))
    in_elems = 0
    if conf.input_type is not None:
        in_elems = int(math.prod(conf.input_type.batch_shape(1)))
    return NetworkMemoryReport(reports, model_name, in_elems, bytes_per)


def compiled_memory_analysis(net, batch: int = 32) -> Dict[str, int]:
    """Exact memory numbers from XLA's buffer assignment for the jitted
    training step — measured, not estimated. Returns byte counts
    (``argument_size``, ``output_size``, ``temp_size``, ``alias_size``,
    ``generated_code_size``) plus ``total``."""
    import jax
    import jax.numpy as jnp

    if net.params is None:
        net.init()
    if net.conf.input_type is None:
        raise ValueError("compiled_memory_analysis requires the configuration "
                         "to have an input type (set_input_type(...)) so the "
                         "step can be traced with concrete shapes")
    dtype = net.conf.global_conf.jnp_dtype()
    in_shape = net.conf.input_type.batch_shape(batch)
    out_type = net.conf.output_type()
    out_shape = out_type.batch_shape(batch)
    x = jnp.zeros(in_shape, dtype)
    y = jnp.zeros(out_shape, dtype)

    def step(params, upd_states, x, y):
        new_params, _, new_upd, loss, _ = net._step_body(
            params, net.states, upd_states, jnp.float32(0), jnp.float32(0),
            (x, y, None, None), None)
        return new_params, new_upd, loss

    lowered = jax.jit(step).lower(net.params, net.updater_states, x, y)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    if ma is None:  # backend without memory analysis
        return {}
    out = {
        "argument_size": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_size": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_size": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_size": int(getattr(ma, "alias_size_in_bytes", 0)),
        "generated_code_size": int(getattr(ma, "generated_code_size_in_bytes", 0)),
    }
    out["total"] = (out["argument_size"] + out["output_size"]
                    + out["temp_size"] + out["generated_code_size"])
    return out
