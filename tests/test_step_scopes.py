"""The names the train step carries into the compiled program
(`observe/scope.py`) and the spans `_fit_batch` opens: the seam the
benchmark's `harness/step_scopes.py` and two of its metrics read."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

VOCAB, SEQ = 64, 16
OP_NAME = re.compile(r'op_name="(jit\(train_step\)/[^"]*)"')
LAYER = re.compile(r"(\w+):([^/()]+)")
#: what the step does outside the gradient and the updates: it splits the
#: random key and counts the iteration
UNPHASED = re.compile(r"threefry|random_|/(add|slice|squeeze)$")


def transformer():
    conf = TransformerLM(vocab_size=VOCAB, max_length=SEQ, n_layers=2,
                         d_model=32, n_heads=2, d_ff=64, seed=0).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    net = ComputationGraph(conf).init()
    tokens = np.random.default_rng(0).integers(
        0, VOCAB, (2, SEQ)).astype(np.int32)
    return net, DataSet(tokens, lm_labels(tokens, VOCAB))


def multilayer():
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=4)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
    return net, DataSet(x, y)


def compiled_step_text(net, ds) -> str:
    it, ep, rng = net._device_tick()
    if isinstance(net, ComputationGraph):
        lowered = net._get_train_step().lower(
            net.params, net.states, net.updater_states, it, ep,
            {"tokens": jnp.asarray(ds.features)}, [jnp.asarray(ds.labels)],
            None, None, rng)
    else:
        lowered = net._get_train_step(False).lower(
            net.params, net.states, net.updater_states, it, ep,
            jnp.asarray(ds.features), jnp.asarray(ds.labels), None, None,
            rng, None)
    return lowered.compile().as_text()


def phase_of(op_name: str) -> str:
    if "/optimizer/" in op_name:
        return "optimizer"
    if "transpose(" in op_name:
        return "backward"
    return "forward" if "jvp(" in op_name else "other"


@pytest.mark.parametrize("build, classes, output", [
    (transformer, {"EmbeddingSequenceLayer", "PositionalEmbeddingLayer",
                   "LayerNormalizationLayer", "CausalSelfAttentionLayer",
                   "DenseLayer", "ElementWiseVertex", "RnnOutputLayer"},
     "RnnOutputLayer:out"),
    (multilayer, {"DenseLayer", "OutputLayer"}, "OutputLayer:2"),
], ids=["computation_graph", "multi_layer_network"])
def test_compiled_step_is_named_and_every_operation_has_a_phase(
        build, classes, output):
    net, ds = build()
    text = compiled_step_text(net, ds)
    assert text.startswith("HloModule jit_train_step")
    names = OP_NAME.findall(text)
    assert len(names) > 50
    by_phase = {}
    for name in names:
        by_phase.setdefault(phase_of(name), []).append(name)
    assert {"forward", "backward", "optimizer"} <= set(by_phase)
    # nothing of the model is outside a phase: only the key and the tick
    stray = [n for n in by_phase.get("other", ()) if not UNPHASED.search(n)]
    assert not stray, sorted(set(stray))
    # every matmul carries a phase and a layer
    for line in text.splitlines():
        if re.search(r" (dot|convolution)\(", line) and "op_name" in line:
            name = OP_NAME.search(line).group(1)
            assert phase_of(name) != "other" and LAYER.search(name), name
    found = {cls for n in names for cls, _ in LAYER.findall(n)}
    assert found == classes
    # the optimizer's operations name their layer, and the loss sits under
    # the output layer in both directions
    assert all(LAYER.search(n) for n in by_phase["optimizer"])
    assert any(f"jvp({output})/loss" in n for n in by_phase["forward"])
    assert any(f"transpose(jvp({output}))/loss" in n
               for n in by_phase["backward"])
    if build is transformer:
        assert any("jvp(cast_params)" in n for n in by_phase["forward"])


@pytest.mark.parametrize("build", [transformer, multilayer],
                         ids=["computation_graph", "multi_layer_network"])
def test_the_other_programs_are_named_too(build):
    net, ds = build()
    net.fit_batches_on_device([ds, ds])
    scan, = [f for k, f in net._jit_cache.items() if k[0] == "train_scan"]
    assert scan.__name__ == "train_steps_scan"
    assert net._get_train_step(True).__name__ == "tbptt_step"


class _KeepLoss:
    """Keeps each step's loss as the device scalar it is: no read."""

    def __init__(self):
        self.losses = []

    def iteration_done(self, model, iteration, epoch):
        self.losses.append(model._score_arr)


@pytest.mark.parametrize("build", [transformer, multilayer],
                         ids=["computation_graph", "multi_layer_network"])
def test_fit_batch_spans_only_under_tracing_and_the_same_loss(
        build, monkeypatch):
    steps = 3

    def losses():
        net, ds = build()
        keep = _KeepLoss()
        net.listeners.append(keep)
        net.fit([ds] * steps, prefetch_depth=0)
        return [float(x) for x in keep.losses]

    # tracing off: no tracer is asked for a span
    observe.disable_tracing()
    opened = []
    for method in ("span", "enter_span", "start_span", "record"):
        monkeypatch.setattr(observe.Tracer, method,
                            lambda *a, **k: opened.append(a))
    untraced = losses()
    monkeypatch.undo()
    assert not opened and len(untraced) == steps

    # tracing on: no span of the loop is open while a device value is read
    # or waited for (`_value` is what `float()` and `np.asarray` go through)
    tracer = observe.enable_tracing()
    array_type = type(jnp.zeros(()))
    read_under = []
    value, block = array_type._value, array_type.block_until_ready
    monkeypatch.setattr(array_type, "_value", property(
        lambda self: (read_under.append(tracer.current_context()),
                      value.fget(self))[1]))
    monkeypatch.setattr(
        array_type, "block_until_ready",
        lambda self: (read_under.append(tracer.current_context()),
                      block(self))[1])
    try:
        traced = losses()
    finally:
        monkeypatch.undo()
        observe.disable_tracing()
    assert len(read_under) >= steps          # the reads after fit() are seen
    assert all(context is None for context in read_under)
    assert traced == untraced
    spans = tracer.recorder.spans()
    dispatch = [s for s in spans if s.name == "step_dispatch"]
    assert [s.attrs["iteration"] for s in dispatch] == list(range(steps))
    assert len([s for s in spans if s.name == "listeners"]) == steps
    # the compile nests under the step that paid for it, and under no other
    compiled = {s.parent_id for s in spans if s.name == "xla_compile"}
    assert dispatch[0].span_id in compiled
    assert dispatch[-1].span_id not in compiled
    # dispatch does not wait for the step: it is asynchronous, so a steady
    # step's span is far shorter than the compile it follows
    assert dispatch[-1].duration_ms < dispatch[0].duration_ms
