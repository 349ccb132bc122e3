"""The train step under a mesh of several TPUs is compiled with the TPU's
asynchronous collectives (``parallel.mesh.step_compiler_options``); every
other step's ``jax.jit`` call is the one it always was. The CPU refuses the
options' names, so the TPU side is a stub mesh here and the options are
checked as what is handed to ``jax.jit``, not compiled."""

import types

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observe import (TraceRecorder, Tracer,
                                        disable_tracing, enable_tracing)
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.parallel.mesh import (ASYNC_COLLECTIVE_OPTIONS,
                                              make_mesh, step_compiler_options)
from deeplearning4j_tpu.parallel.sharding import shard_model_with_rules

COUNTER = "placement.async_collective_steps"


class _StubMesh:
    """What ``step_compiler_options`` reads of a mesh, its devices, and
    what a jit-cache key needs of it, a hash."""

    def __init__(self, *platforms):
        self.devices = np.asarray([types.SimpleNamespace(platform=p)
                                   for p in platforms], dtype=object)


def _stub_mesh(platform, n):
    return _StubMesh(*[platform] * n)


MESHES = {
    "no mesh": lambda: None,
    "one cpu device": lambda: make_mesh({"data": 1}),
    "four cpu devices": lambda: make_mesh({"data": 2, "model": 2}),
    "one tpu": lambda: _stub_mesh("tpu", 1),
    "a tpu beside a cpu": lambda: _StubMesh("tpu", "cpu"),
    "2x2 tpus": lambda: _stub_mesh("tpu", 4),
}


@pytest.mark.parametrize("name", sorted(MESHES))
def test_options_only_for_a_mesh_of_several_tpus(name):
    options = step_compiler_options(MESHES[name]())
    if name != "2x2 tpus":
        assert options is None
        return
    assert options == {k: "true" for k in ASYNC_COLLECTIVE_OPTIONS}
    # the TPU compiler takes Python True too, and then does nothing
    assert all(type(v) is str for v in options.values())


def _net():
    conf = (NeuralNetConfiguration.builder().seed(7).list()
            .layer(DenseLayer(n_in=12, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=4, activation="softmax",
                               loss="negativeloglikelihood")).build())
    return MultiLayerNetwork(conf).init()


def _data(n=8):
    x = np.linspace(-1, 1, n * 12, dtype=np.float32).reshape(n, 12)
    y = np.eye(4, dtype=np.float32)[np.arange(n) % 4]
    return DataSet(x, y)


@pytest.fixture
def jit_calls(monkeypatch):
    """Every ``jax.jit`` call's keywords, the real ``jax.jit`` behind."""
    calls, real = [], jax.jit

    def spy(fn, **kwargs):
        calls.append(kwargs)
        return real(fn, **kwargs)
    monkeypatch.setattr(jax, "jit", spy)
    return calls


@pytest.mark.parametrize("builder", ["_get_train_step",
                                     "_get_multi_train_step"])
@pytest.mark.parametrize("placed", [False, True])
def test_a_step_off_the_tpu_mesh_is_jitted_as_before(jit_calls, builder,
                                                     placed):
    net = _net()
    if placed:      # a mesh of four CPU devices: still no options
        shard_model_with_rules(net, make_mesh({"data": 4}))
    getattr(net, builder)()
    donate = (0, 1, 2, 3, 9) if builder == "_get_train_step" else (0, 1, 2)
    assert jit_calls == [{"donate_argnums": donate}]


def test_the_step_under_a_tpu_mesh_carries_the_options(jit_calls):
    net = _net()
    net._mesh = _stub_mesh("tpu", 4)
    step = net._get_train_step()
    assert jit_calls == [{"compiler_options": {
        k: "true" for k in ASYNC_COLLECTIVE_OPTIONS},
        "donate_argnums": (0, 1, 2, 3, 9)}]
    # the program keeps its name in the trace and the HLO
    assert step.__name__ == "train_step"
    # the options hang on the mesh: the key tells the two steps apart
    net._mesh = None
    assert net._get_train_step() is not step
    assert "compiler_options" not in jit_calls[-1]


@pytest.fixture
def tracer():
    tr = enable_tracing(Tracer(TraceRecorder(capacity=4096)))
    yield tr
    disable_tracing()


@pytest.mark.parametrize("in_force", [False, True])
def test_the_counter_lands_on_the_first_step_dispatch(tracer, monkeypatch,
                                                      in_force):
    if in_force:    # options the CPU compiles: none, but the wrapped path
        monkeypatch.setattr(mesh_mod, "step_compiler_options",
                            lambda mesh: {})
    net = _net()
    shard_model_with_rules(net, make_mesh({"data": 2}))
    ds = _data()
    for _ in range(3):
        net.fit(ds)
    steps = sorted((s for s in tracer.recorder.spans()
                    if s.name == "step_dispatch"), key=lambda s: s.start_ns)
    assert len(steps) == 3
    assert steps[0].counts.get(COUNTER) == (1 if in_force else None)
    assert all(COUNTER not in s.counts for s in steps[1:])
    assert tracer.counters.get(COUNTER) == (1 if in_force else None)
