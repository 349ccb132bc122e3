"""The train step under a mesh of several TPUs is compiled with the TPU's
asynchronous collectives (``parallel.mesh.step_compiler_options``), and
where the mesh has a ``data`` axis with its gradient sums bounded to run
beside the backward pass; every other step's ``jax.jit`` call is the one it
always was. The CPU refuses the options' names, so the TPU side is a stub
mesh here and the options are checked as what is handed to ``jax.jit``, not
compiled."""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observe import (TraceRecorder, Tracer,
                                        disable_tracing, enable_tracing)
from deeplearning4j_tpu.parallel import mesh as mesh_mod
from deeplearning4j_tpu.parallel.mesh import (ASYNC_COLLECTIVE_OPTIONS,
                                              DATA_SUM_OPTIONS, make_mesh,
                                              step_compiler_options)
from deeplearning4j_tpu.parallel.sharding import shard_model_with_rules

COUNTER = "placement.async_collective_steps"
DATA_COUNTER = "placement.data_sum_overlap_steps"
ASYNC = {k: "true" for k in ASYNC_COLLECTIVE_OPTIONS}


class _StubMesh:
    """What ``step_compiler_options`` reads of a mesh, its devices and its
    axes, and what a jit-cache key needs of it, a hash."""

    def __init__(self, *platforms, shape=None):
        self.devices = np.asarray([types.SimpleNamespace(platform=p)
                                   for p in platforms], dtype=object)
        self.shape = shape or {"data": len(platforms)}


def _stub_mesh(platform, **axes):
    return _StubMesh(*[platform] * int(np.prod(list(axes.values()))),
                     shape=axes)


MESHES = {
    "no mesh": lambda: None,
    "one cpu device": lambda: make_mesh({"data": 1}),
    "four cpu devices": lambda: make_mesh({"data": 2, "model": 2}),
    "one tpu": lambda: _stub_mesh("tpu", data=1),
    "a tpu beside a cpu": lambda: _StubMesh("tpu", "cpu"),
    "2x2 tpus": lambda: _stub_mesh("tpu", data=2, model=2),
    "4 tpus over data": lambda: _stub_mesh("tpu", data=4),
    "4 tpus over model": lambda: _stub_mesh("tpu", model=4),
    "4 tpus, a data axis of 1": lambda: _stub_mesh("tpu", data=1, model=4),
}
# what each mesh of several TPUs is given; every other mesh gets None
EXPECTED = {
    "2x2 tpus": {**ASYNC, **DATA_SUM_OPTIONS},
    "4 tpus over data": {**ASYNC, **DATA_SUM_OPTIONS},
    "4 tpus over model": ASYNC,
    "4 tpus, a data axis of 1": ASYNC,
}


@pytest.mark.parametrize("name", sorted(MESHES))
def test_options_only_for_a_mesh_of_several_tpus(name):
    options = step_compiler_options(MESHES[name]())
    assert options == EXPECTED.get(name)
    if options is None:
        return
    # the TPU compiler takes Python True too, and then does nothing
    assert all(type(v) is str for v in options.values())
    assert all(v == "true" for k, v in options.items()
               if k in ASYNC_COLLECTIVE_OPTIONS)
    # the combiner's bound is a whole number of bytes, under the smallest
    # weight gradient a chip holds in GPT-2 large on a 2x2 (640 x 1280 bf16)
    for v in (options.get(k) for k in DATA_SUM_OPTIONS):
        assert v is None or 0 < int(v) < 640 * 1280 * 2


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
    net._mesh = _stub_mesh("tpu", data=2, model=2)
    step = net._get_train_step()
    assert jit_calls == [{"compiler_options": {**ASYNC, **DATA_SUM_OPTIONS},
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


# An option every backend compiles and that changes nothing, in place of
# the data-parallel set, which only the TPU compiler takes.
CPU_OPTION = {"xla_embed_ir_in_executable": False}


@pytest.mark.parametrize("in_force", [None, "async", "data"])
def test_the_counter_lands_on_the_first_step_dispatch(tracer, monkeypatch,
                                                      in_force):
    if in_force:    # options the CPU compiles, but the wrapped path
        options = CPU_OPTION if in_force == "data" else {}
        monkeypatch.setattr(mesh_mod, "DATA_SUM_OPTIONS", CPU_OPTION)
        monkeypatch.setattr(mesh_mod, "step_compiler_options",
                            lambda mesh: dict(options))
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
    # the data-parallel set engages only with its options in the step's
    data = 1 if in_force == "data" else None
    assert steps[0].counts.get(DATA_COUNTER) == data
    assert all(DATA_COUNTER not in s.counts for s in steps[1:])
    assert tracer.counters.get(DATA_COUNTER) == data


# --- what the TPU compiler makes of the options, for a described 2x2 ------
# Compiled for a v5e:2x2 that is described and not attached: nothing runs,
# the text says which sums are asynchronous. The topology is described in a
# fixture, never while the module is imported.

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


WIDTH = 1024    # a weight's gradient: 4 MiB of float32, over the 1 MiB bound


def _entry_sums(net, mesh):
    """The train step compiled for ``mesh``: its asynchronous sums, and for
    each synchronous sum of the entry computation the number of weight
    gradients (``f32[WIDTH,WIDTH]``) it carries."""
    def init():
        net.init()
        return net.params, net.states, net.updater_states
    params, states, upd = jax.eval_shape(init)
    repl = NamedSharding(mesh, P())

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            tree)
    net.params, net.states, net.updater_states = (
        shaped(params), shaped(states), shaped(upd))
    net._mesh = mesh
    net._param_shardings = jax.tree_util.tree_map(lambda _: repl, params)
    net._upd_shardings = jax.tree_util.tree_map(lambda _: repl, upd)
    rows = NamedSharding(mesh, P("data"))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
    text = net._get_train_step().lower(
        net.params, net.states, net.updater_states, scalar, scalar,
        jax.ShapeDtypeStruct((16, WIDTH), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((16, 4), jnp.float32, sharding=rows),
        None, None, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
    ).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    starts = re.findall(r"^\s*%async-collective-start\S* = ", entry, re.M)
    sync = re.findall(r"^\s*%all-reduce\S* = (.*?) all-reduce\(", entry, re.M)
    weight = "f32[%d,%d]" % (WIDTH, WIDTH)
    return len(starts), [shape.count(weight) for shape in sync]


@pytest.mark.parametrize("data_set", [False, True])
def test_the_tpu_compiler_sums_each_weight_gradient_on_its_own(
        topo, monkeypatch, data_set):
    if not data_set:    # the asynchronous set alone: the combiner's default
        monkeypatch.setattr(mesh_mod, "DATA_SUM_OPTIONS", {})
    b = NeuralNetConfiguration.builder().seed(7).list()
    for _ in range(3):
        b = b.layer(DenseLayer(n_in=WIDTH, n_out=WIDTH, activation="relu"))
    net = MultiLayerNetwork(b.layer(OutputLayer(
        n_in=WIDTH, n_out=4, activation="softmax",
        loss="negativeloglikelihood")).build())
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    starts, weights_per_sum = _entry_sums(net, mesh)
    if not data_set:
        # the three weight gradients wait for each other in one
        # synchronous tuple; nothing is asynchronous
        assert starts == 0 and max(weights_per_sum) == 3
        return
    # each weight gradient is a sum of its own, and the compiler makes
    # such sums asynchronous
    assert max(weights_per_sum) <= 1 and starts >= 1
    assert starts + sum(weights_per_sum) == 3
