"""`nn/engine.py`: the train step and the fit loop stand once, under both
network engines. What each engine may not define itself, the three
programs' names, that a chain and its graph take the same steps under every
shipped updater, that the pure step is the jitted step, and the loop's spans
(also round truncated BPTT, whose chunks go through the same dispatch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import updaters
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.engine import TrainingEngine
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (DenseLayer, LSTMLayer, OutputLayer,
                                          RnnOutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.trainer import make_pure_step

ENGINES = [ComputationGraph, MultiLayerNetwork]
#: what `TrainingEngine` owns: a second definition in an engine is a second
#: copy of the step or the loop to patch in lockstep
OWNED = ["_apply_updates", "_get_train_step", "_get_multi_train_step",
         "_pin_placements", "_step_body", "_evict_stale", "_device_tick",
         "_store_tick", "_next_rng", "score_", "_iteration_done",
         "set_listeners", "add_listeners", "_fit_epochs", "_fit_batch",
         "_dispatch_step", "_mesh", "_param_shardings", "_upd_shardings"]
SHIPPED_UPDATERS = "Sgd Nesterovs Adam AdaMax Nadam AMSGrad AdaGrad " \
                   "AdaDelta RmsProp".split()


def chain(updater=None):
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(updater or updaters.Adam(1e-2)).l2(1e-3).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu",
                              gradient_normalization="clip_l2_per_layer"))
            .layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=4)).build())
    return MultiLayerNetwork(conf).init()


def recurrent_chain():
    conf = (NeuralNetConfiguration.builder().seed(2)
            .updater(updaters.Adam(1e-2)).list()
            .layer(LSTMLayer(n_in=5, n_out=12))
            .layer(RnnOutputLayer(n_in=12, n_out=3))
            .backprop_type("truncated_bptt").t_bptt_length(4).build())
    return MultiLayerNetwork(conf).init()


def as_graph(net) -> ComputationGraph:
    """The chain's graph with buffers of its own (the chain's step donates
    the ones `to_computation_graph` shares) and the chain's backprop type,
    which the conversion does not carry."""
    graph = net.to_computation_graph().clone()
    graph.conf.backprop_type = net.conf.backprop_type
    graph.conf.tbptt_fwd_length = net.conf.tbptt_fwd_length
    return graph


def dense_batches(n=3):
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(32, 8)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)])
            for _ in range(n)]


def sequence_batch():
    rng = np.random.default_rng(3)
    return DataSet(rng.normal(size=(6, 8, 5)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, (6, 8))])


def leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("name", OWNED)
@pytest.mark.parametrize("engine", ENGINES, ids=lambda c: c.__name__)
def test_the_engine_does_not_define_it_itself(engine, name):
    assert issubclass(engine, TrainingEngine)
    assert name in vars(TrainingEngine)
    assert name not in vars(engine)
    assert not hasattr(engine, "_build_train_step")


@pytest.mark.parametrize("engine", ENGINES, ids=lambda c: c.__name__)
def test_the_three_programs_carry_their_names(engine):
    net = chain()
    if engine is ComputationGraph:
        net = as_graph(net)
    assert net._get_train_step().__name__ == "train_step"
    assert net._get_train_step(True).__name__ == "tbptt_step"
    assert net._get_multi_train_step().__name__ == "train_steps_scan"
    # one cache, keyed as the zoo's decoders and the helper registry expect;
    # the mesh is part of a step's key, as its compiler options hang on it
    assert {k[:-1] for k in net._jit_cache} == {
        ("train", False, None), ("train", True, None), ("train_scan", None)}
    assert net._get_train_step() is net._get_train_step(False)


@pytest.mark.parametrize("name", SHIPPED_UPDATERS)
def test_a_chain_and_its_graph_take_the_same_steps(name):
    net = chain(getattr(updaters, name)(1e-2))
    graph = as_graph(net)
    batches = dense_batches()
    before = leaves(net.params)
    net.fit(batches, prefetch_depth=0)
    graph.fit(batches, prefetch_depth=0)
    assert net.iteration == graph.iteration == 3
    assert any(not np.array_equal(a, b)
               for a, b in zip(before, leaves(net.params)))
    names = list(graph.params)
    assert len(names) == len(net.params)
    for i, vertex in enumerate(names):
        for a, b in zip(leaves(net.params[i]), leaves(graph.params[vertex])):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        for a, b in zip(leaves(net.updater_states[i]),
                        leaves(graph.updater_states[vertex])):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(net.score_, graph.score_, rtol=1e-6)


@pytest.mark.parametrize("engine", ENGINES, ids=lambda c: c.__name__)
def test_the_pure_step_is_the_jitted_step(engine):
    net = chain()
    ds = dense_batches(1)[0]
    if engine is ComputationGraph:
        net = as_graph(net)
    batch = net._to_batch(ds)
    it, ep = jnp.float32(0), jnp.float32(0)
    key = jax.random.PRNGKey(5)
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    pure = make_pure_step(net)(
        net.params, net.states, net.updater_states, it, ep, *batch,
        jax.random.split(key)[0])           # the jitted step splits its key
    assert len(pure) == 4
    jitted = net._get_train_step()(
        copy(net.params), copy(net.states), copy(net.updater_states),
        jnp.float32(0), ep, *batch, key)
    assert len(jitted) == 7 and float(jitted[5]) == 1.0
    for a, b in zip(leaves(pure[0]) + leaves(pure[2]),
                    leaves(jitted[0]) + leaves(jitted[2])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(pure[3]), float(jitted[3]), rtol=1e-6)


@pytest.mark.parametrize("backprop", ["standard", "truncated_bptt"])
@pytest.mark.parametrize("engine", ENGINES, ids=lambda c: c.__name__)
def test_the_loop_records_its_spans_only_under_tracing(engine, backprop,
                                                       monkeypatch):
    if backprop == "standard":
        net, batches, steps_a_batch = chain(), dense_batches(), 1
    else:                                   # 8 timesteps in chunks of 4
        net, batches, steps_a_batch = recurrent_chain(), [sequence_batch()] * 3, 2
    if engine is ComputationGraph:
        net = as_graph(net)
    seen = []
    net.add_listeners(type("L", (), {"iteration_done": staticmethod(
        lambda model, iteration, epoch: seen.append(iteration))})())

    # tracing off: no tracer is asked for a span
    observe.disable_tracing()
    opened = []
    for method in ("span", "enter_span", "start_span", "record"):
        monkeypatch.setattr(observe.Tracer, method,
                            lambda *a, **k: opened.append(a))
    net.fit(batches[:1], prefetch_depth=0)
    monkeypatch.undo()
    assert not opened and seen == [steps_a_batch]

    tracer = observe.enable_tracing()
    try:
        net.fit(batches, prefetch_depth=0)
    finally:
        observe.disable_tracing()
    spans = tracer.recorder.spans()
    by_name = lambda name: [s for s in spans if s.name == name]
    first = steps_a_batch
    assert [s.attrs["iteration"] for s in by_name("step_dispatch")] == \
        list(range(first, first + 3 * steps_a_batch))
    assert len(by_name("listeners")) == 3
    assert len(by_name("host_wait")) == 4   # three batches and the end
    assert seen[1:] == [first + steps_a_batch * (i + 1) for i in range(3)]
    assert net.last_batch_size == batches[0].features.shape[0]
    assert np.isfinite(net.score_)
