"""`fit()` compiles its train step once.

A fresh tick, and beside a batch placed on a device the model's trees too,
are committed to where the step will leave them, so the second call of the
step finds the first's executable: beside a placed batch, on a mesh after
`shard_model_with_rules`, after a restore, and under `ParallelWrapper`. Counted as the benchmark counts
`steps_that_compiled`: `step_dispatch` spans with an `xla_compile` span
nested in them.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels


@pytest.fixture
def tracer():
    tracer = observe.enable_tracing()
    try:
        yield tracer
    finally:
        observe.disable_tracing()


def _steps_that_compiled(tracer) -> list:
    """Iterations whose `step_dispatch` span has an `xla_compile` under it."""
    spans = {s.span_id: s for s in tracer.recorder.spans()}
    found = set()
    for span in spans.values():
        if span.name != "xla_compile":
            continue
        above = spans.get(span.parent_id)
        while above is not None and above.name != "step_dispatch":
            above = spans.get(above.parent_id)
        if above is not None:
            found.add(above.attrs["iteration"])
    return sorted(found)


def _lm(layers=2):
    return ComputationGraph(TransformerLM(
        vocab_size=64, max_length=32, n_layers=layers, d_model=32, n_heads=2,
        d_ff=64, seed=1).conf())


def _lm_batch(rng, where=None):
    tokens = rng.integers(0, 64, (4, 32)).astype(np.int32)
    ds = DataSet(tokens, lm_labels(tokens, 64))
    if where is not None:
        ds.features = jax.device_put(ds.features, where)
        ds.labels = jax.device_put(ds.labels, where)
    return ds


def _mlp():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=4)).build())
    return MultiLayerNetwork(conf)


def _mlp_batch(rng, where=None):
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    if where is not None:
        x, y = jax.device_put(x, where), jax.device_put(y, where)
    return DataSet(x, y)


def _all_committed(tree) -> bool:
    return all(leaf.committed for leaf in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("engine", ["ComputationGraph", "MultiLayerNetwork"])
@pytest.mark.parametrize("batch", ["placed on the device", "from the host"])
def test_three_batches_compile_the_step_once(rng, tracer, engine, batch):
    where = jax.devices()[0] if batch == "placed on the device" else None
    if engine == "ComputationGraph":
        net, ds = _lm().init(), _lm_batch(rng, where)
    else:
        net, ds = _mlp().init(), _mlp_batch(rng, where)
    for _ in range(3):
        net.fit(ds)
    assert net.iteration == 3 and np.isfinite(net.score_)
    assert _steps_that_compiled(tracer) == [0]
    # a placed batch commits what the step returns; host batches leave
    # every argument uncommitted, as they always did
    assert _all_committed((net.params, net.updater_states, net._tick[1])) \
        == (where is not None)


def test_settling_beside_a_placed_batch_makes_no_copy(rng):
    """The committed array shares the buffer of the one `init()` made: no
    second copy of the parameters or moments is held in set-up. `init()`
    itself commits nothing: uncommitted parameters follow whatever a
    caller's own jit is given (`ParallelInference`, the ring helper)."""
    net = _mlp().init()
    trees = (net.params, net.states, net.updater_states)
    assert not any(leaf.committed
                   for leaf in jax.tree_util.tree_leaves(trees))
    made = jax.tree_util.tree_leaves(trees)
    net._device_tick(_mlp_batch(rng, jax.devices()[0]).features)
    kept = jax.tree_util.tree_leaves(
        (net.params, net.states, net.updater_states))
    assert _all_committed(kept) and _all_committed(net._tick[1])
    assert [a.unsafe_buffer_pointer() for a in kept] == \
        [a.unsafe_buffer_pointer() for a in made]


def _mesh_2x2():
    from deeplearning4j_tpu.parallel import make_mesh
    return make_mesh({"data": 2, "model": 2}, jax.devices()[:4])


def test_a_2x2_mesh_compiles_the_step_once(rng, tracer):
    from jax.sharding import NamedSharding, PartitionSpec
    from deeplearning4j_tpu.parallel.sharding import shard_model_with_rules

    net, mesh = _lm().init(), _mesh_2x2()
    shard_model_with_rules(net, mesh)
    ds = _lm_batch(rng, NamedSharding(mesh, PartitionSpec("data")))

    class Three:
        def reset(self):
            pass

        def __iter__(self):
            return iter([ds] * 3)

    net.fit(Three(), epochs=1, prefetch_depth=0)
    assert net.iteration == 3 and np.isfinite(net.score_)
    assert _steps_that_compiled(tracer) == [0]
    # the tick follows the parameters: replicated over their mesh
    for leaf in net._tick[1]:
        assert leaf.committed and leaf.sharding.is_fully_replicated
        assert leaf.sharding.device_set == set(mesh.devices.flat)
    # and the rules' placement is what the step left
    assert net.params["block0-ff1"]["W"].sharding.spec == \
        net._param_shardings["block0-ff1"]["W"].spec


def test_tick_stays_uncommitted_beside_uncommitted_parameters(rng):
    """Parameters put in by hand (uncommitted) keep today's behaviour: the
    tick is left for jit to place, and nothing clashes."""
    net = _mlp().init()
    net.params = jax.tree_util.tree_map(
        lambda a: jax.numpy.asarray(np.asarray(a)), net.params)
    assert not _all_committed(net.params)
    net.fit(_mlp_batch(rng))
    assert not net._tick[1][1].committed
    assert np.isfinite(net.score_)


@pytest.mark.parametrize("engine", ["ComputationGraph", "MultiLayerNetwork"])
def test_a_restored_checkpoint_trains_and_compiles_once(rng, tracer, tmp_path,
                                                        engine):
    from deeplearning4j_tpu.util.model_serializer import (restore_model,
                                                          write_model)
    if engine == "ComputationGraph":
        net, ds = _lm(1).init(), _lm_batch(rng, jax.devices()[0])
    else:
        net, ds = _mlp().init(), _mlp_batch(rng, jax.devices()[0])
    net.fit(ds)
    path = str(tmp_path / "model.zip")
    write_model(net, path)
    back = restore_model(path)
    assert back.iteration == 1
    host = jax.tree_util.tree_map(np.asarray, back.params)  # on the host
    np.testing.assert_allclose(
        jax.tree_util.tree_leaves(host)[0],
        np.asarray(jax.tree_util.tree_leaves(net.params)[0]))
    before = back.score(ds)
    first = len(_steps_that_compiled(tracer))
    for _ in range(3):
        back.fit(ds)
    assert back.iteration == 4 and back.score(ds) < before
    assert len(_steps_that_compiled(tracer)) - first <= 1


@pytest.mark.parametrize("mode", ["shared_gradients", "averaging"])
def test_parallel_wrapper_still_trains(rng, mode):
    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

    net = _mlp().init()
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[(x[:, 0] > 0) * 1 + (x[:, 1] > 0) * 2]
    data = DataSet(x, y)
    before = net.score(data)
    wrapper = ParallelWrapper(net, make_mesh({"data": 4}, jax.devices()[:4]),
                              mode=mode, averaging_frequency=2)
    wrapper.fit(ListDataSetIterator(data, 16), epochs=4)
    assert net.iteration == 16
    assert net.score(data) < before
    assert {d.id for leaf in jax.tree_util.tree_leaves(net.params)
            for d in leaf.sharding.device_set} == {0, 1, 2, 3}


def test_parallel_wrapper_compiles_its_step_once(rng, tracer):
    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

    net = _mlp().init()
    wrapper = ParallelWrapper(net, make_mesh({"data": 4}, jax.devices()[:4]))
    wrapper.fit(ListDataSetIterator(_mlp_batch(rng), 16), epochs=3)
    assert net.iteration == 3
    assert _steps_that_compiled(tracer) == [0]
