"""Softmax cross entropy on integer class ids (``nn/losses.py``): the same
loss and gradients as one-hot labels give, under both engines and every
way a batch reaches the loss; the one-hot path is bit for bit what it was.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.nn import losses
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    CenterLossOutputLayer, CnnLossLayer, DenseLayer, LSTMLayer, OutputLayer,
    RnnOutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

V = 7          # classes
N, T, D = 4, 6, 5


def _one_hot(ids):
    return np.eye(V, dtype=np.float32)[np.asarray(ids)]


def _data(timed, seed=0):
    rng = np.random.default_rng(seed)
    lead = (N, T) if timed else (N,)
    x = rng.normal(size=lead + (D,)).astype(np.float32)
    ids = rng.integers(0, V, lead).astype(np.int32)
    mask = (rng.uniform(size=lead) > 0.3).astype(np.float32)
    mask.reshape(-1)[0] = 1.0
    return x, ids, mask


# ------------------------------------------------------------ the function
def _old_mcxent_logits(labels, logits, mask=None, weights=None):
    """``mcxent_logits`` as it stood before class ids, line for line."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_out = labels * logp
    if weights is not None:
        per_out = per_out * weights
    per = -jnp.sum(per_out, axis=-1)
    if mask is None:
        return jnp.mean(per)
    mask = jnp.broadcast_to(mask.astype(per.dtype), per.shape)
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@pytest.mark.parametrize("timed", [False, True], ids=["NV", "NTV"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
@pytest.mark.parametrize("fn", [losses.mcxent_logits,
                                losses.negativeloglikelihood_logits,
                                losses.mcxent_probs],
                         ids=["mcxent", "nll", "probs"])
def test_ids_match_one_hot_in_the_loss_function(fn, weighted, masked, timed):
    _, ids, mask = _data(timed)
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=ids.shape + (V,)) * 3, jnp.float32)
    kw = {"mask": jnp.asarray(mask) if masked else None,
          "weights": (jnp.asarray(rng.uniform(0.5, 2.0, V), jnp.float32)
                      if weighted else None)}
    pre = jax.nn.sigmoid if fn is losses.mcxent_probs else (lambda a: a)
    want, g_want = jax.value_and_grad(
        lambda l: fn(jnp.asarray(_one_hot(ids)), pre(l), **kw))(logits)
    got, g_got = jax.value_and_grad(
        lambda l: fn(jnp.asarray(ids), pre(l), **kw))(logits)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("timed", [False, True], ids=["NV", "NTV"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weights"])
def test_one_hot_labels_give_the_bits_they_gave(weighted, masked, timed):
    _, ids, mask = _data(timed, seed=3)
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=ids.shape + (V,)) * 3, jnp.float32)
    soft = jnp.asarray(0.9 * _one_hot(ids) + 0.1 / V)
    kw = {"mask": jnp.asarray(mask) if masked else None,
          "weights": (jnp.asarray(rng.uniform(0.5, 2.0, V), jnp.float32)
                      if weighted else None)}
    for labels in (jnp.asarray(_one_hot(ids)), soft):
        want = jax.jit(jax.value_and_grad(
            lambda l: _old_mcxent_logits(labels, l, **kw)))(logits)
        got = jax.jit(jax.value_and_grad(
            lambda l: losses.mcxent_logits(labels, l, **kw)))(logits)
        assert np.asarray(got[0]).tobytes() == np.asarray(want[0]).tobytes()
        assert np.asarray(got[1]).tobytes() == np.asarray(want[1]).tobytes()


def test_sparse_mcxent_is_the_same_function():
    assert losses.sparse_mcxent_logits is losses.mcxent_logits
    assert losses.resolve("sparse_mcxent", "softmax") == (
        losses.mcxent_logits, True)
    assert losses.resolve("sparse_mcxent", "sigmoid") == (
        losses.mcxent_probs, False)


def test_float_ids_raise_and_integer_one_hot_is_one_hot():
    logits = jnp.zeros((N, V))
    with pytest.raises(ValueError, match="integer class ids"):
        losses.mcxent_logits(jnp.zeros((N,), jnp.float32), logits)
    ids = np.arange(N) % V
    np.testing.assert_allclose(
        float(losses.mcxent_logits(jnp.asarray(_one_hot(ids), jnp.int32),
                                   logits)), np.log(V), rtol=1e-6)


def test_backward_is_one_pass_without_scatter_or_cotangent_sum():
    ids = jnp.asarray(np.arange(N * T).reshape(N, T) % V, jnp.int32)
    logits = jnp.ones((N, T, V))
    text = jax.jit(jax.value_and_grad(
        lambda l: losses.mcxent_logits(ids, l))).lower(logits).as_text()
    assert "scatter" not in text and "gather" not in text
    # max, sum-exp and the picked logit in the forward; the backward reduces
    # nothing (autodiff of log_softmax sums the cotangent over classes)
    # (a fourth reduce is the mean over positions)
    assert len(re.findall(r"stablehlo\.reduce\([^\n]*dimensions = \[2\]",
                          text)) == 3


def test_extreme_logits_stay_finite():
    logits = jnp.asarray([[1e4, -1e4, 0.0], [-1e4, 1e4, 0.0]], jnp.float32)
    ids = jnp.asarray([0, 0], jnp.int32)
    val, grad = jax.value_and_grad(
        lambda l: losses.mcxent_logits(ids, l))(logits)
    np.testing.assert_allclose(float(val), 1e4, rtol=1e-6)
    assert np.isfinite(np.asarray(grad)).all()
    np.testing.assert_allclose(np.asarray(grad)[1], [-0.5, 0.5, 0.0],
                               atol=1e-6)


# ------------------------------------------------------------- the engines
def _net(engine, timed, dtype=None, tbptt=None):
    b = NeuralNetConfiguration.builder().seed(11).updater(Sgd(0.1))
    head = (RnnOutputLayer if timed else OutputLayer)(
        n_out=V, activation="softmax", loss="mcxent")
    body = LSTMLayer(n_out=8) if timed else DenseLayer(n_out=8,
                                                       activation="tanh")
    in_type = InputType.recurrent(D, T) if timed else InputType.feed_forward(D)
    if engine == "graph":
        g = b.graph_builder().add_inputs("in").set_input_types(in_type)
        g.add_layer("body", body, "in")
        g.add_layer("out", head, "body")
        g.set_outputs("out")
        if tbptt:
            g.t_bptt_length(tbptt)
        conf = g.build()
        net = ComputationGraph(conf)
    else:
        lb = b.list().layer(body).layer(head).set_input_type(in_type)
        if tbptt:
            lb.t_bptt_length(tbptt)
        conf = lb.build()
        net = MultiLayerNetwork(conf)
    conf.global_conf.compute_dtype = dtype
    return net.init()


def _loss_and_grads(net, ds):
    batch = net._to_batch(ds)

    def lf(p):
        return net._loss_fn(p, net.states, *batch[:2], None, *batch[2:],
                            train=False)[0]
    return jax.value_and_grad(lf)(net.params)


def _flat(tree):
    return np.concatenate([np.asarray(a, np.float32).ravel()
                           for a in jax.tree_util.tree_leaves(tree)])


@pytest.mark.parametrize("engine", ["graph", "multilayer"])
@pytest.mark.parametrize("timed", [False, True], ids=["NV", "NTV"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("dtype", [None, "bfloat16"], ids=["f32", "bf16"])
def test_ids_match_one_hot_through_the_network(dtype, masked, timed, engine):
    x, ids, mask = _data(timed)
    lm = mask if masked else None
    net = _net(engine, timed, dtype)
    want, g_want = _loss_and_grads(net, DataSet(x, _one_hot(ids),
                                                labels_mask=lm))
    got, g_got = _loss_and_grads(net, DataSet(x, ids, labels_mask=lm))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # every parameter: W and b of the head, and the body behind them
    np.testing.assert_allclose(_flat(g_got), _flat(g_want), rtol=2e-5,
                               atol=1e-7)
    head_got = jax.tree_util.tree_leaves(
        g_got["out"] if engine == "graph" else g_got[-1])
    assert all(np.abs(np.asarray(a)).max() > 0 for a in head_got)


@pytest.mark.parametrize("engine", ["graph", "multilayer"])
@pytest.mark.parametrize("timed", [False, True], ids=["NV", "NTV"])
def test_fit_score_and_score_examples_on_ids(timed, engine):
    x, ids, _ = _data(timed)
    a, b = _net(engine, timed), _net(engine, timed)
    one_hot, by_id = DataSet(x, _one_hot(ids)), DataSet(x, ids)
    np.testing.assert_allclose(b.score(by_id), a.score(one_hot), rtol=1e-6)
    np.testing.assert_allclose(b.score_examples(by_id),
                               a.score_examples(one_hot), rtol=1e-5)
    for _ in range(3):
        a.fit(one_hot)
        b.fit(by_id)
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(b.score_, a.score_, rtol=1e-5)
    grads, score = b.compute_gradient_and_score(x, ids)
    want_grads, want_score = a.compute_gradient_and_score(x, _one_hot(ids))
    np.testing.assert_allclose(score, want_score, rtol=1e-5)
    np.testing.assert_allclose(_flat(grads), _flat(want_grads), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("engine", ["graph", "multilayer"])
def test_fit_batches_on_device_stacks_ids(engine):
    batches = [_data(True, seed=s) for s in range(3)]
    a, b = _net(engine, True), _net(engine, True)
    a.fit_batches_on_device([DataSet(x, _one_hot(i)) for x, i, _ in batches])
    b.fit_batches_on_device([DataSet(x, i) for x, i, _ in batches])
    assert b.iteration == 3
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("engine", ["graph", "multilayer"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
def test_tbptt_cuts_ids_along_time(masked, engine):
    x, ids, mask = _data(True)
    lm = mask if masked else None
    a, b = _net(engine, True, tbptt=2), _net(engine, True, tbptt=2)
    a.fit(DataSet(x, _one_hot(ids), labels_mask=lm))
    b.fit(DataSet(x, ids, labels_mask=lm))
    assert a.iteration == b.iteration == T // 2
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)


def test_tbptt_feeds_per_sequence_ids_whole():
    """ids [N] for a pooled sequence go whole to every chunk, as one-hot
    [N,C] labels do."""
    from deeplearning4j_tpu.nn.layers import GlobalPoolingLayer

    def build():
        g = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1))
             .graph_builder().add_inputs("in")
             .set_input_types(InputType.recurrent(D, T)).t_bptt_length(3))
        g.add_layer("lstm", LSTMLayer(n_out=8), "in")
        g.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), "lstm")
        g.add_layer("out", OutputLayer(n_out=V, loss="mcxent",
                                       activation="softmax"), "pool")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()

    x, _, _ = _data(True)
    ids = np.arange(N, dtype=np.int32) % V
    a, b = build(), build()
    a.fit(x, _one_hot(ids))
    b.fit(x, ids)
    assert b.iteration == 2
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("mode", ["shared_gradients", "averaging"])
def test_parallel_wrapper_shards_ids(mode):
    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel.trainer import ParallelWrapper
    x, ids, _ = _data(True)
    kw = {"averaging_frequency": 1} if mode == "averaging" else {}
    a, b = _net("multilayer", True), _net("multilayer", True)
    ParallelWrapper(a, make_mesh({"data": 4}), mode=mode, **kw).fit(
        x, _one_hot(ids))
    ParallelWrapper(b, make_mesh({"data": 4}), mode=mode, **kw).fit(x, ids)
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)


def test_probability_space_fallback_takes_ids():
    """mcxent on a sigmoid head is computed from probabilities."""
    def build():
        conf = (NeuralNetConfiguration.builder().seed(2).updater(Sgd(0.1))
                .list().layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=V, activation="sigmoid",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(D)).build())
        return MultiLayerNetwork(conf).init()
    x, ids, _ = _data(False)
    a, b = build(), build()
    np.testing.assert_allclose(b.score(DataSet(x, ids)),
                               a.score(DataSet(x, _one_hot(ids))), rtol=1e-6)
    a.fit(x, _one_hot(ids))
    b.fit(x, ids)
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), rtol=1e-5,
                               atol=1e-6)


def test_cnn_loss_layer_and_center_loss_take_ids():
    rng = np.random.default_rng(0)
    maps = jnp.asarray(rng.normal(size=(2, 3, 3, V)), jnp.float32)
    pix = rng.integers(0, V, (2, 3, 3)).astype(np.int32)
    layer = CnnLossLayer(loss="mcxent", activation="softmax")
    np.testing.assert_allclose(
        float(layer.compute_loss({}, maps, jnp.asarray(pix))),
        float(layer.compute_loss({}, maps, jnp.asarray(_one_hot(pix)))),
        rtol=1e-6)
    center = CenterLossOutputLayer(n_in=D, n_out=V, lambda_=0.5)
    params = center.init_params(jax.random.PRNGKey(0))
    params["cL"] = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
    x, ids, _ = _data(False)
    np.testing.assert_allclose(
        float(center.compute_loss(params, jnp.asarray(x), jnp.asarray(ids))),
        float(center.compute_loss(params, jnp.asarray(x),
                                  jnp.asarray(_one_hot(ids)))), rtol=1e-6)


# ---------------------------------------------------------------- the LM
LM_V, LM_T, LM_N = 13, 8, 2


def _lm(dtype=None):
    conf = TransformerLM(vocab_size=LM_V, max_length=LM_T, n_layers=1,
                         d_model=16, n_heads=2, d_ff=32, seed=3).conf()
    conf.global_conf.compute_dtype = dtype
    return ComputationGraph(conf).init()


def _tokens(seed=0):
    return np.random.default_rng(seed).integers(
        0, LM_V, (LM_N, LM_T)).astype(np.int32)


def test_lm_labels_are_checked_int32_ids():
    tokens = _tokens()
    labels = lm_labels(tokens, LM_V)
    assert labels.dtype == np.int32 and labels.shape == (LM_N, LM_T)
    np.testing.assert_array_equal(labels[:, :-1], tokens[:, 1:])
    np.testing.assert_array_equal(labels[:, -1], tokens[:, -1])
    np.testing.assert_array_equal(
        lm_labels(tokens.astype(np.float32), LM_V), labels)
    for bad in (LM_V, LM_V + 5, -1):
        tokens[1, 3] = bad
        with pytest.raises(ValueError, match="token ids must lie in"):
            lm_labels(tokens, LM_V)


def _lowered_step(net, labels):
    it, ep, rng = net._device_tick()
    return net._get_train_step().lower(
        net.params, net.states, net.updater_states, it, ep,
        {"tokens": jnp.asarray(_tokens())}, [jnp.asarray(labels)],
        None, None, rng).as_text()


@pytest.mark.parametrize("dtype", [None, "bfloat16"], ids=["f32", "bf16"])
def test_lowered_lm_step_holds_no_one_hot(dtype):
    logits_type = f"tensor<{LM_N}x{LM_T}x{LM_V}x"
    tracer = observe.enable_tracing()
    try:
        net = _lm(dtype)
        text = _lowered_step(net, lm_labels(_tokens(), LM_V))
        assert tracer.counters.get("loss.class_id_calls") == 1
        assert tracer.counters.get("loss.one_hot_calls", 0) == 0
        signature = text[text.index("func.func public @main"):].split("{\n")[0]
        assert logits_type not in signature
        assert f"tensor<{LM_N}x{LM_T}xi32>" in signature
        # the embedding's gradient is a scatter into [V, d_model]; nothing
        # scatters into the logits' shape
        for m in re.finditer(r"stablehlo\.scatter", text):
            end = text.index("\n", text.index("}) :", m.start()))
            assert logits_type not in text[m.start():end]
        # everything of the logits' shape is float32 (the loss head), and
        # the class axis is reduced three times forward (max, sum-exp, the
        # picked logit) and never backward: the bias gradient reduces N, T
        assert set(re.findall(re.escape(logits_type) + r"(\w+)>", text)) \
            <= {"f32", "i32", "i1"}
        over_classes = re.findall(
            r"stablehlo\.reduce\([^\n]*dimensions = \[2\][^\n]*"
            + re.escape(logits_type), text)
        assert len(over_classes) == 3, over_classes

        one_hot = np.eye(LM_V, dtype=np.float32)[lm_labels(_tokens(), LM_V)]
        other = _lowered_step(_lm(dtype), one_hot)
        assert tracer.counters.get("loss.class_id_calls") == 1
        assert tracer.counters.get("loss.one_hot_calls") == 1
        signature = other[other.index("func.func public @main"):].split(
            "{\n")[0]
        assert logits_type + "f32>" in signature
    finally:
        observe.disable_tracing()


def test_lm_trains_scores_and_evaluates_on_ids():
    tokens = _tokens(1)
    ids = lm_labels(tokens, LM_V)
    one_hot = np.eye(LM_V, dtype=np.float32)[ids]
    lmask = np.ones(tokens.shape, np.float32)
    lmask[:, -1] = 0.0
    a, b = _lm(), _lm()
    np.testing.assert_allclose(
        b.score(DataSet(tokens, ids, labels_mask=lmask)),
        a.score(DataSet(tokens, one_hot, labels_mask=lmask)), rtol=1e-6)
    for _ in range(3):
        a.fit(DataSet(tokens, one_hot, labels_mask=lmask))
        b.fit(DataSet(tokens, ids, labels_mask=lmask))
    # Adam turns the last bit of a small gradient into a step of its own
    np.testing.assert_allclose(_flat(b.params), _flat(a.params), atol=5e-5)
    np.testing.assert_allclose(b.score_, a.score_, rtol=1e-5)
    ev_a = a.evaluate([DataSet(tokens, one_hot, labels_mask=lmask)])
    ev_b = b.evaluate([DataSet(tokens, ids, labels_mask=lmask)])
    np.testing.assert_array_equal(ev_b.confusion, ev_a.confusion)
    assert ev_b.confusion.sum() == LM_N * (LM_T - 1)


# ------------------------------------------------------------ evaluation
@pytest.mark.parametrize("timed", [False, True], ids=["N", "NT"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
def test_evaluation_takes_integer_labels(masked, timed):
    _, ids, mask = _data(timed, seed=5)
    rng = np.random.default_rng(6)
    preds = rng.uniform(size=ids.shape + (V,)).astype(np.float32)
    a, b = Evaluation(top_n=2), Evaluation(top_n=2)
    a.eval(_one_hot(ids), preds, mask=mask if masked else None)
    b.eval(ids, preds, mask=mask if masked else None)
    np.testing.assert_array_equal(b.confusion, a.confusion)
    assert b.top_n_accuracy() == a.top_n_accuracy()
    assert b.confusion.sum() == (mask.sum() if masked else ids.size)
