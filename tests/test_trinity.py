"""The AFMoE family (Trinity-Mini) on the CPU at a small size: the window in
attention's einsum path and in the splash kernel, the gated grouped-query
layer, the helper seam under a window, the shares of an expert layer beside
a shared expert, and the zoo model against the benchmark's plain float32
reference, loss and gradients, with each part of the block shown to
matter."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.configs import afmoe as family
from deeplearning4j_tpu import observe
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn import pallas_kernels as PK
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    GroupedQueryAttentionLayer,
    MixtureOfExpertsLayer,
)
from deeplearning4j_tpu.nn.layers import attention as A
from deeplearning4j_tpu.zoo.models import GatedWindowMoELM, lm_labels

W = 8
SMALL = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2, "num_experts_held": 8,
    "experts_held_first": 0, "num_shared_experts": 1, "vocab_size": 96,
    "max_position_embeddings": 32, "num_layers": 4, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "sliding_window": W, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "compute_dtype": None, "learning_rate": 3e-4, "lr_warmup_steps": 1,
    "lr_total_steps": 100000,
}


def f32(a):
    return np.asarray(a, np.float32)


@pytest.fixture
def tracer():
    tracer = observe.enable_tracing(jax_hook=False)
    try:
        yield tracer
    finally:
        observe.disable_tracing()


@pytest.fixture
def as_on_a_chip(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# ------------------------------------------------- the window, einsum path
def manual_attention(layer, params, x):
    """Plain numpy, the mask written out pair by pair: query i sees key j
    where `0 <= i - j < window` (or `j <= i` without one)."""
    n, t, _ = x.shape
    h, hkv, dh = layer.n_heads, layer._kv_heads(), layer._dh()
    q = (x @ f32(params["Wq"])).reshape(n, t, h, dh)
    kv = (x @ f32(params["Wkv"])).reshape(n, t, hkv, 2, dh)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    q = q / np.sqrt((q * q).mean(-1, keepdims=True) + 1e-5) \
        * f32(params["q_norm"])
    k = k / np.sqrt((k * k).mean(-1, keepdims=True) + 1e-5) \
        * f32(params["k_norm"])
    if layer.rope_theta is not None:
        inv = layer.rope_theta ** (-np.arange(0, dh, 2) / dh)
        ang = np.arange(t)[:, None] * inv[None]
        cos = np.concatenate([np.cos(ang)] * 2, -1)[None, :, None]
        sin = np.concatenate([np.sin(ang)] * 2, -1)[None, :, None]
        half = lambda a: np.concatenate([-a[..., dh // 2:],
                                         a[..., :dh // 2]], -1)
        q, k = q * cos + half(q) * sin, k * cos + half(k) * sin
    k, v = np.repeat(k, h // hkv, 2), np.repeat(v, h // hkv, 2)
    scores = np.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(dh)
    allowed = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            allowed[i, j] = (0 <= i - j < layer.window if layer.window
                             else j <= i)
    scores = np.where(allowed, scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    mixed = np.einsum("nhqk,nkhd->nqhd", w, v).reshape(n, t, h * dh)
    if layer.output_gate:
        mixed = mixed / (1 + np.exp(-(x @ f32(params["Wgate"]))))
    return mixed @ f32(params["Wo"])


def gated_layer(**kw):
    return GroupedQueryAttentionLayer(
        n_in=24, n_out=24, n_heads=4, n_kv_heads=2, head_size=8,
        use_bias=False, qk_norm=True, output_gate=True, **kw)


@pytest.mark.parametrize("rope_theta", [None, 1e4])
@pytest.mark.parametrize("t", [W - 1, W, W + 1, 4 * W])
def test_window_layer_matches_a_mask_written_out(rng, t, rope_theta, tracer):
    layer = gated_layer(window=W, rope_theta=rope_theta)
    params = layer.init_params(jax.random.PRNGKey(3))
    assert params["Wgate"].shape == (24, 32)
    x = f32(rng.normal(size=(2, t, 24)))
    got, _ = layer.forward(params, jnp.asarray(x))
    np.testing.assert_allclose(got, manual_attention(layer, params, x),
                               rtol=2e-4, atol=2e-5)
    # a window that covers the sequence is plain causal attention
    windowed = t > W
    assert tracer.counters == dict(
        {"attention.einsum_calls": 1},
        **({"attention.window_einsum_calls": 1} if windowed else {}))
    if windowed:
        full = gated_layer(rope_theta=rope_theta)
        assert not np.allclose(full.forward(params, jnp.asarray(x))[0], got,
                               atol=1e-4)


def test_window_needs_causal_and_a_key():
    q = jnp.ones((1, 1, 4, 8))
    with pytest.raises(ValueError):
        A.dot_product_attention(q, q, q, window=2)
    with pytest.raises(ValueError):
        A.dot_product_attention(q, q, q, causal=True, window=0)


def test_gate_weights_do_not_move_what_the_others_draw():
    """`Wgate` draws from a key of its own: a layer that gains the gate
    keeps the weights it had."""
    plain = GroupedQueryAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                       use_bias=False)
    gated = GroupedQueryAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                       use_bias=False, output_gate=True)
    a = plain.init_params(jax.random.PRNGKey(5))
    b = gated.init_params(jax.random.PRNGKey(5))
    assert set(b) - set(a) == {"Wgate"}
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    assert "Wgate" in gated.weight_param_names()
    with pytest.raises(ValueError):
        GroupedQueryAttentionLayer(
            n_in=8, n_out=8, n_heads=2, output_gate=True,
            project_input=False).init_params(jax.random.PRNGKey(0))


# --------------------------------------------------------- the stateful path
def test_stateful_path_refuses_a_window_and_carries_the_gate(rng):
    x = jnp.asarray(f32(rng.normal(size=(2, 6, 24))))
    windowed = gated_layer(window=4, rope_theta=1e4, max_cache=8)
    params = windowed.init_params(jax.random.PRNGKey(1))
    with pytest.raises(NotImplementedError, match="window=4"):
        windowed.forward_seq(params, x, carry=windowed.init_carry(2))
    # without a carry it is the full-sequence path, window and all
    np.testing.assert_allclose(windowed.forward_seq(params, x)[0],
                               windowed.forward(params, x)[0])
    full = gated_layer(rope_theta=1e4, max_cache=8)
    whole, _ = full.forward(params, x)
    carry, pieces = full.init_carry(2), []
    for lo, hi in ((0, 1), (1, 4), (4, 6)):
        y, carry = full.forward_seq(params, x[:, lo:hi], carry=carry)
        pieces.append(y)
    np.testing.assert_allclose(jnp.concatenate(pieces, 1), whole,
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------ the helper seam
class _KnowsNoWindow(helpers.AttentionHelper):
    """A helper as a user wrote it before windows: it is never asked about
    a windowed request, and still serves the others."""

    def __init__(self):
        self.asked, self.served = [], []

    def supports(self, layer, q_shape, mask, dropout_active, causal=False):
        self.asked.append(q_shape)
        return causal

    def attend(self, q, k, v):
        self.served.append(q.shape)
        return v


class _Spy(PK.PallasFlashAttentionHelper):
    def __init__(self, calls):
        super().__init__(causal=True)
        self.calls = calls

    def attend(self, q, k, v, window=None):
        self.calls.append((q.shape, window))
        return q


def test_helper_that_knows_no_window_is_never_given_one(tracer):
    helper = _KnowsNoWindow()
    assert not helpers.accepts_window(helper, 4)
    assert helpers.accepts_window(helper, None)
    assert helpers.accepts_window(PK.PallasFlashAttentionHelper(True), 4)
    helpers.set_helper("attention", helper)
    try:
        q = jnp.ones((1, 2, 16, 8))
        A.dot_product_attention(q, q, q, causal=True, window=4)
        assert helper.asked == [] and helper.served == []
        A.dot_product_attention(q, q, q, causal=True)
        A.dot_product_attention(q, q, q, causal=True, window=16)
        assert helper.served == [q.shape, q.shape]
    finally:
        helpers.clear_helper("attention")
    assert tracer.counters == {"attention.einsum_calls": 1,
                               "attention.window_einsum_calls": 1,
                               "attention.kernel_calls": 2}


@pytest.mark.parametrize("t,window,given", [(1024, 256, 256),
                                            (1024, 1024, None),
                                            (1024, 4096, None),
                                            (512, 256, "einsum")])
def test_auto_gate_hands_the_window_to_the_causal_kernel(
        monkeypatch, as_on_a_chip, tracer, t, window, given):
    calls = []
    monkeypatch.setattr(A, "_auto_flash_helper", lambda: _Spy(calls))
    q = jnp.ones((1, 2, t, 128), jnp.bfloat16)
    jax.jit(lambda q: A.dot_product_attention(
        q, q, q, causal=True, window=window)).trace(q)
    if given == "einsum":       # under the gate's length, as without a window
        assert calls == []
        assert tracer.counters == {"attention.einsum_calls": 1,
                                   "attention.window_einsum_calls": 1}
    else:
        assert calls == [(q.shape, given)]
        assert tracer.counters == dict(
            {"attention.kernel_calls": 1},
            **({"attention.window_kernel_calls": 1} if given else {}))


def test_window_is_part_of_the_kernel_cache_key():
    causal = PK._splash_kernel(2, 256, 128, True, True, None)
    assert PK._splash_kernel(2, 256, 128, True, True, None) is causal
    windowed = PK._splash_kernel(2, 256, 128, True, True, 128)
    assert windowed is not causal
    assert PK._splash_kernel(2, 256, 128, True, True, 128) is windowed


# ------------------------------------------- the window, in the splash kernel
@pytest.mark.parametrize("window", [128, 192])
def test_local_mask_kernel_matches_einsum_in_the_interpreter(rng, window):
    """T=384 takes 128-row blocks: a window of one block and of one and a
    half. Forward and all three gradients against the einsum path."""
    helper = PK.PallasFlashAttentionHelper(causal=True, interpret=True)
    q, k, v, w = (jnp.asarray(f32(rng.normal(size=(1, 2, 384, 64))))
                  for _ in range(4))
    sizes = PK._splash_block_sizes(384, 64 * 4)
    assert sizes.block_q == sizes.block_kv == 128

    def kernel(q, k, v):
        return helper.attend(q, k, v, window=window)

    def stock(q, k, v):
        return A.dot_product_attention(q, k, v, causal=True, window=window)

    def out_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))(
                q, k, v)

    np.testing.assert_allclose(jax.jit(kernel)(q, k, v), stock(q, k, v),
                               rtol=2e-4, atol=2e-5)
    causal = A.dot_product_attention(q, k, v, causal=True)
    assert not np.allclose(stock(q, k, v), causal, atol=1e-3)
    (loss_a, grads_a), (loss_b, grads_b) = (out_and_grads(kernel),
                                            out_and_grads(stock))
    assert float(loss_a) == pytest.approx(float(loss_b), rel=2e-4, abs=1e-2)
    for name, a, b in zip(("dq", "dk", "dv"), grads_a, grads_b):
        b = np.asarray(b)
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * max(1.0, float(np.abs(b).max())),
            err_msg=name)


# ------------------------------------------------------- the shares add up
def test_shares_add_up_with_the_shared_expert_counted_once(rng):
    """What the four chips of a layer return, each its routed part plus the
    shared expert, summed with the shared expert counted once, is the uncut
    layer's output; and that is the layer's equation in plain numpy."""
    options = dict(n_in=12, n_out=12, n_hidden=10, n_experts=8, top_k=2,
                   gated=True, activation="silu", gate="sigmoid",
                   expert_bias=True, norm_topk=True, norm_topk_eps=1e-20,
                   routed_scaling=2.826)
    whole = MixtureOfExpertsLayer(**options)
    params = whole.init_params(jax.random.PRNGKey(4))
    x = jnp.asarray(f32(rng.normal(size=(2, 9, 12))))
    w1, w3, w2 = (jnp.asarray(f32(rng.normal(size=s)) * 0.3)
                  for s in ((12, 10), (12, 10), (10, 12)))
    shared = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    uncut = whole.forward(params, x)[0] + shared
    from_chips = []
    for first in (0, 2, 4, 6):
        share = MixtureOfExpertsLayer(experts_held=(first, 2), **options)
        mine = dict(params, **{n: params[n][first:first + 2]
                               for n in ("W1", "W3", "W2")})
        routed, state = share.forward(mine, x, state=share.init_state())
        assert int(state["expert_rows"].sum() + state["rows_elsewhere"]) \
            == 2 * 9 * 2
        from_chips.append(routed + shared)
    summed = sum(from_chips) - 3 * shared
    np.testing.assert_allclose(summed, uncut, rtol=1e-5, atol=1e-6)
    # the equation: s = sigmoid(x Wg), top 2 of s + b, w = s / sum * 2.826
    xs = f32(x).reshape(-1, 12)
    s = 1 / (1 + np.exp(-(xs @ f32(params["Wg"]))))
    want = f32(shared).reshape(-1, 12).copy()
    for row, scores in enumerate(s):
        chosen = np.argsort(-(scores + f32(params["expert_bias"])))[:2]
        weights = scores[chosen] / (scores[chosen].sum() + 1e-20) * 2.826
        for e, weight in zip(chosen, weights):
            h1, h3 = xs[row] @ f32(params["W1"][e]), xs[row] @ f32(params["W3"][e])
            want[row] += weight * ((h1 / (1 + np.exp(-h1)) * h3)
                                   @ f32(params["W2"][e]))
    np.testing.assert_allclose(f32(uncut).reshape(-1, 12), want, rtol=2e-4,
                               atol=2e-5)


def test_norm_epsilon_is_the_layers_own(rng):
    x = jnp.asarray(np.abs(f32(rng.normal(size=(5, 12)))) + 0.5)
    wg = jnp.asarray(f32(rng.normal(size=(12, 8))) * 0.1 - 2.0)  # tiny scores
    from deeplearning4j_tpu.nn.layers.moe import _route
    _, loose = _route(wg, x, 2, "sigmoid", norm_topk=True)
    _, tight = _route(wg, x, 2, "sigmoid", norm_topk=True, norm_eps=1e-20)
    np.testing.assert_allclose(jnp.sum(tight, -1), 1.0, rtol=1e-4)
    assert float(jnp.max(jnp.sum(loose, -1))) < 0.5


# ------------------------------------------------------------ the zoo model
def small_model(config=SMALL, seed=11):
    return ComputationGraph(family.network_conf(config, seed)).init()


@pytest.fixture(scope="module")
def trained():
    """A small model away from its initial weights, and held-out tokens."""
    rng = np.random.default_rng(7)
    net = small_model()
    tokens = rng.integers(0, 96, (3, 32)).astype(np.int32)
    batch = DataSet(tokens, lm_labels(tokens, 96))
    for _ in range(10):
        net.fit(batch)
    return net, rng.integers(0, 96, (2, 32)).astype(np.int32)


def test_zoo_model_builds_the_block_as_published():
    conf = GatedWindowMoELM().conf()
    layers = {name: v.obj for name, v in conf.vertices.items()}
    assert [n for n in layers if n.endswith(("-swa", "-att"))] == [
        f"block{i}-{'att' if i % 4 == 3 else 'swa'}" for i in range(32)]
    swa, att = layers["block0-swa"], layers["block3-att"]
    assert (swa.window, swa.rope_theta, att.window, att.rope_theta) == (
        2048, 1e4, None, None)
    assert swa.output_gate and att.output_gate and swa.qk_norm
    assert (att.n_heads, att.n_kv_heads, att.head_size) == (32, 4, 128)
    assert layers["embed-scale"].scale_factor == 2048 ** 0.5
    assert "block1-moe" not in layers and layers["block1-ff1"].n_out == 6144
    moe = layers["block2-moe"]
    assert (moe.n_experts, moe.top_k, moe.n_hidden) == (128, 8, 1024)
    assert (moe.norm_topk_eps, moe.routed_scaling) == (1e-20, 2.826)
    assert layers["block2-shared1"].n_out == 1024
    assert layers["out"].n_out == 200192
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.vertices["block0-swa"].obj.window == 2048
    assert json.loads(conf.to_json()) == json.loads(again.to_json())
    with pytest.raises(ValueError):
        GatedWindowMoELM(layer_types=("conv",)).conf()


@pytest.mark.parametrize("held", [(0, 8), (4, 4)])
def test_zoo_model_matches_the_reference_loss_and_gradients(rng, held):
    config = dict(SMALL, experts_held_first=held[0], num_experts_held=held[1])
    net = small_model(config)
    tokens = rng.integers(0, 96, (3, 32)).astype(np.int32)
    batch = DataSet(tokens, lm_labels(tokens, 96))
    for _ in range(10):         # away from the initial weights
        net.fit(batch)
    tokens = rng.integers(0, 96, (2, 32)).astype(np.int32)
    grads, loss = net.compute_gradient_and_score(tokens,
                                                 lm_labels(tokens, 96))
    reference = lambda p: family.reference_loss(p, tokens, config=config)
    want, want_grads = jax.value_and_grad(reference)(net.params)
    assert abs(float(want) - np.log(96)) > 0.02
    assert loss == pytest.approx(float(want), rel=1e-5)
    for name, owned in grads.items():
        for key, got in owned.items():
            if key == "expert_bias":
                assert float(jnp.abs(got).max()) == 0.0
                continue
            scale = float(jnp.abs(want_grads[name][key]).max())
            np.testing.assert_allclose(
                got, want_grads[name][key], rtol=1e-4, atol=1e-4 * scale,
                err_msg=f"{name}/{key}")


@pytest.mark.parametrize("fault", sorted(family.FAULTS))
def test_every_part_of_the_block_matters(trained, fault):
    """The gate, the scale of the embedding, each of the four norms, the
    missing rotation of the full layer and the window: with one of them
    wrong the reference no longer agrees with the program."""
    net, tokens = trained
    got = float(net.score(DataSet(tokens, lm_labels(tokens, 96))))
    right = float(family.reference_loss(net.params, tokens, config=SMALL))
    wrong = float(family.reference_loss(net.params, tokens, config=SMALL,
                                        leave_out=(fault,)))
    assert got == pytest.approx(right, rel=1e-5)
    assert abs(wrong - got) > 1e-3 * got, fault


def test_reference_refuses_an_unknown_fault(trained):
    net, tokens = trained
    with pytest.raises(ValueError):
        family.reference_loss(net.params, tokens, config=SMALL,
                              leave_out=("windows",))


def test_zoo_model_trains_through_fit_in_bfloat16(rng, tracer):
    config = dict(SMALL, compute_dtype="bfloat16", experts_held_first=2,
                  num_experts_held=4)
    net = small_model(config)
    tokens = rng.integers(0, 96, (4, 32)).astype(np.int32)
    batch = DataSet(tokens, lm_labels(tokens, 96))
    first = None
    for _ in range(30):
        net.fit(batch)
        first = first if first is not None else float(net.score_)
    assert np.isfinite(float(net.score_)) and float(net.score_) < first - 0.5
    assert net.params["block1-moe"]["W1"].shape == (4, 64, 32)
    # one trace of the step: three window layers on the einsum path here
    assert tracer.counters["attention.window_einsum_calls"] == 3
    assert tracer.counters["attention.einsum_calls"] == 4
    assert "attention.window_kernel_calls" not in tracer.counters
