"""The LFM2-MoE family on the CPU at a small size: each new layer against its
equations, sparse expert dispatch against the all-expert einsum it replaced,
the four shares of an expert layer against the uncut layer, and the zoo
model against the benchmark's plain float32 reference, loss and gradients."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.configs import lfm2 as family
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import (
    GatedShortConvLayer,
    GroupedQueryAttentionLayer,
    MixtureOfExpertsLayer,
    RMSNormLayer,
)
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.layers.attention import rotary_embedding
from deeplearning4j_tpu.nn.layers.moe import (
    EXPERT_AXIS,
    _moe_apply,
    _route,
    ep_forward,
)
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.zoo.models import HybridConvMoELM, lm_labels

SMALL = {
    "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_experts_held": 8, "experts_held_first": 0,
    "vocab_size": 96, "max_position_embeddings": 48, "num_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1e6,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "compute_dtype": None,
}


def f32(a):
    return np.asarray(a, np.float32)


def gated_layer(held=None, **kw):
    return MixtureOfExpertsLayer(
        n_in=12, n_out=12, n_hidden=10, n_experts=8, top_k=3, gated=True,
        activation="silu", gate="sigmoid", expert_bias=True, norm_topk=True,
        experts_held=held, **kw)


# ------------------------------------------------------------------ RMSNorm
def test_rms_norm_is_its_equation(rng):
    layer = RMSNormLayer(n_in=6, eps=1e-5)
    x = f32(rng.normal(size=(2, 3, 6)))
    gamma = f32(rng.normal(size=6))
    got, _ = layer.forward({"gamma": jnp.asarray(gamma)}, jnp.asarray(x))
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * gamma
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert layer.init_params(jax.random.PRNGKey(0))["gamma"].shape == (6,)


# --------------------------------------------------- gated short convolution
def test_short_conv_matches_an_explicit_loop(rng):
    layer = GatedShortConvLayer(n_in=5, n_out=5, kernel_size=3)
    params = layer.init_params(jax.random.PRNGKey(1))
    assert {k: v.shape for k, v in params.items()} == {
        "Win": (5, 15), "K": (5, 3), "Wout": (5, 5)}
    x = f32(rng.normal(size=(2, 7, 5)))
    got, _ = layer.forward(params, jnp.asarray(x))
    w_in, k, w_out = (f32(params[n]) for n in ("Win", "K", "Wout"))
    want = np.zeros_like(x)
    for n in range(2):
        bcx = x[n] @ w_in
        gate_b, gate_c, inner = bcx[:, :5], bcx[:, 5:10], bcx[:, 10:]
        u = gate_b * inner
        for t in range(7):
            v = np.zeros(5, np.float32)
            for j in range(3):          # v_t = sum_j k[:, j] * u_{t-2+j}
                if t - 2 + j >= 0:
                    v += k[:, j] * u[t - 2 + j]
            want[n, t] = (gate_c[t] * v) @ w_out
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_short_conv_is_causal_and_its_carry_continues_the_sequence(rng):
    layer = GatedShortConvLayer(n_in=4, n_out=4, kernel_size=3)
    params = layer.init_params(jax.random.PRNGKey(2))
    x = jnp.asarray(f32(rng.normal(size=(2, 9, 4))))
    whole, _ = layer.forward(params, x)
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_allclose(layer.forward(params, later)[0][:, :5],
                               whole[:, :5], rtol=1e-6)
    carry = layer.init_carry(2)
    assert carry.shape == (2, 2, 4)
    pieces = []
    for lo, hi in ((0, 1), (1, 2), (2, 6), (6, 9)):
        y, carry = layer.forward_seq(params, x[:, lo:hi], carry=carry)
        pieces.append(y)
    np.testing.assert_allclose(jnp.concatenate(pieces, 1), whole,
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------ grouped-query attention
def manual_attention(layer, params, x):
    """Plain numpy: K and V repeated to the query heads, QK-norm, rotary
    positions (rotate-half), causal softmax."""
    n, t, _ = x.shape
    h, hkv, dh = layer.n_heads, layer._kv_heads(), layer._dh()
    q = (x @ f32(params["Wq"])).reshape(n, t, h, dh)
    kv = (x @ f32(params["Wkv"])).reshape(n, t, hkv, 2, dh)
    k, v = kv[:, :, :, 0], kv[:, :, :, 1]
    if layer.qk_norm:
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
    scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    mixed = np.einsum("nhqk,nkhd->nqhd", w, v).reshape(n, t, h * dh)
    return mixed @ f32(params["Wo"])


@pytest.mark.parametrize("qk_norm, rope_theta", [(False, None), (True, None),
                                                 (False, 1e6), (True, 1e6)])
def test_grouped_heads_match_repeated_kv(rng, qk_norm, rope_theta):
    layer = GroupedQueryAttentionLayer(
        n_in=16, n_out=16, n_heads=4, n_kv_heads=2, head_size=4,
        use_bias=False, qk_norm=qk_norm, rope_theta=rope_theta)
    params = layer.init_params(jax.random.PRNGKey(3))
    assert params["Wq"].shape == (16, 16) and params["Wkv"].shape == (16, 16)
    assert not any(n.startswith("b") for n in params)
    if qk_norm:
        params["q_norm"] = jnp.asarray(f32(rng.uniform(0.5, 1.5, 4)))
        params["k_norm"] = jnp.asarray(f32(rng.uniform(0.5, 1.5, 4)))
    x = f32(rng.normal(size=(2, 6, 16)))
    got, _ = layer.forward(params, jnp.asarray(x))
    np.testing.assert_allclose(got, manual_attention(layer, params, x),
                               rtol=2e-4, atol=2e-5)


def test_rotary_embedding_turns_pairs_and_keeps_position_zero(rng):
    x = jnp.asarray(f32(rng.normal(size=(1, 2, 5, 8))))
    out = rotary_embedding(x, jnp.arange(5), 1e6)
    np.testing.assert_allclose(out[:, :, 0], x[:, :, 0], rtol=1e-6)
    # a rotation keeps the length of every (i, i + Dh/2) pair
    pair = lambda a: a[..., :4] ** 2 + a[..., 4:] ** 2
    np.testing.assert_allclose(pair(out), pair(x), rtol=1e-5)
    # relative: <rot(q, m), rot(k, n)> depends on m - n alone
    q, k = x[0, 0, 0], x[0, 1, 0]
    at = lambda v, p: rotary_embedding(v[None, None, None], jnp.array([p]),
                                       1e6)[0, 0, 0]
    assert float(at(q, 7) @ at(k, 3)) == pytest.approx(
        float(at(q, 14) @ at(k, 10)), rel=1e-4)


def test_attention_cache_takes_kv_heads_and_continues_the_sequence(rng):
    layer = GroupedQueryAttentionLayer(
        n_in=16, n_out=16, n_heads=4, n_kv_heads=2, head_size=4,
        use_bias=False, qk_norm=True, rope_theta=1e4, max_cache=16)
    params = layer.init_params(jax.random.PRNGKey(4))
    x = jnp.asarray(f32(rng.normal(size=(2, 7, 16))))
    whole, _ = layer.forward(params, x)
    carry = layer.init_carry(2)
    assert carry[0].shape == (2, 2, 16, 4)      # n_kv_heads, not n_heads
    pieces = []
    for lo, hi in ((0, 3), (3, 4), (4, 7)):
        y, carry = layer.forward_seq(params, x[:, lo:hi], carry=carry)
        pieces.append(y)
    np.testing.assert_allclose(jnp.concatenate(pieces, 1), whole,
                               rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------- routing
def test_sigmoid_routing_bias_moves_the_selection_not_the_weights(rng):
    wg = jnp.asarray(f32(rng.normal(size=(6, 8))))
    x = jnp.asarray(f32(rng.normal(size=(40, 6))))
    plain, w_plain = _route(wg, x, 2, "sigmoid", None, True)
    bias = jnp.zeros(8).at[5].set(10.0)         # expert 5 always selected
    picked, weights = _route(wg, x, 2, "sigmoid", bias, True)
    assert bool(jnp.all(jnp.any(picked == 5, -1)))
    assert not np.array_equal(np.asarray(plain), np.asarray(picked))
    scores = np.asarray(jax.nn.sigmoid(x @ wg))
    want = np.take_along_axis(scores, np.asarray(picked), -1)
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(weights, want, rtol=1e-5)   # s, never s + b
    np.testing.assert_allclose(np.asarray(w_plain).sum(-1), 1.0, atol=1e-4)
    # and the bias takes no gradient
    grad = jax.grad(lambda b: jnp.sum(_route(wg, x, 2, "sigmoid", b, True)[1]
                                      ** 2))(bias)
    assert float(jnp.abs(grad).max()) == 0.0


def test_router_parameters_stay_float32_under_mixed_precision():
    model = HybridConvMoELM(
        vocab_size=64, max_length=16, layer_types=("conv", "conv"),
        num_dense_layers=1, d_model=16, n_heads=2, n_kv_heads=1, d_ff=32,
        n_experts=4, experts_per_token=2, expert_d_ff=8)
    conf = model.conf()
    conf.global_conf.compute_dtype = "bfloat16"
    net = ComputationGraph(conf).init()
    seen = {}
    layer = conf.vertices["block1-moe"].obj
    forward = layer.forward

    def spy(params, x, **kw):
        seen.update({k: v.dtype for k, v in params.items()}, x=x.dtype)
        return forward(params, x, **kw)

    layer.forward = spy
    try:
        net.output(np.zeros((1, 16), np.int32))
    finally:
        del layer.forward
    assert seen["Wg"] == seen["expert_bias"] == jnp.float32
    assert seen["W1"] == seen["W2"] == seen["x"] == jnp.bfloat16


# ------------------------------------------------------------ sparse experts
@pytest.mark.parametrize("top_k, shape", [(1, (9, 6)), (2, (3, 5, 6)),
                                          (4, (17, 6))])
def test_sparse_dispatch_matches_the_all_expert_einsum(rng, top_k, shape):
    """What `_moe_apply` computed before: every expert on every token, the
    gates (softmax over the top-k logits) selecting and weighting."""
    layer = MixtureOfExpertsLayer(n_in=6, n_out=7, n_experts=4, top_k=top_k)
    params = layer.init_params(jax.random.PRNGKey(5))
    params["b"] = jnp.asarray(f32(rng.normal(size=(4, 7))))
    x = jnp.asarray(f32(rng.normal(size=shape)))
    got, gates = _moe_apply(params, x, top_k, layer.act_fn())
    logits = x @ params["Wg"]
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    dense = jnp.sum(jax.nn.one_hot(top_idx, 4)
                    * jax.nn.softmax(top_vals, -1)[..., None], -2)
    hidden = jax.nn.relu(jnp.einsum("...d,edh->...eh", x, params["W"])
                         + params["b"])
    want = jnp.einsum("...eh,...e->...h", hidden, dense)
    np.testing.assert_allclose(gates, dense, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    out, state = layer.forward(params, x)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    tokens = int(np.prod(shape[:-1]))
    assert int(state["expert_rows"].sum()) == tokens * top_k
    assert int(state["rows_elsewhere"]) == 0


def routed_to(experts, only=None):
    """A router that sends every token to `experts` (three of the eight), and
    token `only[0]` to expert `only[1]` in place of the last of them:
    `(Wg, expert_bias, x[:, 0])`. The bias alone selects, so the weights
    stay the sigmoid scores."""
    bias = np.zeros(8, np.float32)
    bias[list(experts)] = 0.1 * (1 + np.arange(len(experts)))
    wg = np.zeros((12, 8), np.float32)
    column = np.zeros(64, np.float32)
    if only is not None:
        wg[0, only[1]] = 10.0       # sigmoid(10) beats 0.5 + 0.1
        column[:] = -1.0
        column[only[0]] = 1.0
    return wg, bias, column


# (tokens, block, held, forced routing, pairs held) with `_ROW_BLOCK` = block:
# the loops over the held rows run 0, 1, 1, 2, 2 and 5 times (the last one
# row into its block), and once over everything with the shipped block
HELD_ROWS = {
    "no pair held": (11, 8, (0, 2), routed_to((5, 6, 7)), 0),
    "one pair held": (11, 8, (0, 1), routed_to((5, 6, 7), only=(3, 0)), 1),
    "a block to its last row": (8, 8, (5, 1), routed_to((5, 6, 7)), 8),
    "two blocks to the last row": (8, 8, (5, 2), routed_to((5, 6, 7)), 16),
    "the average case": (11, 8, (2, 3), None, None),
    "every pair held": (11, 8, None, None, 33),
    "every pair held, one block": (11, None, None, None, 33),
}


@pytest.mark.parametrize("form", ["plain", "kernel twin"])
@pytest.mark.parametrize("case", list(HELD_ROWS))
def test_sparse_dispatch_gradients_match_the_dense_formulation(
        rng, monkeypatch, case, form):
    """The share a layer holds against every held expert on every token,
    output and all gradients, at routings that end the block loops
    (`moe._for_held_blocks`) before the first block, inside one, on a
    block's last row, and after the last; the counts add up each time.
    Both forms of the layer: the plain one as the CPU takes it, and the
    kernels' through their plain twin (`kernel_twin`; the kernels
    themselves compile only on a TPU)."""
    m, block, held, forced, n_held = HELD_ROWS[case]
    if block:
        monkeypatch.setattr(moe, "_ROW_BLOCK", block)
    if form == "kernel twin":
        kernel_twin(monkeypatch)
    layer = gated_layer(held)
    first, count = held or (0, 8)
    params = layer.init_params(jax.random.PRNGKey(6))
    x = f32(rng.normal(size=(m, 12)))
    if forced is not None:
        wg, bias, column = forced
        params["Wg"] = jnp.asarray(wg)
        params["expert_bias"] = jnp.asarray(bias)
        x[:, 0] = column[:m]
    x = jnp.asarray(x)

    def dense(params, x):
        picked, weights = _route(params["Wg"], x, 3, "sigmoid",
                                 params["expert_bias"], True)
        gates = jnp.sum(jax.nn.one_hot(picked, 8) * weights[..., None], -2)
        hidden = jax.nn.silu(jnp.einsum("md,edh->meh", x, params["W1"])) \
            * jnp.einsum("md,edh->meh", x, params["W3"])
        return jnp.einsum("meo,me->mo",
                          jnp.einsum("meh,eho->meo", hidden, params["W2"]),
                          gates[:, first:first + count])

    loss = lambda fn: lambda p, xx: jnp.sum(jnp.sin(fn(p, xx)))
    sparse = lambda p, xx: layer.forward(p, xx)[0]
    np.testing.assert_allclose(sparse(params, x), dense(params, x),
                               rtol=1e-4, atol=1e-5)
    got = jax.grad(loss(sparse), argnums=(0, 1))(params, x)
    want = jax.grad(loss(dense), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)
    assert float(jnp.abs(got[0]["expert_bias"]).max()) == 0.0
    state = layer.forward(params, x)[1]
    rows, elsewhere = int(state["expert_rows"].sum()), \
        int(state["rows_elsewhere"])
    assert rows + elsewhere == m * 3
    if n_held is not None:
        assert rows == n_held


def kernel_twin(monkeypatch):
    """The layer's form for the grouped kernels (`loose`: buffers start
    unwritten, the backward loops write over what they have read), run
    through a plain twin of those kernels that is as hostile as they may
    be: a row that is in no group is never read, whatever it holds, and
    comes back NaN, forward and in the gradient for the rows; an unwritten
    buffer is NaN throughout."""
    def hostile(rows, sizes):
        past = (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]
        return (lambda a: jnp.where(past, 0, a),            # never read
                lambda a: jnp.where(past, jnp.nan, a))      # never written

    def grouped_matmul_vjp(rows, weights, sizes, g):
        unread, poison = hostile(rows, sizes)
        d_rows, d_weights = jax.vjp(
            lambda r, w: jax.lax.ragged_dot(r, w, sizes), unread(rows),
            weights)[1](unread(g))
        return poison(d_rows), d_weights

    def grouped_matmul(rows, weights, sizes):
        unread, poison = hostile(rows, sizes)

        @jax.custom_vjp
        def product(rows, weights):
            return poison(jax.lax.ragged_dot(unread(rows), weights, sizes))

        def forward(rows, weights):
            return product(rows, weights), (rows, weights)

        def backward(res, g):
            return grouped_matmul_vjp(*res, sizes, g)

        product.defvjp(forward, backward)
        return product(rows, weights)

    monkeypatch.setattr(moe, "_grouped_matmul_vjp", grouped_matmul_vjp)
    monkeypatch.setattr(moe, "_grouped_matmul", grouped_matmul)
    monkeypatch.setattr(moe, "_megablox_tiling", lambda *a: (8, 8, 8))
    monkeypatch.setattr(
        moe, "_unwritten", lambda shape, dtype: jnp.full(shape, jnp.nan,
                                                         dtype))


@pytest.mark.parametrize("poison", [False, True])
@pytest.mark.parametrize("held", [None, (4, 4), (0, 2)])
def test_no_row_is_lost_when_every_token_goes_to_one_expert(
        rng, monkeypatch, held, poison):
    """Forced imbalance: the router sends all 50 tokens to experts 5, 6, 7.
    A layer that holds them computes 150 rows there; one that does not
    counts 150 rows elsewhere and returns zero. With `poison`, the layer
    takes the kernels' form through `kernel_twin`, NaN in every row past the
    last group and in every unwritten buffer, in blocks of 64 rows so that
    a block holds both kinds: output and gradients stay finite and the
    same."""
    if poison:
        monkeypatch.setattr(moe, "_ROW_BLOCK", 64)
        kernel_twin(monkeypatch)
    layer = gated_layer(held)
    params = layer.init_params(jax.random.PRNGKey(7))
    params["Wg"] = jnp.zeros((12, 8))
    params["expert_bias"] = jnp.asarray(
        f32([0, 0, 0, 0, 0, 1.0, 2.0, 3.0]))
    x = jnp.asarray(f32(rng.normal(size=(50, 12))))
    out, state = jax.jit(lambda p, xx: layer.forward(p, xx))(params, x)
    rows, elsewhere = np.asarray(state["expert_rows"]), \
        int(state["rows_elsewhere"])
    assert rows.sum() + elsewhere == 50 * 3
    first, count = held or (0, 8)
    want_rows = [50 if first + e >= 5 else 0 for e in range(count)]
    assert rows.tolist() == want_rows
    # equal scores: each selected expert weighs a third
    want = np.zeros((50, 12), np.float32)
    for e in range(count):
        if first + e >= 5:
            w1, w3, w2 = (f32(params[n][e]) for n in ("W1", "W3", "W2"))
            a = f32(x) @ w1
            want += ((a / (1 + np.exp(-a))) * (f32(x) @ w3)) @ w2 / 3
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    assert np.isfinite(np.asarray(out)).all()
    grads = jax.grad(lambda p, xx: jnp.sum(jnp.sin(layer.forward(p, xx)[0])),
                     argnums=(0, 1))(params, x)
    for grad in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(grad)).all()
    if held == (0, 2):          # nothing held: nothing reaches a weight
        assert all(float(jnp.abs(grads[0][n]).max()) == 0.0
                   for n in ("W1", "W2", "W3"))
    else:
        assert float(jnp.abs(grads[0]["W2"][-1]).max()) > 0.0


@pytest.mark.parametrize("block", [None, 16])
def test_the_four_shares_add_up_to_the_uncut_layer(rng, monkeypatch, block):
    """Experts 0-7, 8-15, 16-23 and 24-31 of one seed, each routing over all
    32 and computing its own part: their sum is the whole layer, and so is
    the plain reference's. With the 160 pairs in one block of rows, and in
    ten, of which a share fills about three."""
    if block:
        monkeypatch.setattr(moe, "_ROW_BLOCK", block)
    kw = dict(n_in=16, n_out=16, n_hidden=12, n_experts=32, top_k=4,
              gated=True, activation="silu", gate="sigmoid",
              expert_bias=True, norm_topk=True)
    whole = MixtureOfExpertsLayer(**kw)
    params = whole.init_params(jax.random.PRNGKey(8))
    x = jnp.asarray(f32(rng.normal(size=(2, 20, 16))))
    uncut, state = whole.forward(params, x)
    assert int(state["rows_elsewhere"]) == 0
    total, rows = 0.0, []
    for first in (0, 8, 16, 24):
        share = MixtureOfExpertsLayer(experts_held=(first, 8), **kw)
        held = share.init_params(jax.random.PRNGKey(8))
        np.testing.assert_array_equal(held["W1"],
                                      params["W1"][first:first + 8])
        np.testing.assert_array_equal(held["Wg"], params["Wg"])
        part, st = share.forward(held, x)
        total = total + part
        rows.append(int(st["expert_rows"].sum()))
        assert rows[-1] + int(st["rows_elsewhere"]) == 40 * 4
    assert sum(rows) == 40 * 4
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    # the reference's expert layer, uncut, by its own arithmetic
    config = dict(SMALL, hidden_size=16, moe_intermediate_size=12,
                  num_experts=32, num_experts_per_tok=4, num_experts_held=32)
    scores = jax.nn.sigmoid(x @ params["Wg"])
    _, picked = jax.lax.top_k(scores + params["expert_bias"], 4)
    weights = jnp.take_along_axis(scores, picked, -1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    want = 0.0
    for e in range(config["num_experts"]):
        w_e = jnp.sum(jnp.where(picked == e, weights, 0.0), -1, keepdims=True)
        want = want + w_e * ((jax.nn.silu(x @ params["W1"][e])
                              * (x @ params["W3"][e])) @ params["W2"][e])
    np.testing.assert_allclose(uncut, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("form", ["plain", "kernel twin"])
def test_a_traced_expert_layer_counts_the_form_it_took(rng, monkeypatch, form):
    """`moe.held_rows_plain_calls` / `moe.held_rows_kernel_calls` on the
    tracer, once for each time the layer is traced and never for a run of
    what was traced; nothing is counted with tracing off."""
    from deeplearning4j_tpu import observe

    if form == "kernel twin":
        kernel_twin(monkeypatch)
    name = "moe.held_rows_kernel_calls" if form == "kernel twin" \
        else "moe.held_rows_plain_calls"
    layer = gated_layer((0, 4))
    params = layer.init_params(jax.random.PRNGKey(10))
    x = jnp.asarray(f32(rng.normal(size=(9, 12))))
    step = jax.jit(lambda p, xx: layer.forward(p, xx)[0])
    tracer = observe.enable_tracing()
    try:
        step(params, x)
        step(params, x)
        assert tracer.counters == {name: 1}
    finally:
        observe.disable_tracing()
    jax.jit(lambda p, xx: layer.forward(p, xx)[0] * 2)(params, x)
    assert tracer.counters == {name: 1}


def test_expert_parallel_forward_of_gated_sigmoid_experts(rng):
    layer = gated_layer()
    params = layer.init_params(jax.random.PRNGKey(9))
    x = jnp.asarray(f32(rng.normal(size=(10, 12))))
    plain, _ = layer.forward(params, x)
    sharded = ep_forward(layer, params, x, make_mesh({EXPERT_AXIS: 4}))
    np.testing.assert_allclose(sharded, plain, rtol=2e-5, atol=2e-6)


def test_expert_layer_options_are_checked_and_survive_json():
    with pytest.raises(ValueError):
        MixtureOfExpertsLayer(n_experts=4, gate="tanh")
    with pytest.raises(ValueError):
        MixtureOfExpertsLayer(n_experts=4, experts_held=(2, 4))
    conf = HybridConvMoELM(
        vocab_size=64, max_length=16,
        layer_types=("conv", "full_attention"), num_dense_layers=1,
        d_model=16, n_heads=2, n_kv_heads=1, d_ff=32, n_experts=4,
        experts_per_token=2, expert_d_ff=8, experts_held=(2, 2)).conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    layer = again.vertices["block1-moe"].obj
    assert layer.experts_held == (2, 2) and layer.gate == "sigmoid"
    assert again.vertices["block1-att"].obj.n_kv_heads == 1
    assert again.vertices["block0-conv"].obj.kernel_size == 3
    assert "pos" not in again.vertices
    assert json.loads(conf.to_json()) == json.loads(again.to_json())


# ------------------------------------------------------------ the zoo model
def small_model(config, seed=11):
    conf = family.network_conf(config, seed)
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("held", [(0, 8), (4, 4)])
def test_zoo_model_matches_the_reference_loss_and_gradients(rng, held):
    config = dict(SMALL, experts_held_first=held[0], num_experts_held=held[1])
    net = small_model(config)
    tokens = rng.integers(0, 96, (3, 48)).astype(np.int32)
    batch = DataSet(tokens, lm_labels(tokens, 96))
    for _ in range(10):         # away from the initial weights
        net.fit(batch)
    tokens = rng.integers(0, 96, (2, 48)).astype(np.int32)
    grads, loss = net.compute_gradient_and_score(tokens,
                                                 lm_labels(tokens, 96))
    reference = lambda p: family.reference_loss(p, tokens, config=config)
    want, want_grads = jax.value_and_grad(reference)(net.params)
    assert abs(float(want) - np.log(96)) > 0.02
    assert loss == pytest.approx(float(want), rel=2e-5)
    for name, owned in grads.items():
        for key, got in owned.items():
            if key == "expert_bias":
                assert float(jnp.abs(got).max()) == 0.0
                continue
            np.testing.assert_allclose(
                got, want_grads[name][key], rtol=5e-3, atol=2e-6,
                err_msg=f"{name}/{key}")


def test_reference_notices_wrong_experts_and_wrong_positions(rng):
    net = small_model(SMALL)
    tokens = rng.integers(0, 96, (2, 48)).astype(np.int32)
    want = float(family.reference_loss(net.params, tokens, config=SMALL))
    other_share = dict(SMALL, experts_held_first=0, num_experts_held=4)
    params = {k: dict(v) for k, v in net.params.items()}
    for name in ("block1-moe", "block2-moe"):
        for w in ("W1", "W3", "W2"):
            params[name][w] = params[name][w][:4]
    assert abs(float(family.reference_loss(params, tokens,
                                           config=other_share)) - want) > 1e-4
    no_rotation = dict(SMALL, rope_theta=1.0)   # every frequency 1
    assert abs(float(family.reference_loss(net.params, tokens,
                                           config=no_rotation)) - want) > 1e-5


def test_zoo_model_decodes_step_by_step_like_its_full_forward(rng):
    net = small_model(SMALL)
    tokens = rng.integers(0, 96, (2, 12)).astype(np.int32)
    whole = np.asarray(net.output(tokens))
    net.rnn_clear_previous_state()
    pieces = [np.asarray(net.rnn_time_step(tokens[:, lo:hi, None]))
              for lo, hi in ((0, 5), (5, 6), (6, 12))]
    np.testing.assert_allclose(np.concatenate(pieces, 1), whole,
                               rtol=2e-4, atol=2e-5)


def test_zoo_model_trains_through_fit_in_bfloat16(rng):
    config = dict(SMALL, compute_dtype="bfloat16", experts_held_first=0,
                  num_experts_held=4)
    net = small_model(config)
    tokens = rng.integers(0, 96, (4, 48)).astype(np.int32)
    batch = DataSet(tokens, lm_labels(tokens, 96))
    first = None
    for _ in range(30):
        net.fit(batch)
        first = first if first is not None else float(net.score_)
    assert float(net.score_) < first - 0.3
    state = net.states["block1-moe"]
    assert int(state["expert_rows"].sum()) + int(state["rows_elsewhere"]) \
        == 4 * 48 * 2
    got = float(net.score(DataSet(tokens, lm_labels(tokens, 96))))
    want = float(family.reference_loss(net.params, tokens, config=config))
    assert got == pytest.approx(want, rel=3e-2)
