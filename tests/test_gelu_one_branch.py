"""The ``"gelu"`` activation on a bfloat16 stream (``nn/activations.py``):
one exponential, one reciprocal and one polynomial in place of the three
branches XLA makes of ``erfc``, held over every bfloat16 bit pattern to the
correctly rounded value of the float64 function, forward and derivative;
every other dtype keeps ``jax.nn.gelu(approximate=False)`` to the bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.special import erfc

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

ALL_BITS = np.arange(65536, dtype=np.uint16)
ALL = jnp.asarray(ALL_BITS).view(jnp.bfloat16)
with np.errstate(invalid="ignore"):         # the signalling NaN patterns
    ALL64 = np.asarray(ALL.astype(jnp.float32)).astype(np.float64)
FINITE = np.isfinite(ALL64)
# every finite input, in bands that each hold some thousands of patterns:
# the cancelling middle, the tail down to where the exponential underflows
# (13.4), and the far range where gelu is max(x, 0) exactly
BANDS = {"neg-below-1": (-1.0, -0.0), "neg-1-to-16": (-16.0, -1.0),
         "neg-beyond-16": (-np.inf, -16.0), "pos-below-1": (0.0, 1.0),
         "pos-1-to-16": (1.0, 16.0), "pos-beyond-16": (16.0, np.inf)}


def _band(name):
    lo, hi = BANDS[name]
    sign = np.signbit(ALL64) == name.startswith("neg")
    inside = (np.abs(ALL64) >= min(abs(lo), abs(hi))) \
        & (np.abs(ALL64) < max(abs(lo), abs(hi)))
    return FINITE & sign & inside


def test_the_bands_hold_every_finite_input_once():
    held = sum(_band(name).astype(int) for name in BANDS)
    assert (held[FINITE] == 1).all() and FINITE.sum() == 65280


def _gelu64(x):
    with np.errstate(invalid="ignore"):     # inf * 0 at the infinities
        return 0.5 * x * erfc(-x / np.sqrt(2.0))


def _dgelu64(x):
    with np.errstate(invalid="ignore"):
        return 0.5 * erfc(-x / np.sqrt(2.0)) \
            + x * np.exp(-0.5 * x * x) / np.sqrt(2.0 * np.pi)


def _ulps(got, want):
    """|got - want| in units of bfloat16's last place at ``want`` (8 bits of
    significand; below the smallest normal the spacing stays 2**-133)."""
    with np.errstate(invalid="ignore"):
        exponent = np.floor(np.log2(np.maximum(np.abs(want), 2.0 ** -126)))
        return np.abs(np.asarray(got, np.float64) - want) \
            / 2.0 ** (exponent - 7)


def _erfc_form(x):
    return jax.nn.gelu(x, approximate=False)


def _grad_of(fn):
    return jax.jit(jax.grad(lambda x: jnp.sum(fn(x).astype(jnp.float32))))


# XLA on the CPU and the TPU flush float32 subnormals to zero. So a result at
# or below the smallest normal number may read zero, and where exp(-x^2 / 2)
# itself is below it (|x| > 13.2) the function may read as its limit:
# max(x, 0), with derivative 0 or 1. Every other input is held to the bar.
SMALLEST_NORMAL = 2.0 ** -126
UNDERFLOWN = np.exp(-0.5 * np.where(FINITE, ALL64, 0.0) ** 2) < SMALLEST_NORMAL


def _off_the_bar(got, want, limit_value, bar):
    """The inputs whose result is neither within ``bar`` ulps of float64 nor
    an allowed flush."""
    got = np.asarray(got.astype(jnp.float32), np.float64)
    flushed = ((np.abs(want) <= SMALLEST_NORMAL) & (got == 0)) \
        | (UNDERFLOWN & (got == limit_value))
    return FINITE & ~flushed & ~(_ulps(got, want) <= bar)


# the float64 function and its limit where the exponential has underflown
FORWARD = (_gelu64(ALL64), np.maximum(ALL64, 0))
DERIVATIVE = (_dgelu64(ALL64), (ALL64 > 0).astype(float))


@pytest.fixture(scope="module")
def forward_off():
    got = jax.jit(activations.gelu)(ALL)
    assert got.dtype == jnp.bfloat16
    return _off_the_bar(got, *FORWARD, 0.5001)


@pytest.fixture(scope="module")
def derivative_off():
    got = _grad_of(activations.gelu)(ALL)
    assert got.dtype == jnp.bfloat16
    return _off_the_bar(got, *DERIVATIVE, 0.5001)


@pytest.mark.parametrize("band", list(BANDS))
def test_forward_is_correctly_rounded_at_every_bfloat16(forward_off, band):
    off = forward_off & _band(band)
    assert not off.any(), ALL64[off]


@pytest.mark.parametrize("band", list(BANDS))
def test_derivative_is_correctly_rounded_at_every_bfloat16(derivative_off,
                                                           band):
    off = derivative_off & _band(band)
    assert not off.any(), ALL64[off]


def test_the_erfc_form_is_not_correctly_rounded():
    # what the bar is worth: the form it replaces misses it at hundreds of
    # inputs forward, and its autodiff at more
    got = jax.jit(_erfc_form)(ALL)
    assert _off_the_bar(got, *FORWARD, 0.5001).sum() > 500
    assert _off_the_bar(got, *FORWARD, 1.0).sum() > 100
    grad = _grad_of(_erfc_form)(ALL)
    assert _off_the_bar(grad, *DERIVATIVE, 0.5001).sum() > 1000
    assert _off_the_bar(grad, *DERIVATIVE, 1.0).sum() > 300


def test_zeros_and_non_finite_inputs_as_the_erfc_form():
    at = ~FINITE | (ALL64 == 0)
    assert at.sum() == 65536 - 65280 + 2
    got = np.asarray(jax.jit(activations.gelu)(ALL).astype(jnp.float32))[at]
    want = np.asarray(jax.jit(_erfc_form)(ALL).astype(jnp.float32))[at]
    np.testing.assert_array_equal(got, want)    # NaN where NaN, else equal
    np.testing.assert_array_equal(np.signbit(got[~np.isnan(got)]),
                                  np.signbit(want[~np.isnan(want)]))


@pytest.mark.parametrize("dtype", ["float16", "float32", "float64"])
def test_other_dtypes_keep_jax_gelu_to_the_bit(dtype):
    with jax.enable_x64(dtype == "float64"):
        rng = np.random.default_rng(0)
        x = jnp.asarray(np.concatenate([
            rng.normal(0, 3, 4096), np.linspace(-14, 14, 513),
            [0.0, -0.0, np.inf, -np.inf, np.nan]]), dtype)
        assert x.dtype == jnp.dtype(dtype)
        got, want = jax.jit(activations.gelu)(x), jax.jit(_erfc_form)(x)
        assert got.dtype == want.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(_grad_of(activations.gelu)(x)),
            np.asarray(_grad_of(_erfc_form)(x)))
        assert "erfc" in str(jax.make_jaxpr(activations.gelu)(x))


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_one_exponential_and_no_erf_in_the_jaxpr(which):
    x = jnp.zeros((4, 8), jnp.bfloat16)
    if which == "forward":
        jaxpr = jax.make_jaxpr(activations.gelu)(x)
    else:
        _, pull = jax.vjp(activations.gelu, x)
        jaxpr = jax.make_jaxpr(pull)(x)
    names = list(_primitives(jaxpr.jaxpr))
    assert names.count("exp") == 1, names
    assert names.count("div") == 1, names
    assert not {"erf", "erfc", "erf_inv", "tanh", "logistic"} & set(names)


# ---------------------------------------------------------------- the LM
LM_V, LM_T, LM_N = 13, 8, 2


def _lm_loss_and_grads(dtype):
    conf = TransformerLM(vocab_size=LM_V, max_length=LM_T, n_layers=2,
                         d_model=16, n_heads=2, d_ff=32, seed=3).conf()
    conf.global_conf.compute_dtype = dtype
    net = ComputationGraph(conf).init()
    tokens = np.random.default_rng(0).integers(
        0, LM_V, (LM_N, LM_T)).astype(np.int32)

    def loss(params):
        return net._loss_fn(params, net.states, {"tokens": jnp.asarray(tokens)},
                            [jnp.asarray(lm_labels(tokens, LM_V))], None,
                            None, None, train=False)[0]
    return jax.jit(jax.value_and_grad(loss))(net.params)


@pytest.mark.parametrize("dtype,one_branch,erfc_form",
                         [("bfloat16", 2, 0), (None, 0, 2)],
                         ids=["bf16", "f32"])
def test_two_block_lm_step_against_jax_gelu(monkeypatch, dtype, one_branch,
                                            erfc_form):
    tracer = observe.enable_tracing()
    try:
        loss, grads = _lm_loss_and_grads(dtype)
        assert tracer.counters.get(
            "activation.gelu_one_branch_calls", 0) == one_branch
        assert tracer.counters.get(
            "activation.gelu_erfc_calls", 0) == erfc_form
    finally:
        observe.disable_tracing()
    monkeypatch.setitem(activations._REGISTRY, "gelu", _erfc_form)
    want_loss, want_grads = _lm_loss_and_grads(dtype)
    # float32 takes the same function. In bfloat16 the two differ by a
    # rounding of an activation here and there, which a leaf of this net
    # shows as up to 2.8% of its largest entry, as bfloat16 itself does
    # against float32 (2.3%)
    share = 0.0 if dtype is None else 0.04
    np.testing.assert_allclose(float(loss), float(want_loss),
                               rtol=0 if dtype is None else 2e-3)
    flat, tree = jax.tree_util.tree_flatten(grads)
    want_flat, want_tree = jax.tree_util.tree_flatten(want_grads)
    assert tree == want_tree and len(flat) == 30
    for got, want in zip(flat, want_flat):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.abs(got - want).max() <= share * np.abs(want).max()
