"""Activation functions with DL4J ``Activation`` enum parity.

Reference: the ND4J ``IActivation`` implementations used throughout
deeplearning4j-nn (e.g. layer configs take ``Activation`` values —
``nn/conf/layers/*.java``). Each activation here is a pure jnp function so XLA
fuses it into the surrounding matmul/conv, and ``jax.grad`` differentiates
through them; the one hand-written derivative is ``gelu``'s on bfloat16.

All functions take and return a single array. Parametric activations
(leakyrelu alpha, elu alpha, …) are exposed through ``resolve`` which accepts
either a name or a (name, kwargs) tuple and returns a closed-over callable.
"""

from __future__ import annotations

import math
from typing import Callable, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.observe import trace as _trace

Array = jax.Array
ActivationFn = Callable[[Array], Array]

_SELU_ALPHA = 1.6732632423543772
_SELU_LAMBDA = 1.0507009873554805


def identity(x: Array) -> Array:
    return x


def relu(x: Array) -> Array:
    return jnp.maximum(x, 0)


def relu6(x: Array) -> Array:
    return jnp.clip(x, 0, 6)


def leakyrelu(x: Array, alpha: float = 0.01) -> Array:
    return jnp.where(x >= 0, x, alpha * x)


def elu(x: Array, alpha: float = 1.0) -> Array:
    safe = jnp.where(x > 0, 0.0, x)  # keep exp() off the positive branch
    return jnp.where(x > 0, x, alpha * (jnp.exp(safe) - 1.0))


def selu(x: Array) -> Array:
    safe = jnp.where(x > 0, 0.0, x)
    return _SELU_LAMBDA * jnp.where(x > 0, x, _SELU_ALPHA * (jnp.exp(safe) - 1.0))


# Phi(-a) = 0.5 erfc(a / sqrt 2) = exp(-a^2 / 2) u P(u), u = 1 / (1 + _GELU_Q a),
# for a >= 0. P (degree 7, the 0.5 folded in) is the least-squares fit of the
# RELATIVE error of u P(u) against float64 0.5 * scipy.special.erfcx(t),
# t = a / sqrt 2, at 20,001 Chebyshev nodes of t in [0, 9.5], with u made from
# the float32 _GELU_Q (0.5 / sqrt 2) the program multiplies by; largest
# residual 9.2e-7. Past a = 13.4 the exponential underflows and Phi(-a) is 0.
_GELU_Q = 0.35355338
_GELU_P = (0.14112167060375214, 0.13960091769695282, 0.1348135769367218,
           0.04149802401661873, 0.14648394286632538, -0.12032318115234375,
           0.0072447252459824085, 0.009560791775584221)
_INV_SQRT_2PI = 0.3989422804014327


def _gelu_parts(x: Array):
    """Of a bfloat16 ``x``, in float32: ``x``, ``|x|``, ``e = exp(-x^2 / 2)``
    and ``r = u P(u)``, so that ``e * r`` is ``Phi(-|x|)``: one exponential,
    one reciprocal, one polynomial, no branch. ``x * x / 2`` is exact in
    float32 for a bfloat16 ``x``."""
    x = x.astype(jnp.float32)
    a = jnp.abs(x)
    u = 1.0 / (1.0 + _GELU_Q * a)
    p = _GELU_P[-1]
    for c in _GELU_P[-2::-1]:
        p = p * u + c
    return x, a, jnp.exp(-0.5 * x * x), u * p


@jax.custom_vjp
def _gelu_one_branch(x: Array) -> Array:
    # x Phi(x). The tail is multiplied out x-first: Phi(-13) is below the
    # smallest normal float32 and would be flushed, 13 Phi(-13) is not.
    xf, _, e, r = _gelu_parts(x)
    return jnp.where(xf < 0, (xf * e) * r, xf * (1.0 - e * r)).astype(x.dtype)


def _gelu_one_branch_fwd(x: Array):
    return _gelu_one_branch(x), x


def _gelu_one_branch_bwd(x: Array, g: Array):
    # gelu'(x) = Phi(x) + x phi(x), from the same exponential and polynomial:
    # m = Phi(-|x|) - |x| phi(x), and the derivative is m or 1 - m
    xf, a, e, r = _gelu_parts(x)
    m = e * (r - _INV_SQRT_2PI * a)
    d = jnp.where(xf < 0, m, 1.0 - m)
    return ((g.astype(jnp.float32) * d).astype(x.dtype),)


_gelu_one_branch.defvjp(_gelu_one_branch_fwd, _gelu_one_branch_bwd)


def gelu(x: Array) -> Array:
    # exact (erf-based) gelu — what keras/tf mean by "gelu"; the tanh
    # approximation is registered separately as "gelu_tanh". (Renamed before
    # any released checkpoint serialized "gelu": no committed artifact —
    # fixtures included — references it, so restore semantics are unchanged.)
    #
    # One function, two evaluations, chosen by the input's dtype. bfloat16
    # takes the one-branch form above: jax writes 0.5 x erfc(-x / sqrt 2) in
    # the stream's dtype and XLA expands erfc into all three of its branches
    # for every element (about 95 vector operations; 36 such expansions in a
    # GPT-2-small step, each in the prologue or epilogue of a matmul that
    # then waits for the vector unit). The fit was held to the correctly
    # rounded bfloat16 of the float64 function at every finite input,
    # forward and derivative (tests/test_gelu_one_branch.py; the erfc form
    # misses 925 and 1,719 of them). Every other dtype keeps jax's form to
    # the bit: the fit is not float32-grade, and in float16 it misses that
    # bar at 32 inputs (the erfc form at 10,239).
    one_branch = jnp.result_type(x) == jnp.bfloat16
    tracer = _trace.get_active_tracer()
    if tracer is not None:
        # which evaluation this call took, counted while its step is traced
        tracer.count("activation.gelu_one_branch_calls" if one_branch
                     else "activation.gelu_erfc_calls")
    if one_branch:
        return _gelu_one_branch(jnp.asarray(x))
    return jax.nn.gelu(x, approximate=False)


def gelu_tanh(x: Array) -> Array:
    # tanh approximation (the original BERT formulation)
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def sigmoid(x: Array) -> Array:
    return jax.nn.sigmoid(x)


def hardsigmoid(x: Array) -> Array:
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def tanh(x: Array) -> Array:
    return jnp.tanh(x)


def hardtanh(x: Array) -> Array:
    return jnp.clip(x, -1.0, 1.0)


def rationaltanh(x: Array) -> Array:
    # DL4J RATIONALTANH: 1.7159 * tanh(2x/3) approximated rationally; we use
    # the exact functional form (the rational approximation was a CPU speed
    # hack, irrelevant on TPU).
    return 1.7159 * jnp.tanh(2.0 * x / 3.0)


def rectifiedtanh(x: Array) -> Array:
    return jnp.maximum(0.0, jnp.tanh(x))


def softmax(x: Array) -> Array:
    return jax.nn.softmax(x, axis=-1)


def logsoftmax(x: Array) -> Array:
    return jax.nn.log_softmax(x, axis=-1)


def softplus(x: Array) -> Array:
    return jax.nn.softplus(x)


def softsign(x: Array) -> Array:
    return x / (1.0 + jnp.abs(x))


def cube(x: Array) -> Array:
    return x**3


def swish(x: Array) -> Array:
    return x * jax.nn.sigmoid(x)


def mish(x: Array) -> Array:
    return x * jnp.tanh(jax.nn.softplus(x))


def thresholdedrelu(x: Array, theta: float = 1.0) -> Array:
    return jnp.where(x > theta, x, 0.0)


_REGISTRY: dict[str, ActivationFn] = {
    "identity": identity,
    "linear": identity,
    "relu": relu,
    "relu6": relu6,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "gelu": gelu,
    "gelu_tanh": gelu_tanh,
    "sigmoid": sigmoid,
    "hardsigmoid": hardsigmoid,
    "tanh": tanh,
    "hardtanh": hardtanh,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "softmax": softmax,
    "logsoftmax": logsoftmax,
    "softplus": softplus,
    "softsign": softsign,
    "cube": cube,
    "swish": swish,
    "silu": swish,
    "mish": mish,
    "thresholdedrelu": thresholdedrelu,
}


def names() -> list[str]:
    return sorted(_REGISTRY)


def resolve(activation: Union[str, ActivationFn, tuple, None]) -> ActivationFn:
    """Resolve an activation spec to a callable.

    Accepts a name (``"relu"``), a ``(name, kwargs)`` tuple for parametric
    activations (``("leakyrelu", {"alpha": 0.2})``), an existing callable, or
    ``None`` (identity).
    """
    if activation is None:
        return identity
    if callable(activation):
        return activation
    if isinstance(activation, tuple):
        name, kwargs = activation
        fn = _REGISTRY[name.lower()]
        return lambda x: fn(x, **kwargs)
    key = activation.lower()
    if key not in _REGISTRY:
        raise ValueError(f"Unknown activation {activation!r}; known: {names()}")
    return _REGISTRY[key]
