"""Activation functions with DL4J ``Activation`` enum parity.

Reference: the ND4J ``IActivation`` implementations used throughout
deeplearning4j-nn (e.g. layer configs take ``Activation`` values —
``nn/conf/layers/*.java``). Each activation here is a pure jnp function so XLA
fuses it into the surrounding matmul/conv; there are no hand-written
derivative pairs — ``jax.grad`` differentiates through them.

All functions take and return a single array. Parametric activations
(leakyrelu alpha, elu alpha, …) are exposed through ``resolve`` which accepts
either a name or a (name, kwargs) tuple and returns a closed-over callable.
"""

from __future__ import annotations

import math
from typing import Callable, Union

import jax
import jax.numpy as jnp

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


def gelu(x: Array) -> Array:
    # exact (erf-based) gelu — what keras/tf mean by "gelu"; the tanh
    # approximation is registered separately as "gelu_tanh". (Renamed before
    # any released checkpoint serialized "gelu": no committed artifact —
    # fixtures included — references it, so restore semantics are unchanged.)
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
