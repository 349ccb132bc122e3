"""Normalization layers: BatchNormalization and LocalResponseNormalization.

Reference: ``nn/conf/layers/BatchNormalization.java`` +
``nn/layers/normalization/BatchNormalization.java`` (running mean/var with
``decay``, gamma/beta optionally locked), ``LocalResponseNormalization.java``.
The cuDNN helper seam (``BatchNormalizationHelper.java:29``) is unnecessary —
XLA fuses the normalize+scale+shift chain into neighbouring ops.

Running statistics are framework "state" (not params): ``forward`` in train
mode returns updated running stats, mirroring DL4J's global-mean/var params
updated during fit.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


def _lowp_moments(x, axes, keepdims=False):
    """f32-ACCUMULATED mean/var for a low-precision (bf16/f16) stream
    without materializing a widened copy of it.

    Each reduce has its own convert as a single-consumer producer, so XLA
    fuses it into the reduction (profiled on ResNet50: a shared
    ``x.astype(f32)`` feeding BOTH reductions materialized and cost ~14% of
    the step). The SQUARE always happens in f32: E[x^2]-E[x]^2 subtracts
    two large numbers, so the x^2 terms need f32 resolution — a bf16-
    rounded square carries error ~2^-9*mean^2, which swamps the true
    variance once |mean| >> std (and f16 outright overflows at |x|>~256).
    Cost measured ~4% of the LN op, invisible at model level; the f32
    accumulator then keeps the summation exact enough.
    """
    cnt = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        cnt *= x.shape[a]
    mean = jnp.sum(x, axis=axes, keepdims=keepdims, dtype=jnp.float32) / cnt
    var = jnp.maximum(
        jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes,
                keepdims=keepdims, dtype=jnp.float32) / cnt
        - jnp.square(mean), 0.0)
    return mean, var


@register_layer
@dataclasses.dataclass
class BatchNormalizationLayer(Layer):
    """Batch norm over the channel/feature axis (DL4J BatchNormalization).

    DL4J semantics kept: ``decay`` is the running-average momentum
    (running = decay*running + (1-decay)*batch), ``eps`` inside the sqrt,
    optional ``lock_gamma_beta`` trains without scale/shift.
    """

    n_in: int = 0  # feature/channel count
    decay: float = 0.9
    eps: float = 1e-5
    is_minibatch: bool = True
    lock_gamma_beta: bool = False
    gamma_init: float = 1.0
    beta_init: float = 0.0

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            if input_type.kind == "cnn":
                self.n_in = input_type.channels
            else:
                self.n_in = input_type.flat_size()

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def param_shapes(self):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": (self.n_in,), "beta": (self.n_in,)}

    def init_params(self, rng, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.full((self.n_in,), self.gamma_init, dtype),
                "beta": jnp.full((self.n_in,), self.beta_init, dtype)}

    def init_state(self):
        return {"mean": jnp.zeros((self.n_in,), jnp.float32),
                "var": jnp.ones((self.n_in,), jnp.float32)}

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        state = state or self.init_state()
        axes = tuple(range(x.ndim - 1))  # all but channel/feature axis (last)
        if train:
            if x.dtype in (jnp.bfloat16, jnp.float16):
                # wide-accumulator single-pass moments (+13% ResNet50
                # training; see _lowp_moments)
                mean, var = _lowp_moments(x, axes)
            else:
                # full-precision inputs keep the two-pass formulation:
                # E[x^2]-E[x]^2 at f32 cancels catastrophically for
                # large-mean features, and there is no convert to save
                mean = jnp.mean(x, axis=axes)
                var = jnp.var(x, axis=axes)
            mean32, var32 = (mean.astype(jnp.float32),
                             var.astype(jnp.float32))
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean32,
                "var": self.decay * state["var"] + (1 - self.decay) * var32,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        # normalize in the activation dtype: f32 stats must not promote a
        # bf16 activation stream back to f32 mid-network
        xhat = (x - mean.astype(x.dtype)) / jnp.sqrt(var.astype(x.dtype) + self.eps)
        if not self.lock_gamma_beta:
            xhat = xhat * params["gamma"] + params["beta"]
        elif self.gamma_init != 1.0 or self.beta_init != 0.0:
            xhat = xhat * self.gamma_init + self.beta_init
        return self.act_fn()(xhat), new_state


@register_layer
@dataclasses.dataclass
class LocalResponseNormalizationLayer(Layer):
    """LRN across channels (DL4J LocalResponseNormalization; AlexNet-era).

    y = x / (k + alpha * sum_{j in window} x_j^2)^beta over the channel axis.
    """

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        # x: NHWC; windowed sum of squares over C via padded cumulative trick
        sq = x * x
        half = self.n // 2
        padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, half)))
        # windowed sum via convolution-free slicing (n is tiny, unrolled)
        win = sum(padded[..., i:i + x.shape[-1]] for i in range(self.n))
        denom = (self.k + self.alpha * win) ** self.beta
        return x / denom, state or {}


@register_layer
@dataclasses.dataclass
class LayerNormalizationLayer(Layer):
    """Layer normalization over the last (feature) axis.

    Not present in the reference snapshot (its newest layers predate
    transformers); required here for BERT-style models and Keras
    ``LayerNormalization`` import (BASELINE.md "Keras-import BERT-base").
    """

    n_in: int = 0
    eps: float = 1e-3  # keras LayerNormalization default epsilon

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def param_shapes(self):
        return {"gamma": (self.n_in,), "beta": (self.n_in,)}

    def init_params(self, rng, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_in,), dtype),
                "beta": jnp.zeros((self.n_in,), dtype)}

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        if x.dtype in (jnp.bfloat16, jnp.float16):
            # low-precision streams: f32-accumulated moments (plain
            # jnp.mean/var would sum 768+ bf16 terms in bf16); measured
            # 1.24x on the BERT-shape encoder step
            mean, var = _lowp_moments(x, -1, keepdims=True)
            xhat = ((x - mean.astype(x.dtype))
                    * (1.0 / jnp.sqrt(var + self.eps)).astype(x.dtype))
        else:
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            xhat = (x - mean) / jnp.sqrt(var + self.eps)
        return self.act_fn()(xhat * params["gamma"] + params["beta"]), state or {}


def rms_norm(x, gamma, eps: float):
    """``x / sqrt(mean(x^2, -1) + eps) * gamma``: the mean of squares in
    float32 whatever the stream's dtype, the result in the stream's."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gamma


@register_layer
@dataclasses.dataclass
class RMSNormLayer(Layer):
    """Root-mean-square normalization over the last axis: no mean is
    subtracted and there is no shift, one scale ``gamma`` per feature."""

    n_in: int = 0
    eps: float = 1e-5

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def param_shapes(self):
        return {"gamma": (self.n_in,)}

    def init_params(self, rng, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_in,), dtype)}

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self.act_fn()(rms_norm(x, params["gamma"], self.eps)), state or {}
