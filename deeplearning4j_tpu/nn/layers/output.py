"""Output / loss layers.

Reference configs: ``nn/conf/layers/OutputLayer.java`` (dense + loss),
``RnnOutputLayer``, ``LossLayer`` (loss only, no params), ``RnnLossLayer``,
``CnnLossLayer``, ``CenterLossOutputLayer``. DL4J's ``BaseOutputLayer``
computes score from the pre-activation ("preOut") so softmax+MCXENT is
numerically fused — ``losses.resolve`` reproduces that: when the loss's
canonical activation matches the layer's, ``compute_loss`` feeds logits.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import losses as loss_mod
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.core import DenseLayer


@register_layer
@dataclasses.dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (DL4J OutputLayer). Default MCXENT+softmax."""

    loss: str = "mcxent"

    def __post_init__(self):
        if self.activation is None:
            self.activation = "softmax"

    def has_loss(self) -> bool:
        return True

    def _preact(self, params, x):
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def compute_loss(self, params, x, labels, mask=None):
        """Loss from this layer's INPUT activations (pre-dense)."""
        pre = self._preact(params, x)
        fn, wants_logits = loss_mod.resolve(self.loss, self.activation)
        out = pre if wants_logits else self.act_fn()(pre)
        return fn(_labels_at_width(labels, out, self.n_out), out, mask=mask)

    def forward(self, params, x, **kwargs):
        """The activations of the ``n_out`` published columns: a head whose
        vocabulary placement padded to the mesh
        (``parallel.sharding.shard_model_with_rules``) computes the padded
        columns too, and their softmax weight is 0."""
        y, state = super().forward(params, x, **kwargs)
        if y.shape[-1] > self.n_out:
            y = y[..., :self.n_out]
        return y, state


@register_layer
@dataclasses.dataclass
class RnnOutputLayer(OutputLayer):
    """Per-timestep dense + loss over [N,T,*] (DL4J RnnOutputLayer).

    The dense matmul broadcasts over time; per-timestep masks are honored in
    the loss mean exactly like ``LossUtil``/masked score in the reference.
    """

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def compute_loss(self, params, x, labels, mask=None):
        pre = self._preact(params, x)  # [N,T,n_out]
        fn, wants_logits = loss_mod.resolve(self.loss, self.activation)
        out = pre if wants_logits else self.act_fn()(pre)
        return fn(_labels_at_width(labels, out, self.n_out), out, mask=mask)


@register_layer
@dataclasses.dataclass
class LossLayer(Layer):
    """Loss-only layer, no params (DL4J LossLayer)."""

    loss: str = "mcxent"

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"

    def has_loss(self) -> bool:
        return True

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self.act_fn()(x), state or {}

    def compute_loss(self, params, x, labels, mask=None):
        fn, wants_logits = loss_mod.resolve(self.loss, self.activation)
        out = x if wants_logits else self.act_fn()(x)
        return fn(labels, out, mask=mask)


@register_layer
@dataclasses.dataclass
class RnnLossLayer(LossLayer):
    """Per-timestep loss over [N,T,*] (DL4J RnnLossLayer)."""


@register_layer
@dataclasses.dataclass
class CnnLossLayer(LossLayer):
    """Per-pixel loss over NHWC maps (DL4J CnnLossLayer); the feature axis is
    channels, masks broadcast over H,W."""

    def compute_loss(self, params, x, labels, mask=None):
        fn, wants_logits = loss_mod.resolve(self.loss, self.activation)
        out = x if wants_logits else self.act_fn()(x)
        n = out.shape[0]
        out2 = out.reshape(n, -1, out.shape[-1])
        # one-hot maps [N,H,W,C], or class ids [N,H,W]
        lab2 = (labels.reshape(n, -1, labels.shape[-1])
                if labels.ndim == out.ndim else labels.reshape(n, -1))
        m2 = None if mask is None else mask.reshape(n, -1)
        return fn(lab2, out2, mask=m2)


@register_layer
@dataclasses.dataclass
class CenterLossOutputLayer(OutputLayer):
    """Output layer with an auxiliary center loss
    (``nn/conf/layers/CenterLossOutputLayer.java``): pulls examples toward a
    learned per-class center. Centers update via gradient here (vs the
    reference's manual SGD-on-centers with ``alpha``), same objective.
    """

    alpha: float = 0.05
    lambda_: float = 2e-4

    def param_shapes(self):
        shapes = super().param_shapes()
        shapes["cL"] = (self.n_out, self.n_in)
        return shapes

    def init_params(self, rng, dtype=jnp.float32):
        p = super().init_params(rng, dtype)
        p["cL"] = jnp.zeros((self.n_out, self.n_in), dtype)
        return p

    def compute_loss(self, params, x, labels, mask=None):
        base = super().compute_loss(params, x, labels, mask)
        # center loss: ||x - c_y||^2 / 2 averaged over batch
        # one-hot labels pick centers, as class ids do
        centers = (labels @ params["cL"] if labels.ndim == x.ndim
                   else jnp.take(params["cL"], labels, axis=0))
        center_l = 0.5 * jnp.mean(jnp.sum((x - centers) ** 2, axis=-1))
        return base + self.lambda_ * center_l


def _labels_at_width(labels, scores, n_out: int):
    """One-hot or soft ``labels`` of the published width ``n_out`` with
    zero columns up to the width of ``scores``, which a head whose
    vocabulary placement padded to the mesh computes; class ids, and
    labels of any other width, as they are."""
    pad = scores.shape[-1] - n_out
    if pad <= 0 or labels.ndim != scores.ndim or labels.shape[-1] != n_out:
        return labels
    return jnp.pad(labels, [(0, 0)] * (labels.ndim - 1) + [(0, pad)])
