"""Base layer config/impl class and registry.

The reference splits declarative configs (``nn/conf/layers/``) from imperative
impls with hand-written ``backpropGradient`` (``nn/layers/``, e.g.
``Layer.java:38,88``). Here a layer is ONE dataclass:

- hyperparameters (fields; ``None`` means "inherit the network default")
- shape inference (``set_n_in`` / ``output_type`` — DL4J's InputType system)
- ``init_params(rng, dtype)`` → dict of named arrays (DL4J param names kept:
  "W", "b", "gamma", …) — enables DL4J-checkpoint migration
- ``forward(params, x, ...)`` → pure function of (params, inputs);
  backprop is ``jax.grad`` through it.

Mutable-state layers (BatchNorm running stats) thread a ``state`` dict through
``forward`` and return the updated dict; stateless layers return it unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as act_mod
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Updater, Schedule
from deeplearning4j_tpu.nn.weights import Distribution, init_weight

Array = jax.Array
Params = Dict[str, Array]
State = Dict[str, Array]

LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


@dataclasses.dataclass
class Layer:
    """Common layer hyperparameters (DL4J BaseLayer config fields)."""

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    distribution: Optional[Distribution] = None
    bias_init: Optional[float] = None
    updater: Optional[Updater] = None
    bias_updater: Optional[Updater] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    dropout: Optional[Any] = None  # float keep-prob or IDropout instance
    weight_noise: Optional[Any] = None  # IWeightNoise (DropConnect etc.)
    constraints: Optional[list] = None  # list of LayerConstraint
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    dtype: Optional[Any] = None

    #: parameters that mixed precision leaves in float32 (:func:`cast_params`)
    float32_params = ()

    # ---- filled in by the network builder --------------------------------
    def apply_global_defaults(self, g: "Layer") -> None:
        """Inherit unset hyperparams from the global NeuralNetConfiguration."""
        for f in ("activation", "weight_init", "distribution", "bias_init",
                  "updater", "bias_updater", "l1", "l2", "l1_bias", "l2_bias",
                  "dropout", "weight_noise", "gradient_normalization", "dtype"):
            if getattr(self, f) is None and getattr(g, f, None) is not None:
                setattr(self, f, getattr(g, f))
        if self.gradient_normalization_threshold == 1.0 and \
                getattr(g, "gradient_normalization_threshold", 1.0) != 1.0:
            self.gradient_normalization_threshold = g.gradient_normalization_threshold
        if self.constraints is None:
            # builder-level constrain_all/constrain_weights/constrain_bias
            # (NeuralNetConfiguration.java:1031-1060): attach scoped copies
            cs = ([c.scoped("all") for c in getattr(g, "all_constraints", None) or ()]
                  + [c.scoped("weights") for c in getattr(g, "weight_constraints", None) or ()]
                  + [c.scoped("bias") for c in getattr(g, "bias_constraints", None) or ()])
            if cs:
                self.constraints = cs

    # ---- shape inference --------------------------------------------------
    def set_n_in(self, input_type: InputType) -> None:
        """Infer input size from the previous layer's output type."""

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def input_preprocessor(self, input_type: InputType):
        """Return a (fn, new_input_type) preprocessor if this layer needs its
        input reshaped (DL4J's automatic CnnToFeedForward etc.), else None."""
        return None

    # ---- params ------------------------------------------------------------
    def init_params(self, rng: jax.Array, dtype=jnp.float32) -> Params:
        return {}

    def init_state(self) -> State:
        return {}

    def num_params(self) -> int:
        import math
        shapes = self.param_shapes()
        return sum(int(math.prod(s)) for s in shapes.values())

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {}

    # ---- forward -----------------------------------------------------------
    def forward(self, params: Params, x: Array, *, state: Optional[State] = None,
                train: bool = False, rng: Optional[jax.Array] = None,
                mask: Optional[Array] = None) -> Tuple[Array, State]:
        raise NotImplementedError

    # ---- misc ---------------------------------------------------------------
    def act_fn(self):
        return act_mod.resolve(self.activation)

    def _dropout(self, x: Array, train: bool, rng: Optional[jax.Array]) -> Array:
        """DL4J-style *input* dropout: a float is the keep probability
        (inverted dropout); any IDropout instance (AlphaDropout,
        GaussianDropout, GaussianNoise, SpatialDropout) applies itself."""
        if not train or self.dropout is None or rng is None:
            return x
        from deeplearning4j_tpu.nn.dropout import resolve_dropout
        d = resolve_dropout(self.dropout)
        return x if d is None else d.apply(x, rng, train)

    def _init_w(self, key, shape, fan_in, fan_out, dtype):
        scheme = self.weight_init or "xavier"
        return init_weight(key, shape, scheme, fan_in, fan_out, dtype,
                           distribution=self.distribution)

    def _init_b(self, shape, dtype):
        return jnp.full(shape, self.bias_init or 0.0, dtype)

    def weight_param_names(self) -> Tuple[str, ...]:
        """Params treated as 'weights' for l1/l2 and weight-updater purposes."""
        return tuple(n for n in self.param_shapes() if n not in ("b", "beta", "gamma", "mean", "var"))

    def bias_param_names(self) -> Tuple[str, ...]:
        return tuple(n for n in self.param_shapes() if n == "b")

    def is_pretrain_layer(self) -> bool:
        return False

    def has_loss(self) -> bool:
        """Output-style layers compute the network loss."""
        return False

    # ---- serde --------------------------------------------------------------
    def to_dict(self) -> dict:
        from deeplearning4j_tpu.nn.constraints import LayerConstraint
        from deeplearning4j_tpu.nn.dropout import IDropout
        from deeplearning4j_tpu.nn.layers.vae_distributions import (
            ReconstructionDistribution)
        from deeplearning4j_tpu.nn.weightnoise import IWeightNoise
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, Updater):
                v = v.to_dict()
            elif isinstance(v, Schedule):
                v = v.to_dict()
            elif isinstance(v, Distribution):
                v = v.to_dict()
            elif isinstance(v, (IDropout, IWeightNoise,
                                ReconstructionDistribution)):
                v = v.to_dict()
            elif isinstance(v, Layer):
                v = v.to_dict()
            elif isinstance(v, InputType):
                v = {"@input_type": True, **v.to_dict()}
            elif (isinstance(v, list) and v
                  and all(isinstance(c, LayerConstraint) for c in v)):
                v = [c.to_dict() for c in v]
            d[f.name] = v
        d["@layer"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "Layer":
        return layer_from_dict(d)


def cast_params(layer_of, params, cast):
    """Mixed precision's cast of the master parameters (a dict by vertex
    name, or a list by layer index) for the forward pass: every leaf through
    ``cast``, but the parameters a layer names in ``float32_params`` (a
    router's weights), which it computes with as they are. ``layer_of(key)``
    is the layer that owns ``params[key]``."""
    def one(key, owned):
        keep = layer_of(key).float32_params
        return {n: v if n in keep else jax.tree_util.tree_map(cast, v)
                for n, v in owned.items()}

    if isinstance(params, dict):
        return {key: one(key, owned) for key, owned in params.items()}
    return [one(i, owned) for i, owned in enumerate(params)]


def activation_from_config(v):
    """Parameterized activations (``("leakyrelu", {"alpha": …})``) are
    tuples in memory but JSON lists on disk — ONE normalization shared by
    layer and global-conf deserialization."""
    if (isinstance(v, list) and len(v) == 2 and isinstance(v[0], str)
            and isinstance(v[1], dict)):
        return (v[0], dict(v[1]))
    return v


def layer_from_dict(d: dict) -> Layer:
    from deeplearning4j_tpu.nn.dropout import IDropout
    from deeplearning4j_tpu.nn.weightnoise import IWeightNoise
    d = dict(d)
    cls = LAYER_REGISTRY[d.pop("@layer")]
    kw = {}
    for k, v in d.items():
        if isinstance(v, dict) and "@updater" in v:
            v = Updater.from_dict(v)
        elif isinstance(v, dict) and "@schedule" in v:
            v = Schedule.from_dict(v)
        elif isinstance(v, dict) and "@dropout" in v:
            v = IDropout.from_dict(v)
        elif isinstance(v, dict) and "@weight_noise" in v:
            v = IWeightNoise.from_dict(v)
        elif isinstance(v, dict) and "@recon" in v:
            from deeplearning4j_tpu.nn.layers.vae_distributions import (
                ReconstructionDistribution)
            v = ReconstructionDistribution.from_dict(v)
        elif isinstance(v, dict) and "@layer" in v:
            v = layer_from_dict(v)
        elif isinstance(v, dict) and "@input_type" in v:
            v = dict(v)
            v.pop("@input_type")
            v = InputType.from_dict(v)
        elif k == "distribution" and isinstance(v, dict):
            v = Distribution.from_dict(v)
        elif k == "activation":
            v = activation_from_config(v)
        elif (isinstance(v, list) and v
              and all(isinstance(c, dict) and "@constraint" in c for c in v)):
            from deeplearning4j_tpu.nn.constraints import constraints_from_config
            v = constraints_from_config(v)
        kw[k] = v
    # tuples serialize as lists; normalize common geometry fields
    for k in ("kernel_size", "stride", "padding", "dilation", "block_size",
              "blocks", "pad_top_bottom", "crop"):
        if k in kw and isinstance(kw[k], list):
            kw[k] = tuple(kw[k])
    return cls(**kw)
