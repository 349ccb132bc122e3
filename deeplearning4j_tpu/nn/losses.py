"""Loss functions with ND4J ``ILossFunction`` parity.

Reference: DL4J layer configs carry an ``ILossFunction`` (e.g.
``nn/conf/layers/OutputLayer`` via ``BaseOutputLayer``); the ND4J loss
implementations (LossMCXENT, LossMSE, LossBinaryXENT, …) compute per-example
scores with optional per-output weights and per-example/per-timestep masks.

Design: every loss is ``loss(labels, preactivation_or_probs, mask=None,
weights=None) -> scalar mean score``; losses that fuse with their canonical
activation (softmax+MCXENT, sigmoid+XENT) are computed from *logits* for
numerical stability — the framework passes logits when the output layer's
activation matches the canonical pairing, mirroring how ND4J special-cases
softmax in ``LossMCXENT``.

Masks broadcast like DL4J's: shape [N] or [N, T] (per example / per timestep)
or full label shape.
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.observe import trace as _trace

Array = jax.Array
_EPS = 1e-7


def _apply_mask_mean(per_elem: Array, mask: Optional[Array]) -> Array:
    """Mean of per-example scores, honouring a broadcastable mask.

    ``per_elem`` has shape [N] or [N, T] (already reduced over features).
    DL4J averages the summed score over the number of *unmasked examples*
    (see BaseOutputLayer.computeScore: score / getInputMiniBatchSize, with
    masked timesteps contributing zero).
    """
    if mask is None:
        return jnp.mean(per_elem)
    mask = jnp.broadcast_to(mask.astype(per_elem.dtype), per_elem.shape)
    total = jnp.sum(per_elem * mask)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return total / denom


def _featurewise(per_out: Array, weights: Optional[Array]) -> Array:
    """Apply per-output weights then reduce feature axis → per-example score."""
    if weights is not None:
        per_out = per_out * weights
    return jnp.sum(per_out, axis=-1)


def mse(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    # DL4J LossMSE = LossL2 / nOut (mean over outputs)
    per = _featurewise((preds - labels) ** 2, weights) / labels.shape[-1]
    return _apply_mask_mean(per, mask)


def l2(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise((preds - labels) ** 2, weights)
    return _apply_mask_mean(per, mask)


def l1(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(jnp.abs(preds - labels), weights)
    return _apply_mask_mean(per, mask)


def mae(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(jnp.abs(preds - labels), weights) / labels.shape[-1]
    return _apply_mask_mean(per, mask)


def mape(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(
        jnp.abs((preds - labels) / jnp.where(jnp.abs(labels) < _EPS, _EPS, labels)),
        weights,
    ) * (100.0 / labels.shape[-1])
    return _apply_mask_mean(per, mask)


def msle(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(
        (jnp.log1p(jnp.maximum(preds, -1 + _EPS)) - jnp.log1p(jnp.maximum(labels, -1 + _EPS))) ** 2,
        weights,
    ) / labels.shape[-1]
    return _apply_mask_mean(per, mask)


def _are_class_ids(labels: Array, scores: Array) -> bool:
    """Whether ``labels`` name each target by its class id: an integer array
    with one dimension fewer than the scores it is compared with. One-hot
    and soft labels have the scores' own rank, whatever their dtype."""
    ids = labels.ndim == scores.ndim - 1
    if ids and not jnp.issubdtype(labels.dtype, jnp.integer):
        raise ValueError(
            f"labels of shape {labels.shape} for scores of shape "
            f"{scores.shape} must be integer class ids, not {labels.dtype}")
    tracer = _trace.get_active_tracer()
    if tracer is not None:
        # which form this loss was given, counted while its step is traced
        tracer.count("loss.class_id_calls" if ids else "loss.one_hot_calls")
    return ids


def _is_target(ids: Array, n_classes: int) -> Array:
    """The one-hot of ``ids`` as a comparison with an iota: a fusion reads
    it from the ids, so it never exists as an array of the scores' shape."""
    return jax.lax.broadcasted_iota(
        jnp.int32, ids.shape + (n_classes,), ids.ndim
    ) == ids[..., None].astype(jnp.int32)


@jax.custom_vjp
def softmax_xent_ids(logits: Array, ids: Array) -> Array:
    """Per-position softmax cross entropy ``lse(logits) - logits[ids]`` for
    integer class ``ids`` of the logits' leading shape.

    The backward is written by hand: one pass that reads the logits and
    writes their gradient, ``(softmax - onehot) * g``, with the target as a
    comparison inside the fusion. Autodiff of ``log_softmax`` would add a
    ``sum`` over the cotangent (it cannot know a one-hot sums to one), and
    of a gather a scatter into a zero-filled array of the logits' shape."""
    return _softmax_xent_ids_fwd(logits, ids)[0]


def _softmax_xent_ids_fwd(logits, ids):
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
    picked = jnp.sum(
        jnp.where(_is_target(ids, logits.shape[-1]), logits, 0.0), axis=-1)
    return lse - picked, (logits, lse, ids)


def _softmax_xent_ids_bwd(res, g):
    logits, lse, ids = res
    softmax = jnp.exp(logits - lse[..., None])
    onehot = _is_target(ids, logits.shape[-1]).astype(logits.dtype)
    return (softmax - onehot) * g[..., None], None


softmax_xent_ids.defvjp(_softmax_xent_ids_fwd, _softmax_xent_ids_bwd)


def mcxent_logits(labels: Array, logits: Array, mask=None, weights=None) -> Array:
    """Multi-class cross entropy fused with softmax (stable). ``labels`` are
    one-hot or soft rows of the logits' shape, or integer class ids with one
    dimension fewer (``sparse_mcxent``): the same loss, without the rows."""
    if _are_class_ids(labels, logits):
        per = softmax_xent_ids(logits, labels)
        if weights is not None:
            per = per * jnp.take(weights, labels)
        return _apply_mask_mean(per, mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -_featurewise(labels * logp, weights)
    return _apply_mask_mean(per, mask)


def mcxent_probs(labels: Array, probs: Array, mask=None, weights=None) -> Array:
    if _are_class_ids(labels, probs):
        labels = _is_target(labels, probs.shape[-1]).astype(probs.dtype)
    per = -_featurewise(labels * jnp.log(jnp.clip(probs, _EPS, 1.0)), weights)
    return _apply_mask_mean(per, mask)


# class ids are a form of label, not another loss
sparse_mcxent_logits = mcxent_logits


def xent_logits(labels: Array, logits: Array, mask=None, weights=None) -> Array:
    """Binary cross entropy fused with sigmoid (stable)."""
    per_out = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    per = _featurewise(per_out, weights)
    return _apply_mask_mean(per, mask)


def xent_probs(labels: Array, probs: Array, mask=None, weights=None) -> Array:
    p = jnp.clip(probs, _EPS, 1.0 - _EPS)
    per = -_featurewise(labels * jnp.log(p) + (1 - labels) * jnp.log1p(-p), weights)
    return _apply_mask_mean(per, mask)


def negativeloglikelihood_logits(labels, logits, mask=None, weights=None) -> Array:
    # DL4J LossNegativeLogLikelihood extends LossMCXENT (same math when
    # paired with softmax).
    return mcxent_logits(labels, logits, mask, weights)


def hinge(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    # labels in {-1, +1}
    per = _featurewise(jnp.maximum(0.0, 1.0 - labels * preds), weights)
    return _apply_mask_mean(per, mask)


def squared_hinge(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(jnp.maximum(0.0, 1.0 - labels * preds) ** 2, weights)
    return _apply_mask_mean(per, mask)


def kl_divergence(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    lab = jnp.clip(labels, _EPS, 1.0)
    prd = jnp.clip(preds, _EPS, 1.0)
    per = _featurewise(lab * (jnp.log(lab) - jnp.log(prd)), weights)
    return _apply_mask_mean(per, mask)


def poisson(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(preds - labels * jnp.log(jnp.clip(preds, _EPS, None)), weights)
    return _apply_mask_mean(per, mask)


def cosine_proximity(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    ln = jnp.linalg.norm(labels, axis=-1)
    pn = jnp.linalg.norm(preds, axis=-1)
    dot = jnp.sum(labels * preds, axis=-1)
    per = -dot / jnp.maximum(ln * pn, _EPS)
    return _apply_mask_mean(per, mask)


def wasserstein(labels: Array, preds: Array, mask=None, weights=None) -> Array:
    per = _featurewise(labels * preds, weights)
    return _apply_mask_mean(per, mask)


LossFn = Callable[..., Array]

# name -> (loss_from_canonical_input, fused_activation or None)
# When fused_activation matches the output layer's activation, the framework
# calls the loss with raw logits; otherwise with activated outputs.
_REGISTRY: dict[str, tuple[LossFn, Optional[str]]] = {
    "mse": (mse, None),
    "l2": (l2, None),
    "l1": (l1, None),
    "mae": (mae, None),
    "mean_absolute_error": (mae, None),
    "mean_squared_logarithmic_error": (msle, None),
    "msle": (msle, None),
    "mape": (mape, None),
    "mean_absolute_percentage_error": (mape, None),
    "mcxent": (mcxent_logits, "softmax"),
    "negativeloglikelihood": (negativeloglikelihood_logits, "softmax"),
    "sparse_mcxent": (mcxent_logits, "softmax"),
    "xent": (xent_logits, "sigmoid"),
    "binary_xent": (xent_logits, "sigmoid"),
    "hinge": (hinge, None),
    "squared_hinge": (squared_hinge, None),
    "kl_divergence": (kl_divergence, None),
    "reconstruction_crossentropy": (xent_probs, None),
    "poisson": (poisson, None),
    "cosine_proximity": (cosine_proximity, None),
    "wasserstein": (wasserstein, None),
}

# probability-space fallbacks for fused losses when the output activation does
# NOT match the canonical pairing (e.g. MCXENT with sigmoid outputs).
_PROB_SPACE: dict[str, LossFn] = {
    "mcxent": mcxent_probs,
    "negativeloglikelihood": mcxent_probs,
    "sparse_mcxent": mcxent_probs,
    "xent": xent_probs,
    "binary_xent": xent_probs,
}


def names() -> list[str]:
    return sorted(_REGISTRY)


def resolve(loss: Union[str, LossFn], activation: Optional[str] = None):
    """Resolve a loss spec to ``(fn, wants_logits: bool)``.

    ``wants_logits`` is True when ``fn`` should be fed the *pre-activation*
    output of the final layer (fused stable path), which happens when the loss
    has a canonical activation equal to ``activation``.
    """
    if callable(loss):
        return loss, False
    key = loss.lower()
    if key not in _REGISTRY:
        raise ValueError(f"Unknown loss {loss!r}; known: {names()}")
    fn, fused_act = _REGISTRY[key]
    if fused_act is not None:
        if activation is None or activation.lower() == fused_act:
            return fn, True
        return _PROB_SPACE[key], False
    return fn, False
