"""The names the train step's parts carry into the compiled program.

A ``jax.named_scope`` is metadata on the traced operations: it costs nothing
when the step runs, so these are always on. The name reaches the compiled
HLO (``metadata={op_name="jit(train_step)/jvp(DenseLayer:0)/dot_general"}``)
and, through the instruction's name, the device trace. ``jax.grad`` adds the
phase itself: an operation of the forward pass reads ``jvp(<scope>)``, one of
the backward pass ``transpose(jvp(<scope>))``; what runs outside the gradient
keeps the bare scope (``optimizer/DenseLayer:0/...``).

- a layer or vertex: ``<Class>:<name>`` (:func:`layer_scope`);
- ``loss``: an output layer's ``compute_loss``, nested in that layer's scope;
- ``regularization``: the l1/l2 penalty over all parameters;
- ``optimizer``: gradient normalisation, updaters and constraints, with each
  layer's scope nested in it;
- ``cast_params``: the mixed-precision cast of the master parameters.
"""

from __future__ import annotations

import jax

LOSS = "loss"
REGULARIZATION = "regularization"
OPTIMIZER = "optimizer"
CAST_PARAMS = "cast_params"


def layer_scope(name, layer):
    """The scope of one layer (or vertex) of a model: ``DenseLayer:fc1``.
    ``/`` separates scopes in an operation's name, so a name may hold none."""
    return jax.named_scope(
        f"{type(layer).__name__}:{str(name).replace('/', '_')}")
