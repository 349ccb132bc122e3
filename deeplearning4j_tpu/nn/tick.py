"""Device-resident training tick: (iteration, epoch, rng key) carried
through the donated train step.

The naive fit loop performs a host-side ``jax.random.split`` plus two scalar
``jnp.asarray`` placements per step — three extra device dispatches on the
host's critical path. Instead the jitted
step splits the key ON DEVICE and returns ``(it + 1, next_key)``; the fit
loop re-feeds them with zero additional host-side device ops. The host keeps
plain-int mirrors for listeners; any external mutation of
``model.iteration`` / ``model.epoch`` (restore, manual reset, epoch
boundary) invalidates the cached tick via mirror mismatch and a fresh one is
placed from host state.
"""

from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deeplearning4j_tpu.observe import trace as _trace

_TLS = threading.local()


@contextlib.contextmanager
def schedule_tick(it, ep):
    """Make ``(iteration, epoch)`` visible to schedule-bearing configs
    (dropout ``pSchedule`` — ``conf/dropout/Dropout.java:45,68``) while the
    train step traces. The values are the step's device tracers, so a
    scheduled retain probability compiles INTO the step instead of
    fragmenting it — the reason schedules were rejected before the device
    tick existed. Thread-local: safe under ParallelWrapper's worker
    threads."""
    prev = getattr(_TLS, "tick", None)
    _TLS.tick = (it, ep)
    try:
        yield
    finally:
        _TLS.tick = prev


def current_schedule_tick():
    """(iteration, epoch) of the train step being traced, or ``(0, 0)``
    outside one (a scheduled value then evaluates at its initial point —
    e.g. a probe forward before training starts)."""
    t = getattr(_TLS, "tick", None)
    return t if t is not None else (0.0, 0.0)


def _placement(params, batch):
    """``(where, settle)``: where the step will leave the tick it returns,
    and whether the model's trees have yet to be put there.

    Committed parameters decide: their device, or replicated over their
    mesh. Uncommitted ones follow a batch that was placed on one device,
    as jit makes them (``settle``). Anything else is ``None``: every
    argument uncommitted, or a placement of the caller's own, and jit is
    left to it."""
    def committed_leaf(tree):
        return next((leaf for leaf in jax.tree_util.tree_leaves(tree)
                     if getattr(leaf, "committed", False)), None)

    leaf = committed_leaf(params)
    settle = leaf is None
    if settle:
        leaf = committed_leaf(batch)
    if leaf is None:
        return None, False
    sharding = leaf.sharding
    if isinstance(sharding, NamedSharding) and not settle:
        return NamedSharding(sharding.mesh, PartitionSpec()), False
    if len(sharding.device_set) != 1:
        return None, False
    return sharding, settle


def device_tick(model, batch=None):
    """(it, ep, rng) device arrays for the next step, which will be given
    ``batch``: cached while the host-side mirrors are unchanged.

    A fresh tick is committed to where the step will leave the one it
    returns, and where a batch placed on a device decides that, so are the
    model's trees (no copy is made on their own device: the committed
    array shares the buffer). jit keeps one executable for uncommitted
    arguments and another for committed ones, and everything a step
    returns is committed as soon as one argument was; left as ``init()``
    makes them, the second call traces, lowers and loads the step a second
    time (PERF.md §6, PR 29)."""
    mirror = (model.iteration, model.epoch)
    cached = getattr(model, "_tick", None)
    if cached is not None and cached[0] == mirror:
        return cached[1]
    tick = (jnp.asarray(float(model.iteration), jnp.float32),
            jnp.asarray(float(model.epoch), jnp.float32),
            model._next_rng())
    where, settle = _placement(model.params, batch)
    if settle:
        trees = (model.params, model.states, model.updater_states)
        # under tracing a ``state_commit`` span; a cached tick opens none
        with _trace.span("state_commit", category="setup") as span:
            model.params, model.states, model.updater_states = \
                jax.device_put(trees, where)
            if span is not None:
                span.set_attribute("bytes", sum(
                    int(leaf.nbytes)
                    for leaf in jax.tree_util.tree_leaves(trees)))
    if where is not None:
        tick = jax.device_put(tick, where)
    model._tick = (mirror, tick)
    return tick


def store_tick(model, new_it, new_rng) -> None:
    """Adopt the step's returned (it+1, next_key); call AFTER incrementing
    ``model.iteration`` so the mirror matches."""
    cached = getattr(model, "_tick", None)
    if cached is None:
        return
    _, (_, ep, _) = cached
    model._tick = ((model.iteration, model.epoch), (new_it, ep, new_rng))
