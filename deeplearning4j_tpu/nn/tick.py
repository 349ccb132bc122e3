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

import jax.numpy as jnp

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


def device_tick(model):
    """(it, ep, rng) device arrays for the next step — cached while the
    host-side mirrors are unchanged."""
    mirror = (model.iteration, model.epoch)
    cached = getattr(model, "_tick", None)
    if cached is not None and cached[0] == mirror:
        return cached[1]
    it = jnp.asarray(float(model.iteration), jnp.float32)
    ep = jnp.asarray(float(model.epoch), jnp.float32)
    rng = model._next_rng()
    model._tick = (mirror, (it, ep, rng))
    return it, ep, rng


def store_tick(model, new_it, new_rng) -> None:
    """Adopt the step's returned (it+1, next_key); call AFTER incrementing
    ``model.iteration`` so the mirror matches."""
    cached = getattr(model, "_tick", None)
    if cached is None:
        return
    _, (_, ep, _) = cached
    model._tick = ((model.iteration, model.epoch), (new_it, ep, new_rng))
