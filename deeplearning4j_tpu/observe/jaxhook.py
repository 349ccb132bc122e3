"""JAX compile/execute attribution via ``jax.monitoring`` events.

``jax`` emits named duration events around tracing, lowering and backend
compilation (``/jax/core/compile/*``). Registering one process-wide
listener turns those into spans on the ACTIVE tracer, parented by whatever
span is current on the emitting thread — so a recompile triggered inside a
``train_step`` or ``batch_execute`` span nests under it and is impossible
to miss in the exported timeline.

The listener is installed once per process and is a cheap no-op while no
tracer is active (``jax.monitoring`` offers no single-listener removal, so
install is one-way by design). Import of ``jax`` is deferred to install
time: merely importing ``observe`` never pulls in the backend.
"""

from __future__ import annotations

import threading

from deeplearning4j_tpu.observe import trace as _trace

# monitoring event name → span name recorded on the active tracer
_EVENT_SPANS = {
    # the big one: XLA backend compilation (the recompile alarm)
    "/jax/core/compile/backend_compile_duration": "xla_compile",
    # jaxpr → StableHLO lowering (cheap, but visible when it isn't)
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lowering",
}

_installed = False
_install_lock = threading.Lock()


def _on_event_duration(name: str, duration_s: float, **kwargs) -> None:
    span_name = _EVENT_SPANS.get(name)
    if span_name is None:
        return
    tracer = _trace.get_active_tracer()
    if tracer is None:
        return
    try:
        tracer.note_compile_event(span_name, duration_s)
    except Exception:  # noqa: BLE001 — observability must never break compute
        pass


def install_jax_hook() -> None:
    """Register the monitoring listener (idempotent)."""
    global _installed
    with _install_lock:
        if not _installed:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _installed = True


def hook_installed() -> bool:
    return _installed
