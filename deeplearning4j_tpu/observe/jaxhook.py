"""JAX compile/execute attribution via ``jax.monitoring`` events.

``jax`` emits named duration events around tracing, lowering and backend
compilation (``/jax/core/compile/*``) and around its persistent compile
cache (``/jax/compilation_cache/*``). Registering one process-wide listener
turns those into spans on the ACTIVE tracer, parented by whatever span is
current on the emitting thread — so a recompile triggered inside a
``train_step`` or ``batch_execute`` span nests under it and is impossible
to miss in the exported timeline, and the first ``step_dispatch`` of a
``fit()`` says what it paid for: ``jax_trace`` (Python tracing to a jaxpr),
``jax_lowering`` (jaxpr to StableHLO), ``xla_compile`` (getting the
executable, from the compiler or the cache) and, inside that, ``cache_load``
(reading and deserialising a cache entry). The cache's hit and miss events
become the counts ``compile_cache.hits`` / ``compile_cache.misses``
(``Tracer.count``: the process-wide tally, and ``counts`` of the span that
was open, e.g. ``model_init`` for the small programs ``init()`` fetches).

Each span is recorded after the fact, from the duration jax reports, as a
child of the open span, with the function jax names as its ``fun_name``
(``train_step``, ``_normal``; a cache load comes without one). jax reports a trace duration for an inner ``jit``
inside an outer one, so ``jax_trace`` spans of one thread overlap (the
outer's interval holds the inner's): a reader takes their union, never
their sum. ``xla_compile`` covers ``cache_load`` in the same way.

The listeners are installed once per process and are a cheap no-op while no
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
    # Python → jaxpr tracing: the part of a first step no cache saves
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    # a persistent-cache hit: the file read and the deserialisation
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

# monitoring event name → tally counted on the active tracer
_EVENT_COUNTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache.hits",
    "/jax/compilation_cache/cache_misses": "compile_cache.misses",
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
        tracer.note_compile_event(span_name, duration_s,
                                  kwargs.get("fun_name"))
    except Exception:  # noqa: BLE001 — observability must never break compute
        pass


def _on_event(name: str, **kwargs) -> None:
    count_name = _EVENT_COUNTS.get(name)
    if count_name is None:
        return
    tracer = _trace.get_active_tracer()
    if tracer is not None:
        tracer.count(count_name)


def install_jax_hook() -> None:
    """Register the monitoring listeners (idempotent)."""
    global _installed
    with _install_lock:
        if not _installed:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            jax.monitoring.register_event_listener(_on_event)
            _installed = True


def hook_installed() -> bool:
    return _installed
