"""Unified observability layer: tracing + metrics across train and serve.

One subsystem answers "where did this millisecond go" end to end
(the role of DL4J's listener/StatsListener/training-UI stack plus the
Dapper-style request tracing the reference never had):

- ``trace``    — ``Span``/``Tracer``/``TraceRecorder``: contextvar-nested
  spans, explicit cross-thread handoff, W3C ``traceparent`` in/out,
  bounded ring buffer; ``enable_tracing()`` flips every instrumented hot
  path (``fit()``'s own ``host_wait`` / ``step_dispatch`` / ``listeners``
  spans in both engines, ParallelWrapper steps, the ParallelInference
  dispatcher, the ModelServer request path, streaming routes) from no-op to
  recording, and with them the path from ``init()`` to the first step
  (category ``setup``): ``model_init`` (either engine's ``init()``, with
  ``parameters`` and ``bytes``), ``place_params`` (``shard_model`` /
  ``shard_model_with_rules``: ``leaves``, ``bytes``, ``devices``) and
  ``state_commit`` (the one ``device_put`` that commits a model's trees
  before its first step). ``Tracer.count(name)`` keeps a process-wide
  tally (``tracer.counters``: which path each traced attention, loss,
  expert layer and GELU took) and puts the same count on the innermost
  open span (``Span.counts``, exported among the span's ``args``), so the
  first ``step_dispatch`` says by itself what its program was made of;
- ``scope``    — the ``jax.named_scope`` labels the train step carries into
  the compiled HLO and the device trace (``Class:name`` per layer, ``loss``,
  ``regularization``, ``optimizer``, ``cast_params``). Metadata only: they
  cost nothing when the step runs, so they are always on, tracing or not;
- ``jaxhook``  — JAX compile/lowering attribution: ``jax.monitoring``
  events become ``jax_trace``/``jax_lowering``/``xla_compile``/``cache_load``
  spans nested under whatever span triggered them (a train step's under its
  ``step_dispatch``, ``init()``'s small programs under ``model_init``), so
  recompiles show up loudly and a first step says how much of it was
  Python tracing, lowering, and loading from the persistent compile cache;
  the cache's hit and miss events become the counts
  ``compile_cache.hits`` / ``compile_cache.misses`` on the span that paid;
- ``export``   — Chrome trace-event JSON (``chrome://tracing``/Perfetto)
  with flow arrows across threads, plus a terminal text timeline;
- ``metrics``  — the Prometheus registry core (promoted from
  ``serving.metrics``; that path remains as a deprecation re-export);
- ``listener`` — ``TraceListener``: the TrainingListener bridge that makes
  any ``fit()`` record spans and export ``training_*`` series through the
  same ``/metrics`` the serving tier already exposes;
- ``log``      — structured JSON-lines logging with automatic
  ``trace_id``/``span_id`` correlation from the active span, a bounded
  ring with drop accounting, a stdlib-``logging`` bridge and rate-limit
  gates (``enable_structured_logging()`` flips it on process-wide);
- ``health``   — ``TrainingWatchdog`` (NaN/Inf loss+params, gradient-norm
  EWMA, loss divergence, step stalls — with log/raise/callback action
  policies) and the serving ``HealthReport`` probes behind ``/livez``;
- ``alerts``   — threshold/absence/rate-of-change/multiwindow burn-rate
  rules evaluated over any registry's Prometheus exposition, with a
  deduping firing/resolved state machine, pluggable sinks and the
  ``AlertManager`` background evaluator (injectable clock);
- ``fleet``    — the multi-process operator plane for elastic/pod jobs:
  worker-side metrics snapshot files + crash-durable span streams,
  supervisor-side ``FleetRegistry`` federation (relabeled
  ``{slot,host,generation}`` union served at ``/metrics`` and fed to the
  alert engine) and ``merge_chrome_traces`` clock-aligned trace
  stitching;
- ``incident`` — the flight recorder: one bounded, schema'd
  ``incident_*`` bundle per elastic recovery decision
  (``tools/validate_incident.py`` lints it), plus ``capture_bundle``:
  the ``/debug/capture?seconds=N`` on-demand mini bundle;
- ``cost``     — the request-cost ledger: per-request device-time
  apportionment from ``batch_execute`` spans (compile excluded),
  conservation-checked, billed once into ``request_device_ms`` with
  exemplars;
- ``slo``      — declarative SLOs (latency-threshold and availability)
  compiled into multiwindow burn-rate rules for the alert engine, with
  a ``/slo`` compliance surface.
"""

from deeplearning4j_tpu.observe.metrics import (  # noqa: F401
    Counter,
    Exemplar,
    Gauge,
    Histogram,
    HTTPObserverMixin,
    MetricsRegistry,
    default_registry,
    exemplar_trace_ids,
    format_exemplar,
    instrument_http,
    parse_prometheus_text,
)
from deeplearning4j_tpu.observe.trace import (  # noqa: F401
    Span,
    SpanContext,
    TraceRecorder,
    Tracer,
    current_traceparent,
    disable_tracing,
    enable_tracing,
    get_active_tracer,
    parse_traceparent,
    span,
)
from deeplearning4j_tpu.observe.export import (  # noqa: F401
    merge_chrome_traces,
    text_timeline,
    to_chrome_trace,
    write_chrome_trace,
)
from deeplearning4j_tpu.observe.fleet import (  # noqa: F401
    FleetMetricsServer,
    FleetRegistry,
    MetricsFileExporter,
    SpanFileWriter,
    TailSampler,
    read_span_file,
)
from deeplearning4j_tpu.observe.incident import (  # noqa: F401
    IncidentRecorder,
    capture_bundle,
)
from deeplearning4j_tpu.observe.cost import (  # noqa: F401
    CostLedger,
    RequestCost,
)
from deeplearning4j_tpu.observe.slo import (  # noqa: F401
    SLO,
    LatencyBurnRateRule,
    SLOSet,
    load_slos,
)
from deeplearning4j_tpu.observe.listener import TraceListener  # noqa: F401
from deeplearning4j_tpu.observe.jaxhook import install_jax_hook  # noqa: F401
from deeplearning4j_tpu.observe.log import (  # noqa: F401
    LogHub,
    LogRecord,
    LogRing,
    StructuredLogger,
    at_most_every,
    disable_structured_logging,
    enable_structured_logging,
    every_n,
    get_active_hub,
    get_logger,
)
from deeplearning4j_tpu.observe.health import (  # noqa: F401
    HealthCheck,
    HealthEvent,
    HealthReport,
    ServingHealth,
    TrainingWatchdog,
    WatchdogAlarm,
    attach_observability,
)
from deeplearning4j_tpu.observe.alerts import (  # noqa: F401
    AbsenceRule,
    AlertManager,
    BurnRateRule,
    CallbackSink,
    LogSink,
    Notification,
    RateOfChangeRule,
    SLOSpec,
    ThresholdRule,
    WebhookSink,
    load_rules,
)
