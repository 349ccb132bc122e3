"""Unified tracing + metrics: one timeline across training and serving.

The observe/ layer end to end — the Dapper-style answer to "where did this
millisecond go" that the reference's listener/StatsListener/training-UI
stack never had:

- enable process-wide tracing (``observe.enable_tracing``) with the JAX
  compile hook: every XLA compile becomes an ``xla_compile`` span nested
  under whatever triggered it, so step-0 compilation and later recompiles
  show up loudly; set-up has spans of its own (``model_init`` round
  ``init()``, with every small program it fetched inside), and the
  timeline from ``init()`` to the end of the first step is printed;
- train data-parallel over the mesh with ``ParallelWrapper`` — per-step
  ``train_step`` spans (device-synced, with loss/batch attrs) — plus a
  ``TraceListener`` that exports ``training_*`` Prometheus series through
  the SAME registry the serving tier scrapes;
- serve the trained model and call it with ``ModelServingClient`` while a
  client span is open: the W3C ``traceparent`` header joins client →
  ``http_request`` → ``queue_wait``/``batch_execute`` (dispatcher thread)
  into ONE trace, and the server echoes ``X-Trace-Id``;
- run a traced streaming route (per-transform spans);
- export everything as a Chrome trace-event JSON (loadable in
  ``chrome://tracing`` / Perfetto), validate it with
  ``tools/validate_trace.py``, and print the terminal timeline;
- scrape ``/metrics`` and show the ``training_*`` and serving series side
  by side — one exposition for the whole stack.

Run: python examples/25_tracing_and_profiling.py   (CPU-friendly, ~1 min)
"""

import json
import os
import sys
import tempfile

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.observe import (TraceListener, default_registry,
                                        disable_tracing, enable_tracing,
                                        parse_prometheus_text, text_timeline)
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.serving import (ModelRegistry, ModelServer,
                                        ModelServingClient)
from deeplearning4j_tpu.streaming.route import Route

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def main():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(256, 12)).astype(np.float32)
    w = rng.normal(size=(12, 3)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[np.argmax(x @ w, axis=1)]

    metrics = default_registry()
    tracer = enable_tracing(metrics=metrics)  # + JAX compile hook

    # -- traced training: ParallelWrapper steps + TraceListener bridge -----
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=12, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=3, activation="softmax",
                               loss="negativeloglikelihood"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.add_listeners(TraceListener(tracer, metrics, model_name="demo"))
    pw = ParallelWrapper(net, metrics=metrics, metrics_name="demo")
    pw.fit(ListDataSetIterator(DataSet(x, y), 64), epochs=2)

    compile_spans = [s for s in tracer.recorder.spans()
                     if s.name == "xla_compile"]
    step_spans = [s for s in tracer.recorder.spans()
                  if s.name == "train_step"]
    print(f"training: {len(step_spans)} train_step spans, "
          f"{len(compile_spans)} xla_compile spans "
          f"(step 0 pays the compile; steady state recompiles would be loud)")

    # -- set-up from inside: what init() and the first step paid for ------
    spans = tracer.recorder.spans()
    init_span, = [s for s in spans if s.name == "model_init"]
    first_step = min(step_spans, key=lambda s: s.start_ns)
    setup = [s for s in spans if s.start_ns < first_step.end_ns]
    fetched = [s for s in setup if s.name == "xla_compile"
               and s.parent_id == init_span.span_id]
    print(f"set-up: init() drew {init_span.attrs['parameters']} parameters "
          f"({init_span.attrs['bytes']} bytes with the updater state) in "
          f"{init_span.duration_ms:.0f} ms and fetched {len(fetched)} small "
          f"programs on the way; jax traced "
          f"{sum(s.name == 'jax_trace' for s in setup)} functions before "
          f"the first step ended. Its timeline, the traces left out:")
    print(text_timeline([s for s in setup if s.name != "jax_trace"]))

    # -- traced serving: traceparent joins client, HTTP and dispatcher -----
    registry = ModelRegistry(metrics=metrics, wait_ms=1.0)
    registry.register("demo", model=net)
    server = ModelServer(registry, metrics=metrics)
    server.start()
    try:
        client = ModelServingClient(server.url)
        with tracer.span("user_code"):  # the client span parents under this
            out = client.predict("demo", x[:8])
        print(f"served 1 request: outputs {np.asarray(out).shape}, "
              f"server echoed X-Trace-Id={client.last_trace_id}")

        # -- a traced streaming route (per-transform spans) ----------------
        sunk = []
        (Route().from_source([x[i:i + 4] for i in range(0, 16, 4)])
         .transform(lambda b: b * 2.0)
         .filter(lambda b: b.shape[0] == 4)
         .to_list(sunk)).run()
        print(f"routed {len(sunk)} mini-batches through a traced pipeline")

        # -- one /metrics exposition for train AND serve -------------------
        series = parse_prometheus_text(client.metrics_text())
        training = sorted(k for k in series if k.startswith("training_"))
        serving = sorted(k for k in series if k.startswith("serving_")
                         or k.startswith("inference_"))
        print("training series:", ", ".join(training))
        print("serving  series:", ", ".join(serving))
        assert "training_steps_total" in series
        assert "training_step_seconds_bucket" in series
    finally:
        server.stop(drain=True, shutdown_registry=True)
        disable_tracing()

    # -- export: Chrome trace JSON + schema validation + text timeline -----
    trace_path = os.path.join(tempfile.mkdtemp(), "train_and_serve.json")
    tracer.write_chrome_trace(trace_path)
    sys.path.insert(0, TOOLS)
    from validate_trace import validate_file
    errors = validate_file(trace_path)
    assert not errors, errors
    n_events = len(json.load(open(trace_path))["traceEvents"])
    print(f"wrote {trace_path}: {n_events} Chrome trace events, "
          f"schema-valid (load it in chrome://tracing or ui.perfetto.dev)")

    names = {s.name for s in tracer.recorder.spans()}
    for expected in ("model_init", "parallel_fit", "train_step",
                     "train_iteration", "jax_trace", "jax_lowering",
                     "xla_compile", "client_predict", "http_request",
                     "inference_request", "queue_wait", "batch_execute",
                     "route.run"):
        assert expected in names, (expected, sorted(names))
    print("\nlast spans (terminal timeline):")
    print(tracer.timeline(limit=25))


if __name__ == "__main__":
    main()
