"""Health & alerting: the observability loop closed end to end.

The third pillar (``observe/log.py``, ``observe/health.py``,
``observe/alerts.py``) on top of the spans + metrics from example 25 —
signals become *action*:

- structured JSON-lines logging with automatic ``trace_id``/``span_id``
  correlation (the Dapper contract: a log line emitted inside a traced
  run is findable from the trace id, including every stdlib ``logging``
  call through the bridge);
- a deliberately-diverging training run (SGD at lr=1000 on MSE explodes
  within a few steps): a ``TrainingWatchdog`` with the ``raise`` policy
  aborts the fit with ``WatchdogAlarm`` the step the loss goes
  non-finite, and the ``PreemptionHandler`` rollback flow restores the
  pre-divergence checkpoint;
- a saturated model server (``max_inflight=1``, slow model, concurrent
  burst): 429 rejections drive the error ratio of
  ``serving_requests_total`` over a multiwindow burn-rate SLO rule
  (Google SRE Workbook shape) — the alert FIRES, notifies its sink
  exactly once, and RESOLVES after recovery traffic, all on an injected
  ``ManualTimeSource`` clock (no waiting for real windows);
- the server's ``/livez?verbose=1`` health report and ``/alerts`` rule
  states over HTTP, and the shipped ``alert_rules.json`` validated with
  ``tools/validate_alert_rules.py``.

Run: python examples/26_health_and_alerting.py   (CPU-friendly, <1 min)
"""

import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from urllib.request import urlopen

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.observe import (AlertManager, CallbackSink, LogSink,
                                        TrainingWatchdog, WatchdogAlarm,
                                        attach_observability,
                                        default_registry, disable_tracing,
                                        disable_structured_logging,
                                        enable_structured_logging,
                                        enable_tracing, get_active_hub,
                                        get_logger, load_rules)
from deeplearning4j_tpu.parallel.time_source import ManualTimeSource
from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
from deeplearning4j_tpu.util.preemption import PreemptionHandler

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
RULES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "alert_rules.json")


def diverging_training(tmp):
    print("=== 1. watchdog catches a diverging run; rollback recovers ===")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x @ rng.normal(size=(8, 1)).astype(np.float32))
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(1000.0))  # deliberately explosive
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=1, activation="identity",
                               loss="mse"))
            .build())
    net = MultiLayerNetwork(conf).init()

    ckpt = os.path.join(tmp, "pre_divergence.zip")
    handler = PreemptionHandler(net, ckpt)
    handler.save()  # the known-good snapshot the rollback restores

    tracer = enable_tracing(metrics=default_registry())
    # ONE attachment path for TraceListener + watchdog; raise policy stops
    # the run the step the loss goes non-finite
    attach_observability(net, tracer=tracer, metrics=default_registry(),
                         model_name="diverging",
                         watchdog={"action": "raise",
                                   "divergence_windows": 3})
    it = ListDataSetIterator(DataSet(x, y), 16)
    slog = get_logger("example26")
    alarm = None
    with tracer.span("diverging_run") as sp:
        slog.info("starting deliberately-diverging fit")
        try:
            net.fit(it, epochs=50)
        except WatchdogAlarm as e:
            alarm = e
    assert alarm is not None, "watchdog never fired on an lr=1000 run"
    print(f"watchdog fired: {alarm}")

    # every structured record emitted inside the span carries its ids
    hub = get_active_hub()
    correlated = [r for r in hub.ring.records()
                  if r.trace_id == sp.trace_id]
    assert correlated, "no log records correlated to the run's trace"
    print(f"{len(correlated)} log record(s) carry trace_id "
          f"{sp.trace_id[:8]}… (incl. the watchdog finding)")

    restored, state = handler.rollback()
    for group in restored.params:
        for name, arr in group.items():
            assert np.all(np.isfinite(np.asarray(arr))), name
    print(f"rollback restored finite params from {os.path.basename(ckpt)} "
          f"(iteration {state['iteration']})\n")
    disable_tracing()


class SlowModel:
    """50 ms per batch: enough overlap for a burst to overflow admission."""

    def output(self, x):
        time.sleep(0.05)
        return np.asarray(x).sum(axis=tuple(range(1, np.asarray(x).ndim)),
                                 keepdims=True)


def saturated_serving():
    print("=== 2. saturated server drives a burn-rate alert ===")
    metrics = default_registry()
    rules = load_rules(RULES)
    clock = ManualTimeSource(0)
    notifications = []
    mgr = AlertManager(metrics, rules,
                       [LogSink(), CallbackSink(notifications.append)],
                       time_source=clock)

    registry = ModelRegistry(metrics=metrics, wait_ms=1.0)
    registry.register("slow", model=SlowModel())
    server = ModelServer(registry, metrics=metrics, max_inflight=1,
                         alerts=mgr)
    port = server.start()
    url = f"http://127.0.0.1:{port}"

    mgr.evaluate_once()  # baseline sample at t=0

    def predict():
        import urllib.error
        body = json.dumps({"inputs": [[1.0, 2.0]]}).encode()
        try:
            from urllib.request import Request
            with urlopen(Request(f"{url}/v1/models/slow/predict", body),
                         timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    # burst: 16 concurrent requests against max_inflight=1 → mostly 429s
    with ThreadPoolExecutor(16) as pool:
        codes = list(pool.map(lambda _: predict(), range(16)))
    n_429 = codes.count(429)
    print(f"burst statuses: {sorted(set(codes))} ({n_429}/16 shed as 429)")
    assert n_429 > 0, "burst never overflowed admission"

    clock.advance(seconds=60)
    fired = mgr.evaluate_once()
    assert any(n.rule == "predict_slo_burn" and n.state == "firing"
               for n in fired), mgr.describe()
    print(f"fired: {[n.rule for n in fired if n.state == 'firing']}")

    # /alerts and /livez over HTTP while firing
    alerts = json.load(urlopen(f"{url}/alerts", timeout=5))
    assert "predict_slo_burn" in alerts["firing"]
    livez = json.load(urlopen(f"{url}/livez?verbose=1", timeout=5))
    print(f"/livez status={livez['status']} "
          f"({len(livez['checks'])} checks); "
          f"/alerts firing={alerts['firing']}")

    # recovery: sequential successes only, clock past the short window →
    # the short-window burn rate drops to 0 and the alert resolves. A
    # handler answers INSIDE its admission slot, so the client holds its
    # 200 a moment before the slot is free; on a busy machine the next
    # request can arrive in that moment and be shed (max_inflight=1). Wait
    # for the state the recovery needs, an idle admission, before each one.
    for _ in range(4):
        assert server.admission.wait_idle(timeout=30)
        assert predict() == 200
    clock.advance(seconds=400)
    resolved = mgr.evaluate_once()
    assert any(n.rule == "predict_slo_burn" and n.state == "resolved"
               for n in resolved), mgr.describe()
    burn_notes = [n for n in notifications if n.rule == "predict_slo_burn"]
    assert [n.state for n in burn_notes] == ["firing", "resolved"], \
        [n.state for n in burn_notes]
    print("resolved after recovery traffic; sink saw exactly one "
          "firing + one resolved notification\n")
    server.stop(drain=True, shutdown_registry=True)


def validate_shipped_rules():
    print("=== 3. shipped rules file passes the validator ===")
    sys.path.insert(0, TOOLS)
    from validate_alert_rules import validate_file
    errors = validate_file(RULES)
    assert not errors, errors
    print(f"OK {os.path.basename(RULES)}: "
          f"{len(load_rules(RULES))} rule(s) valid\n")


def main():
    with tempfile.TemporaryDirectory() as tmp:
        enable_structured_logging(
            path=os.path.join(tmp, "run.jsonl"), level="debug")
        try:
            diverging_training(tmp)
            saturated_serving()
            validate_shipped_rules()
            # the JSON-lines stream parses back, line by line
            with open(os.path.join(tmp, "run.jsonl")) as fh:
                lines = [json.loads(l) for l in fh]
            assert any("trace_id" in l for l in lines)
            print(f"structured log stream: {len(lines)} JSON lines, "
                  "trace-correlated")
        finally:
            disable_structured_logging()
    print("example 26 complete")


if __name__ == "__main__":
    main()
