"""Serving latency benchmark — ONE JSON line, the BENCH_SERVING series.

The serving counterpart of ``bench.py``'s training suite: drives a real
``ModelServer`` over HTTP with the keep-alive client and reports what a
caller actually feels —

- **cold vs warm first request**: the same model registered with
  ``warmup="off"`` vs ``warmup="sync"`` — the XLA compile spike the AOT
  bucket warmup removes from the request path, and what it cost at
  registration instead (``warmup_seconds``);
- **closed loop**: N worker threads in lockstep request/response —
  p50/p95/p99 latency and saturated throughput;
- **open loop**: fixed arrival rate (latency-independent, the
  coordinated-omission-free number) — achieved rate, SLO hit rate, and
  goodput (completed-within-SLO per second);
- **steady_state_compiles**: XLA compiles observed while the measured
  traffic ran. The fast path's invariant is that this is ZERO; it is also
  the deterministic regression oracle ``--check`` enforces (wall-clock
  latency on shared CI flakes; "did a compile hit the hot path" does not);
- **dispatch_micro**: the host-side coalesce+pad step timed in isolation,
  preallocated pad buffer vs the old concatenate-then-pad path, plus one
  in-process ``ParallelInference`` round-trip time for context;
- **int8**: the quantized-serving config — same measurements through a
  ``dtype_policy="int8"`` version plus calibration error and weight bytes.

Comparator discipline (same as bench.py): latencies through a loopback
HTTP stack on a shared host drift session to session; ``cold - warm``
first-request delta, ``steady_state_compiles``, compile/bucket counts and
byte ratios are the stable comparators. BENCH_SERVING_r01.json is the
committed r01 of this series.

Round 2 (``--chaos``) — availability under injected faults: a fault plan
crashes the live version's forward repeatedly while a retry-budget client
drives traffic. The run proves (and ``--check BENCH_SERVING_r02.json``
re-proves deterministically on every CI run) that the breaker trips, the
dispatcher restarts under its budget, traffic fails over to the designated
fallback with ZERO client-visible 5xx after the trip, the breaker
half-opens and closes once the faults stop — and client-observed
availability stays at/above the recorded floor the whole way. All control
timing runs on a ``ManualTimeSource`` (breaker cooldowns and restart
backoff are *advanced*, not slept), so the choreography is exact.

Round 3 (``--slo``) — the request-cost & SLO plane under open-loop load:
a cost-metered, tail-sampled server carries a latency SLO whose threshold
sits below the lowest histogram bucket, so every request is a
deterministic budget violation. The run proves (and ``--check
BENCH_SERVING_r03.json`` re-proves on every CI run) that the compiled
burn-rate rule fires exactly once and resolves on traffic silence (pure
``ManualTimeSource``, zero control-path sleeps), the cost ledger's
conservation invariant holds with zero steady-state compiles (compile
time is excluded from request bills by construction), the tail sampler
both keeps the injected stall's trace and drops the boring ones, and the
latency histogram's tail-bucket exemplar names a trace that
``capture_bundle`` actually returns.

Usage:
    python bench_serving.py                       # full run, prints JSON
    python bench_serving.py --chaos               # chaos/recovery record
    python bench_serving.py --slo                 # cost/SLO-plane record
    python bench_serving.py --out FILE            # also write FILE
    python bench_serving.py --check BENCH_SERVING_rNN.json
        # regression mode: tiny config, deterministic oracles only —
        # exercised by the smoke tier on every CI run (r01 = fast path,
        # r02 = chaos/recovery, r03 = cost/SLO plane)
"""

import argparse
import json
import sys
import threading
import time

import numpy as np

SCHEMA_CONFIG_KEYS = ("config", "buckets", "warmup_seconds",
                      "cold_first_request_ms", "warm_first_request_ms",
                      "steady_state_compiles", "closed_loop", "open_loop")


# --------------------------------------------------------------------- models
def _mlp(seed=7):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_in=64, n_out=256, activation="relu"))
            .layer(DenseLayer(n_in=256, n_out=256, activation="relu"))
            .layer(OutputLayer(n_in=256, n_out=16, activation="softmax",
                               loss="negativeloglikelihood"))
            .build())
    return MultiLayerNetwork(conf).init()


def _lenet(seed=7):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.models import LeNet
    net = MultiLayerNetwork(LeNet(num_labels=10, seed=seed).conf())
    return net.init()


def _tiny(seed=7):
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                               loss="negativeloglikelihood"))
            .build())
    return MultiLayerNetwork(conf).init()


CONFIGS = {
    "mlp_ff": dict(
        make=_mlp, row_shape=(64,), buckets=[1, 2, 4, 8, 16, 32],
        desc="3-layer MLP 64-256-256-16, f32", slo_ms=50.0,
        closed_threads=4, closed_reps=60, open_rps=60.0, open_s=3.0),
    "lenet_cnn": dict(
        make=_lenet, row_shape=(28, 28, 1), buckets=[1, 4, 16],
        desc="zoo LeNet 28x28x1, f32", slo_ms=150.0,
        closed_threads=4, closed_reps=30, open_rps=40.0, open_s=3.0),
}


# ---------------------------------------------------------------- measurement
def _percentiles(lat_ms):
    lat = np.asarray(sorted(lat_ms))
    return {"p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p95_ms": round(float(np.percentile(lat, 95)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3)}


def _stack(model, buckets, *, warmup, metrics=None):
    from deeplearning4j_tpu.serving import (MetricsRegistry, ModelRegistry,
                                            ModelServer, ModelServingClient)
    m = metrics if metrics is not None else MetricsRegistry()
    registry = ModelRegistry(metrics=m, buckets=buckets, warmup=warmup,
                             max_batch_size=max(buckets))
    registry.register("bench", model)
    server = ModelServer(registry, metrics=m, max_inflight=256)
    server.start()
    return registry, server, ModelServingClient(server.url)


def _teardown(registry, server, client):
    client.close()
    server.stop(drain=False)
    registry.shutdown()


def _first_request_ms(client, rows, row_shape):
    x = np.random.default_rng(0).normal(size=(rows,) + row_shape)
    x = x.astype(np.float32)
    t0 = time.perf_counter()
    client.predict("bench", x, binary=True)
    return (time.perf_counter() - t0) * 1e3


def _closed_loop(client, row_shape, *, threads, reps, max_rows):
    """Lockstep request/response workers — saturated-latency numbers."""
    lat, errors = [], []
    lock = threading.Lock()
    rows_cycle = [1, 2, max(1, max_rows // 2), max_rows]

    def worker(wid):
        rng = np.random.default_rng(wid)
        mine = []
        for i in range(reps):
            x = rng.normal(size=(rows_cycle[i % len(rows_cycle)],)
                           + row_shape).astype(np.float32)
            t0 = time.perf_counter()
            try:
                client.predict("bench", x, binary=True)
                mine.append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # noqa: BLE001 — count, keep measuring
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
        with lock:
            lat.extend(mine)

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    elapsed = time.perf_counter() - t0
    rec = {"threads": threads, "requests": len(lat),
           "throughput_rps": round(len(lat) / elapsed, 1), **_percentiles(lat)}
    if errors:
        rec["errors"] = len(errors)
        rec["first_error"] = errors[0]
    return rec


def _open_loop(client, row_shape, *, target_rps, duration_s, slo_ms):
    """Fixed arrival rate, unbounded concurrency — requests are launched on
    schedule whether or not earlier ones returned, so slow responses can't
    slow the arrival process (no coordinated omission)."""
    lat, errors = [], []
    lock = threading.Lock()
    threads = []
    rng = np.random.default_rng(42)
    n = int(target_rps * duration_s)
    xs = [rng.normal(size=(1,) + row_shape).astype(np.float32)
          for _ in range(min(n, 16))]

    def fire(i):
        t0 = time.perf_counter()
        try:
            client.predict("bench", xs[i % len(xs)], binary=True)
            with lock:
                lat.append((time.perf_counter() - t0) * 1e3)
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(f"{type(e).__name__}: {e}")

    interval = 1.0 / target_rps
    start = time.perf_counter()
    for i in range(n):
        due = start + i * interval
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=fire, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    done = len(lat)
    within = sum(1 for x in lat if x <= slo_ms)
    rec = {"target_rps": target_rps,
           "achieved_rps": round(done / elapsed, 1),
           "slo_ms": slo_ms,
           "slo_hit_rate": round(within / n, 4) if n else 0.0,
           "goodput_rps": round(within / elapsed, 1)}
    if lat:
        rec.update(_percentiles(lat))
    if errors:
        rec["errors"] = len(errors)
    return rec


def _dispatch_micro(row_shape=(2048,), reps=2000):
    """The host-side coalesce+pad tax, isolated: four 6-row requests
    assembled into a 32-bucket batch, preallocated pad buffer vs the old
    concatenate-then-pad-concatenate (which allocates AND copies the full
    padded batch twice). ``_assemble`` is timed directly because the full
    ``output()`` round-trip (queue handoff, device transfer, forward,
    result materialization) is ~0.5 ms of fixed cost that swamps the
    ~30 µs copy delta into run-to-run noise; ``roundtrip_ms_per_req`` is
    reported once as that context."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    class _Identity:
        def output(self, x):
            return np.asarray(x)

    class _Rows:
        def __init__(self, x):
            self.x = x

    pi = ParallelInference(_Identity(), max_batch_size=32, buckets=[32],
                           mode="sequential")
    rng = np.random.default_rng(9)
    batch = [_Rows(rng.normal(size=(6,) + row_shape).astype(np.float32))
             for _ in range(4)]
    out = {"rows": 24, "bucket": 32,
           "row_floats": int(np.prod(row_shape))}
    for label, reuse in (("assemble_reuse_us", True),
                         ("assemble_concat_us", False)):
        pi.reuse_pad_buffer = reuse
        for _ in range(max(50, reps // 10)):  # warm the path
            pi._assemble(batch, 24, 32)
        t0 = time.perf_counter()
        for _ in range(reps):
            pi._assemble(batch, 24, 32)
        out[label] = round((time.perf_counter() - t0) / reps * 1e6, 2)
    pi.shutdown()

    bpi = ParallelInference(_Identity(), max_batch_size=32, buckets=[32],
                            wait_ms=0.0)
    x = np.concatenate([r.x for r in batch], axis=0)
    for _ in range(20):
        bpi.output(x)
    t0 = time.perf_counter()
    for _ in range(200):
        bpi.output(x)
    out["roundtrip_ms_per_req"] = round((time.perf_counter() - t0) / 200
                                        * 1e3, 4)
    bpi.shutdown()
    return out


def _compile_count():
    from deeplearning4j_tpu.observe import trace as _trace
    tracer = _trace.get_active_tracer()
    return tracer.compile_count if tracer is not None else 0


def _bench_config(name, spec, *, int8=False):
    buckets = spec["buckets"]
    rec = {"config": spec["desc"] + (" + int8 weights" if int8 else ""),
           "buckets": buckets}

    # cold: no warmup — the first request pays the compile spike
    model = spec["make"](seed=3)
    registry, server, client = _stack(model, buckets, warmup="off")
    rec["cold_first_request_ms"] = round(
        _first_request_ms(client, max(buckets), spec["row_shape"]), 2)
    _teardown(registry, server, client)

    # warm: AOT bucket warmup at registration; fresh model object so its
    # jit cache is genuinely cold at register time
    model = spec["make"](seed=3)
    kw = {}
    if int8:
        sample = np.random.default_rng(5).normal(
            size=(max(buckets),) + spec["row_shape"]).astype(np.float32)
        kw = dict(dtype_policy="int8", sample_input=sample)
    from deeplearning4j_tpu.serving import MetricsRegistry, ModelRegistry
    from deeplearning4j_tpu.serving import ModelServer, ModelServingClient
    m = MetricsRegistry()
    registry = ModelRegistry(metrics=m, buckets=buckets, warmup="sync",
                             max_batch_size=max(buckets))
    registry.register("bench", model, **kw)
    state = registry.warmup_state("bench")
    rec["warmup_seconds"] = state["seconds"]
    assert state["status"] == "warm", state
    server = ModelServer(registry, metrics=m, max_inflight=256)
    server.start()
    client = ModelServingClient(server.url)

    c0 = _compile_count()
    rec["warm_first_request_ms"] = round(
        _first_request_ms(client, max(buckets), spec["row_shape"]), 2)
    rec["closed_loop"] = _closed_loop(
        client, spec["row_shape"], threads=spec["closed_threads"],
        reps=spec["closed_reps"], max_rows=max(buckets))
    rec["open_loop"] = _open_loop(
        client, spec["row_shape"], target_rps=spec["open_rps"],
        duration_s=spec["open_s"], slo_ms=spec["slo_ms"])
    rec["steady_state_compiles"] = _compile_count() - c0

    if int8:
        from deeplearning4j_tpu.serving.quantize import param_nbytes
        served = registry.get("bench")
        mv = served.versions[served.current_version]
        rec["quant_error"] = mv.quant_error
        rec["param_bytes_float32"] = param_nbytes(model.params)
        rec["param_bytes_int8"] = mv.model.param_nbytes
    _teardown(registry, server, client)
    return rec


def run_full():
    import jax
    from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                            enable_tracing)
    enable_tracing(Tracer())  # compile counting only; ring buffer bounded
    try:
        record = {"series": "BENCH_SERVING", "round": 1,
                  "backend": jax.default_backend(),
                  "devices": len(jax.devices())}
        configs = {}
        for name, spec in CONFIGS.items():
            try:
                configs[name] = _bench_config(name, spec)
            except Exception as e:  # noqa: BLE001 — isolate per config
                configs[name] = {"error": f"{type(e).__name__}: {e}"}
        try:
            # int8 dequantizes per forward — on the CPU bench host that is
            # pure overhead (the byte win pays off on HBM-bound devices),
            # so drive it at a rate it can absorb; the stable comparators
            # are quant_error and the 3.8x weight-byte cut
            int8_spec = dict(CONFIGS["mlp_ff"], open_rps=30.0)
            configs["mlp_ff_int8"] = _bench_config(
                "mlp_ff_int8", int8_spec, int8=True)
        except Exception as e:  # noqa: BLE001
            configs["mlp_ff_int8"] = {"error": f"{type(e).__name__}: {e}"}
        record["configs"] = configs
        try:
            record["dispatch_micro"] = _dispatch_micro()
        except Exception as e:  # noqa: BLE001
            record["dispatch_micro"] = {"error": f"{type(e).__name__}: {e}"}
        return record
    finally:
        disable_tracing()


# --------------------------------------------------------------------- chaos
CHAOS_SCHEMA_KEYS = ("config", "requests", "successes", "availability",
                     "availability_floor", "errors_5xx_after_trip",
                     "breaker_opened_total", "breaker_closed_again",
                     "dispatcher_restarts", "degraded_requests",
                     "recovery_requests", "recovery_wall_ms",
                     "client_retries",
                     "observability_reachable_during_quarantine")

CHAOS_AVAILABILITY_FLOOR = 0.99


def run_chaos():
    """Drive the serving-resilience choreography end to end over real
    HTTP and record what the CLIENT observed. Control time (breaker
    cooldown, restart backoff) lives on a manual clock; only the HTTP
    round-trips are wall time."""
    import jax

    from deeplearning4j_tpu.parallel.elastic import BackoffPolicy
    from deeplearning4j_tpu.parallel.time_source import ManualTimeSource
    from deeplearning4j_tpu.serving import (MetricsRegistry, ModelRegistry,
                                            ModelServer, ModelServingClient,
                                            RetryPolicy)
    from deeplearning4j_tpu.util import faultinject

    ts = ManualTimeSource()
    m = MetricsRegistry()
    registry = ModelRegistry(
        metrics=m, buckets=[2, 4], max_batch_size=4,
        max_dispatcher_restarts=5,
        restart_backoff=BackoffPolicy(base_s=1.0, jitter=0.0),
        breaker=dict(failure_threshold=2, window_s=60.0, cooldown_s=10.0,
                     half_open_probes=1),
        time_source=ts)
    registry.register("bench", _tiny(seed=3))
    registry.register("bench", _tiny(seed=4))   # v2 goes live
    registry.set_fallback("bench", ["previous"])
    server = ModelServer(registry, metrics=m, max_inflight=64)
    server.start()
    cm = MetricsRegistry()
    client = ModelServingClient(
        server.url, metrics=cm,
        retry=RetryPolicy(max_retries=3, jitter=0.0),
        sleep=lambda s: None)  # backoff is advice here, not wall time
    # the live client is serial, so HTTP request seq == dispatch seq;
    # seqs 0-1 are the healthy baseline, 2-4 the crash storm
    plan = {"faults": [
        {"type": "crash_forward", "model": "bench", "step": s}
        for s in (2, 3, 4)]}
    faultinject.set_plan(faultinject.FaultPlan.parse(plan))
    x = np.zeros((2, 8), np.float32)
    outcomes = []          # (ok, after_trip)
    tripped = False
    recovery_requests = None
    t_first_crash = None

    def drive(n=1):
        nonlocal tripped, recovery_requests, t_first_crash
        for _ in range(n):
            try:
                client.predict("bench", x, binary=True)
                ok = True
            except Exception:  # noqa: BLE001 — the record counts these
                ok = False
            brk = registry.get("bench").breakers.get(2)
            if brk is not None and brk.opened_total and not tripped:
                tripped = True
            outcomes.append((ok, tripped))

    try:
        drive(2)                      # seqs 0-1: healthy baseline on v2
        t_first_crash = time.perf_counter()
        drive(1)                      # seq 2: crash -> failover to v1
        drive(1)                      # restart pending -> failover
        ts.advance(seconds=2)         # past restart backoff #1
        drive(1)                      # seq 3: crash #2 -> breaker OPENS
        drive(2)                      # open: quarantined, fallback serves
        # the observability plane must survive the data-plane death:
        # /livez answers (degraded, not down) and /metrics scrapes while
        # the live version is quarantined and the dispatcher is down
        import urllib.request
        observability_ok = True
        for probe in ("/livez", "/metrics"):
            try:
                with urllib.request.urlopen(server.url + probe,
                                            timeout=5) as r:
                    observability_ok &= r.status == 200
            except Exception:  # noqa: BLE001 — recorded, not raised
                observability_ok = False
        ts.advance(seconds=15)        # past cooldown AND backoff #2
        drive(1)                      # half-open probe: seq 4 crash ->
        #                               re-open; the request still serves
        ts.advance(seconds=15)
        drive(1)                      # probe succeeds -> breaker CLOSES
        brk = registry.get("bench").breakers[2]
        closed_again = brk.state == "closed"
        for i in range(3):            # primary serves again
            drive(1)
        recovery_wall_ms = (time.perf_counter() - t_first_crash) * 1e3
        # first post-crash request served by the PRIMARY again
        recovery_requests = 8         # by construction of the schedule
        pi = registry.get("bench").inference
        successes = sum(1 for ok, _ in outcomes if ok)
        record = {
            "config": "tiny MLP 8-16-4, v2 live + v1 fallback, "
                      "crash_forward storm at dispatch seqs 2-4",
            "plan": plan,
            "requests": len(outcomes),
            "successes": successes,
            "availability": round(successes / len(outcomes), 4),
            "availability_floor": CHAOS_AVAILABILITY_FLOOR,
            "errors_5xx_after_trip": sum(
                1 for ok, after in outcomes if after and not ok),
            "breaker_opened_total": brk.opened_total,
            "breaker_closed_again": closed_again,
            "dispatcher_restarts": pi.restarts_used,
            "degraded_requests": int(
                m.get("serving_degraded_requests_total").total()),
            "recovery_requests": recovery_requests,
            "recovery_wall_ms": round(recovery_wall_ms, 1),
            "client_retries": int(cm.get("client_retries_total").total()),
            "observability_reachable_during_quarantine": observability_ok,
        }
        return {"series": "BENCH_SERVING", "round": 2,
                "backend": jax.default_backend(),
                "devices": len(jax.devices()),
                "chaos": record}
    finally:
        faultinject.set_plan(None)
        client.close()
        server.stop(drain=False)
        registry.shutdown()


def run_chaos_check(committed_path):
    """Deterministic chaos oracles for the smoke tier: the committed r02
    record carries the schema and its invariants hold (availability at or
    above its floor, zero 5xx after the trip, breaker closed again,
    restarts within budget), and a fresh in-process chaos run reproduces
    them exactly — plus /livez and /metrics answer during quarantine."""
    failures = []
    with open(committed_path) as f:
        committed = json.load(f)
    if committed.get("series") != "BENCH_SERVING":
        failures.append(f"{committed_path}: series != BENCH_SERVING")
    chaos = committed.get("chaos")
    if not isinstance(chaos, dict):
        failures.append(f"{committed_path}: no 'chaos' record")
        chaos = {}
    for key in CHAOS_SCHEMA_KEYS:
        if key not in chaos:
            failures.append(f"{committed_path}: chaos missing {key!r}")
    if chaos.get("availability", 0) < chaos.get("availability_floor", 1):
        failures.append(f"{committed_path}: availability "
                        f"{chaos.get('availability')} below floor")
    if chaos.get("errors_5xx_after_trip", 1) != 0:
        failures.append(f"{committed_path}: recorded 5xx after the trip")
    if not chaos.get("breaker_closed_again", False):
        failures.append(f"{committed_path}: breaker never closed again")

    fresh = run_chaos()["chaos"]
    if fresh["availability"] < fresh["availability_floor"]:
        failures.append(
            f"live chaos availability {fresh['availability']} below "
            f"floor {fresh['availability_floor']}")
    if fresh["errors_5xx_after_trip"] != 0:
        failures.append(f"live chaos saw {fresh['errors_5xx_after_trip']} "
                        f"client-visible 5xx after the breaker tripped")
    if not fresh["breaker_closed_again"]:
        failures.append("live chaos breaker did not close after faults "
                        "stopped")
    if not fresh["breaker_opened_total"]:
        failures.append("live chaos breaker never opened")
    if fresh["dispatcher_restarts"] < 1:
        failures.append("live chaos dispatcher never restarted")
    if not fresh["observability_reachable_during_quarantine"]:
        failures.append("live chaos: /livez or /metrics unreachable while "
                        "the dispatcher was down")

    if failures:
        for f_ in failures:
            print(f"CHECK FAIL: {f_}", file=sys.stderr)
        return 1
    print(f"bench_serving chaos check OK against {committed_path} "
          f"(availability {fresh['availability']}, "
          f"{fresh['breaker_opened_total']} breaker trip(s), "
          f"{fresh['dispatcher_restarts']} dispatcher restart(s), "
          f"zero 5xx after trip)")
    return 0


# ----------------------------------------------------------------------- slo
SLO_SCHEMA_KEYS = ("config", "slo_spec", "compliance", "burn",
                   "alert_states", "open_loop", "steady_state_compiles",
                   "cost", "sampler", "exemplar_trace_captured")


class _ListSink:
    """In-memory keep target for the tail sampler (the bench needs the
    accounting, not the disk format)."""

    def __init__(self):
        self.spans = []

    def add(self, span):
        self.spans.append(span)


def run_slo():
    """Round 3 — the request-cost & SLO plane under open-loop load.

    Open-loop traffic (fixed arrival rate) with one injected
    ``slow_forward`` stall runs against a server carrying a latency SLO
    whose threshold sits below the lowest histogram bucket — every
    request is a deterministic budget violation, so the burn-rate
    choreography (fire exactly once, resolve on silence) is exact on a
    ``ManualTimeSource`` with zero control-path sleeps. The record
    captures what the plane promises: compliance + burn at fire time,
    the cost ledger's conservation invariant (attributed + unattributed
    == total device ms, compile time separate), the tail sampler's
    keep/drop accounting, and that the latency histogram's tail-bucket
    exemplar names a trace ``capture_bundle`` can actually return."""
    import jax

    from deeplearning4j_tpu.observe import (AlertManager, CallbackSink,
                                            MetricsRegistry, TailSampler,
                                            Tracer, disable_tracing,
                                            enable_tracing, load_slos,
                                            parse_prometheus_text)
    from deeplearning4j_tpu.observe.incident import capture_bundle
    from deeplearning4j_tpu.parallel.time_source import ManualTimeSource
    from deeplearning4j_tpu.serving import (ModelRegistry, ModelServer,
                                            ModelServingClient)
    from deeplearning4j_tpu.util import faultinject

    m = MetricsRegistry()
    sampler = TailSampler(_ListSink(), default_slow_ms=150.0, metrics=m)
    tracer = enable_tracing(Tracer(sampler), metrics=m)
    slo_set = load_slos({"slos": [{
        "name": "bench-latency", "sli": "latency",
        "metric": "serving_request_latency_seconds",
        "labels": {"model": "bench"},
        "threshold_ms": 0.001, "objective": 0.99,
        "windows": [{"long_s": 3600, "short_s": 10, "factor": 2.0}]}]})
    clock = ManualTimeSource(0)
    notes = []
    mgr = AlertManager(m, slo_set.rules(), [CallbackSink(notes.append)],
                       time_source=clock)
    registry = ModelRegistry(metrics=m, buckets=[1, 2, 4], warmup="sync",
                             max_batch_size=4)
    registry.register("bench", _tiny(seed=3))
    server = ModelServer(registry, metrics=m, max_inflight=64,
                         alerts=mgr, slo=slo_set)
    server.start()
    client = ModelServingClient(server.url)
    faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
        {"type": "slow_forward", "model": "bench", "step": 5,
         "duration_s": 0.3}]}))
    try:
        mgr.evaluate_once()   # baseline sample at t=0
        c0 = tracer.compile_count
        open_loop = _open_loop(client, (8,), target_rps=40.0,
                               duration_s=2.0, slo_ms=50.0)
        leaked = tracer.compile_count - c0

        clock.advance(seconds=5)
        mgr.evaluate_once()   # the burn-rate rule fires here
        status = slo_set.status(metrics=m, alerts=mgr)
        entry = status["slos"][0]
        compliance, burn = entry["compliance"], entry["burn"][0]
        clock.advance(seconds=400)
        mgr.evaluate_once()   # traffic silence: short window drains
        states = [n.state for n in notes
                  if n.rule == "slo_burn:bench-latency"]

        # the tail-bucket exemplar must name a retrievable trace
        parsed = parse_prometheus_text(m.exposition())
        tail_le, tail_tid = -1.0, None
        for (series, labels), ex in parsed.exemplars.items():
            ld = dict(labels)
            if series != "serving_request_latency_seconds_bucket" \
                    or ld.get("model") != "bench":
                continue
            le = float(ld["le"])
            if le != float("inf") and le > tail_le:
                tail_le, tail_tid = le, ex.labels.get("trace_id")
        bundle = capture_bundle(seconds=120, metrics=m, cost=server.cost,
                                sampler=sampler, max_spans=4096)
        captured = tail_tid is not None and any(
            e.get("args", {}).get("trace_id") == tail_tid
            for e in bundle["trace"]["traceEvents"])

        cons = server.cost.conservation("bench")
        acct = sampler.describe()
        record = {
            "config": "tiny MLP 8-16-4 warm, open-loop 40 rps x 2 s, one "
                      "300 ms slow_forward stall at dispatch seq 5, "
                      "latency SLO threshold below the lowest bucket",
            "slo_spec": slo_set.describe()[0],
            "compliance": compliance,
            "burn": burn,
            "alert_states": states,
            "open_loop": open_loop,
            "steady_state_compiles": leaked,
            "cost": {
                "conservation_ok": cons["ok"],
                "error_ms": round(cons["error_ms"], 9),
                "device_ms": round(cons["device_ms"], 3),
                "attributed_device_ms": round(
                    cons["attributed_device_ms"], 3),
                "unattributed_device_ms": round(
                    cons["unattributed_device_ms"], 3),
                "compile_ms": round(cons["compile_ms"], 3),
                "requests": cons["requests"],
                "batches": cons["batches"]},
            "sampler": {
                "kept_traces": acct["kept_traces"],
                "kept_spans": acct["kept_spans"],
                "dropped_traces": acct["dropped_traces"],
                "dropped_spans": acct["dropped_spans"],
                "keep_reasons": acct["keep_reasons"],
                "bytes_written": acct["bytes_written"]},
            "exemplar_trace_captured": captured,
        }
        return {"series": "BENCH_SERVING", "round": 3,
                "backend": jax.default_backend(),
                "devices": len(jax.devices()),
                "slo": record}
    finally:
        faultinject.set_plan(None)
        client.close()
        server.stop(drain=False)
        registry.shutdown()
        disable_tracing()
        sampler.close()


def run_slo_check(committed_path):
    """Deterministic SLO/cost oracles for the smoke tier: the committed
    r03 record carries the schema and its invariants hold, and a fresh
    in-process run reproduces every one of them — fire-once/resolve
    choreography, cost conservation with zero steady-state compiles,
    tail-sampler keeps AND drops, exemplar-to-trace retrievability.
    Latency/throughput numbers are deliberately not gated."""
    failures = []
    with open(committed_path) as f:
        committed = json.load(f)
    if committed.get("series") != "BENCH_SERVING":
        failures.append(f"{committed_path}: series != BENCH_SERVING")
    rec = committed.get("slo")
    if not isinstance(rec, dict):
        failures.append(f"{committed_path}: no 'slo' record")
        rec = {}
    for key in SLO_SCHEMA_KEYS:
        if key not in rec:
            failures.append(f"{committed_path}: slo missing {key!r}")

    def _gate(r, where):
        out = []
        if r.get("alert_states") != ["firing", "resolved"]:
            out.append(f"{where}: burn alert did not fire exactly once "
                       f"and resolve (states {r.get('alert_states')})")
        if r.get("compliance", {}).get("met") is not False:
            out.append(f"{where}: sub-bucket threshold did not violate "
                       f"compliance")
        if not r.get("burn", {}).get("active", False):
            out.append(f"{where}: burn windows never went active")
        if not r.get("cost", {}).get("conservation_ok", False):
            out.append(f"{where}: cost ledger conservation broken "
                       f"(error {r.get('cost', {}).get('error_ms')} ms)")
        if r.get("cost", {}).get("requests", 0) < 1:
            out.append(f"{where}: ledger attributed no requests")
        if r.get("steady_state_compiles", 1) != 0:
            out.append(f"{where}: compiles leaked into measured traffic "
                       f"(compile exclusion untestable)")
        if r.get("sampler", {}).get("kept_traces", 0) < 1:
            out.append(f"{where}: tail sampler kept nothing (the stall "
                       f"trace must earn its keep)")
        if r.get("sampler", {}).get("dropped_traces", 0) < 1:
            out.append(f"{where}: tail sampler dropped nothing (it is "
                       f"not sampling)")
        if not r.get("exemplar_trace_captured", False):
            out.append(f"{where}: tail-bucket exemplar's trace not "
                       f"retrievable from the capture bundle")
        return out

    failures += _gate(rec, committed_path)
    fresh = run_slo()["slo"]
    failures += _gate(fresh, "live slo run")

    if failures:
        for f_ in failures:
            print(f"CHECK FAIL: {f_}", file=sys.stderr)
        return 1
    print(f"bench_serving slo check OK against {committed_path} "
          f"(fired once + resolved, conservation error "
          f"{fresh['cost']['error_ms']} ms, "
          f"{fresh['sampler']['kept_traces']} trace(s) kept / "
          f"{fresh['sampler']['dropped_traces']} dropped, "
          f"exemplar trace captured)")
    return 0


# -------------------------------------------------------------------- --check
def run_check(committed_path):
    """Deterministic regression oracles, cheap enough for the smoke tier:

    1. the committed series file parses and carries the full schema;
    2. a tiny model registered with warmup covers every declared bucket;
    3. ZERO XLA compiles while steady-state traffic spans those buckets;
    4. the keep-alive client holds one connection across requests.

    Latency numbers are deliberately NOT gated — on shared CI they flake;
    a compile leaking into the hot path is the regression that matters.
    """
    failures = []
    with open(committed_path) as f:
        committed = json.load(f)
    if committed.get("series") != "BENCH_SERVING":
        failures.append(f"{committed_path}: series != BENCH_SERVING")
    for cname, crec in committed.get("configs", {}).items():
        if "error" in crec:
            failures.append(f"{committed_path}: config {cname} recorded an "
                            f"error: {crec['error']}")
            continue
        for key in SCHEMA_CONFIG_KEYS:
            if key not in crec:
                failures.append(f"{committed_path}: {cname} missing {key!r}")
        if crec.get("steady_state_compiles", 1) != 0:
            failures.append(f"{committed_path}: {cname} recorded "
                            f"steady_state_compiles != 0")

    from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                            enable_tracing)
    from deeplearning4j_tpu.serving import ModelServingClient
    tracer = enable_tracing(Tracer())
    try:
        buckets = [2, 4]
        registry, server, client = _stack(_tiny(), buckets, warmup="sync")
        try:
            state = registry.warmup_state("bench")
            if state["status"] != "warm" or state["warm"] != buckets:
                failures.append(f"warmup did not cover buckets: {state}")
            c0 = tracer.compile_count
            rng = np.random.default_rng(0)
            for rows in (1, 2, 3, 4, 1, 4):
                client.predict(
                    "bench", rng.normal(size=(rows, 8)).astype(np.float32),
                    binary=True)
            leaked = tracer.compile_count - c0
            if leaked:
                failures.append(
                    f"{leaked} XLA compile(s) leaked into steady-state "
                    f"serving across declared buckets")
            conn = client._connection()
            client.predict("bench", np.zeros((1, 8), np.float32),
                           binary=True)
            if client._connection() is not conn:
                failures.append("keep-alive client did not reuse its "
                                "connection")
            assert isinstance(client, ModelServingClient)
        finally:
            _teardown(registry, server, client)
    finally:
        disable_tracing()

    if failures:
        for f_ in failures:
            print(f"CHECK FAIL: {f_}", file=sys.stderr)
        return 1
    print(f"bench_serving check OK against {committed_path} "
          f"(warm buckets, zero steady-state compiles, keep-alive)")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(prog="bench_serving.py")
    p.add_argument("--check", metavar="BENCH_SERVING_rNN.json", default=None,
                   help="regression mode: verify the committed series file "
                        "and its deterministic invariants (fast path for "
                        "r01-style records, chaos/recovery for r02, "
                        "SLO/cost plane for r03)")
    p.add_argument("--chaos", action="store_true",
                   help="record the chaos/recovery series (breaker trip, "
                        "failover, restart, availability under fault) "
                        "instead of the latency suite")
    p.add_argument("--slo", action="store_true",
                   help="record the request-cost & SLO series (burn-rate "
                        "fire/resolve, cost-ledger conservation, tail "
                        "sampling, exemplar retrievability) instead of "
                        "the latency suite")
    p.add_argument("--out", default=None,
                   help="also write the JSON record here")
    args = p.parse_args(argv)
    from deeplearning4j_tpu.util.compile_cache import (
        enable_persistent_compile_cache)
    enable_persistent_compile_cache()
    if args.check:
        with open(args.check) as f:
            committed = json.load(f)
        if "chaos" in committed:
            return run_chaos_check(args.check)
        if "slo" in committed:
            return run_slo_check(args.check)
        return run_check(args.check)
    if args.slo:
        record = run_slo()
    elif args.chaos:
        record = run_chaos()
    else:
        record = run_full()
    line = json.dumps(record)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
