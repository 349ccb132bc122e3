"""Serving fast path (round 9): AOT bucket warmup, persistent compile
cache, int8 quantized inference, keep-alive client, dispatcher hot path.

The load-bearing oracle is the ``observe/jaxhook.py`` compile counter: a
fresh ``Tracer`` counts ``/jax/core/compile/backend_compile_duration``
events process-wide, so "zero XLA compiles during steady-state serving"
and "exactly one compile per bucket at registration" are directly
assertable — no timing, no flakes.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observe import Tracer, disable_tracing, enable_tracing
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.serving import (MetricsRegistry, ModelRegistry,
                                        ModelServer, ModelServingClient,
                                        QuantizedModel, ServingError,
                                        quantize_model)
from deeplearning4j_tpu.serving.quantize import (QTensor, calibrate,
                                                 param_nbytes,
                                                 quantize_array)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_net(seed=7, n_in=12, n_out=4):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list()
            .layer(DenseLayer(n_in=n_in, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=n_out, activation="softmax",
                               loss="negativeloglikelihood"))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture
def tracer():
    """A fresh tracer purely for its process-wide compile counter."""
    t = enable_tracing(Tracer())
    yield t
    disable_tracing()


class _GateModel:
    """Blocks in ``output`` until released; used to hold warmup open so the
    cold-bucket readiness window is deterministic. Carries a fake ``conf``-
    free surface, so the row spec must come from ``input_shape=``."""

    def __init__(self, n_out=2):
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.n_out = n_out

    def output(self, x):
        self.entered.set()
        assert self.gate.wait(10.0), "test forgot to release the gate"
        x = np.asarray(x)
        return np.zeros((x.shape[0], self.n_out), np.float32)


# ------------------------------------------------------------------- buckets
class TestDeclaredBuckets:
    def test_default_buckets_are_pow2_to_max(self):
        pi = ParallelInference(small_net(), max_batch_size=32)
        try:
            assert pi.buckets == (1, 2, 4, 8, 16, 32)
        finally:
            pi.shutdown()

    def test_explicit_buckets_and_fallback(self):
        pi = ParallelInference(small_net(), max_batch_size=32,
                               buckets=[4, 16])
        try:
            assert pi.buckets == (4, 16)
            assert pi._bucket_for(1) == (4, True)
            assert pi._bucket_for(4) == (4, True)
            assert pi._bucket_for(5) == (16, True)
            # beyond every declared bucket: pow2 fallback, flagged cold
            assert pi._bucket_for(17) == (32, False)
        finally:
            pi.shutdown()

    def test_mesh_rounds_buckets_to_data_axis(self):
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        mesh = make_mesh()
        d = mesh.shape.get("data", 1)
        pi = ParallelInference(small_net(), max_batch_size=8, mesh=mesh,
                               buckets=[1, 2, 8])
        try:
            assert all(b % d == 0 for b in pi.buckets)
        finally:
            pi.shutdown()

    def test_bad_buckets_rejected(self):
        with pytest.raises(ValueError):
            ParallelInference(small_net(), buckets=[])
        with pytest.raises(ValueError):
            ParallelInference(small_net(), buckets=[0, 4])

    def test_coalescing_never_exceeds_largest_bucket(self, rng):
        """Two 12-row requests against buckets=[16] must dispatch as two
        16-padded batches, not one cold 32-batch (the carry path)."""
        seen = []

        class Spy:
            def output(self, x):
                x = np.asarray(x)
                seen.append(x.shape[0])
                return x[:, :4]

        pi = ParallelInference(Spy(), max_batch_size=16, buckets=[16],
                               wait_ms=50.0)
        try:
            xs = rng.normal(size=(12, 12)).astype(np.float32)
            results = []
            ts = [threading.Thread(
                target=lambda: results.append(pi.output(xs)))
                for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert len(results) == 2
            assert all(r.shape == (12, 4) for r in results)
            assert seen and all(s == 16 for s in seen)
        finally:
            pi.shutdown()

    def test_cold_counter_catches_unwarmed_dtype(self, rng):
        """A declared bucket hit with a dtype warmup never executed is a
        new jit signature → must count as a cold dispatch."""
        from deeplearning4j_tpu.serving import MetricsRegistry, \
            parse_prometheus_text
        metrics = MetricsRegistry()
        pi = ParallelInference(small_net(), max_batch_size=4, buckets=[4],
                               wait_ms=0.0, metrics=metrics,
                               metrics_name="m")
        try:
            pi.warmup((12,), dtype=np.float32)

            def cold_count():
                parsed = parse_prometheus_text(metrics.exposition())
                series = parsed.get("inference_cold_dispatches_total", {})
                return series.get((("model", "m"),), 0)

            pi.output(rng.normal(size=(2, 12)).astype(np.float32))
            assert cold_count() == 0
            pi.output(rng.integers(0, 2, size=(2, 12)).astype(np.int32))
            assert cold_count() == 1
        finally:
            pi.shutdown()

    def test_cold_counter_catches_update_model_without_warmup(self, rng):
        """update_model() publishes a model whose jit cache is cold — its
        first dispatches must count cold even though the OLD model's
        signatures were warmed (warm state cannot cross a swap)."""
        from deeplearning4j_tpu.serving import MetricsRegistry, \
            parse_prometheus_text
        metrics = MetricsRegistry()
        pi = ParallelInference(small_net(seed=1), max_batch_size=4,
                               buckets=[4], wait_ms=0.0, metrics=metrics,
                               metrics_name="m")
        try:
            pi.warmup((12,), dtype=np.float32)

            def cold_count():
                parsed = parse_prometheus_text(metrics.exposition())
                series = parsed.get("inference_cold_dispatches_total", {})
                return series.get((("model", "m"),), 0)

            x = rng.normal(size=(2, 12)).astype(np.float32)
            pi.output(x)
            assert cold_count() == 0
            pi.update_model(small_net(seed=2))  # never warmed
            pi.output(x)
            assert cold_count() == 1
            pi.warmup((12,), dtype=np.float32)  # re-warm the new model
            pi.output(x)
            assert cold_count() == 1
        finally:
            pi.shutdown()

    def test_pad_buffer_reused_and_zeroed(self, rng):
        """Same bucket twice → one buffer; the second batch's tail must not
        contain the first batch's rows."""
        captured = []

        class Capture:
            def output(self, x):
                captured.append(np.asarray(x).copy())
                return np.asarray(x)[:, :2]

        pi = ParallelInference(Capture(), max_batch_size=8, buckets=[8],
                               wait_ms=0.0)
        try:
            a = np.full((6, 3), 7.0, np.float32)
            b = np.full((2, 3), 3.0, np.float32)
            pi.output(a)
            pi.output(b)
            assert len(pi._pad_buffers) == 1
            second = captured[1]
            assert np.all(second[:2] == 3.0)
            assert np.all(second[2:] == 0.0)  # rows of `a` fully cleared
        finally:
            pi.shutdown()

    def test_pad_buffer_cache_is_bounded(self):
        """Clients pick row shape/dtype on the binary path — the per-
        signature buffers must not grow without bound."""

        class Echo:
            def output(self, x):
                return np.asarray(x)

        pi = ParallelInference(Echo(), max_batch_size=4, buckets=[4],
                               wait_ms=0.0)
        try:
            cap = pi._max_pad_buffers
            for width in range(1, cap + 8):  # each width = a new signature
                pi.output(np.zeros((2, width), np.float32))
            assert len(pi._pad_buffers) <= cap
        finally:
            pi.shutdown()


# -------------------------------------------------------------- AOT warmup
class TestWarmupCompiles:
    def test_zero_compiles_steady_state_and_exact_per_bucket(self, tracer,
                                                             rng):
        """THE acceptance oracle: (a) registration warms every declared
        bucket; (b) a SECOND identical-architecture registration pays
        exactly one XLA compile per bucket (utility kernels are process-
        warm by then); (c) steady-state traffic spanning the buckets pays
        ZERO."""
        buckets = [4, 8]
        metrics = MetricsRegistry()
        registry = ModelRegistry(metrics=metrics, buckets=buckets,
                                 warmup="sync")
        try:
            registry.register("a", small_net(1))  # utility kernels warm now
            c0 = tracer.compile_count
            registry.register("b", small_net(2))
            per_bucket = tracer.compile_count - c0
            assert per_bucket == len(buckets), \
                f"expected one compile per bucket, saw {per_bucket}"
            state = registry.warmup_state("b")
            assert state["status"] == "warm"
            assert state["warm"] == buckets
            # steady state: every reachable batch size, repeatedly
            c1 = tracer.compile_count
            for rows in (1, 2, 3, 4, 5, 8, 7, 1, 8):
                out = registry.predict(
                    "b", rng.normal(size=(rows, 12)).astype(np.float32))
                assert out.shape == (rows, 4)
            assert tracer.compile_count == c1, \
                "XLA compile leaked into steady-state serving"
        finally:
            registry.shutdown()

    def test_hot_swap_keeps_warm(self, tracer, rng):
        """v2 is warmed at ITS registration; activating it must not compile
        anything, and serving v2 stays compile-free."""
        registry = ModelRegistry(buckets=[4], warmup="sync")
        try:
            registry.register("m", small_net(1))
            registry.register("m", small_net(2), activate=False)
            c0 = tracer.compile_count
            registry.activate("m", 2)
            for _ in range(3):
                registry.predict(
                    "m", rng.normal(size=(3, 12)).astype(np.float32))
            assert tracer.compile_count == c0
            # and rollback lands on the still-warm v1
            registry.rollback("m")
            registry.predict("m",
                             rng.normal(size=(2, 12)).astype(np.float32))
            assert tracer.compile_count == c0
        finally:
            registry.shutdown()

    def test_rewarm_is_idempotent(self, tracer):
        """Warming an already-warm model compiles nothing — proof the
        warmup path is byte-identical to the dispatch path."""
        registry = ModelRegistry(buckets=[2, 4], warmup="sync")
        try:
            registry.register("m", small_net())
            served = registry.get("m")
            c0 = tracer.compile_count
            served.inference.warmup((12,))
            assert tracer.compile_count == c0
        finally:
            registry.shutdown()

    def test_warmup_metrics_exported(self):
        metrics = MetricsRegistry()
        registry = ModelRegistry(metrics=metrics, buckets=[2, 4],
                                 warmup="sync")
        try:
            registry.register("m", small_net())
            from deeplearning4j_tpu.serving import parse_prometheus_text
            parsed = parse_prometheus_text(metrics.exposition())
            assert parsed["serving_buckets_warm"][(("model", "m"),)] == 2
            assert parsed["serving_warmup_seconds"][(("model", "m"),)] > 0
        finally:
            registry.shutdown()

    def test_stub_without_spec_skips_warmup_and_stays_ready(self):
        registry = ModelRegistry(warmup="sync")
        server = ModelServer(registry)
        server.start()
        try:
            gate = _GateModel()
            gate.gate.set()  # never blocks: warmup is skipped entirely
            registry.register("stub", gate)
            state = registry.warmup_state("stub")
            assert state["status"] == "skipped"
            assert "input spec" in state["reason"]
            ready, body = server.readiness_detail()
            assert ready and body["reason"] == "ok"
        finally:
            server.stop(drain=False)
            registry.shutdown()

    def test_warmup_off_restores_lazy_behavior(self):
        registry = ModelRegistry(warmup="off", buckets=[2])
        try:
            registry.register("m", small_net())
            assert registry.warmup_state("m")["status"] == "skipped"
            assert registry.warmed()  # off == no readiness gate
        finally:
            registry.shutdown()

    def test_warmup_failure_is_contained(self):
        """A model whose forward raises records an error state instead of
        killing registration; /readyz lists its buckets as cold AND names
        the failure so an operator can tell it from a running warmup."""

        class Boom:
            def output(self, x):
                raise RuntimeError("kaboom")

        registry = ModelRegistry(warmup="sync", buckets=[2])
        try:
            registry.register("bad", Boom(), input_shape=(3,))
            state = registry.warmup_state("bad")
            assert state["status"] == "error"
            assert "kaboom" in state["reason"]
            assert registry.cold_buckets() == {"bad": [2]}
            assert "kaboom" in registry.warmup_errors()["bad"]
            ready, body = ModelServer(registry).readiness_detail()
            assert ready is False
            assert "kaboom" in body["warmup_errors"]["bad"]
        finally:
            registry.shutdown()

    def test_rewarm_recovers_failed_warmup(self):
        """rewarm() is the no-restart recovery path: a transient failure
        at registration-time warmup must be repairable in-process."""

        class FlakyOnce:
            def __init__(self):
                self.calls = 0

            def output(self, x):
                self.calls += 1
                if self.calls == 1:
                    raise RuntimeError("transient device hiccup")
                return np.asarray(x)[:, :1]

        registry = ModelRegistry(warmup="sync", buckets=[2])
        try:
            registry.register("flaky", FlakyOnce(), input_shape=(3,))
            assert registry.warmup_state("flaky")["status"] == "error"
            assert not registry.warmed()
            registry.rewarm("flaky")
            assert registry.warmup_state("flaky")["status"] == "warm"
            assert registry.warmed()
            assert registry.warmup_errors() == {}
        finally:
            registry.shutdown()

    def test_float64_sample_input_warms_the_float32_wire_dtype(self):
        """np.random defaults to float64, but requests arrive float32
        (JSON parse); warming '<f8' would leave every live dispatch
        falsely counted cold."""
        registry = ModelRegistry(warmup="sync", buckets=[2])
        try:
            spec = registry._resolve_row_spec(
                small_net(), None, np.random.default_rng(0).normal(
                    size=(4, 12)))  # float64 sample
            assert spec == ((12,), np.float32)
        finally:
            registry.shutdown()

    def test_async_activate_defers_hot_swap_until_warm(self):
        """Registering v2 with warmup='async' must NOT swap live traffic
        onto the still-cold version — activation happens when its warmup
        completes."""
        gate = _GateModel()
        registry = ModelRegistry(warmup="async", buckets=[2])
        try:
            registry.register("m", small_net(seed=1))
            deadline = time.monotonic() + 10.0
            while (time.monotonic() < deadline
                   and registry.warmup_state("m")["status"] != "warm"):
                time.sleep(0.02)
            assert registry.warmup_state("m")["status"] == "warm"
            v2 = registry.register("m", gate, input_shape=(5,))
            assert gate.entered.wait(5.0)  # v2 warmup underway...
            assert registry.get("m").describe()["current_version"] == 1
            gate.gate.set()
            deadline = time.monotonic() + 10.0
            while (time.monotonic() < deadline
                   and registry.get("m").describe()["current_version"] != v2):
                time.sleep(0.02)
            assert registry.get("m").describe()["current_version"] == v2
            assert registry.warmup_state("m", v2)["status"] == "warm"
        finally:
            gate.gate.set()
            registry.shutdown()


# ------------------------------------------------------- readiness & async
class TestReadyzColdBuckets:
    def test_readyz_503_lists_cold_buckets_until_warm(self):
        """Async warmup held open by a gate: /readyz must answer 503 with
        the cold bucket list, then flip to 200 when warmup finishes."""
        gate = _GateModel()
        registry = ModelRegistry(warmup="async", buckets=[2, 4])
        server = ModelServer(registry)
        server.start()
        client = ModelServingClient(server.url)
        try:
            registry.register("g", gate, input_shape=(5,))
            assert gate.entered.wait(5.0)  # warmup thread is inside bucket 1
            with pytest.raises(ServingError) as ei:
                client._request("/readyz")
            assert ei.value.status == 503
            body = json.loads(ei.value.message or "{}") \
                if ei.value.message.startswith("{") else None
            # the client surfaces .message from the "error" key only; go
            # to the wire for the full body
            import urllib.request
            try:
                urllib.request.urlopen(server.url + "/readyz", timeout=5)
                pytest.fail("expected 503")
            except urllib.error.HTTPError as e:
                payload = json.loads(e.read().decode())
            assert payload["ready"] is False
            assert payload["reason"] == "warmup incomplete"
            assert payload["cold_buckets"]["g"], payload
            gate.gate.set()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if client.ready():
                    break
                time.sleep(0.02)
            assert client.ready()
            assert registry.warmup_state("g")["status"] == "warm"
            assert registry.cold_buckets() == {}
        finally:
            gate.gate.set()
            client.close()
            server.stop(drain=False)
            registry.shutdown()


# ------------------------------------------------------------- quantization
class TestInt8Inference:
    def test_quantize_array_shapes_and_passthrough(self, rng):
        w = rng.normal(size=(32, 16)).astype(np.float32)
        qt = quantize_array(w)
        assert isinstance(qt, QTensor)
        assert np.asarray(qt.q).dtype == np.int8
        assert np.asarray(qt.scale).shape == (1, 16)  # per output channel
        # reconstruction error bounded by half a quantization step
        back = np.asarray(qt.dequantize())
        step = np.asarray(qt.scale)
        assert np.all(np.abs(back - w) <= step / 2 + 1e-7)
        # tiny and 1-d leaves pass through untouched
        b = rng.normal(size=(16,)).astype(np.float32)
        assert quantize_array(b) is b

    def test_int8_output_tolerance_vs_float32(self, rng):
        net = small_net(3, n_in=24, n_out=6)
        q = quantize_model(net, "int8")
        x = rng.normal(size=(8, 24)).astype(np.float32)
        stats = calibrate(net, q, x)
        # softmax outputs: int8 weight error stays in the third decimal
        assert stats["max_abs_err"] < 0.05
        assert stats["rel_err"] < 0.05
        got = np.asarray(q.output(x))
        ref = np.asarray(net.output(x))
        np.testing.assert_allclose(got, ref, atol=0.05)

    def test_bf16_policy(self, rng):
        net = small_net(4)
        q = quantize_model(net, "bf16")
        x = rng.normal(size=(4, 12)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(q.output(x)),
                                   np.asarray(net.output(x)), atol=0.05)
        assert q.param_nbytes < param_nbytes(net.params)

    def test_float32_policy_is_identity(self):
        net = small_net()
        assert quantize_model(net, "float32") is net
        assert quantize_model(net, None) is net

    def test_path_loaded_int8_releases_float_params(self, tmp_path, rng):
        """A registry-owned checkpoint load must not pin a full float
        param copy next to the quantized one; a live-object registration
        must (the caller may still train it)."""
        from deeplearning4j_tpu.util.model_serializer import write_model
        net = small_net(seed=9)
        zip_path = tmp_path / "m.zip"
        write_model(net, zip_path)
        registry = ModelRegistry(warmup="sync", buckets=[2])
        try:
            registry.register("frompath", path=str(zip_path),
                              dtype_policy="int8")
            q = registry.get("frompath").versions[1].model
            assert isinstance(q, QuantizedModel)
            assert q.base.params is None  # float copy released
            x = rng.normal(size=(2, 12)).astype(np.float32)
            assert np.asarray(q.output(x)).shape == (2, 4)  # still serves
            registry.register("live", net, dtype_policy="int8")
            live = registry.get("live").versions[1].model
            assert live.base.params is not None  # caller's object untouched
        finally:
            registry.shutdown()

    def test_graph_model_quantizes(self, rng):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = (NeuralNetConfiguration.builder().seed(5).graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=10, n_out=32,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=32, n_out=3,
                                              activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out").build())
        g = ComputationGraph(conf).init()
        q = quantize_model(g, "int8")
        x = rng.normal(size=(6, 10)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(q.output(x)),
                                   np.asarray(g.output(x)), atol=0.05)

    def test_registry_serves_int8_version_with_metadata(self, rng):
        registry = ModelRegistry(buckets=[4], warmup="sync")
        server = ModelServer(registry)
        server.start()
        client = ModelServingClient(server.url)
        try:
            net = small_net(6)
            sample = rng.normal(size=(4, 12)).astype(np.float32)
            v = registry.register("m", net, dtype_policy="int8",
                                  sample_input=sample)
            served = registry.get("m")
            mv = served.versions[v]
            assert isinstance(mv.model, QuantizedModel)
            assert mv.dtype_policy == "int8"
            assert mv.quant_error["rel_err"] < 0.05
            desc = client.model("m")
            vd = desc["versions"][-1]
            assert vd["dtype_policy"] == "int8"
            assert "quant_error" in vd
            out = client.predict("m", sample)
            np.testing.assert_allclose(
                out, np.asarray(net.output(sample)), atol=0.05)
        finally:
            client.close()
            server.stop(drain=False)
            registry.shutdown()

    def test_quant_tolerance_rejects_at_registration(self, rng):
        registry = ModelRegistry(warmup="off")
        try:
            with pytest.raises(ValueError, match="tolerance"):
                registry.register(
                    "m", small_net(8), dtype_policy="int8",
                    sample_input=rng.normal(size=(4, 12)).astype(np.float32),
                    quant_tolerance=1e-9)
            assert not registry.has("m")
        finally:
            registry.shutdown()

    def test_unknown_policy_rejected(self):
        registry = ModelRegistry(warmup="off")
        try:
            with pytest.raises(ValueError, match="dtype_policy"):
                registry.register("m", small_net(), dtype_policy="fp4")
        finally:
            registry.shutdown()


# -------------------------------------------------------- persistent cache
class TestPersistentCompileCache:
    @pytest.fixture(autouse=True)
    def _explicit_dir_only(self, monkeypatch):
        """These tests place the cache by argument. A cache the environment
        placed would (rightly) refuse that, so the variable goes, and with
        it whatever cache jax already opened under it. The cache a test
        opens is closed after it: left open, every later compile in the
        process would write to it, and a tracer's exact counts would gain
        ``compile_cache.misses``."""
        import jax
        from jax._src import compilation_cache as jax_cc

        from deeplearning4j_tpu.util import compile_cache
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        before = {name: getattr(jax.config, name) for name in names}
        monkeypatch.setattr(compile_cache, "_enabled_dir",
                            compile_cache._enabled_dir)
        if os.environ.get(compile_cache.ENV_VAR):
            monkeypatch.delenv(compile_cache.ENV_VAR)
            compile_cache._enabled_dir = None
            jax.config.update("jax_compilation_cache_dir", None)
            jax_cc.reset_cache()
        yield
        for name, value in before.items():
            jax.config.update(name, value)
        jax_cc.reset_cache()

    def test_registry_populates_cache_dir(self, tmp_path):
        cache = tmp_path / "xla-cache"
        registry = ModelRegistry(buckets=[2], warmup="sync",
                                 compile_cache_dir=str(cache))
        try:
            registry.register("m", small_net())
            files = list(cache.iterdir())
            assert files, "warmup wrote nothing into the compile cache"
        finally:
            registry.shutdown()

    def test_retarget_rejected(self, tmp_path):
        from deeplearning4j_tpu.util.compile_cache import (
            enable_persistent_compile_cache, persistent_compile_cache_dir)
        enable_persistent_compile_cache(
            persistent_compile_cache_dir() or str(tmp_path / "first"))
        with pytest.raises(ValueError, match="already active"):
            enable_persistent_compile_cache(str(tmp_path / "elsewhere"))


# ------------------------------------------------------- keep-alive client
class TestClientKeepAlive:
    def test_connection_reused_across_predicts(self, rng):
        registry = ModelRegistry(buckets=[4], warmup="sync")
        server = ModelServer(registry)
        server.start()
        client = ModelServingClient(server.url)
        try:
            registry.register("m", small_net())
            client.predict("m", rng.normal(size=(2, 12)).astype(np.float32))
            conn = client._connection()
            sock = conn.sock
            assert sock is not None  # still open after the response
            for _ in range(3):
                client.predict("m",
                               rng.normal(size=(1, 12)).astype(np.float32))
            assert client._connection() is conn
            assert client._connection().sock is sock
        finally:
            client.close()
            assert client._connection().sock is None or True
            server.stop(drain=False)
            registry.shutdown()

    def test_connection_survives_error_responses(self, rng):
        """4xx must not poison the persistent connection (body drained)."""
        registry = ModelRegistry(buckets=[4], warmup="sync")
        server = ModelServer(registry)
        server.start()
        client = ModelServingClient(server.url)
        try:
            registry.register("m", small_net())
            with pytest.raises(ServingError) as ei:
                client.predict("nope", [[0.0] * 12])
            assert ei.value.status == 404
            conn = client._connection()
            out = client.predict("m", rng.normal(size=(2, 12))
                                 .astype(np.float32))
            assert out.shape == (2, 4)
            assert client._connection() is conn
        finally:
            client.close()
            server.stop(drain=False)
            registry.shutdown()

    def test_reconnects_after_server_restart(self, rng):
        """A server bounce (new listener, same port) looks like a dropped
        keep-alive connection; the client must reconnect transparently."""
        registry = ModelRegistry(buckets=[2], warmup="sync")
        server = ModelServer(registry)
        port = server.start()
        client = ModelServingClient(server.url)
        try:
            registry.register("m", small_net())
            client.predict("m", rng.normal(size=(1, 12)).astype(np.float32))
            server.stop(drain=False)
            server2 = ModelServer(registry, port=port)
            server2.start()
            try:
                out = client.predict(
                    "m", rng.normal(size=(1, 12)).astype(np.float32))
                assert out.shape == (1, 4)
            finally:
                server2.stop(drain=False)
        finally:
            client.close()
            registry.shutdown()


# ------------------------------------------------------------ bench --check
@pytest.mark.smoke
class TestBenchServingCheck:
    def test_check_mode_passes_against_committed_series(self):
        """The regression harness itself is exercised every run: tiny
        model, 2 buckets, deterministic oracles (schema, warm coverage,
        zero steady-state compiles, keep-alive)."""
        committed = os.path.join(REPO_ROOT, "BENCH_SERVING_r01.json")
        assert os.path.exists(committed), \
            "BENCH_SERVING_r01.json must be committed with the series"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "bench_serving.py"),
             "--check", committed],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, \
            f"stdout: {proc.stdout}\nstderr: {proc.stderr}"
        assert "check OK" in proc.stdout

    def test_committed_series_records_acceptance_numbers(self):
        """The acceptance criteria live in the committed JSON: warm p99 and
        cold first-request latency for at least two model configs."""
        with open(os.path.join(REPO_ROOT, "BENCH_SERVING_r01.json")) as f:
            rec = json.load(f)
        assert rec["series"] == "BENCH_SERVING"
        ok = [c for c in rec["configs"].values()
              if "error" not in c
              and c["closed_loop"].get("p99_ms") is not None
              and c["cold_first_request_ms"] > 0
              and c["warm_first_request_ms"] > 0
              and c["steady_state_compiles"] == 0]
        assert len(ok) >= 2, "need >= 2 clean configs in the series"
