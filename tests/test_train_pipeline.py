"""Zero-stall training input pipeline tests: fit() auto-prefetch
(AsyncDataSetIterator + device-put stage), the transfer/host-wait
observability, and the donated-buffer audit of the fused train step.

Models the reference's async-ETL contract (MultiLayerNetwork.java:1262-1267
wraps fit iterators in AsyncDataSetIterator unless the source carries
asyncSupported() == false) plus this framework's observe conventions.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import (DataSet, ListDataSetIterator,
                                                 batch_nbytes)
from deeplearning4j_tpu.datasets.iterators import (AsyncDataSetIterator,
                                                   AsyncShieldDataSetIterator,
                                                   DefaultCallback,
                                                   device_put_batch,
                                                   wrap_for_prefetch)
from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    helpers.clear_all_helpers()
    yield
    helpers.clear_all_helpers()


def _net(seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=24, activation="relu"))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(12)).build())
    return MultiLayerNetwork(conf).init()


def _dataset(rng, b=64):
    x = rng.normal(size=(b, 12)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, size=b)]
    return DataSet(x, y)


class TestWrapForPrefetch:
    def test_plain_iterator_wrapped_and_batches_preserved(self, rng):
        it = ListDataSetIterator(_dataset(rng), batch_size=16)
        base = [np.asarray(ds.features) for ds in it]
        wrapped = wrap_for_prefetch(it, 2)
        assert isinstance(wrapped, AsyncDataSetIterator)
        got = list(wrapped)
        assert len(got) == len(base)
        for ref, ds in zip(base, got):
            # the device-put stage ran in the producer thread
            assert isinstance(ds.features, jax.Array)
            np.testing.assert_array_equal(np.asarray(ds.features), ref)

    def test_depth_none_defaults_on_zero_disables(self, rng):
        it = ListDataSetIterator(_dataset(rng), batch_size=16)
        assert isinstance(wrap_for_prefetch(it, None), AsyncDataSetIterator)
        assert wrap_for_prefetch(it, 0) is it

    def test_async_shield_never_wrapped(self, rng):
        shield = AsyncShieldDataSetIterator(
            ListDataSetIterator(_dataset(rng), batch_size=16))
        assert wrap_for_prefetch(shield, 2) is shield

    def test_existing_async_iterator_kept(self, rng):
        it = AsyncDataSetIterator(
            ListDataSetIterator(_dataset(rng), batch_size=16), queue_size=4)
        assert wrap_for_prefetch(it, 2) is it

    def test_single_batch_list_not_wrapped(self, rng):
        src = [_dataset(rng, b=8)]
        assert wrap_for_prefetch(src, 2) is src
        multi = [_dataset(rng, b=8), _dataset(rng, b=8)]
        assert isinstance(wrap_for_prefetch(multi, 2), AsyncDataSetIterator)

    def test_device_put_batch_moves_masks_too(self, rng):
        b, t = 4, 6
        ds = DataSet(rng.normal(size=(b, t, 3)).astype(np.float32),
                     rng.normal(size=(b, t, 2)).astype(np.float32),
                     np.ones((b, t), np.float32), np.ones((b, t), np.float32))
        out = device_put_batch(ds)
        assert out is ds
        for a in (ds.features, ds.labels, ds.features_mask, ds.labels_mask):
            assert isinstance(a, jax.Array)


class TestDefaultCallbackMasks:
    def test_masks_device_put_alongside_features(self, rng):
        """Regression: DefaultCallback used to ship features/labels but DROP
        the masks, so masked RNN batches re-transferred their masks on the
        training thread every step."""
        b, t = 4, 6
        ds = DataSet(rng.normal(size=(b, t, 3)).astype(np.float32),
                     rng.normal(size=(b, t, 2)).astype(np.float32),
                     np.ones((b, t), np.float32), np.ones((b, t), np.float32))
        DefaultCallback().call(ds)
        for a in (ds.features, ds.labels, ds.features_mask, ds.labels_mask):
            assert isinstance(a, jax.Array)


class TestFitPrefetch:
    def test_mln_fit_with_prefetch_trains_and_counts_transfer(self, rng):
        net = _net()
        data = _dataset(rng)
        it = ListDataSetIterator(data, batch_size=16)
        expected = sum(batch_nbytes(ds) for ds in it)
        before = net.transfer_bytes
        net.fit(it, epochs=2, prefetch_depth=2)
        assert net.iteration == 8  # 4 batches x 2 epochs
        assert net.transfer_bytes - before == 2 * expected

    def test_mln_fit_prefetch_matches_plain_path(self, rng):
        """Prefetch is a scheduling change, not a numeric one: same data,
        same steps, bit-identical parameters either way."""
        data = _dataset(rng)
        a, b = _net(seed=9), _net(seed=9)
        a.fit(ListDataSetIterator(data, batch_size=16), epochs=1,
              prefetch_depth=0)
        b.fit(ListDataSetIterator(data, batch_size=16), epochs=1,
              prefetch_depth=2)
        for la, lb in zip(a.params, b.params):
            for k in la:
                np.testing.assert_array_equal(np.asarray(la[k]),
                                              np.asarray(lb[k]))

    def test_graph_fit_with_prefetch(self, rng):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = (NeuralNetConfiguration.builder().seed(2).updater(Adam(1e-2))
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=12, n_out=16,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=16, n_out=3), "d")
                .set_outputs("out")
                .build())
        g = ComputationGraph(conf).init()
        it = ListDataSetIterator(_dataset(rng), batch_size=16)
        g.fit(it, epochs=1, prefetch_depth=2)
        assert g.iteration == 4
        assert g.transfer_bytes > 0

    def test_parallel_wrapper_prefetch_passthrough(self, rng):
        from deeplearning4j_tpu.parallel import ParallelWrapper
        net = _net(seed=4)
        it = ListDataSetIterator(_dataset(rng), batch_size=16)
        ParallelWrapper(net).fit(it, epochs=1, prefetch_depth=1)
        assert net.iteration == 4

    def test_host_wait_span_and_transfer_metric_exported(self, rng):
        from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                                enable_tracing)
        from deeplearning4j_tpu.observe.listener import TraceListener
        from deeplearning4j_tpu.observe.metrics import MetricsRegistry

        net = _net(seed=3)
        it = ListDataSetIterator(_dataset(rng), batch_size=16)
        metrics = MetricsRegistry()
        tracer = enable_tracing(Tracer(metrics=metrics))
        net.listeners.append(TraceListener(tracer, metrics, model_name="m"))
        try:
            net.fit(it, epochs=1, prefetch_depth=2)
        finally:
            disable_tracing()
        waits = [s for s in tracer.recorder.spans() if s.name == "host_wait"]
        # one wait per batch plus the end-of-iterator probe
        assert len(waits) == 5
        counter = metrics.get("training_transfer_bytes_total")
        assert counter is not None
        assert counter.value(model="m") == net.transfer_bytes


def _train_step_args(net, ds):
    return (net.params, net.states, net.updater_states,
            jnp.float32(0.0), jnp.float32(0.0),
            jnp.asarray(np.asarray(ds.features)),
            jnp.asarray(np.asarray(ds.labels)),
            None, None, jax.random.PRNGKey(0), None)


class TestDonationAudit:
    """HLO audit: the train step must KEEP donating its param/updater-state
    buffers with the fused updater registered (in-place RMW is the point),
    and the inference path must donate nothing (serving reuses inputs)."""

    def test_train_step_keeps_donation_with_fused_updater(self, rng):
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        net = _net(seed=6)
        ds = _dataset(rng, b=16)
        helpers.set_helper("updater", PallasUpdaterHelper(interpret=True))
        fn = net._get_train_step(False)
        hlo = fn.lower(*_train_step_args(net, ds)).compile().as_text()
        assert "input_output_alias" in hlo

    def test_train_step_donates_on_stock_path_too(self, rng):
        net = _net(seed=6)
        ds = _dataset(rng, b=16)
        fn = net._get_train_step(False)
        hlo = fn.lower(*_train_step_args(net, ds)).compile().as_text()
        assert "input_output_alias" in hlo

    def test_predict_donates_nothing(self, rng):
        net = _net(seed=6)
        x = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
        fn = net._output_fn()
        hlo = fn.lower(net.params, net.states, x, None).compile().as_text()
        assert "input_output_alias" not in hlo


@pytest.mark.smoke
class TestBenchTrainPipelineCheck:
    """The committed BENCH_TRAIN series must keep passing its own --check
    (same pattern as bench_serving --check in the smoke tier)."""

    COMMITTED = os.path.join(REPO, "BENCH_TRAIN_r01.json")

    def test_committed_record_schema(self):
        with open(self.COMMITTED, encoding="utf-8") as fh:
            rec = json.load(fh)
        assert rec["metric"] == "train_pipeline"
        assert rec["series"] == "BENCH_TRAIN_r01"
        pre = rec["prefetch"]
        assert pre["on"]["wall_ms_per_step"] < pre["off"]["wall_ms_per_step"]
        assert pre["on"]["steady_state_compiles"] == 0
        assert pre["off"]["steady_state_compiles"] == 0
        fu = rec["fused_updater"]
        assert fu["max_abs_param_diff"] <= 2e-5
        assert fu["pallas_calls_in_train_step"] == fu["fusable_tensors"] > 0

    def test_check_passes(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--train-pipeline", "--check", self.COMMITTED],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=560)
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "train-pipeline check OK" in proc.stdout
