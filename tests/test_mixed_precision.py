"""Mixed-precision (f32 master weights, bf16 compute) tests."""

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import ConvolutionLayer, DenseLayer, OutputLayer, SubsamplingLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    y_idx = rng.integers(0, 3, n)
    x = rng.normal(size=(n, 10)).astype(np.float32)
    x[np.arange(n), y_idx] += 2.5
    return DataSet(x, np.eye(3, dtype=np.float32)[y_idx])


def _conf(compute_dtype):
    return (NeuralNetConfiguration.builder().seed(1)
            .compute_dtype(compute_dtype).list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(10)).build())


class TestMixedPrecision:
    def test_params_stay_f32_and_training_works(self):
        net = MultiLayerNetwork(_conf("bfloat16")).init()
        ds = _data()
        net.fit(ListDataSetIterator(ds, 128, shuffle=True), epochs=8)
        # master weights keep the storage dtype
        assert net.params[0]["W"].dtype == jnp.float32
        ev = net.evaluate(ListDataSetIterator(ds, 256))
        assert ev.accuracy() > 0.9

    def test_forward_activation_is_compute_dtype(self):
        net = MultiLayerNetwork(_conf("bfloat16")).init()
        x = jnp.zeros((4, 10), jnp.float32)
        h, _, _ = net._forward_all(net.params, net.states, x, train=False,
                                   rng=None, mask=None)
        assert h.dtype == jnp.bfloat16

    def test_matches_f32_training_approximately(self):
        ds = _data(256, seed=3)

        def train(cd):
            net = MultiLayerNetwork(_conf(cd)).init()
            net.fit(ListDataSetIterator(ds, 128, shuffle=True, seed=5), epochs=5)
            return net

        f32 = train(None)
        mixed = train("bfloat16")
        # same data/seed: losses land in the same regime
        assert abs(float(f32.score_) - float(mixed.score_)) < 0.15

    def test_graph_mixed_precision(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        g = (NeuralNetConfiguration.builder().seed(1)
             .compute_dtype("bfloat16").graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.feed_forward(10)))
        g.add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
        g.add_layer("out", OutputLayer(n_out=3), "d")
        conf = g.set_outputs("out").build()
        net = ComputationGraph(conf)
        net.init()
        ds = _data(256)
        net.fit(ListDataSetIterator(ds, 128), epochs=5)
        first = next(iter(net.params.values()))
        assert first["W"].dtype == jnp.float32
        assert float(net.score_) < 1.2


class TestGradientCheckpointing:
    def test_same_results_with_remat(self):
        """Remat changes memory, not math: training trajectories match."""
        ds = _data(256, seed=2)

        def train(remat):
            conf = (NeuralNetConfiguration.builder().seed(1)
                    .gradient_checkpointing(remat).list()
                    .layer(DenseLayer(n_out=32, activation="tanh"))
                    .layer(DenseLayer(n_out=32, activation="tanh"))
                    .layer(OutputLayer(n_out=3))
                    .set_input_type(InputType.feed_forward(10)).build())
            net = MultiLayerNetwork(conf).init()
            net.fit(ListDataSetIterator(ds, 128, shuffle=True, seed=3),
                    epochs=4)
            return net

        plain, remat = train(False), train(True)
        assert abs(float(plain.score_) - float(remat.score_)) < 1e-5
        for pl, pr in zip(plain.params, remat.params):
            for k in pl:
                np.testing.assert_allclose(np.asarray(pl[k]), np.asarray(pr[k]),
                                           rtol=1e-5, atol=1e-6)

    def test_remat_compiles_and_reports_memory(self):
        """Remat composes with the XLA memory analysis. (The buffer-assignment
        savings materialize on the TPU backend; the CPU scheduler may order
        the recompute clusters differently, so no inequality is asserted
        here.)"""
        from deeplearning4j_tpu.nn.conf import compiled_memory_analysis

        def analyze(remat):
            b = (NeuralNetConfiguration.builder().seed(1)
                 .gradient_checkpointing(remat).list())
            for _ in range(12):
                b.layer(DenseLayer(n_out=512, activation="tanh"))
            conf = (b.layer(OutputLayer(n_out=8))
                    .set_input_type(InputType.feed_forward(64)).build())
            net = MultiLayerNetwork(conf).init()
            return compiled_memory_analysis(net, batch=256)

        plain = analyze(False)
        remat = analyze(True)
        if not (plain and remat):
            import pytest
            pytest.skip("backend does not expose XLA memory analysis")
        assert plain["total"] > 0 and remat["total"] > 0

    def test_graph_remat(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        g = (NeuralNetConfiguration.builder().seed(1)
             .gradient_checkpointing(True).graph_builder()
             .add_inputs("in").set_input_types(InputType.feed_forward(10)))
        g.add_layer("d1", DenseLayer(n_out=16, activation="relu"), "in")
        g.add_layer("out", OutputLayer(n_out=3), "d1")
        net = ComputationGraph(g.set_outputs("out").build())
        net.init()
        ds = _data(128)
        net.fit(ListDataSetIterator(ds, 64), epochs=3)
        assert float(net.score_) < 1.2


class TestBatchNormMixedPrecisionInference:
    """Regression: f32 BN running stats must not promote the bf16 stream
    back to f32 mid-network — inference after bf16 training used to crash
    with a conv dtype mismatch."""

    def _bn_conf(self, compute_dtype):
        from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer
        return (NeuralNetConfiguration.builder().seed(2)
                .compute_dtype(compute_dtype).list()
                .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalizationLayer(activation="relu"))
                .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(OutputLayer(n_out=2))
                .set_input_type(InputType.convolutional(8, 8, 1)).build())

    def test_mln_train_then_infer(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 8, 8, 1)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        net = MultiLayerNetwork(self._bn_conf("bfloat16")).init()
        net.fit(x, y, epochs=2)
        out = np.asarray(net.output(x))
        assert out.shape == (8, 2)
        assert np.isfinite(out).all()
        # running stats stay f32 even though compute is bf16
        assert net.states[1]["mean"].dtype == jnp.float32

    def test_graph_train_then_infer(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer, LossLayer
        g = (NeuralNetConfiguration.builder().seed(3)
             .compute_dtype("bfloat16").graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.convolutional(8, 8, 1)))
        g.add_layer("c1", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                           convolution_mode="same"), "in")
        g.add_layer("bn", BatchNormalizationLayer(activation="relu"), "c1")
        g.add_layer("c2", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                           convolution_mode="same"), "bn")
        g.add_layer("gap", __import__("deeplearning4j_tpu.nn.layers",
                                      fromlist=["GlobalPoolingLayer"]
                                      ).GlobalPoolingLayer(), "c2")
        g.add_layer("out", OutputLayer(n_out=2), "gap")
        g.set_outputs("out")
        net = ComputationGraph(g.build()).init()
        rng = np.random.default_rng(1)
        x = rng.normal(size=(4, 8, 8, 1)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)]
        net.fit(x, y)
        out = np.asarray(net.output(x))
        assert out.shape == (4, 2) and np.isfinite(out).all()


def test_batchnorm_f32_large_mean_stable():
    """Full-precision BN must keep the two-pass variance: E[x^2]-E[x]^2 at
    f32 cancels catastrophically for large-mean features (the fused
    formulation is bf16/f16-only, where the f32 accumulator is wide)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers import BatchNormalizationLayer

    l = BatchNormalizationLayer(n_in=4)
    p = l.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(64, 4)) + 1e4).astype(np.float32)  # mean 1e4, std 1
    y, st = l.forward(p, jnp.asarray(x), state=l.init_state(), train=True)
    y = np.asarray(y)
    assert np.isfinite(y).all()
    # normalized output: per-feature std ~1 (variance was not clamped to 0)
    assert 0.5 < y.std() < 2.0, y.std()
    var = np.asarray(st["var"]) * 10  # decay 0.9: blended 0.1 * batch var
    assert (var > 0.3).all(), var


def test_layernorm_bf16_accumulates_in_f32():
    """bf16 LayerNorm moments must accumulate in f32: the normalized output
    should track the f32 reference much closer than bf16 resolution."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers import LayerNormalizationLayer

    l = LayerNormalizationLayer(n_in=768)
    p = l.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x32 = (rng.normal(size=(4, 768)) + 5.0).astype(np.float32)  # nonzero mean
    ref, _ = l.forward(p, jnp.asarray(x32))
    out16, _ = l.forward(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), p), jnp.asarray(x32, jnp.bfloat16))
    err = np.abs(np.asarray(out16, np.float32) - np.asarray(ref)).max()
    assert err < 0.05, err  # bf16-rounded inputs, f32-accumulated moments


def test_lowp_moments_f16_no_overflow():
    """f16 streams square in f32 inside the moment reduction — |x| > 256
    must not overflow to inf variance (bf16 shares f32's exponent range and
    squares in-stream)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers.norm import _lowp_moments

    x = jnp.asarray(np.full((4, 8), 1000.0), jnp.float16)
    mean, var = _lowp_moments(x, -1, keepdims=True)
    assert np.isfinite(np.asarray(mean)).all()
    assert np.isfinite(np.asarray(var)).all()
    xb = jnp.asarray(np.full((4, 8), 1e10), jnp.bfloat16)
    mean, var = _lowp_moments(xb, -1, keepdims=True)
    assert np.isfinite(np.asarray(mean)).all()


def test_lowp_moments_large_mean_accuracy():
    """bf16 rows with mean >> std: the f32 square keeps the variance
    estimate meaningful (a bf16 square's rounding error ~2^-9*mean^2 would
    swamp it)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers.norm import _lowp_moments

    rng = np.random.default_rng(0)
    x32 = (rng.normal(size=(8, 768)) + 100.0).astype(np.float32)
    mean, var = _lowp_moments(jnp.asarray(x32, jnp.bfloat16), -1,
                              keepdims=True)
    true_var = x32.var(axis=-1, keepdims=True)
    # the bf16 INPUT quantization itself adds ~(100*2^-9)^2/12 ≈ 0.003
    # variance noise; the estimate must stay within ~25% of truth, not
    # collapse toward the zero clamp
    rel = np.abs(np.asarray(var) - true_var) / true_var
    assert rel.max() < 0.25, (rel.max(), np.asarray(var).min())
