"""Helper-seam tests: Pallas fused LSTM must match the built-in XLA path
(the reference's ValidateCudnnLSTM / CuDNNGradientChecks pattern)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import LSTMLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.pallas_kernels import PallasLSTMHelper


@pytest.fixture(autouse=True)
def _clean_registry():
    helpers.clear_all_helpers()
    yield
    helpers.clear_all_helpers()


def _net(seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).list()
            .layer(LSTMLayer(n_out=24))
            .layer(RnnOutputLayer(n_out=4))
            .set_input_type(InputType.recurrent(8)).build())
    return MultiLayerNetwork(conf).init()


def _data(rng, b=8, t=12, c=8, k=4):
    x = rng.normal(size=(b, t, c)).astype(np.float32)
    y = np.eye(k, dtype=np.float32)[rng.integers(0, k, size=(b, t))]
    return x, y


class TestRegistry:
    def test_set_get_clear(self):
        h = PallasLSTMHelper(interpret=True)
        helpers.set_helper("lstm", h)
        assert helpers.get_helper("lstm") is h
        helpers.clear_helper("lstm")
        assert helpers.get_helper("lstm") is None

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError):
            helpers.set_helper("quantum", object())

    def test_supports_gating(self):
        h = PallasLSTMHelper(interpret=True)
        std = LSTMLayer(n_in=4, n_out=8)
        assert h.supports(std, None)
        assert not h.supports(std, np.ones((2, 3)))  # masked → built-in path
        from deeplearning4j_tpu.nn.layers import GravesLSTMLayer
        graves = GravesLSTMLayer(n_in=4, n_out=8)
        assert not h.supports(graves, None)  # peepholes → built-in path


class TestPallasLSTMEquivalence:
    def test_forward_matches_builtin(self, rng):
        """Same-math validation (ValidateCudnnLSTM pattern). Registration
        after a compiled call must still take effect (registry version is in
        the jit cache key) — and the helper must actually be consulted."""
        net = _net()
        x, _ = _data(rng)
        base = np.asarray(net.output(x))  # compiles the stock path first

        calls = []
        orig = PallasLSTMHelper.forward_seq

        class Spy(PallasLSTMHelper):
            def forward_seq(self, layer, params, xx, carry):
                calls.append(1)
                return orig(self, layer, params, xx, carry)

        helpers.set_helper("lstm", Spy(interpret=True))
        fused = np.asarray(net.output(x))
        assert calls, "helper was never consulted after registration"
        np.testing.assert_allclose(fused, base, rtol=2e-5, atol=2e-6)
        # clearing restores the stock path without manual cache clearing
        helpers.clear_helper("lstm")
        calls.clear()
        np.asarray(net.output(x))
        assert not calls

    def test_gradients_match_builtin(self, rng):
        """CuDNNGradientChecks pattern: grads through the helper == grads
        through the built-in path (custom_vjp reuses the reference scan)."""
        net = _net()
        x, y = _data(rng)
        g_base, loss_base = net.compute_gradient_and_score(x, y)
        helpers.set_helper("lstm", PallasLSTMHelper(interpret=True))
        g_fused, loss_fused = net.compute_gradient_and_score(x, y)
        assert abs(loss_base - loss_fused) < 1e-5
        for lb, lf in zip(g_base, g_fused):
            for k in lb:
                np.testing.assert_allclose(np.asarray(lf[k]), np.asarray(lb[k]),
                                           rtol=1e-4, atol=1e-6)

    def test_training_with_helper(self, rng):
        net = _net()
        helpers.set_helper("lstm", PallasLSTMHelper(interpret=True))
        from deeplearning4j_tpu.datasets.dataset import DataSet
        x, y = _data(rng, b=16)
        before = float(net.score(DataSet(x, y)))
        net.fit(DataSet(x, y))
        net.fit(DataSet(x, y))
        after = float(net.score(DataSet(x, y)))
        assert after < before

    def test_stateful_inference_carry(self, rng):
        """rnn_time_step carry flows through the fused kernel."""
        net = _net()
        x, _ = _data(rng, b=4, t=6)
        base_full = np.asarray(net.rnn_time_step(x))
        net.rnn_clear_previous_state()
        helpers.set_helper("lstm", PallasLSTMHelper(interpret=True))
        step1 = np.asarray(net.rnn_time_step(x[:, :3]))
        step2 = np.asarray(net.rnn_time_step(x[:, 3:]))
        fused_full = np.concatenate([step1, step2], axis=1)
        np.testing.assert_allclose(fused_full, base_full, rtol=2e-5, atol=2e-6)


class TestFlashAttentionHelper:
    def test_supports_gating(self):
        from deeplearning4j_tpu.nn.pallas_kernels import PallasFlashAttentionHelper
        h = PallasFlashAttentionHelper()
        on_tpu = jax.default_backend() == "tpu"
        # shape gate holds regardless of backend (backend gate may veto)
        assert h.supports(None, (2, 8, 256, 64), None, False) == on_tpu
        assert not h.supports(None, (2, 8, 200, 64), None, False)  # T % 128
        assert not h.supports(None, (2, 8, 256, 48), None, False)  # dh
        assert not h.supports(None, (2, 8, 256, 64), np.ones((2, 256)), False)
        assert not h.supports(None, (2, 8, 256, 64), None, True)  # dropout

    def test_matches_einsum_on_tpu(self, rng):
        if jax.default_backend() != "tpu":
            pytest.skip("flash attention kernel requires the TPU backend")
        from deeplearning4j_tpu.nn.pallas_kernels import PallasFlashAttentionHelper
        from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
        q = jnp.asarray(rng.normal(size=(2, 4, 256, 64)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 4, 256, 64)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 4, 256, 64)).astype(np.float32))
        base = dot_product_attention(q, k, v)
        helpers.set_helper("attention", PallasFlashAttentionHelper())
        fused = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                                   rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("dtype,rtol,atol", [("float32", 2e-4, 2e-5),
                                             ("bfloat16", 5e-2, 3e-2)])
@pytest.mark.parametrize("dh", [64, 128])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attend_matches_einsum_in_interpreter(rng, causal, dh, dtype,
                                                    rtol, atol):
    """`attend` called directly (`supports()` refuses the CPU) in the Pallas
    interpreter: forward and all three gradients against the einsum path.
    bf16 gets chip_smoke.py's tolerances: the einsum path rounds its scores
    to bf16, the kernel keeps them in float32."""
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.nn.pallas_kernels import PallasFlashAttentionHelper

    helper = PallasFlashAttentionHelper(causal=causal, interpret=True)
    assert helper.supports(None, (2, 2, 256, dh), None, False,
                           causal=causal) == (jax.default_backend() == "tpu")
    q, k, v, w = (jnp.asarray(rng.normal(size=(2, 2, 256, dh))
                              .astype(np.float32)).astype(dtype)
                  for _ in range(4))

    def stock(q, k, v):
        return dot_product_attention(q, k, v, causal=causal)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum((fn(q, k, v) * w).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)

    out = jax.jit(helper.attend)(q, k, v)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(stock(q, k, v), np.float32),
                               rtol=rtol, atol=atol)
    for name, a, b in zip(("dq", "dk", "dv"), grads(helper.attend),
                          grads(stock)):
        assert a.dtype == q.dtype, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=rtol,
            atol=atol * max(1.0, float(np.abs(b).max())), err_msg=name)


def test_flash_kernel_object_is_built_once_per_shape(monkeypatch):
    """The mask is processed on the host when the kernel object is built;
    a step is traced more than once, so one shape builds it once."""
    from jax.experimental.pallas.ops.tpu import splash_attention as splash
    from deeplearning4j_tpu.nn import pallas_kernels as PK

    built = []
    make = splash.make_splash_mha

    def counting(mask, **kw):
        built.append(mask.shape)
        return make(mask, **kw)

    monkeypatch.setattr(splash, "make_splash_mha", counting)
    PK._splash_kernel.cache_clear()
    try:
        helper = PK.PallasFlashAttentionHelper(causal=True, interpret=True)
        q = jax.ShapeDtypeStruct((2, 3, 128, 64), jnp.float32)
        for _ in range(2):  # two traces, as set-up lowers the step twice
            jax.jit(helper.attend).trace(q, q, q)
        jax.jit(jax.grad(lambda q, k, v: helper.attend(q, k, v).sum(),
                         argnums=(0, 1, 2))).trace(q, q, q)
        assert built == [(3, 128, 128)]
        # another helper object of the same request shares it; another
        # masking or shape does not
        PK.PallasFlashAttentionHelper(causal=True, interpret=True).attend(
            *(jnp.zeros(q.shape, q.dtype),) * 3)
        assert len(built) == 1
        jax.jit(PK.PallasFlashAttentionHelper(interpret=True).attend).trace(
            q, q, q)
        assert len(built) == 2
    finally:
        PK._splash_kernel.cache_clear()


class TestCausalFlashAttentionHelper:
    """causal=True flash helper serves causal layers through the seam (the
    causal flag is part of the request since the decoder work); measured on
    v5e: 1.45x LM train step at T=2048, 2.64x at T=4096 (rounds 1-5, not re-measured: PERF.md)."""

    def test_causal_gating(self):
        from deeplearning4j_tpu.nn.pallas_kernels import PallasFlashAttentionHelper
        on_tpu = jax.default_backend() == "tpu"
        h = PallasFlashAttentionHelper(causal=True)
        assert h.supports(None, (2, 8, 256, 64), None, False,
                          causal=True) == on_tpu
        # a causal kernel must never serve a bidirectional request
        assert not h.supports(None, (2, 8, 256, 64), None, False)
        # and a non-causal kernel must never serve a causal one
        h2 = PallasFlashAttentionHelper()
        assert not h2.supports(None, (2, 8, 256, 64), None, False, causal=True)

    def test_causal_lm_outputs_unchanged_on_tpu(self, rng):
        if jax.default_backend() != "tpu":
            pytest.skip("flash attention kernel requires the TPU backend")
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.pallas_kernels import PallasFlashAttentionHelper
        from deeplearning4j_tpu.zoo.models import TransformerLM

        m = TransformerLM(vocab_size=100, max_length=256, n_layers=1,
                          d_model=128, n_heads=2, d_ff=256, seed=1)  # dh=64
        net = ComputationGraph(m.conf()).init()
        ids = rng.integers(0, 100, (2, 256)).astype(np.float32)
        ref = np.asarray(net.output(ids))

        calls = []

        class Spy(PallasFlashAttentionHelper):
            def attend(self, q, k, v):
                calls.append(q.shape)
                return super().attend(q, k, v)

        helpers.set_helper("attention", Spy(causal=True))
        try:
            out = np.asarray(net.output(ids))
        finally:
            helpers.clear_helper("attention")
        assert calls, "causal flash helper was never consulted"
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-3)


class TestAutoFlashAttention:
    """With NO helper registered, causal attention at T >= 1024 auto-uses
    the causal flash kernel (opt-out via set_auto_flash_attention) — the
    measured LM-training win should not depend on knowing the seam exists."""

    def _spy(self, calls):
        class Spy:
            def supports(self, layer, q_shape, mask, dropout_active,
                         causal=False):
                return causal
            def attend(self, q, k, v):
                calls.append(q.shape)
                # distinguishable-but-wrong output is fine: only SELECTION
                # is under test here (numerics are covered on TPU above)
                return q * 0 + 7.0
        return Spy()

    def _qkv(self, t):
        import jax.numpy as jnp
        shape = (1, 2, t, 64)
        q = jnp.ones(shape, jnp.float32)
        return q, q, q

    def test_auto_used_in_win_region_only(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import attention as A
        calls = []
        monkeypatch.setattr(A, "_auto_flash_helper", lambda: self._spy(calls))
        q, k, v = self._qkv(2048)
        out = A.dot_product_attention(q, k, v, causal=True)
        assert len(calls) == 1 and float(out[0, 0, 0, 0]) == 7.0
        # below the threshold: einsum path
        q2, k2, v2 = self._qkv(512)
        A.dot_product_attention(q2, k2, v2, causal=True)
        assert len(calls) == 1
        # non-causal: never auto (the kernel's semantics are causal)
        A.dot_product_attention(q, k, v, causal=False)
        assert len(calls) == 1

    def test_opt_out_and_version_bump(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import attention as A
        calls = []
        monkeypatch.setattr(A, "_auto_flash_helper", lambda: self._spy(calls))
        q, k, v = self._qkv(2048)
        v0 = helpers.version()
        helpers.set_auto_flash_attention(False)
        try:
            assert helpers.version() == v0 + 1  # compiled nets must retrace
            A.dot_product_attention(q, k, v, causal=True)
            assert not calls
        finally:
            helpers.set_auto_flash_attention(True)
        assert helpers.version() == v0 + 2
        A.dot_product_attention(q, k, v, causal=True)
        assert len(calls) == 1

    def test_registered_helper_takes_precedence(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import attention as A
        auto_calls, reg_calls = [], []
        monkeypatch.setattr(A, "_auto_flash_helper",
                            lambda: self._spy(auto_calls))
        helpers.set_helper("attention", self._spy(reg_calls))
        try:
            q, k, v = self._qkv(2048)
            A.dot_product_attention(q, k, v, causal=True)
            assert reg_calls and not auto_calls
        finally:
            helpers.clear_helper("attention")


def _mlp_net(updater, seed=5, width=48):
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(updater)
            .list()
            .layer(DenseLayer(n_out=width, activation="relu"))
            .layer(OutputLayer(n_out=4))
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def _mlp_data(rng, b=32):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    x = rng.normal(size=(b, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=b)]
    return DataSet(x, y)


def _count_pallas_eqns(jaxpr):
    """pallas_call equations, recursing into pjit/scan/cond sub-jaxprs."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for u in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(u, "jaxpr", u)
                if hasattr(inner, "eqns"):
                    n += _count_pallas_eqns(inner)
    return n


def _train_step_jaxpr(net, ds):
    fn = net._get_train_step(False)
    return jax.make_jaxpr(fn)(
        net.params, net.states, net.updater_states,
        jnp.float32(0.0), jnp.float32(0.0),
        jnp.asarray(np.asarray(ds.features)),
        jnp.asarray(np.asarray(ds.labels)),
        None, None, jax.random.PRNGKey(0), None).jaxpr


class TestPallasUpdaterHelper:
    """Fused optimizer-update kernel behind the "updater" helper seam: the
    whole param+m+v read-modify-write as ONE kernel over donated buffers.
    Same validation contract as the fused LSTM (ValidateCudnnLSTM pattern):
    numerics vs stock XLA, consult/clear behavior, launch-count oracle."""

    ALL_UPDATERS = "Sgd NoOp Nesterovs Adam AdaMax Nadam AMSGrad " \
                   "AdaGrad AdaDelta RmsProp".split()

    def test_supports_gating(self):
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        from deeplearning4j_tpu.nn.updaters import Adam, Sgd
        h = PallasUpdaterHelper(interpret=True)
        p = jnp.zeros((24, 16), jnp.float32)
        assert h.supports(Adam(1e-3), p, p)
        assert not h.supports(Sgd(1e-2), p, p)  # no state to fuse
        # EXACT types only: a subclass may override update() — its math is
        # unknown to the kernel, so it must take the stock path

        class TweakedAdam(Adam):
            pass

        assert not h.supports(TweakedAdam(1e-3), p, p)
        assert not h.supports(Adam(1e-3), p.astype(jnp.bfloat16),
                              p.astype(jnp.bfloat16))
        assert not h.supports(Adam(1e-3), p, jnp.zeros((24, 8), jnp.float32))

    @pytest.mark.parametrize("name", ALL_UPDATERS)
    def test_matches_stock_every_updater(self, rng, name):
        """Twin nets, 3 identical steps: fused-registered params must land
        on the stock-path params within 2e-5 for EVERY shipped updater —
        fused classes agree through the kernel, the rest must be untouched
        by the seam (exact fallback)."""
        import deeplearning4j_tpu.nn.updaters as U
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        upd = getattr(U, name)(1e-2)
        ds = _mlp_data(rng)
        stock = _mlp_net(upd)
        fused = _mlp_net(upd)
        for _ in range(3):
            stock._fit_batch(ds)
        helpers.set_helper("updater", PallasUpdaterHelper(interpret=True))
        for _ in range(3):
            fused._fit_batch(ds)
        for lb, lf in zip(stock.params, fused.params):
            for k in lb:
                np.testing.assert_allclose(
                    np.asarray(lf[k]), np.asarray(lb[k]),
                    rtol=2e-5, atol=2e-5,
                    err_msg=f"{name}: fused diverged from stock on {k}")

    def test_consulted_and_clear_restores_stock(self, rng):
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        from deeplearning4j_tpu.nn.updaters import Adam
        net = _mlp_net(Adam(1e-3))
        ds = _mlp_data(rng)
        net._fit_batch(ds)  # compiles the stock step first

        calls = []

        class Spy(PallasUpdaterHelper):
            def apply(self, updater, param, grad, state, lr, t):
                calls.append(param.shape)
                return super().apply(updater, param, grad, state, lr, t)

        helpers.set_helper("updater", Spy(interpret=True))
        net._fit_batch(ds)
        # consulted once per fusable tensor (w+b per layer), despite the
        # already-compiled stock step: registry version keys the jit cache
        assert len(calls) == 4
        helpers.clear_helper("updater")
        calls.clear()
        net._fit_batch(ds)
        assert not calls

    def test_one_kernel_launch_per_tensor(self, rng):
        """HLO/compile-count oracle: with the fused updater registered the
        train step carries exactly ONE pallas_call per fusable parameter
        tensor — and none at all without it (no silent leftovers)."""
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        from deeplearning4j_tpu.nn.updaters import Adam
        net = _mlp_net(Adam(1e-3))
        ds = _mlp_data(rng)
        assert _count_pallas_eqns(_train_step_jaxpr(net, ds)) == 0
        helpers.set_helper("updater", PallasUpdaterHelper(interpret=True))
        assert _count_pallas_eqns(_train_step_jaxpr(net, ds)) == 4

    def test_nonsquare_and_vector_params_pad_correctly(self, rng):
        """The (R,128) lane-tiling flattens/zero-pads every shape; padding
        must never leak into the real elements (Adam math is closed under
        zero rows: 0-grad 0-state rows stay 0)."""
        from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
        from deeplearning4j_tpu.nn.updaters import Adam
        h = PallasUpdaterHelper(interpret=True)
        u = Adam(1e-3)
        rng_np = np.random.default_rng(3)
        for shape in ((5,), (3, 7), (129,), (130, 257)):
            p = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
            g = jnp.asarray(rng_np.normal(size=shape).astype(np.float32))
            state = {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
            upd_ref, s_ref = u.update(g, state, 1e-3, 1.0)
            p_ref = p - upd_ref
            p_new, s_new = h.apply(u, p, g, state, 1e-3, 1.0)
            assert p_new.shape == p.shape
            np.testing.assert_allclose(np.asarray(p_new), np.asarray(p_ref),
                                       rtol=2e-5, atol=2e-6)
            for k in s_ref:
                np.testing.assert_allclose(
                    np.asarray(s_new[k]), np.asarray(s_ref[k]),
                    rtol=2e-5, atol=2e-6)


class TestAutoFusedLSTM:
    """With NO helper registered, LSTM forward at T >= 256 and lane-aligned
    modest H auto-uses the fused kernel (opt-out via set_auto_fused_lstm) —
    the same promotion pattern as the causal-flash auto fallback."""

    def _spy(self, calls):
        class Spy:
            def supports(self, layer, mask):
                return mask is None

            def forward_seq(self, layer, params, x, carry):
                calls.append(x.shape)
                # distinguishable-but-wrong output: only SELECTION is under
                # test (numerics are covered by TestPallasLSTMEquivalence)
                return jnp.zeros(x.shape[:2] + (layer.n_out,)) + 7.0, carry
        return Spy()

    def _layer(self, h=128):
        layer = LSTMLayer(n_in=8, n_out=h)
        params = layer.init_params(jax.random.PRNGKey(0))
        return layer, params

    def test_win_region_predicate(self):
        from deeplearning4j_tpu.nn.layers import recurrent as R
        x = np.zeros((2, 256, 8), np.float32)
        short = np.zeros((2, 128, 8), np.float32)
        assert R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=128), x)
        assert R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=256), x)
        assert not R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=128), short)
        assert not R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=96), x)
        assert not R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=384), x)

    def test_auto_used_in_win_region_only(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import recurrent as R
        calls = []
        monkeypatch.setattr(R, "_auto_lstm_helper", lambda: self._spy(calls))
        layer, params = self._layer()
        x = jnp.ones((2, 256, 8), jnp.float32)
        y, _ = layer.forward_seq(params, x)
        assert len(calls) == 1 and float(y[0, 0, 0]) == 7.0
        # below the threshold: the stock scan path
        y2, _ = layer.forward_seq(params, jnp.ones((2, 16, 8), jnp.float32))
        assert len(calls) == 1 and float(y2[0, 0, 0]) != 7.0
        # masked sequences: the helper's supports() veto is honored
        layer.forward_seq(params, x, mask=jnp.ones((2, 256), jnp.float32))
        assert len(calls) == 1

    def test_opt_out_and_version_bump(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import recurrent as R
        calls = []
        monkeypatch.setattr(R, "_auto_lstm_helper", lambda: self._spy(calls))
        layer, params = self._layer()
        x = jnp.ones((2, 256, 8), jnp.float32)
        v0 = helpers.version()
        helpers.set_auto_fused_lstm(False)
        try:
            assert helpers.version() == v0 + 1  # compiled nets must retrace
            layer.forward_seq(params, x)
            assert not calls
        finally:
            helpers.set_auto_fused_lstm(True)
        assert helpers.version() == v0 + 2
        layer.forward_seq(params, x)
        assert len(calls) == 1

    def test_registered_helper_takes_precedence(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers import recurrent as R
        auto_calls, reg_calls = [], []
        monkeypatch.setattr(R, "_auto_lstm_helper",
                            lambda: self._spy(auto_calls))
        helpers.set_helper("lstm", self._spy(reg_calls))
        layer, params = self._layer()
        layer.forward_seq(params, jnp.ones((2, 256, 8), jnp.float32))
        assert reg_calls and not auto_calls

    def test_off_tpu_factory_declines(self):
        from deeplearning4j_tpu.nn.layers import recurrent as R
        if jax.default_backend() == "tpu":
            assert R._auto_lstm_helper() is not None
        else:
            # interpret-mode would be a slowdown, not a win — never auto
            assert R._auto_lstm_helper() is None
