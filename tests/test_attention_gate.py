"""The sequence length chooses attention's path and the kernel's blocks.

All on the CPU: the backend string is patched where the real ``supports()``
has to answer as on a chip, and the kernel itself runs in the Pallas
interpreter (``interpret=True``) or is only traced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.nn import pallas_kernels as PK
from deeplearning4j_tpu.nn.layers import attention as A


@pytest.fixture
def tracer():
    tracer = observe.enable_tracing(jax_hook=False)
    try:
        yield tracer
    finally:
        observe.disable_tracing()


@pytest.fixture
def as_on_a_chip(monkeypatch):
    """`supports()` asks for the backend's name; the kernel is only traced
    or replaced by a spy under this, never compiled."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class _Spy(PK.PallasFlashAttentionHelper):
    """The real `supports()`, and an `attend` that only says it was asked."""

    def __init__(self, calls):
        super().__init__(causal=True)
        self.calls = calls

    def attend(self, q, k, v):
        self.calls.append(q.shape)
        return q


def _on_mesh(x, axes=None, spec=("data",)):
    """`x` on a mesh of host devices (the 2x2 unless `axes` says another),
    its dimensions split as `spec` says."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    axes = axes or {"data": 2, "model": 2}
    n = int(np.prod(list(axes.values())))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))


@pytest.mark.parametrize("t,how,kernel", [
    (2048, "plain", True),
    (1024, "plain", True),
    (512, "plain", False),
    (1024, "mask", False),
    (1024, "dropout", False),
    (1024, "partitioned", True),
    (512, "partitioned", False),
    (1024, "not causal", False),
])
def test_auto_gate_by_length_and_request(monkeypatch, as_on_a_chip, tracer,
                                         t, how, kernel):
    """Under the 2x2 the helper is asked about, and given, one shard: half
    the batch, half the heads, all of T and Dh."""
    calls = []
    monkeypatch.setattr(A, "_auto_flash_helper", lambda: _Spy(calls))
    q = jnp.ones((2, 2, t, 64), jnp.bfloat16)
    kwargs = {"causal": how != "not causal"}
    if how == "mask":
        kwargs["mask"] = jnp.ones((2, t))
    if how == "dropout":
        kwargs.update(dropout_rate=0.1, rng=jax.random.PRNGKey(0),
                      train=True)
    if how == "partitioned":
        q = _on_mesh(q)
    jax.jit(lambda q: A.dot_product_attention(q, q, q, **kwargs)).trace(q)
    sharded = kernel and how == "partitioned"
    assert calls == ([(1, 1, t, 64) if sharded else (2, 2, t, 64)]
                     if kernel else [])
    counters = {
        "attention.kernel_calls" if kernel else "attention.einsum_calls": 1}
    if sharded:
        counters["attention.sharded_kernel_calls"] = 1
    assert tracer.counters == counters


def test_gate_is_where_the_sweep_put_it():
    assert A._AUTO_FLASH_MIN_T == 1024


_FIELDS = PK._SPLASH_BLOCK_FIELDS


@pytest.mark.parametrize("row_bytes", [128, 256, 512, 1024])
@pytest.mark.parametrize("t", [128, 256, 384, 512, 640, 1024, 1152, 1536,
                               2048, 2560, 4096, 8192])
def test_block_table(t, row_bytes):
    """Every block divides `t`; a compute block divides its memory block;
    float32 at Dh=256 (1,024 bytes a row) stays at 512, and so does bf16 at
    Dh=256 (512 bytes) below T=2048, where the chip's compiler refused
    more; from T=2048 up the blocks are the ones PR 26 measured, and at
    T=1024 the ones PR 29 did."""
    sizes = PK._splash_block_sizes(t, row_bytes)
    blocks = {f: getattr(sizes, f) for f in _FIELDS}
    assert all(t % b == 0 and b % 128 == 0 for b in blocks.values()), blocks
    assert blocks["block_kv"] % blocks["block_kv_compute"] == 0
    assert blocks["block_kv_dkv"] % blocks["block_kv_dkv_compute"] == 0
    assert sizes.use_fused_bwd_kernel
    if row_bytes > 512 or (row_bytes == 512 and t < 2048):
        assert max(blocks.values()) <= 512
    if t in (2048, 4096, 8192) and row_bytes <= 512:
        assert tuple(blocks.values()) == (1024, 1024, 512, 1024, 1024, 512)
    if t == 1024 and row_bytes <= 256:
        assert tuple(blocks.values()) == (1024, 1024, 512, 1024, 1024, 1024)


def test_block_table_is_ordered_and_complete():
    """Longest first, and a last row that takes whatever `supports()`
    admits (any multiple of 128, rows up to float32 at Dh=256)."""
    mins = [min_t for min_t, _, _ in PK._SPLASH_BLOCKS]
    assert mins == sorted(mins, reverse=True)
    assert PK._SPLASH_BLOCKS[-1][:2] == (0, 256 * 4)
    assert all(len(row) == len(_FIELDS) for _, _, row in PK._SPLASH_BLOCKS)


@pytest.mark.parametrize("dtype,rtol,atol", [("float32", 2e-4, 2e-5),
                                             ("bfloat16", 5e-2, 3e-2)])
def test_kernel_matches_einsum_at_the_gate(rng, dtype, rtol, atol):
    """[2,4,1024,64], causal, with the blocks the table gives T=1024, in the
    Pallas interpreter: forward and all three gradients against the einsum
    path, at `test_flash_attend_matches_einsum_in_interpreter`'s
    tolerances."""
    helper = PK.PallasFlashAttentionHelper(causal=True, interpret=True)
    q, k, v, w = (jnp.asarray(rng.normal(size=(2, 4, 1024, 64))
                              .astype(np.float32)).astype(dtype)
                  for _ in range(4))

    def stock(q, k, v):
        return A.dot_product_attention(q, k, v, causal=True)

    def out_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum((fn(q, k, v) * w).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)

    np.testing.assert_allclose(
        np.asarray(jax.jit(helper.attend)(q, k, v), np.float32),
        np.asarray(stock(q, k, v), np.float32), rtol=rtol, atol=atol)
    (loss_a, grads_a), (loss_b, grads_b) = (out_and_grads(helper.attend),
                                            out_and_grads(stock))
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=rtol,
                               atol=1.0)
    for name, a, b in zip(("dq", "dk", "dv"), grads_a, grads_b):
        assert a.dtype == q.dtype, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), b, rtol=rtol,
            atol=atol * max(1.0, float(np.abs(b).max())), err_msg=name)


def _loss_and_grads(fn, q, k, v, w):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum((fn(q, k, v) * w).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)


@pytest.fixture
def interpreter_kernel():
    """The causal kernel registered by hand and run by the Pallas
    interpreter: in a partitioned program a registered helper is placed by
    the same rule as the auto gate's."""
    from deeplearning4j_tpu.nn import helpers
    helpers.set_helper("attention", PK.PallasFlashAttentionHelper(
        causal=True, interpret=True))
    try:
        yield
    finally:
        helpers.clear_helper("attention")


@pytest.mark.parametrize("window", [None, 128])
def test_kernel_under_the_mesh_matches_einsum(rng, as_on_a_chip, tracer,
                                              interpreter_kernel, window):
    """[4,4,256,64] float32 over data=2 x model=2: each device runs the
    kernel on its [2,2,256,64]; values and the three gradients against the
    einsum path on one device."""
    from deeplearning4j_tpu.nn import helpers

    q, k, v, w = (jnp.asarray(rng.normal(size=(4, 4, 256, 64))
                              .astype(np.float32)) for _ in range(4))

    def attend(q, k, v):
        return A.dot_product_attention(q, k, v, causal=True, window=window)

    spec = ("data", "model")
    placed = [_on_mesh(x, spec=spec) for x in (q, k, v)]
    out = jax.jit(attend)(*placed)
    loss_a, grads_a = _loss_and_grads(attend, *placed, _on_mesh(w, spec=spec))
    windows = {} if window is None else {"attention.window_kernel_calls": 2}
    assert tracer.counters == {"attention.kernel_calls": 2,
                               "attention.sharded_kernel_calls": 2, **windows}
    assert out.sharding.spec == jax.sharding.PartitionSpec(*spec)

    helpers.clear_helper("attention")
    np.testing.assert_allclose(np.asarray(out), np.asarray(attend(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    loss_b, grads_b = _loss_and_grads(attend, q, k, v, w)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=2e-4)
    for name, a, b in zip(("dq", "dk", "dv"), grads_a, grads_b):
        assert a.sharding.spec == jax.sharding.PartitionSpec(*spec), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5 * float(np.abs(b).max()),
                                   err_msg=name)


@pytest.mark.parametrize("how,axes,shape", [
    ("heads the model axis does not divide", {"data": 2, "model": 3},
     (2, 4, 1024, 64)),
    ("a batch the data axis does not divide", {"data": 2, "model": 2},
     (3, 2, 1024, 64)),
    ("a third axis larger than 1", {"data": 2, "model": 2, "seq": 2},
     (2, 2, 1024, 64)),
    ("T below the gate", {"data": 2, "model": 2}, (2, 2, 512, 64)),
])
def test_meshes_the_rule_does_not_serve_take_the_einsum_path(
        monkeypatch, rng, as_on_a_chip, tracer, how, axes, shape):
    """No helper is asked, the counters say einsum, and the partitioned
    program gives what one device gives, gradients too."""
    calls = []
    monkeypatch.setattr(A, "_auto_flash_helper", lambda: _Spy(calls))
    q, k, v, w = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
                  for _ in range(4))
    # a batch of 3 cannot be split in two: replicated, it still carries
    # the mesh into the traced type
    spec = () if shape[0] % axes["data"] else ("data",)

    def attend(q, k, v):
        return A.dot_product_attention(q, k, v, causal=True)

    loss_a, grads_a = _loss_and_grads(
        attend, *(_on_mesh(x, axes, spec) for x in (q, k, v, w)))
    assert calls == []
    assert tracer.counters == {"attention.einsum_calls": 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    loss_b, grads_b = _loss_and_grads(attend, q, k, v, w)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-4)
    for name, a, b in zip(("dq", "dk", "dv"), grads_a, grads_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4, err_msg=name)


@pytest.mark.parametrize("mode,sharded", [("shared_gradients", True),
                                          ("averaging", False)])
def test_parallel_wrapper_reaches_the_kernel(monkeypatch, as_on_a_chip,
                                             tracer, mode, sharded):
    """Two workers, a batch of four: either way a worker's kernel sees two
    sequences. The compiler partitions the shared-gradients step, so the
    seam makes `data` manual; the averaging step's own `shard_map` already
    has, and the seam finds nothing left to do."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (CausalSelfAttentionLayer,
                                              RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.trainer import ParallelWrapper

    calls = []
    monkeypatch.setattr(A, "_auto_flash_helper", lambda: _Spy(calls))
    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).list()
        .layer(CausalSelfAttentionLayer(n_out=128, n_heads=2,
                                        max_cache=1024))
        .layer(RnnOutputLayer(n_out=4))
        .set_input_type(InputType.recurrent(128, 1024)).build()).init()
    wrapper = ParallelWrapper(net, make_mesh({"data": 2}, jax.devices()[:2]),
                              mode=mode, averaging_frequency=1)
    labels = np.zeros((4, 1024, 4), np.float32)
    labels[..., 0] = 1
    wrapper.fit(DataSet(np.zeros((4, 1024, 128), np.float32), labels))
    assert calls == [(2, 2, 1024, 64)]
    assert tracer.counters.get("attention.kernel_calls") == 1
    assert tracer.counters.get("attention.sharded_kernel_calls",
                               0) == int(sharded)


def _twelve_layer_step(t):
    """The train step of a 12-layer causal LM (Dh=64) and its arguments,
    as `fit()` would call it; nothing has run."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

    net = ComputationGraph(TransformerLM(
        vocab_size=32, max_length=t, n_layers=12, d_model=128, n_heads=2,
        d_ff=128, seed=3).conf()).init()
    tokens = np.zeros((1, t), np.int32)
    it, ep, rng = net._device_tick()
    return net._get_train_step(), (
        net.params, net.states, net.updater_states, it, ep,
        {"tokens": jnp.asarray(tokens)}, [jnp.asarray(lm_labels(tokens, 32))],
        None, None, rng)


def test_tracer_counts_the_path_of_every_layer(as_on_a_chip, tracer):
    step, args = _twelve_layer_step(1024)
    step.trace(*args)
    assert tracer.counters == {"attention.kernel_calls": 12,
                               "activation.gelu_erfc_calls": 12,
                               "loss.class_id_calls": 1}


def test_tracer_counts_the_einsum_path_below_the_gate(as_on_a_chip, tracer):
    step, args = _twelve_layer_step(512)
    step.trace(*args)
    assert tracer.counters == {"attention.einsum_calls": 12,
                               "activation.gelu_erfc_calls": 12,
                               "loss.class_id_calls": 1}
