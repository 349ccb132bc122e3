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


def _on_mesh(x):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec("data")))


@pytest.mark.parametrize("t,how,kernel", [
    (2048, "plain", True),
    (1024, "plain", True),
    (512, "plain", False),
    (1024, "mask", False),
    (1024, "dropout", False),
    (1024, "partitioned", False),
    (1024, "not causal", False),
])
def test_auto_gate_by_length_and_request(monkeypatch, as_on_a_chip, tracer,
                                         t, how, kernel):
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
    assert len(calls) == int(kernel)
    assert tracer.counters == {
        "attention.kernel_calls" if kernel else "attention.einsum_calls": 1}


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
