"""What can be held about the chip path without a chip: chip_smoke.py
refuses the CPU, the compile cache lands where the environment says, and
every Pallas kernel the program can reach lowers for the TPU from here
(the Mosaic module is built; only the chip can compile and run it)."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.util import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert "platform=cpu" in r.stdout  # the facts are printed first
    assert not r.stdout.rstrip().endswith("}")  # and no result line


def _chip_smoke_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_result_line_has_the_contract_keys_only():
    mod = _chip_smoke_module()
    dev = jax.devices()[0]
    line = mod.result_line({"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(jax.devices())})
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True, "device": {"platform": dev.platform,
                                          "kind": dev.device_kind,
                                          "count": len(jax.devices())}}


# --------------------------------------------------- cache dir resolution
@pytest.fixture
def cache_state(monkeypatch):
    """Fresh module state, and every jax.config.update recorded, not made."""
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    return updates


def test_cache_dir_from_environment(cache_state, monkeypatch, tmp_path):
    placed = tmp_path / "placed"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(placed))
    assert compile_cache.enable_persistent_compile_cache() == str(placed)
    assert placed.is_dir()
    # JAX reads the variable itself; only the two floors are lowered
    assert sorted(n for n, _ in cache_state) == [
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"]
    assert compile_cache.enable_persistent_compile_cache(str(placed)) \
        == str(placed)
    with pytest.raises(ValueError, match=compile_cache.ENV_VAR):
        compile_cache.enable_persistent_compile_cache(str(tmp_path / "other"))


def test_cache_dir_default_is_fixed_under_the_checkout(cache_state,
                                                       monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.default_cache_dir() == os.path.join(REPO,
                                                             ".jax_cache")
    assert compile_cache.default_cache_dir() \
        == compile_cache.default_cache_dir()
    # on the CPU backend the default directory is not switched on
    assert compile_cache.enable_persistent_compile_cache() is None
    assert cache_state == []
    # on an accelerator it is
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable_persistent_compile_cache() \
        == compile_cache.default_cache_dir()
    assert ("jax_compilation_cache_dir",
            compile_cache.default_cache_dir()) in cache_state


# ------------------------------------ kernels and the partitioning compiler
def _on_mesh(x, spec=("data",)):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec))), mesh


def test_partitioned_by_compiler_reads_the_traced_type():
    """Found on four real chips: 'Mosaic kernels cannot be automatically
    partitioned'. jit puts the mesh of a sharded argument into the type of
    everything computed from it; a shard_map makes the axes manual."""
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    seen = {}

    def probe(tag):
        def f(x):
            seen[tag] = helpers.partitioned_by_compiler(x * 2.0)
            return x
        return f

    x = jnp.ones((8, 4))
    xs, mesh = _on_mesh(x)
    jax.jit(probe("one device"))(x)
    jax.jit(probe("sharded"))(xs)
    jax.jit(shard_map(probe("shard_map"), mesh=mesh,
                      in_specs=P(("data", "model")),
                      out_specs=P(("data", "model"))))(xs)
    assert seen == {"one device": False, "sharded": True,
                    "shard_map": False}


def test_auto_gates_under_a_partitioned_program(monkeypatch):
    """Attention's gate hands the kernel one shard at a time, under manual
    axes (`helpers.kernel_shards`); the LSTM's still stands aside."""
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.layers import LSTMLayer
    from deeplearning4j_tpu.nn.layers import attention as A
    from deeplearning4j_tpu.nn.layers import recurrent as R

    class Spy:
        seen = []

        def supports(self, layer, q_shape, *a, **k):
            return True

        def attend(self, q, k, v):
            Spy.seen.append((q.shape, helpers.partitioned_by_compiler(q)))
            return q

    monkeypatch.setattr(A, "_auto_flash_helper", Spy)
    q = jnp.ones((4, 2, 2048, 64))
    attend = jax.jit(lambda q: A.dot_product_attention(q, q, q, causal=True))
    attend(q)
    assert Spy.seen == [((4, 2, 2048, 64), False)]  # one device: whole
    out = attend(_on_mesh(q)[0])
    # over data=2 x model=2: a shard of batch and heads, and no axis left
    # to the compiler round the kernel
    assert Spy.seen[1:] == [((2, 1, 2048, 64), False)]
    assert out.shape == q.shape

    layer = LSTMLayer(n_in=8, n_out=128)
    region = {}

    def lstm(tag):
        def f(x):
            region[tag] = R._auto_lstm_win_region(layer, x)
            return x
        return f

    x = jnp.ones((4, 256, 8))
    jax.jit(lstm("one device"))(x)
    jax.jit(lstm("sharded"))(_on_mesh(x)[0])
    assert region == {"one device": True, "sharded": False}


# ------------------------------------------------------ cross-lowering
def _lowers_for_tpu(fn, *args) -> str:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


def _kernel_calls(text: str) -> list:
    """The Mosaic kernels of a lowered program in order: `(name, operand
    types)` of each `tpu_custom_call`."""
    return [(name, re.findall(r"tensor<[^>]*>", operands))
            for name, operands in re.findall(
                r'custom_call @tpu_custom_call.*?kernel_name = "(\w+)"'
                r'.*? : \((.*?)\) -> ', text)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hidden", [128, 256])
def test_fused_lstm_lowers_for_tpu(dtype, hidden):
    """bf16 was refused before the gate math moved to f32: 'vector.broadcast'
    (f32) -> vector<16x256xbf16>. The auto gate admits both dtypes."""
    from deeplearning4j_tpu.nn.layers import LSTMLayer
    from deeplearning4j_tpu.nn.layers import recurrent as R
    from deeplearning4j_tpu.nn.pallas_kernels import lstm_fused

    t, n = 256, 16
    assert R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=hidden),
                                   jnp.zeros((n, t, 8), dtype))
    xw = jax.ShapeDtypeStruct((t, n, 4 * hidden), dtype)
    rw = jax.ShapeDtypeStruct((hidden, 4 * hidden), dtype)
    hc = jax.ShapeDtypeStruct((n, hidden), dtype)
    _lowers_for_tpu(lambda *a: lstm_fused(*a, False), xw, rw, hc, hc)


def test_auto_lstm_gate_refuses_other_dtypes():
    from deeplearning4j_tpu.nn.layers import LSTMLayer
    from deeplearning4j_tpu.nn.layers import recurrent as R
    assert not R._auto_lstm_win_region(LSTMLayer(n_in=8, n_out=128),
                                       jnp.zeros((2, 256, 8), jnp.float16))


@pytest.mark.parametrize("shape", [(768, 3072), (5,)])
@pytest.mark.parametrize("name", ["Adam", "Nadam", "AMSGrad"])
def test_fused_updater_lowers_for_tpu(name, shape):
    import deeplearning4j_tpu.nn.updaters as U
    from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper

    helper = PallasUpdaterHelper()
    assert helper.interpret is False  # the interpreter is never a default
    u = getattr(U, name)(1e-3)
    p = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert helper.supports(u, p, p)
    state = {"m": p, "v": p}
    if name == "AMSGrad":
        state["v_hat"] = p
    _lowers_for_tpu(lambda p, g, s: helper.apply(u, p, g, s, 1e-3, 3.0),
                    p, p, state)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_lowers_for_tpu(causal, dtype):
    from deeplearning4j_tpu.nn.pallas_kernels import (
        PallasFlashAttentionHelper)

    helper = PallasFlashAttentionHelper(causal=causal)
    assert helper.interpret is False  # the interpreter is never a default
    q = jax.ShapeDtypeStruct((1, 12, 2048, 64), dtype)
    calls = _kernel_calls(_lowers_for_tpu(helper.attend, q, q, q))
    assert [name for name, _ in calls] == ["splash_mha_fwd_no_residuals"]
    text = _lowers_for_tpu(
        jax.value_and_grad(lambda q, k, v: helper.attend(q, k, v)
                           .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        q, q, q)
    # the kernels' metadata stays on its instruction's line (a newline is
    # `\0A` here): the benchmark reads the compiled step line by line
    assert "kernel_metadata" in text and "\\0A" not in text
    calls = _kernel_calls(text)
    # one forward and one fused backward kernel (dQ, dK, dV together) ...
    assert [name for name, _ in calls] == ["splash_mha_fwd_residuals",
                                           "splash_mha_dkv_no_residuals"]
    # the name chip_smoke.py's train phase looks for in the lowered step
    assert calls[0][0] == _chip_smoke_module().FLASH_KERNEL_IN_STEP
    # ... which reads the log-sum-exp and di as rows (over 8 sublanes),
    # not spread to 128 lanes as the kernel before it did
    operands = calls[1][1]
    assert operands.count("tensor<12x8x2048xf32>") == 2
    assert not [t for t in operands if t.endswith("x128xf32>")]


def test_expert_grouped_products_lower_for_tpu(monkeypatch):
    """The sparse expert layer at LFM2-8B-A1B's widths (8 held experts of
    2048 x 1792, 8,192 tokens x 4): on a TPU its nine grouped products are
    megablox kernels, three forward (`gmm`) and, backward, three for the
    rows (`gmm`) and three for the weights (`tgmm`); float32 and the CPU
    take `ragged_dot`. With the kernels the buffers the loops fill (the
    gathered rows, forward and again backward, and the gated product) start
    unwritten: a kernel with no body and no operand each, in place of a
    memset."""
    from deeplearning4j_tpu.nn.layers import MixtureOfExpertsLayer
    from deeplearning4j_tpu.nn.layers import moe

    layer = MixtureOfExpertsLayer(
        n_in=2048, n_out=2048, n_hidden=1792, n_experts=32, top_k=4,
        gated=True, activation="silu", gate="sigmoid", expert_bias=True,
        norm_topk=True, experts_held=(0, 8))
    dtype_of = lambda n: (jnp.float32 if n in layer.float32_params
                          else jnp.bfloat16)
    params = {n: jax.ShapeDtypeStruct(s, dtype_of(n))
              for n, s in layer.param_shapes().items()}
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    rows = jax.ShapeDtypeStruct((32768, 2048), jnp.bfloat16)
    assert moe._megablox_tiling(rows, params["W1"]) is None     # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._megablox_tiling(rows, params["W1"]) == (256, 1024, 1024)
    assert moe._megablox_tiling(
        jax.ShapeDtypeStruct((32768, 2048), jnp.float32), params["W1"]) is None
    assert moe._megablox_tiling(
        jax.ShapeDtypeStruct((100, 2048), jnp.bfloat16), params["W1"]) is None
    loss = lambda p, xx: layer.forward(p, xx)[0].astype(jnp.float32).sum()
    text = _lowers_for_tpu(jax.value_and_grad(loss, argnums=(0, 1)),
                           params, x)
    # a jitted kernel is one function of the lowered module however often it
    # is called: W1 and W3 share theirs, forward and backward apart
    calls = _kernel_calls(text)
    assert [operands for name, operands in calls if name == "unwritten"] \
        == [[], [], []]
    assert len(calls) == 6 + 3
    assert len(re.findall(r"call @gmm", text)) == 6
    assert len(re.findall(r"call @tgmm", text)) == 3


# ------------------------------------------- the kernels under the mesh
def _two_layer_step_on_the_2x2(t):
    """The train step of a two-layer causal LM (Dh=64, four heads) placed
    on data=2 x model=2 by `DEFAULT_2D_RULES`, and its arguments as `fit()`
    would pass them; nothing has run."""
    import numpy as np

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.sharding import (place_batch,
                                                      shard_model_with_rules)
    from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

    mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    net = ComputationGraph(TransformerLM(
        vocab_size=32, max_length=t, n_layers=2, d_model=256, n_heads=4,
        d_ff=256, seed=3).conf()).init()
    shard_model_with_rules(net, mesh)
    tokens = np.zeros((4, t), np.int32)
    it, ep, rng = net._device_tick()
    return net._get_train_step(), (
        net.params, net.states, net.updater_states, it, ep,
        {"tokens": place_batch(jnp.asarray(tokens), mesh)},
        [place_batch(jnp.asarray(lm_labels(tokens, 32)), mesh)],
        None, None, rng)


def test_train_step_on_the_2x2_lowers_with_two_kernels_a_layer(monkeypatch):
    """At the gate's T the partitioned step holds a forward and a fused
    backward kernel per layer, each on a shard of [2, 2, 1024, 64]."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args = _two_layer_step_on_the_2x2(1024)
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    # a function that holds a kernel is lowered once and called by each
    # layer
    runs = []
    for body in re.split(r"\n  func\.func ", text)[1:]:
        func = re.match(r"(?:private |public )?@(\w+)", body).group(1)
        calls = _kernel_calls(body)
        runs += [name for name, _ in calls] * len(
            re.findall(rf"call @{func}\b", text))
        assert all("tensor<2x2x1024x64xf32>" in operands
                   for _, operands in calls), calls
    assert sorted(runs) == ["splash_mha_dkv_no_residuals"] * 2 + [
        "splash_mha_fwd_residuals"] * 2


def test_kernels_under_the_mesh_add_no_collective(monkeypatch):
    """The `shard_map`'s layout is the one the compiler had chosen for q, k
    and v (batch over `data`, heads over `model`): compiled for the host's
    2x2 with the interpreter's kernel in the einsum's place, the step has
    no more collectives of any kind."""
    from deeplearning4j_tpu import observe
    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.pallas_kernels import (
        PallasFlashAttentionHelper)

    def collectives(tracer):
        step, args = _two_layer_step_on_the_2x2(128)
        text = step.lower(*args).compile().as_text()
        counted = dict(tracer.counters)
        tracer.counters.clear()
        return counted, {kind: len(re.findall(rf"\b{kind}(?:-start)?\(", text))
                         for kind in ("all-reduce", "all-gather",
                                      "all-to-all", "collective-permute",
                                      "reduce-scatter")}

    tracer = observe.enable_tracing(jax_hook=False)
    try:
        path, einsum = collectives(tracer)
        assert path["attention.einsum_calls"] == 2
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        helpers.set_helper("attention", PallasFlashAttentionHelper(
            causal=True, interpret=True))
        try:
            path, kernel = collectives(tracer)
        finally:
            helpers.clear_helper("attention")
        assert path["attention.sharded_kernel_calls"] == 2
    finally:
        observe.disable_tracing()
    assert einsum["all-reduce"] > 0
    assert all(kernel[kind] <= einsum[kind] for kind in einsum), (kernel,
                                                                  einsum)
