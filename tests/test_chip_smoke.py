"""What can be held about the chip path without a chip: chip_smoke.py
refuses the CPU, the compile cache lands where the environment says, and
every Pallas kernel the program can reach lowers for the TPU from here
(the Mosaic module is built; only the chip can compile and run it)."""

import json
import os
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


def test_chip_smoke_result_line_has_the_contract_keys_only():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
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


def test_auto_gates_leave_partitioned_programs_alone(monkeypatch):
    from deeplearning4j_tpu.nn.layers import LSTMLayer
    from deeplearning4j_tpu.nn.layers import attention as A
    from deeplearning4j_tpu.nn.layers import recurrent as R

    class Spy:
        calls = 0

        def supports(self, *a, **k):
            return True

        def attend(self, q, k, v):
            Spy.calls += 1
            return q

    monkeypatch.setattr(A, "_auto_flash_helper", Spy)
    q = jnp.ones((4, 2, 2048, 64))
    attend = jax.jit(lambda q: A.dot_product_attention(q, q, q, causal=True))
    attend(q)
    assert Spy.calls == 1  # one device: the gate opens
    attend(_on_mesh(q)[0])
    assert Spy.calls == 1  # sharded over the mesh: the einsum path

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
    q = jax.ShapeDtypeStruct((1, 12, 2048, 64), dtype)
    assert "_flash_attention_kernel" in _lowers_for_tpu(helper.attend,
                                                        q, q, q)
    grad = jax.grad(lambda q, k, v: helper.attend(q, k, v)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = _lowers_for_tpu(grad, q, q, q)
    assert "_flash_attention_dq_kernel" in text
    assert "_flash_attention_dkv_kernel" in text
