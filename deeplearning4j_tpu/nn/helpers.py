"""Native acceleration helper seam.

Parity with the reference's L1 helper layer (SURVEY.md §1): five helper
interfaces (`ConvolutionHelper.java:35`, `SubsamplingHelper.java:31`,
`LSTMHelper.java:34`, `BatchNormalizationHelper.java:29`,
`LocalResponseNormalizationHelper.java:29`) loaded reflectively by the layer
implementations (`ConvolutionLayer.java:76-84`) so cuDNN can replace the
built-in math. Here the default math IS the compiled fast path (XLA), so
helpers are **opt-in Pallas kernels** registered per kind; layers consult the
registry exactly like the reference's reflective load, and un-registering
restores stock XLA. The validation contract is the reference's too: a helper
must produce the same numbers as the built-in path (`ValidateCudnnLSTM.java`
pattern — see tests/test_helpers.py).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

_HELPERS: Dict[str, object] = {}
_VERSION = 0  # bumped on every registry change; part of every jit cache key

KINDS = ("lstm", "convolution", "subsampling", "batch_norm", "lrn",
         "attention", "updater")


def evict_stale_jit_entries(cache: Dict, current_version: int) -> None:
    """Drop jit-cache entries compiled under an older registry version
    (version-suffixed tuple keys). Shared by MultiLayerNetwork and
    ComputationGraph so the eviction rule lives in one place."""
    for k in [k for k in cache
              if isinstance(k, tuple) and k[-1] != current_version]:
        del cache[k]


def version() -> int:
    """Registry generation. Networks include this in their jit cache keys so
    set/clear AFTER a network has compiled still takes effect on the next
    call (the registry is consulted at trace time)."""
    return _VERSION


def set_helper(kind: str, helper) -> None:
    global _VERSION
    if kind not in KINDS:
        raise ValueError(f"unknown helper kind {kind!r} (expected one of {KINDS})")
    _HELPERS[kind] = helper
    _VERSION += 1


def get_helper(kind: str):
    return _HELPERS.get(kind)


def clear_helper(kind: str) -> None:
    global _VERSION
    if _HELPERS.pop(kind, None) is not None:
        _VERSION += 1


def clear_all_helpers() -> None:
    global _VERSION
    if _HELPERS:
        _VERSION += 1
    _HELPERS.clear()


def partitioned_by_compiler(x) -> bool:
    """True when ``x`` is being traced for a program the compiler will
    partition: its type carries a mesh with more than one device along an
    axis that GSPMD manages, i.e. not one a ``shard_map`` made manual (jit
    puts the mesh of any sharded argument into the type of everything
    computed from it). Mosaic refuses such a program — "Mosaic kernels
    cannot be automatically partitioned" — and only a chip with several
    devices ever shows it, so the expert layer's and the LSTM's auto gates
    ask here before they pick a Pallas kernel; attention asks
    :func:`kernel_shards`, which also says how the kernel may run there."""
    return bool(_mesh_and_compiler_axes(x)[1])


def _mesh_and_compiler_axes(x):
    """The mesh in ``x``'s traced type, and name and size of each of its
    axes that has more than one device and that the compiler still
    manages."""
    import jax
    from jax.sharding import AxisType

    mesh = jax.typeof(x).sharding.mesh
    return mesh, {name: size for name, size, kind in zip(
        mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
        if size > 1 and kind != AxisType.Manual}


class KernelShards(NamedTuple):
    """Where a Pallas kernel over ``[N, H, ...]`` operands runs: on the
    whole operand (``mesh`` is None: no compiler partitions the program),
    or once per shard of ``shape`` under a ``shard_map`` that makes the
    mesh's axes manual round it."""

    mesh: object
    spec: object
    shape: tuple

    def per_shard(self, fn, **keywords):
        """``fn(*operands, **keywords)`` with every operand laid out by
        ``spec`` and each call on one shard; the result is laid out the
        same way."""
        from deeplearning4j_tpu.parallel.mesh import shard_map
        return shard_map(functools.partial(fn, **keywords), mesh=self.mesh,
                         in_specs=self.spec, out_specs=self.spec)


def kernel_shards(x) -> Optional[KernelShards]:
    """**The rule for a kernel under a mesh**, stated here once. ``x`` is
    ``[N, H, ...]``: dimension 0 is split over ``DATA_AXIS`` and dimension
    1 over ``MODEL_AXIS`` (`parallel/mesh.py`: where ``place_batch`` puts
    the batch and ``DEFAULT_2D_RULES`` the head-major ``Wqkv`` columns);
    no other dimension is ever split. That serves a program in which every
    axis the compiler manages (larger than 1, not already manual) is one
    of those two, ``data`` divides N and ``model`` divides H. Any other
    partitioned program (a ``seq``, ``pipe`` or ``expert`` axis larger
    than 1, 20 heads over a ``model`` of 3, a batch ``data`` does not
    divide) gets None: Mosaic would refuse the kernel there, so the caller
    keeps its XLA path. The mesh is read from the traced type, as
    :func:`partitioned_by_compiler` reads it."""
    from jax.sharding import PartitionSpec

    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    mesh, axes = _mesh_and_compiler_axes(x)
    if not axes:
        return KernelShards(None, None, x.shape)
    data, model = axes.pop(DATA_AXIS, 1), axes.pop(MODEL_AXIS, 1)
    if axes or x.shape[0] % data or x.shape[1] % model:
        return None
    return KernelShards(
        mesh,
        PartitionSpec(DATA_AXIS if data > 1 else None,
                      MODEL_AXIS if model > 1 else None),
        (x.shape[0] // data, x.shape[1] // model) + x.shape[2:])


# -- flash-attention auto-registration ---------------------------------------
# When NO attention helper is registered, causal attention on a TPU backend
# at the sequence lengths where the kernel was measured to win
# (layers/attention.py:_AUTO_FLASH_MIN_T and up; PERF.md §6 has the sweep)
# automatically uses the causal PallasFlashAttentionHelper, which skips the
# masked upper triangle the einsum path still computes; in a program the
# compiler partitions it runs per shard where `kernel_shards` has a rule.
# Registering any helper, or set_auto_flash_attention(False), overrides.
_AUTO_FLASH = True


def set_auto_flash_attention(enabled: bool) -> None:
    """Opt out of (or back into) the automatic causal-flash fallback.
    Bumps the registry version so already-compiled networks retrace."""
    global _AUTO_FLASH, _VERSION
    if _AUTO_FLASH != bool(enabled):
        _AUTO_FLASH = bool(enabled)
        _VERSION += 1


def auto_flash_attention_enabled() -> bool:
    return _AUTO_FLASH


# -- fused-LSTM auto-registration ---------------------------------------------
# When NO lstm helper is registered, a standard LSTM on a TPU backend in the
# fused kernel's win region (see layers/recurrent.py:_AUTO_LSTM_MIN_T)
# automatically uses PallasLSTMHelper — same promotion pattern as the causal
# flash fallback above. Registering any lstm helper, or
# set_auto_fused_lstm(False), overrides.
_AUTO_LSTM = True


def set_auto_fused_lstm(enabled: bool) -> None:
    """Opt out of (or back into) the automatic fused-LSTM fallback.
    Bumps the registry version so already-compiled networks retrace."""
    global _AUTO_LSTM, _VERSION
    if _AUTO_LSTM != bool(enabled):
        _AUTO_LSTM = bool(enabled)
        _VERSION += 1


def auto_fused_lstm_enabled() -> bool:
    return _AUTO_LSTM


class LSTMHelper:
    """Interface (`LSTMHelper.java:34`): accelerate the LSTM sequence pass."""

    def supports(self, layer, mask) -> bool:  # pragma: no cover - interface
        return False

    def forward_seq(self, layer, params, x, carry):  # pragma: no cover
        raise NotImplementedError


class UpdaterHelper:
    """Interface for fused optimizer-update kernels (the role ND4J's native
    updater ops play under ``UpdaterBlock.update``). ``apply`` performs the
    WHOLE read-modify-write for one parameter tensor — new param AND new
    updater state — so a kernel implementation can fuse the per-param
    elementwise chain into one launch over donated buffers.

    ``_apply_updates`` consults the seam per parameter at trace time; the
    registry version is part of every train-step jit cache key, so
    registration after compile retraces (same contract as the layer kinds).
    A helper must only accept (``supports``) updaters whose math it
    reproduces within the equivalence tolerance of tests/test_helpers.py."""

    def supports(self, updater, param, grad) -> bool:  # pragma: no cover
        return False

    def apply(self, updater, param, grad, state, lr, t):  # pragma: no cover
        """Returns ``(new_param, new_state)`` for one parameter tensor."""
        raise NotImplementedError


class AttentionHelper:
    """Interface for fused attention kernels (no reference counterpart —
    the snapshot predates attention; same seam pattern as the cuDNN five).

    ``causal`` describes the REQUESTED semantics: a helper must only accept
    a request whose causality matches what its ``attend`` computes, so
    registering any helper can never change model outputs. So does
    ``window`` (a causal query sees its last ``window`` keys only): a
    helper that serves windows takes the keyword in both methods, and one
    that does not name it is never asked about a windowed request
    (:func:`accepts_window`). In a program the compiler partitions,
    ``supports`` is asked about one shard's shape and ``attend`` is given
    one shard at a time (:func:`kernel_shards`), a helper registered by
    hand like the auto gate's."""

    def supports(self, layer, q_shape, mask, dropout_active,
                 causal=False) -> bool:  # pragma: no cover - interface
        return False

    def attend(self, q, k, v):  # pragma: no cover - interface
        raise NotImplementedError


def accepts_window(helper, window) -> bool:
    """Whether ``helper`` may be asked about a request with ``window``:
    always where there is none, else only where its ``supports`` and its
    ``attend`` both take the keyword."""
    import inspect

    return window is None or all(
        "window" in inspect.signature(method).parameters
        for method in (helper.supports, helper.attend))
