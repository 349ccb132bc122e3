"""Recurrent layers: LSTM / GravesLSTM (peepholes) / SimpleRnn / wrappers.

Reference: ``nn/conf/layers/LSTM.java``, ``GravesLSTM.java``,
``GravesBidirectionalLSTM.java``, ``SimpleRnn``, shared math in
``nn/layers/recurrent/LSTMHelpers.java:58`` (``activateHelper:68``), wrappers
``Bidirectional``, ``LastTimeStep``, ``MaskZeroLayer``. The reference
hand-writes forward+backward per timestep in Java loops; here the recurrence
is one ``lax.scan`` — XLA compiles the whole unrolled graph, and the big
[x,h] @ [W;RW] matmul per step rides the MXU.

Layout: [batch, time, features]; scan runs time-major internally. Gate order
is DL4J's IFOG (input, forget, output, cell-gate). Param names match
``LSTMParamInitializer``: W [n_in, 4H], RW [n_out, 4H] (+3H peephole columns
appended for Graves), b [4H] with forget-gate bias init.

Masking: a [N,T] mask freezes the carried state and zeroes the output at
masked steps (matches DL4J variable-length semantics). TBPTT/stateful
inference use ``forward_seq(params, x, carry)`` which returns the final carry.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations as act_mod
from deeplearning4j_tpu.nn import helpers as _helpers
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


# -- fused-LSTM auto-registration (helpers.set_auto_fused_lstm to opt out) ----
# Region for auto-using PallasLSTMHelper with NO helper registered: long
# sequences with lane-aligned, modest hidden sizes, in the two dtypes the
# kernel is proven to compile for. What is measured: a TIE with stock XLA at
# H=512/T=128 f32 on a v5e (pallas_kernels.py header), and — by
# chip_smoke.py on every run — that the kernel compiles under Mosaic and
# agrees with the XLA scan at H in {128, 256}, T=256, f32 and bf16. What is
# NOT measured: any timing inside this region. T >= 256 and H <= 256 were
# inferred (per-step overhead of the scan should dominate there), so the
# gate is a candidate for ROADMAP S3, not a recorded win.
_AUTO_LSTM_MIN_T = 256
_AUTO_LSTM_MAX_H = 256
_auto_lstm_cache: dict = {}


def _auto_lstm_helper():
    """The auto-fallback candidate, or None off the kernel's target backend
    (on CPU the interpreter would be a slowdown, not a win)."""
    if jax.default_backend() != "tpu":
        return None
    h = _auto_lstm_cache.get("std")
    if h is None:
        from deeplearning4j_tpu.nn.pallas_kernels import PallasLSTMHelper
        h = _auto_lstm_cache["std"] = PallasLSTMHelper()
    return h


def _auto_lstm_win_region(layer, x) -> bool:
    return (x.shape[1] >= _AUTO_LSTM_MIN_T
            and layer.n_out % 128 == 0
            and layer.n_out <= _AUTO_LSTM_MAX_H
            and x.dtype in (jnp.float32, jnp.bfloat16)
            and not _helpers.partitioned_by_compiler(x))


def check_carry_capacity(named_layers, t_total: int, context: str) -> None:
    """Reject sequences longer than any finite carry BEFORE a jitted step
    silently clamps a dynamic_update_slice write. One implementation for all
    host-side loops (TBPTT fit, stateful rnn_time_step, generate)."""
    for label, layer in named_layers:
        if isinstance(layer, BaseRecurrentLayer):
            cap = layer.carry_capacity()
            if cap is not None and t_total > cap:
                raise ValueError(
                    f"{context}: sequence length {t_total} exceeds {label} "
                    f"carry capacity {cap}; raise max_cache/max_len, "
                    f"shorten the sequence, or rnn_clear_previous_state()")


class BaseRecurrentLayer(Layer):
    """Mixin API for layers that carry recurrent state."""

    def init_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError

    def carry_capacity(self):
        """Max total timesteps the carry can absorb, or None if unbounded
        (LSTM-style state). Finite-capacity carries (KV caches, positional
        offsets) report it so host-side loops (TBPTT, generate) can reject
        overlong sequences BEFORE a jitted step silently clamps a
        dynamic_update_slice write."""
        return None

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        """[N,T,C] → ([N,T,H], final_carry)."""
        raise NotImplementedError

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y, _ = self.forward_seq(params, x, carry=None, mask=mask, train=train, rng=rng)
        return y, state or {}

    def input_preprocessor(self, input_type: InputType):
        if input_type.kind == "cnn_seq":
            # image sequences flatten per step for flat-input recurrent layers
            # (ConvLSTM2D overrides this — it consumes [N,T,H,W,C] directly)
            return input_type.cnn_seq_to_rnn()
        return None

    def _scan_seq(self, params, xws, carry, ms):
        """Shared masked scan over time-major precomputed inputs ``xws``
        [T,N,*]; cells implement ``_cell_pre(params, xw_t, carry) ->
        (h, new_carry)``. Masked steps freeze every carry component and zero
        the output (DL4J variable-length semantics) — ONE implementation for
        LSTM/GRU/SimpleRnn so the masking convention cannot drift."""

        def step(c, inp):
            if ms is None:
                h, new_c = self._cell_pre(params, inp, c)
                return new_c, h
            xw_t, m_t = inp
            h, new_c = self._cell_pre(params, xw_t, c)
            m = m_t.reshape(m_t.shape + (1,) * (h.ndim - 1))
            new_c = tuple(m * n + (1 - m) * o for n, o in zip(new_c, c))
            return new_c, h * m

        inputs = xws if ms is None else (xws, ms)
        return lax.scan(step, carry, inputs)


@register_layer
@dataclasses.dataclass
class LSTMLayer(BaseRecurrentLayer, Layer):
    """Standard LSTM (DL4J ``LSTM`` — no peepholes)."""

    n_in: int = 0
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"

    peephole = False

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def param_shapes(self):
        h = self.n_out
        # Graves peepholes live in 3 extra RW *columns* (each [H]), matching
        # DL4J's LSTMParamInitializer layout [nOut, 4*nOut+3]
        rw_cols = 4 * h + (3 if self.peephole else 0)
        return {"W": (self.n_in, 4 * h), "RW": (h, rw_cols), "b": (4 * h,)}

    def init_params(self, rng, dtype=jnp.float32):
        h = self.n_out
        k1, k2, k3 = jax.random.split(rng, 3)
        w = self._init_w(k1, (self.n_in, 4 * h), self.n_in, 4 * h, dtype)
        rw_cols = 4 * h + (3 if self.peephole else 0)
        rw = self._init_w(k2, (h, rw_cols), h, rw_cols, dtype)
        b = jnp.zeros((4 * h,), dtype)
        # forget gate block is [h:2h] in IFOG order
        b = b.at[h:2 * h].set(self.forget_gate_bias_init)
        return {"W": w, "RW": rw, "b": b}

    def init_carry(self, batch: int, dtype=jnp.float32):
        h = self.n_out
        return (jnp.zeros((batch, h), dtype), jnp.zeros((batch, h), dtype))

    def _cell(self, params, x_t, carry):
        return self._cell_pre(params, x_t @ params["W"] + params["b"], carry)

    def _cell_pre(self, params, xw_t, carry):
        """Cell step given the precomputed input projection ``x_t @ W + b``.

        The input projection for ALL timesteps is hoisted out of the scan as
        one [N*T, C] x [C, 4H] MXU matmul (XLA cannot batch matmuls across
        scan iterations); only the recurrent h @ RW matmul stays sequential —
        the same split cuDNN's fused RNN uses."""
        h_prev, c_prev = carry
        H = self.n_out
        gate_act = act_mod.resolve(self.gate_activation)
        cell_act = self.act_fn()
        rw = params["RW"][:, :4 * H]
        z = xw_t + h_prev @ rw
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if self.peephole:
            # per-unit (diagonal) peephole vectors: RW columns 4H, 4H+1, 4H+2
            pi = params["RW"][:, 4 * H]
            pf = params["RW"][:, 4 * H + 1]
            po = params["RW"][:, 4 * H + 2]
            zi = zi + c_prev * pi
            zf = zf + c_prev * pf
        i = gate_act(zi)
        f = gate_act(zf)
        g = cell_act(zg)
        c = f * c_prev + i * g
        if self.peephole:
            zo = zo + c * po
        o = gate_act(zo)
        h = o * cell_act(c)
        return h, (h, c)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        # helper seam (ConvolutionLayer.java:76-84 reflective-load pattern):
        # a registered LSTM helper (e.g. the Pallas fused kernel) takes the
        # sequence pass when it supports this configuration
        helper = _helpers.get_helper("lstm")
        if helper is not None and helper.supports(self, mask):
            return helper.forward_seq(self, params, x, carry)
        if (helper is None and _helpers.auto_fused_lstm_enabled()
                and _auto_lstm_win_region(self, x)):
            # no helper registered: auto-use the fused kernel in its win
            # region (same promotion pattern as the causal-flash fallback in
            # layers/attention.py); opt out via helpers.set_auto_fused_lstm
            cand = _auto_lstm_helper()
            if cand is not None and cand.supports(self, mask):
                return cand.forward_seq(self, params, x, carry)
        n, t, _ = x.shape
        if carry is None:
            carry = self.init_carry(n, x.dtype)
        # hoist the input projection out of the recurrence: one big matmul
        xw = x @ params["W"] + params["b"]           # [N,T,4H] on the MXU
        xws = jnp.swapaxes(xw, 0, 1)                 # [T,N,4H]
        ms = None if mask is None else jnp.swapaxes(mask.astype(x.dtype), 0, 1)  # [T,N]
        final_carry, ys = self._scan_seq(params, xws, carry, ms)
        return jnp.swapaxes(ys, 0, 1), final_carry


@register_layer
@dataclasses.dataclass
class GravesLSTMLayer(LSTMLayer):
    """LSTM with peephole connections (DL4J GravesLSTM)."""

    peephole = True


@register_layer
@dataclasses.dataclass
class SimpleRnnLayer(BaseRecurrentLayer, Layer):
    """Vanilla RNN: h_t = act(x W + h_{t-1} RW + b) (DL4J SimpleRnn)."""

    n_in: int = 0
    n_out: int = 0

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def param_shapes(self):
        return {"W": (self.n_in, self.n_out), "RW": (self.n_out, self.n_out),
                "b": (self.n_out,)}

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        return {
            "W": self._init_w(k1, (self.n_in, self.n_out), self.n_in, self.n_out, dtype),
            "RW": self._init_w(k2, (self.n_out, self.n_out), self.n_out, self.n_out, dtype),
            "b": self._init_b((self.n_out,), dtype),
        }

    def init_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype),)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        n, t, _ = x.shape
        if carry is None:
            carry = self.init_carry(n, x.dtype)
        # input projection hoisted out of the recurrence (one MXU matmul)
        xws = jnp.swapaxes(x @ params["W"] + params["b"], 0, 1)  # [T,N,H]
        ms = None if mask is None else jnp.swapaxes(mask.astype(x.dtype), 0, 1)
        final_carry, ys = self._scan_seq(params, xws, carry, ms)
        return jnp.swapaxes(ys, 0, 1), final_carry

    def _cell_pre(self, params, xw_t, carry):
        (h_prev,) = carry
        h = self.act_fn()(xw_t + h_prev @ params["RW"])
        return h, (h,)


@register_layer
@dataclasses.dataclass
class BidirectionalWrapper(BaseRecurrentLayer, Layer):
    """Bidirectional RNN wrapper (DL4J ``Bidirectional``): runs the wrapped
    recurrent layer forward and on the time-reversed sequence, then combines
    (CONCAT/ADD/MUL/AVERAGE)."""

    layer: Optional[Layer] = None
    mode: str = "concat"  # "concat" | "add" | "mul" | "average"

    def set_n_in(self, input_type: InputType) -> None:
        self.layer.set_n_in(input_type)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        if inner.kind == "cnn_seq":  # ConvLSTM2D: combine over channels
            c = inner.channels * 2 if self.mode == "concat" else inner.channels
            return InputType.recurrent_convolutional(inner.height, inner.width,
                                                     c, inner.timesteps)
        size = inner.size * 2 if self.mode == "concat" else inner.size
        return InputType.recurrent(size, inner.timesteps)

    def apply_global_defaults(self, g):
        super().apply_global_defaults(g)
        if self.layer is not None:
            self.layer.apply_global_defaults(g)

    def input_preprocessor(self, input_type: InputType):
        return self.layer.input_preprocessor(input_type)

    def param_shapes(self):
        inner = self.layer.param_shapes()
        return {f"f_{k}": v for k, v in inner.items()} | {f"b_{k}": v for k, v in inner.items()}

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        fwd = self.layer.init_params(k1, dtype)
        bwd = self.layer.init_params(k2, dtype)
        return {f"f_{k}": v for k, v in fwd.items()} | {f"b_{k}": v for k, v in bwd.items()}

    def init_carry(self, batch: int, dtype=jnp.float32):
        return (self.layer.init_carry(batch, dtype), self.layer.init_carry(batch, dtype))

    @staticmethod
    def _reverse_masked(x, mask):
        if mask is None:
            return jnp.flip(x, axis=1)
        # reverse only the valid prefix per example (DL4J ReverseOp w/ mask):
        lengths = jnp.sum(mask.astype(jnp.int32), axis=1)  # [N]
        t = x.shape[1]
        idx = jnp.arange(t)[None, :]
        rev_idx = jnp.where(idx < lengths[:, None], lengths[:, None] - 1 - idx, idx)
        rev_idx = rev_idx.reshape(rev_idx.shape + (1,) * (x.ndim - 2))
        return jnp.take_along_axis(x, rev_idx, axis=1)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        fwd_p = {k[2:]: v for k, v in params.items() if k.startswith("f_")}
        bwd_p = {k[2:]: v for k, v in params.items() if k.startswith("b_")}
        c_f, c_b = carry if carry is not None else (None, None)
        y_f, cf = self.layer.forward_seq(fwd_p, x, carry=c_f, mask=mask, train=train, rng=rng)
        x_rev = self._reverse_masked(x, mask)
        y_b, cb = self.layer.forward_seq(bwd_p, x_rev, carry=c_b, mask=mask, train=train, rng=rng)
        y_b = self._reverse_masked(y_b, mask)
        m = self.mode.lower()
        if m == "concat":
            y = jnp.concatenate([y_f, y_b], axis=-1)
        elif m == "add":
            y = y_f + y_b
        elif m == "mul":
            y = y_f * y_b
        elif m == "average":
            y = 0.5 * (y_f + y_b)
        else:
            raise ValueError(self.mode)
        return y, (cf, cb)


@register_layer
@dataclasses.dataclass
class GravesBidirectionalLSTMLayer(BidirectionalWrapper):
    """DL4J GravesBidirectionalLSTM = Bidirectional(GravesLSTM, CONCAT) with
    ADD combining in the original; reference default combines via CONCAT in
    new API. We expose n_in/n_out directly for config parity."""

    n_in: int = 0
    n_out: int = 0
    forget_gate_bias_init: float = 1.0

    def __post_init__(self):
        if self.layer is None:
            self.layer = GravesLSTMLayer(n_in=self.n_in, n_out=self.n_out,
                                         forget_gate_bias_init=self.forget_gate_bias_init,
                                         activation=self.activation)
        if self.mode == "concat":
            self.mode = "add"  # DL4J GravesBidirectionalLSTM sums directions

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size
        self.layer.n_in = self.n_in
        self.layer.n_out = self.n_out


@register_layer
@dataclasses.dataclass
class LastTimeStepWrapper(Layer):
    """Wraps a recurrent layer, emitting only the last (unmasked) step
    (DL4J ``LastTimeStep``). Not itself a recurrent layer: output is 2-D, so
    it cannot sit inside a TBPTT chunk chain."""

    layer: Optional[Layer] = None

    def set_n_in(self, input_type: InputType) -> None:
        self.layer.set_n_in(input_type)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        if inner.kind == "cnn_seq":  # e.g. wrapped ConvLSTM2D → one image
            return InputType.convolutional(inner.height, inner.width, inner.channels)
        return InputType.feed_forward(inner.size)

    def apply_global_defaults(self, g):
        super().apply_global_defaults(g)
        if self.layer is not None:
            self.layer.apply_global_defaults(g)

    def input_preprocessor(self, input_type: InputType):
        return self.layer.input_preprocessor(input_type)

    def param_shapes(self):
        return self.layer.param_shapes()

    def init_params(self, rng, dtype=jnp.float32):
        return self.layer.init_params(rng, dtype)

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y, _ = self.layer.forward_seq(params, x, mask=mask, train=train, rng=rng)
        if mask is None:
            out = y[:, -1]
        else:
            lengths = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1), 1)
            idx = (lengths - 1).reshape((-1,) + (1,) * (y.ndim - 1))
            out = jnp.take_along_axis(y, idx, axis=1)[:, 0]
        return out, state or {}


@register_layer
@dataclasses.dataclass
class MaskZeroLayer(BaseRecurrentLayer, Layer):
    """Sets time steps equal to ``mask_value`` in the input to zero activations
    by constructing a mask (DL4J MaskZeroLayer wrapper)."""

    layer: Optional[Layer] = None
    mask_value: float = 0.0

    def set_n_in(self, input_type: InputType) -> None:
        self.layer.set_n_in(input_type)

    def output_type(self, input_type: InputType) -> InputType:
        return self.layer.output_type(input_type)

    def apply_global_defaults(self, g):
        super().apply_global_defaults(g)
        if self.layer is not None:
            self.layer.apply_global_defaults(g)

    def input_preprocessor(self, input_type: InputType):
        return self.layer.input_preprocessor(input_type)

    def param_shapes(self):
        return self.layer.param_shapes()

    def init_params(self, rng, dtype=jnp.float32):
        return self.layer.init_params(rng, dtype)

    def init_carry(self, batch: int, dtype=jnp.float32):
        return self.layer.init_carry(batch, dtype)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        derived = jnp.any(x != self.mask_value, axis=-1).astype(x.dtype)  # [N,T]
        if mask is not None:
            derived = derived * mask.astype(x.dtype)
        return self.layer.forward_seq(params, x, carry=carry, mask=derived,
                                      train=train, rng=rng)


@register_layer
@dataclasses.dataclass
class GRULayer(BaseRecurrentLayer, Layer):
    """GRU with Keras semantics (needed for Keras-import completeness —
    SURVEY.md §7 hard parts; the reference itself predates GRU).

    Gate order z|r|h in the fused matrices (the Keras kernel layout).
    ``reset_after=True`` (Keras 2+ default) applies the reset gate AFTER the
    recurrent matmul and keeps separate input/recurrent biases (b [2, 3H]);
    ``reset_after=False`` is the classic formulation with one bias [3H].
    """

    n_in: int = 0
    n_out: int = 0
    reset_after: bool = True
    gate_activation: str = "sigmoid"

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def param_shapes(self):
        h = self.n_out
        b = (2, 3 * h) if self.reset_after else (3 * h,)
        return {"W": (self.n_in, 3 * h), "RW": (h, 3 * h), "b": b}

    def init_params(self, rng, dtype=jnp.float32):
        h = self.n_out
        k1, k2 = jax.random.split(rng)
        b_shape = (2, 3 * h) if self.reset_after else (3 * h,)
        return {"W": self._init_w(k1, (self.n_in, 3 * h), self.n_in, 3 * h, dtype),
                "RW": self._init_w(k2, (h, 3 * h), h, 3 * h, dtype),
                "b": jnp.zeros(b_shape, dtype)}

    def init_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype),)

    def _cell_pre(self, params, xw_t, carry):
        (h_prev,) = carry
        H = self.n_out
        gate = act_mod.resolve(self.gate_activation)
        act = self.act_fn()
        if self.reset_after:
            rec = h_prev @ params["RW"] + params["b"][1]
            xz, xr, xh = jnp.split(xw_t, 3, axis=-1)
            rz, rr, rh = jnp.split(rec, 3, axis=-1)
            z = gate(xz + rz)
            r = gate(xr + rr)
            hh = act(xh + r * rh)
        else:
            rw = params["RW"]
            xz, xr, xh = jnp.split(xw_t, 3, axis=-1)
            # one fused matmul for the z|r recurrent contributions
            zr = h_prev @ rw[:, :2 * H]
            z = gate(xz + zr[:, :H])
            r = gate(xr + zr[:, H:])
            hh = act(xh + (r * h_prev) @ rw[:, 2 * H:])
        h = z * h_prev + (1.0 - z) * hh
        return h, (h,)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        n, t, _ = x.shape
        if carry is None:
            carry = self.init_carry(n, x.dtype)
        b_in = params["b"][0] if self.reset_after else params["b"]
        # input projection hoisted out of the recurrence (one MXU matmul)
        xws = jnp.swapaxes(x @ params["W"] + b_in, 0, 1)  # [T,N,3H]
        ms = None if mask is None else jnp.swapaxes(mask.astype(x.dtype), 0, 1)
        final_carry, ys = self._scan_seq(params, xws, carry, ms)
        return jnp.swapaxes(ys, 0, 1), final_carry


@register_layer
@dataclasses.dataclass
class ConvLSTM2DLayer(BaseRecurrentLayer, Layer):
    """Convolutional LSTM over image sequences [N, T, H, W, C] (Keras
    ``ConvLSTM2D`` semantics; needed for Keras-import completeness — the
    reference itself has no ConvLSTM, its recurrent family stops at LSTM
    variants, ``nn/conf/layers/``).

    Gates are convolutions instead of matmuls: the input convolution for ALL
    timesteps is hoisted out of the scan as one [N*T,H,W,C] conv (the MXU
    sees one big batched conv); only the recurrent conv of h stays
    sequential. Gate order is IFOG along the channel axis, matching our LSTM,
    so the Keras importer reuses the same i|f|c|o → i|f|o|g reorder.

    Weights: W [kh,kw,C,4F] (input conv, stride/padding per config),
    RW [kh,kw,F,4F] (recurrent conv, always stride 1 / SAME), b [4F].
    """

    n_in: int = 0   # input channels
    n_out: int = 0  # filters
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"  # "truncate" (valid) | "same"
    has_bias: bool = True
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    def __post_init__(self):
        if self.activation is None:
            self.activation = "tanh"
        pair = lambda v: (int(v[0]), int(v[1])) if isinstance(v, (tuple, list)) else (int(v), int(v))
        self.kernel_size = pair(self.kernel_size)
        self.stride = pair(self.stride)
        self.padding = pair(self.padding)
        self.dilation = pair(self.dilation)
        self._out_hw = None  # set by output_type during config build

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.channels

    def input_preprocessor(self, input_type: InputType):
        return None  # consumes [N,T,H,W,C] directly

    def output_type(self, input_type: InputType) -> InputType:
        from deeplearning4j_tpu.nn.layers.conv import conv_out_size
        kh, kw = self.kernel_size
        sh, sw = self.stride
        ph, pw = self.padding
        dh, dw = self.dilation
        h = conv_out_size(input_type.height, kh, sh, ph, dh, self.convolution_mode)
        w = conv_out_size(input_type.width, kw, sw, pw, dw, self.convolution_mode)
        self._out_hw = (h, w)
        return InputType.recurrent_convolutional(h, w, self.n_out,
                                                 input_type.timesteps)

    def param_shapes(self):
        kh, kw = self.kernel_size
        shapes = {"W": (kh, kw, self.n_in, 4 * self.n_out),
                  "RW": (kh, kw, self.n_out, 4 * self.n_out)}
        if self.has_bias:
            shapes["b"] = (4 * self.n_out,)
        return shapes

    def init_params(self, rng, dtype=jnp.float32):
        kh, kw = self.kernel_size
        f = self.n_out
        k1, k2 = jax.random.split(rng)
        p = {"W": self._init_w(k1, (kh, kw, self.n_in, 4 * f),
                               self.n_in * kh * kw, 4 * f * kh * kw, dtype),
             "RW": self._init_w(k2, (kh, kw, f, 4 * f),
                                f * kh * kw, 4 * f * kh * kw, dtype)}
        if self.has_bias:
            b = jnp.zeros((4 * f,), dtype)
            p["b"] = b.at[f:2 * f].set(self.forget_gate_bias_init)
        return p

    def init_carry(self, batch: int, dtype=jnp.float32):
        if self._out_hw is None:
            raise ValueError(
                "ConvLSTM2DLayer carry shape is unknown until output_type() "
                "has run (build the layer inside a network config)")
        h, w = self._out_hw
        z = jnp.zeros((batch, h, w, self.n_out), dtype)
        return (z, z)

    def _padding_spec(self):
        if self.convolution_mode == "same":
            return "SAME"
        ph, pw = self.padding
        return [(ph, ph), (pw, pw)]

    def _cell_pre(self, params, xw_t, carry):
        h_prev, c_prev = carry
        gate = act_mod.resolve(self.gate_activation)
        act = self.act_fn()
        z = xw_t + lax.conv_general_dilated(
            h_prev, params["RW"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        i = gate(zi)
        f = gate(zf)
        g = act(zg)
        c = f * c_prev + i * g
        o = gate(zo)
        h = o * act(c)
        return h, (h, c)

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        n, t = x.shape[:2]
        xf = x.reshape((n * t,) + x.shape[2:])
        z = lax.conv_general_dilated(
            xf, params["W"], window_strides=self.stride,
            padding=self._padding_spec(), rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            z = z + params["b"]
        ho, wo = z.shape[1], z.shape[2]
        xws = jnp.swapaxes(z.reshape(n, t, ho, wo, 4 * self.n_out), 0, 1)
        if carry is None:
            zero = jnp.zeros((n, ho, wo, self.n_out), x.dtype)
            carry = (zero, zero)
        ms = None if mask is None else jnp.swapaxes(mask.astype(x.dtype), 0, 1)
        final_carry, ys = self._scan_seq(params, xws, carry, ms)
        return jnp.swapaxes(ys, 0, 1), final_carry
