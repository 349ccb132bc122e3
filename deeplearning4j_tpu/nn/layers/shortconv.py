"""Gated short convolution: the sequence operator of the LFM2 family's
``conv`` layers.

No reference counterpart. A token mixes with the ``kernel_size - 1`` tokens
before it through a depthwise causal convolution, between two elementwise
gates computed from the same input projection::

    [B, C, X] = split3(x W_in)        u = B * X
    v_t = sum_j K[:, j] * u_{t - (L-1) + j}      (zeros left of the sequence)
    y = (C * v) W_out

The convolution is ``kernel_size`` shifted multiply-adds, which XLA fuses
with the gates; there is no kernel of its own yet.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import register_layer
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer


@register_layer
@dataclasses.dataclass
class GatedShortConvLayer(BaseRecurrentLayer):
    """[N,T,n_in] → [N,T,n_out]; the gates and the convolution are ``n_out``
    wide. The carry of the stateful path (``rnn_time_step``, TBPTT) is the
    last ``kernel_size - 1`` rows of ``u``, so a sequence fed in pieces
    gives what the whole sequence gives."""

    n_in: int = 0
    n_out: int = 0
    kernel_size: int = 3

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size
        if not self.n_out:
            self.n_out = self.n_in

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def param_shapes(self):
        return {"Win": (self.n_in, 3 * self.n_out),
                "K": (self.n_out, self.kernel_size),
                "Wout": (self.n_out, self.n_out)}

    def init_params(self, rng, dtype=jnp.float32):
        k_in, k_conv, k_out = jax.random.split(rng, 3)
        width, taps = self.n_out, self.kernel_size
        return {"Win": self._init_w(k_in, (self.n_in, 3 * width), self.n_in,
                                    3 * width, dtype),
                "K": self._init_w(k_conv, (width, taps), taps, 1, dtype),
                "Wout": self._init_w(k_out, (width, width), width, width,
                                     dtype)}

    def init_carry(self, batch: int, dtype=jnp.float32):
        return jnp.zeros((batch, self.kernel_size - 1, self.n_out), dtype)

    def forward_seq(self, params, x, carry=None, mask=None, train=False,
                    rng=None):
        t, taps = x.shape[1], self.kernel_size
        gate_b, gate_c, inner = jnp.split(x @ params["Win"], 3, axis=-1)
        u = gate_b * inner
        before = (jnp.zeros((x.shape[0], taps - 1, self.n_out), u.dtype)
                  if carry is None else carry.astype(u.dtype))
        padded = jnp.concatenate([before, u], axis=1)
        v = sum(params["K"][:, j] * padded[:, j:j + t] for j in range(taps))
        y = (gate_c * v) @ params["Wout"]
        return self.act_fn()(y), padded[:, t:]
