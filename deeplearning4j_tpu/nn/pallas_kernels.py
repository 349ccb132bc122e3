"""Pallas TPU kernels — the opt-in fused implementations behind the helper
seam (the cuDNN role, `CudnnLSTMHelper.java:49` / ``cudnnRNNForwardTraining``).

``PallasLSTMHelper`` fuses the whole LSTM recurrence into ONE kernel launch:
the input projection is precomputed as a single MXU matmul outside, then a
sequential grid over time keeps h/c in VMEM scratch across steps — recurrent
matmul + all four gate activations + state update stay in VMEM.
Differentiation is handled with ``jax.custom_vjp``: the backward pass reuses
the reference scan implementation's VJP, so the helper is safe under
``jax.grad``.

Measured on TPU v5e (2x512 LSTM, B=64, T=128, f32): the fused kernel matches
stock XLA scan inference within noise (~6 ms/call both, bit-identical
outputs) — XLA already keeps this recurrence's carry on-chip at these sizes.
The helper seam's value is the cuDNN-parity architecture: an opt-in kernel
slot per layer family, validated by same-math equivalence tests, ready for
shapes/fusions where the compiler does leave perf on the table. (The win
that did generalize — hoisting the input projection out of the scan — lives
in the default path in ``layers/recurrent.py`` and is helper-independent:
1.62x on LSTM training.)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.helpers import (AttentionHelper, LSTMHelper,
                                            UpdaterHelper)


def _lstm_kernel(hidden: int, t_total: int,
                 xw_ref, rw_ref, h0_ref, c0_ref,
                 ys_ref, hn_ref, cn_ref, h_scr, c_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    # gate math in f32 whatever the storage dtype: Mosaic refuses the
    # transcendentals' scalar constants against bf16 vectors, and the v5e
    # VPU/EUP has no bf16 path to lose; h/c round to the storage dtype once
    # per step, as the reference scan's carry does
    f32 = jnp.float32
    z = xw_ref[0].astype(f32) + jnp.dot(h_scr[:], rw_ref[:],
                                        preferred_element_type=f32)
    H = hidden
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H:2 * H])
    o = jax.nn.sigmoid(z[:, 2 * H:3 * H])
    g = jnp.tanh(z[:, 3 * H:])
    c = f * c_scr[:].astype(f32) + i * g
    h = (o * jnp.tanh(c)).astype(h_scr.dtype)
    c = c.astype(c_scr.dtype)
    h_scr[:] = h
    c_scr[:] = c
    ys_ref[0] = h

    @pl.when(t == t_total - 1)
    def _final():
        hn_ref[:] = h
        cn_ref[:] = c


def _lstm_pallas_fwd(xw, rw, h0, c0, *, interpret: bool):
    """xw [T,N,4H] (input projection + bias), rw [H,4H] → (ys [T,N,H], hN, cN)."""
    T, N, H4 = xw.shape
    H = H4 // 4
    grid = (T,)
    return pl.pallas_call(
        functools.partial(_lstm_kernel, H, T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((N, H), lambda t: (0, 0)),
            pl.BlockSpec((N, H), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((N, H), lambda t: (0, 0)),
            pl.BlockSpec((N, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N, H), xw.dtype),
            jax.ShapeDtypeStruct((N, H), xw.dtype),
            jax.ShapeDtypeStruct((N, H), xw.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, H), xw.dtype),
            pltpu.VMEM((N, H), xw.dtype),
        ],
        interpret=interpret,
    )(xw, rw, h0, c0)


def _lstm_ref_scan(xw, rw, h0, c0):
    """Reference recurrence (identical math to LSTMLayer._cell_pre with
    sigmoid gates / tanh cell): supplies the VJP for the pallas forward."""
    H = rw.shape[0]

    def step(carry, xw_t):
        h_prev, c_prev = carry
        z = xw_t + h_prev @ rw
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H:2 * H])
        o = jax.nn.sigmoid(z[:, 2 * H:3 * H])
        g = jnp.tanh(z[:, 3 * H:])
        c = f * c_prev + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (hn, cn), ys = jax.lax.scan(step, (h0, c0), xw)
    return ys, hn, cn


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def lstm_fused(xw, rw, h0, c0, interpret: bool = False):
    return _lstm_pallas_fwd(xw, rw, h0, c0, interpret=interpret)


def _fused_fwd(xw, rw, h0, c0, interpret):
    out = _lstm_pallas_fwd(xw, rw, h0, c0, interpret=interpret)
    return out, (xw, rw, h0, c0)


def _fused_bwd(interpret, res, cts):
    xw, rw, h0, c0 = res
    _, vjp = jax.vjp(_lstm_ref_scan, xw, rw, h0, c0)
    return vjp(tuple(cts))


lstm_fused.defvjp(_fused_fwd, _fused_bwd)


class PallasLSTMHelper(LSTMHelper):
    """Fused-LSTM helper: standard LSTM (sigmoid gates, tanh cell, no
    peepholes, no mask). The kernel is compiled by Mosaic for the TPU;
    ``interpret=True`` runs it in the Pallas interpreter instead and is for
    CPU tests only — it is never chosen from the backend string."""

    def __init__(self, interpret: bool = False):
        self.interpret = interpret

    def supports(self, layer, mask) -> bool:
        return (mask is None
                and not getattr(layer, "peephole", False)
                and layer.gate_activation == "sigmoid"
                and layer.activation in ("tanh",))

    def forward_seq(self, layer, params, x, carry):
        n, t, _ = x.shape
        if carry is None:
            carry = layer.init_carry(n, x.dtype)
        h0, c0 = carry
        xw = jnp.swapaxes(x @ params["W"] + params["b"], 0, 1)  # [T,N,4H]
        rw = params["RW"][:, :4 * layer.n_out]
        ys, hn, cn = lstm_fused(xw, rw, h0, c0, self.interpret)
        return jnp.swapaxes(ys, 0, 1), (hn, cn)


# -- fused optimizer update ---------------------------------------------------
#
# One kernel launch per parameter tensor replaces the stock per-param
# elementwise chain (~10 XLA ops for Adam: two muls+adds for the moments, a
# sqrt, a divide, the bias-corrected step, the subtraction). param/m/v ride
# through ``input_output_aliases`` so the launch is a true in-place
# read-modify-write over the train step's donated buffers. The bias-correction
# scalars (which depend on the traced iteration count) are computed OUTSIDE
# the kernel — identical ops to the stock updater math — and arrive as one
# small SMEM coefficient row, so the kernel body is pure elementwise work on
# (rows, 128) f32 tiles.

_UPD_BLOCK_ROWS = 256  # (256, 128) f32 blocks: 128 KiB per operand in VMEM


def _adam_kernel(amsgrad: bool, coef_ref, *refs):
    # coef row: [beta1, beta2, eps, alpha, 0, 0] where
    # alpha = lr * sqrt(1 - beta2^t) / (1 - beta1^t) (precomputed outside)
    b1, b2, eps, alpha = (coef_ref[0, 0], coef_ref[0, 1], coef_ref[0, 2],
                          coef_ref[0, 3])
    if amsgrad:
        p_ref, m_ref, v_ref, vh_ref, g_ref, po, mo, vo, vho = refs
    else:
        p_ref, m_ref, v_ref, g_ref, po, mo, vo = refs
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    denom = v
    if amsgrad:
        denom = jnp.maximum(vh_ref[...], v)
        vho[...] = denom
    po[...] = p_ref[...] - alpha * m / (jnp.sqrt(denom) + eps)
    mo[...] = m
    vo[...] = v


def _nadam_kernel(coef_ref, p_ref, m_ref, v_ref, g_ref, po, mo, vo):
    # coef row: [beta1, beta2, eps, lr, 1-beta1^t, 1-beta2^t] — the kernel
    # divides by the same (1 - beta^t) denominators the stock path does, so
    # the math is op-for-op identical
    b1, b2, eps, lr = (coef_ref[0, 0], coef_ref[0, 1], coef_ref[0, 2],
                       coef_ref[0, 3])
    om1, om2 = coef_ref[0, 4], coef_ref[0, 5]
    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    m_hat = m / om1
    v_hat = v / om2
    m_bar = b1 * m_hat + (1.0 - b1) * g / om1
    po[...] = p_ref[...] - lr * m_bar / (jnp.sqrt(v_hat) + eps)
    mo[...] = m
    vo[...] = v


def _fused_update_rows(kind: str, coef, bufs, *, interpret: bool):
    """Run the fused update on (R, 128) row-tiled operands.

    ``bufs`` = (p, m, v[, v_hat], g); returns the same tuple minus ``g``,
    updated. All state operands alias their outputs (in-place RMW)."""
    R = bufs[0].shape[0]
    block_r = min(_UPD_BLOCK_ROWS, R)
    grid = (R // block_r,)
    bs = lambda: pl.BlockSpec((block_r, 128), lambda i: (i, 0))  # noqa: E731
    n_state = len(bufs) - 1  # p/m/v(/v_hat) alias; g does not
    kernel = (_nadam_kernel if kind == "nadam"
              else functools.partial(_adam_kernel, kind == "amsgrad"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
                 + [bs() for _ in bufs],
        out_specs=[bs() for _ in range(n_state)],
        out_shape=[jax.ShapeDtypeStruct((R, 128), bufs[0].dtype)
                   for _ in range(n_state)],
        input_output_aliases={1 + i: i for i in range(n_state)},
        interpret=interpret,
    )(coef, *bufs)


class PallasUpdaterHelper(UpdaterHelper):
    """Fused Adam/Nadam/AMSGrad update: new param + new moments in ONE
    kernel launch per parameter tensor, in place over donated buffers.
    Other updater classes (and non-f32 params) fall back to the stock XLA
    chain via ``supports``. The kernel is compiled by Mosaic for the TPU;
    ``interpret=True`` runs it in the Pallas interpreter instead and is for
    CPU tests only — it is never chosen from the backend string."""

    def __init__(self, interpret: bool = False):
        self.interpret = interpret

    def supports(self, updater, param, grad) -> bool:
        from deeplearning4j_tpu.nn.updaters import Adam, AMSGrad, Nadam

        # exact types only: a subclass may override the math the kernel bakes
        if type(updater) not in (Adam, Nadam, AMSGrad):
            return False
        return (param.dtype == jnp.float32
                and getattr(grad, "shape", None) == param.shape
                and param.size > 0)

    @staticmethod
    def _rows(a, block_r):
        """Flatten + zero-pad to (R, 128) with R a multiple of ``block_r``.
        Zero padding is closed under the Adam-family math (moments stay 0,
        sqrt(0)+eps keeps the quotient finite), so padded lanes never
        contaminate real ones."""
        flat = a.reshape(-1)
        n = flat.shape[0]
        rows = -(-n // 128)
        r_pad = -(-rows // block_r) * block_r
        pad = r_pad * 128 - n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(r_pad, 128)

    def apply(self, updater, param, grad, state, lr, t):
        from deeplearning4j_tpu.nn.updaters import AMSGrad, Nadam

        f32 = jnp.float32
        b1 = jnp.asarray(updater.beta1, f32)
        b2 = jnp.asarray(updater.beta2, f32)
        eps = jnp.asarray(updater.epsilon, f32)
        lr = jnp.asarray(lr, f32)
        t = jnp.asarray(t, f32)
        om1 = 1.0 - updater.beta1 ** t  # same exponentiation as the stock path
        om2 = 1.0 - updater.beta2 ** t
        if isinstance(updater, Nadam):
            kind = "nadam"
            coef = jnp.stack([b1, b2, eps, lr, om1, om2])
            names = ("m", "v")
        else:
            kind = "amsgrad" if isinstance(updater, AMSGrad) else "adam"
            alpha = lr * jnp.sqrt(om2) / om1
            coef = jnp.stack([b1, b2, eps, alpha, jnp.zeros((), f32),
                              jnp.zeros((), f32)])
            names = ("m", "v", "v_hat") if kind == "amsgrad" else ("m", "v")

        rows = -(-param.size // 128)
        block_r = min(_UPD_BLOCK_ROWS, -(-rows // 8) * 8)  # f32 tile: 8 rows
        to_rows = lambda a: self._rows(a, block_r)  # noqa: E731
        bufs = ([to_rows(param)] + [to_rows(state[n]) for n in names]
                + [to_rows(grad.astype(param.dtype))])
        outs = _fused_update_rows(kind, coef.reshape(1, 6), tuple(bufs),
                                  interpret=self.interpret)
        unrows = lambda a: a.reshape(-1)[:param.size].reshape(param.shape)  # noqa: E731
        new_param = unrows(outs[0])
        new_state = {n: unrows(outs[1 + i]) for i, n in enumerate(names)}
        return new_param, new_state


class PallasFlashAttentionHelper(AttentionHelper):
    """Blockwise (flash) attention via the Pallas TPU kernel bundled with
    jax (`jax.experimental.pallas.ops.tpu.flash_attention`) — O(T) memory
    instead of materializing the [N,H,T,T] score matrix, with the module's
    own custom VJP for the backward.

    With the tuned 512-wide block sizes below (measured v5e, 8 heads, dh=64,
    forward): flash beats the einsum path 1.9x at T=8192 (15.9 vs 29.9 ms),
    1.1x at T=4096, and ties at T=1024-2048 — while keeping memory linear in
    T instead of the einsum path's O(T^2) score matrix. Default block sizes
    were 2.5x worse than tuned at T=8192; re-measure per TPU generation.

    Conservative support gate: TPU backend, no mask, no attention dropout,
    sequence length a multiple of 128, head dim in {64, 128, 256} (the tile
    shapes the kernel is built for); everything else falls back to the
    built-in einsum attention.
    """

    def __init__(self, causal: bool = False):
        self.causal = causal

    def supports(self, layer, q_shape, mask, dropout_active,
                 causal=False) -> bool:
        if jax.default_backend() != "tpu":
            return False
        if causal != self.causal:
            # semantics must match the request exactly: a causal kernel must
            # not serve a bidirectional layer and vice versa
            return False
        if mask is not None or dropout_active:
            return False
        t, dh = q_shape[-2], q_shape[-1]
        return t % 128 == 0 and dh in (64, 128, 256)

    @staticmethod
    def _block_sizes(t: int):
        from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

        b = next(c for c in (512, 256, 128) if t % c == 0)
        return BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=1,
            block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
            block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)

    def attend(self, q, k, v):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)

        scale = float(1.0 / (q.shape[-1] ** 0.5))
        return flash_attention(q, k, v, causal=self.causal, sm_scale=scale,
                               block_sizes=self._block_sizes(q.shape[-2]))
