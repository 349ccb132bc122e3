"""Self-attention layers.

The reference snapshot has no attention layer (SURVEY.md §5 long-context), but
BASELINE.json's BERT-import config requires attention ops; DL4J's later
releases added ``SelfAttentionLayer``/``LearnedSelfAttentionLayer`` on
SameDiff. Built TPU-first: one fused QKV projection (single MXU matmul),
scaled dot-product attention with optional masking, bf16-friendly. The op is
sequence-shardable — see ``parallel/ring.py`` for the ring-attention variant
used under sequence parallelism.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.observe import trace as _trace


#: shortest causal sequence the auto gate hands to the kernel: the sweep
#: that set it, and every speed on either side of it, is in PERF.md (§6)
_AUTO_FLASH_MIN_T = 1024
_auto_flash_cache: dict = {}


def _auto_flash_helper():
    h = _auto_flash_cache.get("causal")
    if h is None:
        from deeplearning4j_tpu.nn.pallas_kernels import (
            PallasFlashAttentionHelper)
        h = _auto_flash_cache["causal"] = PallasFlashAttentionHelper(
            causal=True)
    return h


def dot_product_attention(q, k, v, mask=None, dropout_rate=0.0, rng=None,
                          train=False, causal=False, window=None):
    """q,k,v: [N, H, T, Dh]; mask: [N, T] (1=valid) or [N, 1, Tq, Tk];
    ``causal=True`` additionally lower-triangular-masks the scores, and
    with ``window=W`` a query sees only the last ``W`` keys up to its own:
    ``0 <= q - k < W``. A window that covers the sequence is plain causal
    attention, and is treated as such from here on.

    Consults the "attention" helper seam first: a registered fused kernel
    takes the requests it supports, causality and window included, and in a
    program the compiler partitions one shard of batch and heads at a time
    (`helpers.kernel_shards` has the rule); else the einsum path below runs.
    """
    from deeplearning4j_tpu.nn import helpers as _helpers
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window!r} needs causal=True and at "
                             f"least one key")
        if window >= k.shape[-2]:
            window = None
    helper = _helpers.get_helper("attention")
    dropout_active = bool(train and dropout_rate > 0 and rng is not None)
    if (helper is None and causal and q.shape[-2] >= _AUTO_FLASH_MIN_T
            and _helpers.auto_flash_attention_enabled()):
        # no helper registered: the causal kernel serves the lengths at
        # which it was measured to win (PERF.md §6), seam known or not
        helper = _auto_flash_helper()
    # a helper that knows no window is never given one
    windowed = {} if window is None else {"window": window}
    shards = None if helper is None else _helpers.kernel_shards(q)
    kernel = (shards is not None
              and _helpers.accepts_window(helper, window)
              and helper.supports(None, shards.shape, mask, dropout_active,
                                  causal=causal, **windowed)
              and q.shape == k.shape == v.shape)
    tracer = _trace.get_active_tracer()
    if tracer is not None:
        # which path this call took, counted while its step is traced
        path = "kernel_calls" if kernel else "einsum_calls"
        tracer.count("attention." + path)
        if window is not None:
            tracer.count("attention.window_" + path)
        if kernel and shards.mesh is not None:
            tracer.count("attention.sharded_kernel_calls")
    if kernel and shards.mesh is not None:
        return shards.per_shard(helper.attend, **windowed)(q, k, v)
    if kernel:
        return helper.attend(q, k, v, **windowed)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
    m = None
    if mask is not None:
        m = (mask[:, None, None, :] if mask.ndim == 2 else mask) > 0
    if causal:
        tri = jnp.tril(jnp.ones((q.shape[-2], k.shape[-2]), bool))
        if window is not None:
            tri = jnp.logical_and(tri, jnp.logical_not(jnp.tril(tri, -window)))
        tri = tri[None, None]
        m = tri if m is None else jnp.logical_and(m, tri)
    if m is not None:
        scores = jnp.where(m, scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores, axis=-1)
    if train and dropout_rate > 0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("nhqk,nhkd->nhqd", w, v)


@register_layer
@dataclasses.dataclass
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over [N,T,C] with optional output projection."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None
    project_input: bool = True
    attn_dropout: float = 0.0
    causal: bool = False

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size
        if not self.n_out:
            self.n_out = self.n_in

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def _dh(self):
        return self.head_size or self.n_out // self.n_heads

    def param_shapes(self):
        # Wqkv columns are HEAD-MAJOR [H, 3, Dh] (each head's q|k|v block
        # contiguous), NOT [3, H, Dh]: a column-sharded Wqkv then propagates
        # through the (n,t,h,3,dh) reshape under GSPMD whenever tp divides
        # n_heads, keeping tensor-parallel attention at one all-reduce per
        # block. The [3,H,Dh] order measured 5 extra qkv all-gathers on a
        # tp=4 mesh (tests/test_parallel.py::test_attention_collectives).
        dh = self._dh()
        inner = self.n_heads * dh
        shapes = {"Wqkv": (self.n_in, 3 * inner), "bqkv": (3 * inner,)}
        if self.project_input:
            shapes["Wo"] = (inner, self.n_out)
            shapes["bo"] = (self.n_out,)
        return shapes

    def init_params(self, rng, dtype=jnp.float32):
        dh = self._dh()
        inner = self.n_heads * dh
        if not self.project_input and inner != self.n_out:
            raise ValueError(
                f"project_input=False requires n_heads*head_size == n_out "
                f"(got {inner} != {self.n_out})")
        k1, k2 = jax.random.split(rng)
        p = {
            "Wqkv": self._init_w(k1, (self.n_in, 3 * inner), self.n_in, 3 * inner, dtype),
            "bqkv": jnp.zeros((3 * inner,), dtype),
        }
        if self.project_input:
            p["Wo"] = self._init_w(k2, (inner, self.n_out), inner, self.n_out, dtype)
            p["bo"] = jnp.zeros((self.n_out,), dtype)
        return p

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        n, t, _ = x.shape
        h, dh = self.n_heads, self._dh()
        qkv = x @ params["Wqkv"] + params["bqkv"]              # [N,T,H*3*Dh]
        qkv = qkv.reshape(n, t, h, 3, dh).transpose(3, 0, 2, 1, 4)  # [3,N,H,T,Dh]
        q, k, v = qkv[0], qkv[1], qkv[2]
        out = dot_product_attention(q, k, v, mask=mask, causal=self.causal,
                                    dropout_rate=self.attn_dropout,
                                    rng=rng, train=train)
        y = out.transpose(0, 2, 1, 3).reshape(n, t, h * dh)
        if self.project_input:
            y = y @ params["Wo"] + params["bo"]
        return self.act_fn()(y), state or {}


@register_layer
@dataclasses.dataclass
class CausalSelfAttentionLayer(SelfAttentionLayer, BaseRecurrentLayer):
    """Causal (autoregressive) multi-head self-attention.

    No reference counterpart — the snapshot predates attention (SURVEY.md §5);
    this is the decoder-side twin of :class:`SelfAttentionLayer`, required for
    the text-generation transformer in the zoo. Two execution modes:

    - ``forward`` (training / full-sequence): one fused QKV matmul, scores
      masked with the lower-triangular causal mask ∧ the padding mask. XLA
      fuses mask+softmax into the attention einsums.
    - ``forward_seq`` with a carry (stateful decoding via ``rnn_time_step``):
      a fixed-capacity KV cache — (k_cache, v_cache, key_validity, position),
      all static shapes so the step jits once and new tokens are written with
      ``lax.dynamic_update_slice``. Decoding T new tokens costs O(T·max_cache)
      instead of re-running the full quadratic attention per step.

    The carry rides the same ``BaseRecurrentLayer`` protocol the LSTMs use, so
    ``MultiLayerNetwork.rnn_time_step`` / ``ComputationGraph.rnn_time_step``
    (rnnTimeStep:2800 parity) and TBPTT chunking (the chunk attends over all
    cached previous chunks, Transformer-XL style) work unchanged.
    """

    max_cache: int = 512
    causal: bool = True  # full-sequence forward = SelfAttentionLayer's, masked

    # ------------------------------------------------- stateful decode path
    def carry_capacity(self):
        return self.max_cache

    def _kv_heads(self) -> int:
        """Heads the KV cache holds: a subclass with grouped heads has fewer
        than ``n_heads``, each serving a group of query heads."""
        return self.n_heads

    def _qkv(self, params, x, positions):
        """q ``[N,H,T,Dh]`` and k, v ``[N,Hkv,T,Dh]`` of the tokens ``x``
        at ``positions`` (which only a layer with rotary positions reads)."""
        n, t, _ = x.shape
        qkv = x @ params["Wqkv"] + params["bqkv"]
        qkv = qkv.reshape(n, t, self.n_heads, 3, self._dh())
        qkv = qkv.transpose(3, 0, 2, 1, 4)
        return qkv[0], qkv[1], qkv[2]

    def _project(self, params, out, x):
        """``[N,H,T,Dh]`` attention output to the layer's ``[N,T,n_out]``;
        ``x`` is the layer's input (which only a gated subclass reads)."""
        n, h, t, dh = out.shape
        y = out.transpose(0, 2, 1, 3).reshape(n, t, h * dh)
        if self.project_input:
            y = y @ params["Wo"] + params["bo"]
        return self.act_fn()(y)

    def init_carry(self, batch: int, dtype=jnp.float32):
        h, dh, tc = self._kv_heads(), self._dh(), self.max_cache
        return (jnp.zeros((batch, h, tc, dh), dtype),   # K cache
                jnp.zeros((batch, h, tc, dh), dtype),   # V cache
                jnp.zeros((batch, tc), dtype),          # key validity
                jnp.zeros((), jnp.int32))               # write position

    def forward_seq(self, params, x, carry=None, mask=None, train=False, rng=None):
        if carry is None:
            y, _ = self.forward(params, x, train=train, rng=rng, mask=mask)
            return y, None
        n, t, _ = x.shape
        hkv, dh, tc = self._kv_heads(), self._dh(), self.max_cache
        kc, vc, valid, pos = carry
        if not isinstance(pos, jax.core.Tracer) and int(pos) + t > tc:
            raise ValueError(
                f"KV cache overflow: writing {t} token(s) at position "
                f"{int(pos)} exceeds max_cache={tc}; raise max_cache or "
                f"rnn_clear_previous_state() first")
        q, k, v = self._qkv(params, x, pos + jnp.arange(t))
        zero = jnp.zeros((), pos.dtype)  # match pos dtype (x64 mode safe)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (zero, zero, pos, zero))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (zero, zero, pos, zero))
        block_valid = (jnp.ones((n, t)) if mask is None
                       else (mask[:, :t] > 0)).astype(valid.dtype)
        valid = jax.lax.dynamic_update_slice(valid, block_valid, (zero, pos))
        # query i (absolute position pos+i) may see cache slots <= pos+i that
        # hold valid keys
        causal = jnp.arange(tc)[None, :] <= (pos + jnp.arange(t))[:, None]
        m = jnp.logical_and(causal[None, None, None],
                            (valid > 0)[:, None, None, None, :])
        # each cached head serves its group of query heads (a group of one
        # where the head counts are equal)
        q = q.reshape(n, hkv, -1, t, dh)
        scale = 1.0 / jnp.sqrt(jnp.asarray(dh, q.dtype))
        scores = jnp.einsum("ngiqd,ngkd->ngiqk", q, kc.astype(q.dtype)) * scale
        scores = jnp.where(m, scores, jnp.finfo(scores.dtype).min)
        w = jax.nn.softmax(scores, axis=-1)
        if train and self.attn_dropout > 0 and rng is not None:
            # TBPTT training through the cache must regularize like the
            # full-sequence path
            keep = jax.random.bernoulli(rng, 1.0 - self.attn_dropout, w.shape)
            w = jnp.where(keep, w / (1.0 - self.attn_dropout), 0.0)
        out = jnp.einsum("ngiqk,ngkd->ngiqd", w, vc.astype(q.dtype))
        return (self._project(params, out.reshape(n, self.n_heads, t, dh), x),
                (kc, vc, valid, pos + t))


#: stamped into checkpoint metadata by the serializers; its absence marks a
#: pre-round-5 checkpoint whose fused attention weights use the legacy
#: [3|2, H, Dh] block-major column order and need repacking on load
QKV_LAYOUT = "head_major"

_FUSED_PARTS = {"Wqkv": 3, "bqkv": 3, "Wkv": 2, "bkv": 2}

#: updater-state slots that are elementwise per-parameter accumulators and
#: therefore share the param's fused-column indexing (every slot the
#: nn/updaters.py registry defines: momentum/velocity and the various
#: squared-gradient accumulators). Only these repack with the param — a
#: future same-shaped slot that is NOT column-indexed must be added here
#: explicitly, never permuted by a shape match.
_COLUMN_INDEXED_SLOTS = frozenset(
    {"v", "m", "u", "h", "v_hat", "eg2", "edx2", "g2"})


def repack_legacy_fused_qkv(model) -> int:
    """Migrate a model whose attention params were saved in the pre-round-5
    block-major fused order ([3,H,Dh] / [2,H,Dh] columns) to the current
    head-major order ([H,3,Dh] / [H,2,Dh] — the layout that lets a
    column-sharded Wqkv propagate through the qkv reshape under GSPMD).
    Repacks params AND matching updater-state slots in place; returns the
    number of arrays repacked. Called by the checkpoint restorers when the
    checkpoint metadata carries no ``qkv_layout`` stamp."""
    import numpy as np

    def layer_items():
        if isinstance(model.params, dict):
            for name, vd in model.conf.vertices.items():
                if vd.is_layer and name in model.params:
                    yield name, vd.obj
        else:
            for i, layer in enumerate(model.layers):
                yield i, layer

    def repack(arr, parts, h, dh):
        a = np.asarray(arr)
        if a.ndim == 1:
            return jnp.asarray(
                a.reshape(parts, h, dh).transpose(1, 0, 2).reshape(-1))
        d = a.shape[0]
        return jnp.asarray(
            a.reshape(d, parts, h, dh).transpose(0, 2, 1, 3).reshape(d, -1))

    n_repacked = 0
    for key, layer in layer_items():
        if not isinstance(layer, SelfAttentionLayer):
            continue
        h, dh = layer.n_heads, layer._dh()
        if h <= 1:
            continue  # single head: both layouts are identical
        pd = model.params[key]
        for pn, parts in _FUSED_PARTS.items():
            if pn not in pd:
                continue
            pd[pn] = repack(pd[pn], parts, h, dh)
            n_repacked += 1
            upd = model.updater_states[key].get(pn, {}) \
                if model.updater_states is not None else {}
            for slot, arr in upd.items():
                if (slot in _COLUMN_INDEXED_SLOTS
                        and np.asarray(arr).shape
                        == np.asarray(pd[pn]).shape):
                    upd[slot] = repack(arr, parts, h, dh)
                    n_repacked += 1
    return n_repacked


@register_layer
@dataclasses.dataclass
class LearnedSelfAttentionLayer(SelfAttentionLayer):
    """Attention with n_queries learned query vectors (DL4J
    LearnedSelfAttentionLayer): output is [N, n_queries, n_out]."""

    n_queries: int = 1

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, self.n_queries)

    def param_shapes(self):
        dh = self._dh()
        inner = self.n_heads * dh
        return {"Wkv": (self.n_in, 2 * inner), "bkv": (2 * inner,),
                "Q": (self.n_queries, self.n_heads, dh),
                "Wo": (inner, self.n_out), "bo": (self.n_out,)}

    def init_params(self, rng, dtype=jnp.float32):
        dh = self._dh()
        inner = self.n_heads * dh
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "Wkv": self._init_w(k1, (self.n_in, 2 * inner), self.n_in, 2 * inner, dtype),
            "bkv": jnp.zeros((2 * inner,), dtype),
            "Q": self._init_w(k2, (self.n_queries, self.n_heads, dh), dh, dh, dtype),
            "Wo": self._init_w(k3, (inner, self.n_out), inner, self.n_out, dtype),
            "bo": jnp.zeros((self.n_out,), dtype),
        }

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        n, t, _ = x.shape
        h, dh = self.n_heads, self._dh()
        kv = x @ params["Wkv"] + params["bkv"]  # head-major [H,2,Dh] columns
        kv = kv.reshape(n, t, h, 2, dh).transpose(3, 0, 2, 1, 4)
        k, v = kv[0], kv[1]
        q = jnp.broadcast_to(params["Q"].transpose(1, 0, 2)[None], (n, h, self.n_queries, dh))
        out = dot_product_attention(q, k, v, mask=mask, dropout_rate=self.attn_dropout,
                                    rng=rng, train=train)
        out = out.transpose(0, 2, 1, 3).reshape(n, self.n_queries, h * dh)
        y = out @ params["Wo"] + params["bo"]
        return self.act_fn()(y), state or {}


@register_layer
@dataclasses.dataclass
class CrossAttentionLayer(Layer):
    """Multi-head cross-attention: query from one graph input, key/value from
    another (Keras ``MultiHeadAttention(query, value[, key])`` semantics —
    the importer maps true cross-attention MHA here). Consumes MULTIPLE graph
    inputs via the graph's multi-input layer protocol; inputs arrive in Keras
    call order [query, value(, key)] (key defaults to value).

    Separate projections (Wq/Wk/Wv) rather than the fused Wqkv of
    SelfAttentionLayer, because the sources (and ``value_size``) may differ.
    """

    n_in: int = 0          # query feature dim
    k_in: int = 0          # key source feature dim
    v_in: int = 0          # value source feature dim
    n_out: int = 0         # output dim (default: query dim)
    n_heads: int = 1
    head_size: Optional[int] = None   # Dh for q/k
    value_size: Optional[int] = None  # Dv (defaults to head_size)
    attn_dropout: float = 0.0

    consumes_multiple_inputs = True

    def _dh(self) -> int:
        return self.head_size or max(1, self.n_in // self.n_heads)

    def _dv(self) -> int:
        return self.value_size or self._dh()

    def set_n_in_multi(self, input_types) -> None:
        if not self.n_in:
            self.n_in = input_types[0].size
        if len(input_types) > 1 and not self.v_in:
            self.v_in = input_types[1].size
        if not self.k_in:
            self.k_in = (input_types[2].size if len(input_types) > 2
                         else self.v_in or self.n_in)
        if not self.v_in:
            self.v_in = self.n_in
        if not self.n_out:
            self.n_out = self.n_in

    def output_type_multi(self, input_types) -> InputType:
        return InputType.recurrent(self.n_out or input_types[0].size,
                                   input_types[0].timesteps)

    def param_shapes(self):
        h, dh, dv = self.n_heads, self._dh(), self._dv()
        return {"Wq": (self.n_in, h * dh), "bq": (h * dh,),
                "Wk": (self.k_in, h * dh), "bk": (h * dh,),
                "Wv": (self.v_in, h * dv), "bv": (h * dv,),
                "Wo": (h * dv, self.n_out), "bo": (self.n_out,)}

    def init_params(self, rng, dtype=jnp.float32):
        out = {}
        keys = jax.random.split(rng, 4)
        shapes = self.param_shapes()
        for k, name in zip(keys, ("Wq", "Wk", "Wv", "Wo")):
            s = shapes[name]
            out[name] = self._init_w(k, s, s[0], s[1], dtype)
            out["b" + name[1:].lower()] = jnp.zeros(shapes["b" + name[1:].lower()], dtype)
        return out

    def forward_multi(self, params, inputs, *, state=None, train=False,
                      rng=None, masks=None):
        xq = inputs[0]
        xv = inputs[1] if len(inputs) > 1 else xq
        xk = inputs[2] if len(inputs) > 2 else xv
        n, tq, _ = xq.shape
        tk = xk.shape[1]
        h, dh, dv = self.n_heads, self._dh(), self._dv()
        q = (xq @ params["Wq"] + params["bq"]).reshape(n, tq, h, dh).transpose(0, 2, 1, 3)
        k = (xk @ params["Wk"] + params["bk"]).reshape(n, tk, h, dh).transpose(0, 2, 1, 3)
        v = (xv @ params["Wv"] + params["bv"]).reshape(n, tk, h, dv).transpose(0, 2, 1, 3)
        kv_mask = None
        if masks is not None:
            # mask over KEYS: the key source's mask, falling back to the
            # value source's; in the single-input (self-attention) case the
            # query mask IS the key mask
            kv_mask = masks[2] if len(masks) > 2 and masks[2] is not None \
                else (masks[1] if len(masks) > 1 else
                      (masks[0] if masks else None))
        out = dot_product_attention(q, k, v, mask=kv_mask,
                                    dropout_rate=self.attn_dropout,
                                    rng=rng, train=train)
        out = out.transpose(0, 2, 1, 3).reshape(n, tq, h * dv)
        y = out @ params["Wo"] + params["bo"]
        return self.act_fn()(y), state or {}

    def forward(self, params, x, *, state=None, train=False, rng=None, mask=None):
        # single-input degenerate case == self-attention over x (the mask
        # applies to the keys, which are x itself)
        return self.forward_multi(params, [x], state=state, train=train,
                                  rng=rng,
                                  masks=None if mask is None else [mask])


def rotary_embedding(x, positions, theta: float):
    """Rotary position embedding over the whole last axis of ``x``
    ``[N, H, T, Dh]`` in the rotate-half convention: dimension ``i`` pairs
    with ``i + Dh/2``, both turned by ``positions * theta**(-2i/Dh)``.
    Angles and the rotation are float32; the result has ``x``'s dtype."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + turned * sin).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class GroupedQueryAttentionLayer(CausalSelfAttentionLayer):
    """Causal self-attention as today's decoders have it: ``n_kv_heads`` key
    and value heads, each serving ``n_heads / n_kv_heads`` query heads
    (``Wq`` and a head-major ``Wkv``, columns ``[kv head, (k, v), Dh]``);
    biases only where ``use_bias``; with ``qk_norm`` an RMSNorm over each
    head of q and of k (one weight of ``Dh`` each), before the rotation;
    with ``rope_theta`` rotary positions on q and k and no learned ones
    (``None``: the layer sees no positions at all); with ``window`` a query
    sees its last ``window`` keys only (:func:`dot_product_attention`);
    with ``output_gate`` the heads' output is multiplied, element by
    element, by ``sigmoid(x Wgate)`` before ``Wo``.
    The stateful path is :class:`CausalSelfAttentionLayer`'s, its KV cache
    holding ``n_kv_heads`` heads; it refuses a window, because a cache that
    forgets keys as they leave it is not written (ROADMAP R-M5).

    The full-sequence path repeats K and V to ``n_heads`` and goes through
    :func:`dot_product_attention`, so it takes the same road to a fused
    kernel as every other attention layer.
    """

    n_kv_heads: Optional[int] = None
    use_bias: bool = True
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5
    rope_theta: Optional[float] = None
    window: Optional[int] = None
    output_gate: bool = False

    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def param_shapes(self):
        dh, h, hkv = self._dh(), self.n_heads, self._kv_heads()
        shapes = {"Wq": (self.n_in, h * dh), "Wkv": (self.n_in, 2 * hkv * dh)}
        if self.output_gate:
            shapes["Wgate"] = (self.n_in, h * dh)
        if self.use_bias:
            shapes.update(bq=(h * dh,), bkv=(2 * hkv * dh,))
        if self.qk_norm:
            shapes.update(q_norm=(dh,), k_norm=(dh,))
        if self.project_input:
            shapes["Wo"] = (h * dh, self.n_out)
            if self.use_bias:
                shapes["bo"] = (self.n_out,)
        return shapes

    def weight_param_names(self):
        return tuple(n for n in self.param_shapes() if n.startswith("W"))

    def init_params(self, rng, dtype=jnp.float32):
        if self.n_heads % self._kv_heads():
            raise ValueError(f"n_heads ({self.n_heads}) is no multiple of "
                             f"n_kv_heads ({self._kv_heads()})")
        if not self.project_input and self.n_heads * self._dh() != self.n_out:
            raise ValueError("project_input=False requires "
                             "n_heads*head_size == n_out")
        if self.output_gate and not self.project_input:
            raise ValueError("output_gate gates what Wo projects: it needs "
                             "project_input")
        keys = dict(zip(("Wq", "Wkv", "Wo"), jax.random.split(rng, 3)))
        # a key of its own, so that the other three draw what they drew
        keys["Wgate"] = jax.random.fold_in(rng, 3)
        params = {}
        for name, shape in self.param_shapes().items():
            if name.startswith("W"):
                params[name] = self._init_w(keys[name], shape, shape[0],
                                            shape[1], dtype)
            elif name.endswith("_norm"):
                params[name] = jnp.ones(shape, dtype)
            else:
                params[name] = jnp.zeros(shape, dtype)
        return params

    def _qkv(self, params, x, positions):
        from deeplearning4j_tpu.nn.layers.norm import rms_norm

        n, t, _ = x.shape
        h, hkv, dh = self.n_heads, self._kv_heads(), self._dh()
        q, kv = x @ params["Wq"], x @ params["Wkv"]
        if self.use_bias:
            q, kv = q + params["bq"], kv + params["bkv"]
        q = q.reshape(n, t, h, dh).transpose(0, 2, 1, 3)
        kv = kv.reshape(n, t, hkv, 2, dh).transpose(3, 0, 2, 1, 4)
        k, v = kv[0], kv[1]
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.qk_norm_eps)
            k = rms_norm(k, params["k_norm"], self.qk_norm_eps)
        if self.rope_theta is not None:
            q = rotary_embedding(q, positions, self.rope_theta)
            k = rotary_embedding(k, positions, self.rope_theta)
        return q, k, v

    def _project(self, params, out, x):
        n, h, t, dh = out.shape
        y = out.transpose(0, 2, 1, 3).reshape(n, t, h * dh)
        if self.output_gate:
            y = y * jax.nn.sigmoid(x @ params["Wgate"]).astype(y.dtype)
        if self.project_input:
            y = y @ params["Wo"]
            if self.use_bias:
                y = y + params["bo"]
        return self.act_fn()(y)

    def forward(self, params, x, *, state=None, train=False, rng=None,
                mask=None):
        q, k, v = self._qkv(params, x, jnp.arange(x.shape[1]))
        groups = self.n_heads // self._kv_heads()
        if groups > 1:
            k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
        out = dot_product_attention(q, k, v, mask=mask, causal=True,
                                    window=self.window,
                                    dropout_rate=self.attn_dropout,
                                    rng=rng, train=train)
        return self._project(params, out, x), state or {}

    def forward_seq(self, params, x, carry=None, mask=None, train=False,
                    rng=None):
        if carry is not None and self.window is not None:
            raise NotImplementedError(
                f"the stateful path (a KV cache of max_cache={self.max_cache}"
                f" keys) would attend over every cached key: a cache that "
                f"forgets keys as they leave window={self.window} is not "
                f"written; run the whole sequence through forward()")
        return super().forward_seq(params, x, carry=carry, mask=mask,
                                   train=train, rng=rng)
