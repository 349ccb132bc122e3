"""Mixture-of-experts layer: sparse dispatch, an expert share, and
expert-parallel mesh execution.

Not in the reference (SURVEY.md §2.b lists expert parallelism as absent) —
a TPU-first addition: a gated expert FFN layer usable like any other layer.
The router scores every expert for every token in float32; only the
(token, expert) pairs it chose are computed: the pairs are sorted by expert,
each expert multiplies its own run of rows (grouped matrix products), and
the results go back to their tokens weighted by the router. Shapes are
static under ``jit``: the sorted buffer has room for tokens x ``top_k``
rows. What runs over it follows the pairs *held here*, which sort first:
the gathers, the gated product and the weighted sum are loops over row
blocks whose trip count is a value of the program (``_for_held_blocks``),
as the grouped kernels' tiles are, so a share that holds a quarter of the
experts moves about a quarter of the rows. No pair is ever dropped, however
uneven the routing: there is no capacity factor, and a batch that lands
wholly on held experts runs every block.

A layer may hold a *share* of the experts (``experts_held=(first, count)``):
it routes over all ``n_experts``, holds the weights of its own ``count``, and
returns what those add to the result — the weights normalised over every
selected expert, held or not. That is what one chip of an expert-parallel
group computes. :func:`ep_forward` runs the shares of a whole layer over a
mesh axis and sums them with one ``psum``, numerically the uncut layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.observe import trace as _trace

EXPERT_AXIS = "expert"
GATES = ("softmax_topk", "sigmoid")
#: standard deviation at which a selection bias is drawn: small beside the
#: sigmoid scores it is added to, and not zero, so that selecting by
#: ``s + b`` and weighting by ``s`` differ
EXPERT_BIAS_SCALE = 0.02


def _route(wg, x, top_k: int, gate: str = "softmax_topk", bias=None,
           norm_topk: bool = False, scaling: float = 1.0,
           norm_eps: float = 1e-6):
    """Router, in float32 whatever the stream's dtype: ``(experts, weights)``,
    both ``[..., k]``. Shared by the layer and the expert-parallel worker so
    the two paths can never diverge.

    - ``softmax_topk``: the top k logits, softmax over those k;
    - ``sigmoid``: scores ``s = sigmoid(logits)``; the top k of ``s + bias``
      are selected (``bias`` moves the selection only and takes no
      gradient); the weights are ``s`` on the selected, divided by their
      sum plus ``norm_eps`` where ``norm_topk``.
    """
    logits = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)   # [..., E]
    k = min(top_k, logits.shape[-1])
    if gate == "softmax_topk":
        top_vals, experts = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(top_vals, axis=-1)
    elif gate == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else \
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
        _, experts = jax.lax.top_k(biased, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (jnp.sum(weights, -1, keepdims=True)
                                 + norm_eps)
    else:
        raise ValueError(f"gate {gate!r} is none of {GATES}")
    return experts, weights * scaling


def _dense_gates(experts, weights, n_experts: int):
    """``[..., E]``: each token's weight on every expert, zero off its k."""
    return jnp.sum(jax.nn.one_hot(experts, n_experts, dtype=weights.dtype)
                   * weights[..., None], axis=-2)


#: sorted rows a step of the block loops moves (PERF.md §6, PR 33, has the
#: sweep on a v5e at 32,768 rows of 2048): the loops run over the blocks that
#: hold a held pair, so a layer wastes half a block on average
_ROW_BLOCK = 1024


def _for_held_blocks(n_held, block: int, body, init):
    """``body(start, valid, carry)`` for every block of ``block`` sorted rows
    that holds a held pair (the first ``n_held`` rows are those pairs);
    ``valid`` ``[block, 1]`` says which of the block's rows are. The trip
    count is a value of the program, not of its shapes: the work follows
    ``n_held``, and a batch that lands wholly on held experts runs every
    block."""
    def step(i, carry):
        start = i * block
        valid = (start + jnp.arange(block)) < n_held
        return body(start, valid[:, None], carry)

    return jax.lax.fori_loop(0, (n_held + block - 1) // block, step, init)


def _block_of(rows, start, block: int):
    return jax.lax.dynamic_slice_in_dim(rows, start, block, axis=0)


def _put_block(rows, block_rows, start):
    return jax.lax.dynamic_update_slice_in_dim(
        rows, block_rows.astype(rows.dtype), start, axis=0)


def _take(rows, index):
    return rows.at[index].get(mode="promise_in_bounds")


def _unwritten(shape, dtype):
    """A buffer that nothing has written: a kernel with one output and no
    body. For rows that the loops fill block by block and the grouped
    kernels read group by group, a memset of the whole buffer is the only
    pass that would still run over tokens x ``top_k`` rows."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="unwritten")()


def _row_major(x):
    """``x`` held to the layout it would have anyway, rows major. From a
    scatter that makes a layer's output the TPU compiler lays the whole
    residual stream out tokens-minor, and every layer that reads the
    stream pays: 1.9% of the LFM2 cell's tokens a second (PERF.md §6,
    PR 33)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _room(shape, dtype, loose: bool):
    """Where a loop over the held blocks writes its rows. The rows of the
    blocks it does not reach are zero, or with ``loose`` undefined."""
    return _unwritten(shape, dtype) if loose else jnp.zeros(shape, dtype)


def _sum_held(block_at, index, n_held, block: int, shape):
    """Float32 ``shape``: row ``index[i]`` is the sum of ``block_at(start)[i
    - start]`` over the held rows ``i`` that name it (a token has up to
    ``top_k`` of them)."""
    def body(start, valid, out):
        rows = jnp.where(valid, block_at(start), 0).astype(jnp.float32)
        return out.at[_block_of(index, start, block)].add(
            rows, mode="promise_in_bounds")

    return _for_held_blocks(n_held, block, body,
                            jnp.zeros(shape, jnp.float32))


# The passes below see the sorted buffer through `_for_held_blocks` alone.
# Each takes `how = (block, loose)`. `loose` is for grouped products
# that neither read nor write a row that is in no group (the kernels): a
# buffer then starts unwritten, the backward loops write over the buffers
# they have just read, and what lies past the held blocks is undefined.
# Without it every such row is zero, which `jax.lax.ragged_dot` and the
# gather of an expert's bias need.

def _held_rows(tokens, token_of_row, n_held, how):
    """The token of each held pair, pairs sorted by expert: ``[rows,
    width]``, the rows past the held blocks as ``_room`` leaves them."""
    block, loose = how

    def body(start, valid, out):
        taken = _take(tokens, _block_of(token_of_row, start, block))
        return _put_block(out, jnp.where(valid, taken, 0), start)

    return _for_held_blocks(
        n_held, block, body,
        _room((token_of_row.shape[0], tokens.shape[-1]), tokens.dtype, loose))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_products(tokens, weights, token_of_row, n_held, expert_rows, how):
    """The held pairs' tokens times each of ``weights`` (a tuple of ``[G,
    width, hidden]``), group by group: a tuple of ``[rows, hidden]``. The
    gathered rows are not kept for the backward pass, which gathers them
    again (a quarter of a millisecond a layer for a buffer of tokens x
    ``top_k`` rows; PERF.md §6, PR 33). Backward, each product's gradient
    for the rows is added block by block as a token sums the rows of its
    held pairs: no sum over the whole buffer."""
    with jax.named_scope("dispatch"):
        rows = _held_rows(tokens, token_of_row, n_held, how)
    with jax.named_scope("experts"):
        return tuple(_grouped_matmul(rows, w, expert_rows) for w in weights)


def _held_products_fwd(tokens, weights, token_of_row, n_held, expert_rows,
                       how):
    return (_held_products(tokens, weights, token_of_row, n_held,
                           expert_rows, how),
            (tokens, weights, token_of_row, n_held, expert_rows))


def _held_products_bwd(how, res, gs):
    tokens, weights, token_of_row, n_held, expert_rows = res
    block = how[0]
    with jax.named_scope("dispatch"):
        rows = _held_rows(tokens, token_of_row, n_held, how)
    with jax.named_scope("experts"):
        pulled = [_grouped_matmul_vjp(rows, w, expert_rows, g)
                  for w, g in zip(weights, gs)]
    with jax.named_scope("dispatch"):
        d_tokens = _sum_held(
            lambda start: sum(_block_of(d_rows, start, block)
                              .astype(jnp.float32) for d_rows, _ in pulled),
            token_of_row, n_held, block, tokens.shape).astype(tokens.dtype)
    return (d_tokens, tuple(d_w for _, d_w in pulled), None, None, None)


_held_products.defvjp(_held_products_fwd, _held_products_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _on_held_rows(fn, arrays, n_held, how):
    """``fn(*arrays)``, a function of each row alone, on the held blocks of
    ``arrays`` (each ``[rows, width]``): ``[rows, width]``. What the arrays
    hold past ``n_held`` is never kept, NaN included, forward or backward."""
    block, loose = how
    made = jax.eval_shape(fn, *(
        jax.ShapeDtypeStruct((block,) + a.shape[1:], a.dtype)
        for a in arrays))

    def body(start, valid, out):
        kept = fn(*(_block_of(a, start, block) for a in arrays))
        return _put_block(out, jnp.where(valid, kept, 0), start)

    return _for_held_blocks(
        n_held, block, body,
        _room((arrays[0].shape[0],) + made.shape[1:], made.dtype, loose))


def _on_held_rows_fwd(fn, arrays, n_held, how):
    return _on_held_rows(fn, arrays, n_held, how), (arrays, n_held)


def _on_held_rows_bwd(fn, how, res, g):
    arrays, n_held = res
    block, loose = how

    def body(start, valid, outs):
        _, pull = jax.vjp(fn, *(_block_of(a, start, block)
                                for a in (outs if loose else arrays)))
        grads = pull(jnp.where(valid, _block_of(g, start, block), 0))
        return tuple(_put_block(out, jnp.where(valid, grad, 0), start)
                     for out, grad in zip(outs, grads))

    init = arrays if loose else tuple(jnp.zeros_like(a) for a in arrays)
    return _for_held_blocks(n_held, block, body, init), None


_on_held_rows.defvjp(_on_held_rows_fwd, _on_held_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _sum_to_tokens(out_rows, row_weights, token_of_row, n_held, how, m: int,
                   dtype):
    """``[m, width]`` in ``dtype``: each token's held rows, weighted by the
    router and summed in float32. Only held rows are read, forward and
    backward, and no ``[top_k, m, width]`` is ever made."""
    block, loose = how

    def block_at(start):
        return _block_of(out_rows, start, block).astype(jnp.float32) \
            * _block_of(row_weights, start, block)[:, None]

    out = _sum_held(block_at, token_of_row, n_held, block,
                    (m, out_rows.shape[-1])).astype(dtype)
    return _row_major(out) if loose else out


def _sum_to_tokens_fwd(out_rows, row_weights, token_of_row, n_held, how, m,
                       dtype):
    return (_sum_to_tokens(out_rows, row_weights, token_of_row, n_held, how,
                           m, dtype),
            (out_rows, row_weights, token_of_row, n_held))


def _sum_to_tokens_bwd(how, m, dtype, res, g):
    out_rows, row_weights, token_of_row, n_held = res
    block, loose = how

    def body(start, valid, outs):
        d_rows, d_weights = outs
        taken = _take(g, _block_of(token_of_row, start, block)) \
            .astype(jnp.float32)
        computed = _block_of(d_rows if loose else out_rows, start, block)
        d_row = taken * _block_of(row_weights, start, block)[:, None]
        d_weight = jnp.sum(taken * computed.astype(jnp.float32), -1)
        return (_put_block(d_rows, jnp.where(valid, d_row, 0), start),
                _put_block(d_weights, jnp.where(valid[:, 0], d_weight, 0),
                           start))

    d_rows, d_weights = _for_held_blocks(
        n_held, block, body,
        (out_rows if loose else jnp.zeros_like(out_rows),
         jnp.zeros_like(row_weights)))
    return d_rows, d_weights, None, None


_sum_to_tokens.defvjp(_sum_to_tokens_fwd, _sum_to_tokens_bwd)


#: megablox tile sizes (rows, contraction, columns) from a sweep on a v5e at
#: 32,768 rows of 2048 by 8 experts of 1792: PERF.md §6, PR 27, has every
#: candidate's numbers
_GMM_TILING = (256, 1024, 1024)


def _megablox_tiling(rows, weights, n_rows=None):
    """Tile sizes for the grouped-matmul kernel bundled with jax
    (``jax.experimental.pallas.ops.tpu.megablox``), or None where it cannot
    serve: a backend that is no TPU, a program the compiler partitions
    (Mosaic kernels cannot be), a dtype other than bfloat16 (float32 tiles
    of this size do not fit VMEM), or a row count (``n_rows`` where
    ``rows`` only stands for the buffer to come) its row tile does not
    divide."""
    from deeplearning4j_tpu.nn import helpers as _helpers

    tile_rows, tile_in, tile_out = _GMM_TILING
    if (jax.default_backend() != "tpu" or rows.dtype != jnp.bfloat16
            or weights.dtype != jnp.bfloat16
            or (rows.shape[0] if n_rows is None else n_rows) % tile_rows
            or _helpers.partitioned_by_compiler(rows)):
        return None
    return (tile_rows, min(tile_in, weights.shape[1]),
            min(tile_out, weights.shape[2]))


def _grouped_matmul(rows, weights, group_sizes):
    """``rows[i] @ weights[g]`` for the group ``g`` that row ``i`` falls in:
    rows ``[R, a]`` sorted by group, weights ``[G, a, b]``, ``group_sizes``
    ``[G]``. Rows past the last group come back undefined. The megablox
    kernels where they can serve (their work follows the rows that are in a
    group), else ``jax.lax.ragged_dot``."""
    tiling = _megablox_tiling(rows, weights)
    if tiling is None:
        return jax.lax.ragged_dot(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(rows, weights, group_sizes, rows.dtype, tiling)


def _grouped_matmul_vjp(rows, weights, group_sizes, g):
    """``(d_rows, d_weights)`` of :func:`_grouped_matmul` for the cotangent
    ``g``, without the product itself: what its own backward pass runs (for
    the kernels ``gmm`` with the weights transposed and ``tgmm``, under
    those names), for a backward pass that makes ``rows`` again."""
    tiling = _megablox_tiling(rows, weights)
    if tiling is None:
        return jax.vjp(lambda r, w: jax.lax.ragged_dot(r, w, group_sizes),
                       rows, weights)[1](g)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return (megablox.backend.gmm(g, weights, group_sizes, rows.dtype, tiling,
                                 transpose_rhs=True),
            megablox.backend.tgmm(rows.swapaxes(0, 1), g, group_sizes,
                                  weights.dtype, tiling,
                                  num_actual_groups=weights.shape[0]))


def _moe_share(params, x, *, top_k: int, act, gate: str = "softmax_topk",
               norm_topk: bool = False, scaling: float = 1.0,
               norm_eps: float = 1e-6, gated: bool = False, first=0):
    """What the experts held here add to the layer's result.

    x: [..., d_in] → [..., d_out]. ``params`` hold the router for all experts
    and the weights of ``count`` of them (the leading axis of ``W`` or
    ``W1``), which are the experts ``first .. first + count`` of the layer.
    Returns the output and ``(experts, weights, expert_rows,
    rows_elsewhere)``: the router's choice ``[..., k]`` twice, the pairs each
    held expert computed ``[count]`` and the pairs whose expert is not held.
    """
    lead, tokens = x.shape[:-1], x.reshape(-1, x.shape[-1])
    count = params["W1" if gated else "W"].shape[0]
    with jax.named_scope("route"):
        experts, weights = _route(params["Wg"], tokens, top_k, gate,
                                  params.get("expert_bias"), norm_topk,
                                  scaling, norm_eps)
    m, k = experts.shape
    block = min(_ROW_BLOCK, m * k)
    n_rows = -(-m * k // block) * block         # whole blocks
    # the kernels leave alone every row that is in no group, so a buffer
    # may be undefined there; an expert's bias is gathered for every row
    loose = gated and _megablox_tiling(tokens, params["W1"],
                                       n_rows) is not None
    how = (block, loose)
    tracer = _trace.get_active_tracer()
    if tracer is not None:
        # which form this layer took, counted while its step is traced
        tracer.count("moe.held_rows_kernel_calls" if loose
                     else "moe.held_rows_plain_calls")
    with jax.named_scope("dispatch"):
        # a pair's group: its expert's place among those held, or `count`.
        # Pair `j * m + t` is token t's j-th expert; sorted by group, the
        # held pairs come first, and only they are moved from here on
        local = experts.T.reshape(-1) - first
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=count + 1).astype(jnp.int32)
        expert_rows, rows_elsewhere = sizes[:count], sizes[count]
        n_held = jnp.sum(expert_rows)
        pad = lambda a: jnp.pad(a, (0, n_rows - m * k))
        token_of_row = pad(order % m)
    products = _held_products(
        tokens, (params["W1"], params["W3"]) if gated else (params["W"],),
        token_of_row, n_held, expert_rows, how)
    with jax.named_scope("experts"):
        if gated:
            hidden = _on_held_rows(lambda h1, h3: act(h1) * h3, products,
                                   n_held, how)
            out_rows = _grouped_matmul(hidden, params["W2"], expert_rows)
        else:
            expert_of_row = pad(jnp.minimum(group[order], count - 1))
            out_rows = _on_held_rows(
                lambda h, b: act(h + b),
                products + (params["b"][expert_of_row],), n_held, how)
    with jax.named_scope("combine"):
        row_weights = pad(weights.T.reshape(-1).at[order].get(
            unique_indices=True, mode="promise_in_bounds"))
        out = _sum_to_tokens(out_rows, row_weights, token_of_row, n_held,
                             how, m, x.dtype)
    return (out.reshape(lead + out.shape[-1:]),
            (experts.reshape(lead + (k,)), weights.reshape(lead + (k,)),
             expert_rows, rows_elsewhere))


def _moe_apply(params, x, top_k: int, act):
    """The whole layer with its first options (softmax over the top-k
    logits, one biased matrix per expert): ``(out, gates [..., E])``."""
    out, (experts, weights, _, _) = _moe_share(params, x, top_k=top_k,
                                               act=act)
    return out, _dense_gates(experts, weights, params["Wg"].shape[-1])


@register_layer
@dataclasses.dataclass
class MixtureOfExpertsLayer(Layer):
    """Expert FFN: a router picks ``top_k`` of ``n_experts`` per token, and
    only the chosen (token, expert) pairs are computed (module docstring).

    - ``gate``: ``softmax_topk`` (softmax over the top-k logits) or
      ``sigmoid`` (see :func:`_route`), with ``expert_bias`` (a constant
      ``[n_experts]`` added for the selection only, drawn at
      ``EXPERT_BIAS_SCALE``; nothing updates it in training), ``norm_topk``
      (with ``norm_topk_eps`` beside the sum) and ``routed_scaling``;
    - ``gated``: each expert is ``W2(act(W1 x) * W3 x)`` with hidden width
      ``n_hidden`` and no bias, else ``act(W x + b)``;
    - ``experts_held=(first, count)``: the layer holds that share.

    The layer's state carries, from the last forward pass, ``expert_rows``
    (int32 per held expert: the pairs it computed) and ``rows_elsewhere``
    (the pairs whose expert is not held); together they are tokens x
    ``top_k``, always.
    """

    n_in: int = 0
    n_out: int = 0
    n_experts: int = 4
    top_k: int = 2
    # opt-in: surface routing gates through the layer state (costs one extra
    # train-step recompile when the state structure changes and serializes
    # the last batch's gates with checkpoints — leave off unless inspecting
    # router behaviour)
    collect_gates: bool = False
    gate: str = "softmax_topk"
    expert_bias: bool = False
    norm_topk: bool = False
    norm_topk_eps: float = 1e-6
    routed_scaling: float = 1.0
    gated: bool = False
    n_hidden: int = 0
    experts_held: Optional[Tuple[int, int]] = None

    float32_params = ("Wg", "expert_bias")

    def __post_init__(self):
        if self.activation is None:
            self.activation = "relu"
        if self.gate not in GATES:
            raise ValueError(f"gate {self.gate!r} is none of {GATES}")
        if self.experts_held is not None:
            first, count = self.experts_held = tuple(self.experts_held)
            if first < 0 or count < 1 or first + count > self.n_experts:
                raise ValueError(f"experts_held {self.experts_held} is no "
                                 f"part of {self.n_experts} experts")

    def _held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def set_n_in(self, input_type: InputType) -> None:
        if not self.n_in:
            self.n_in = input_type.size
        if not self.n_out:
            self.n_out = self.n_in

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def param_shapes(self):
        count = self._held()[1]
        shapes = {"Wg": (self.n_in, self.n_experts)}
        if self.expert_bias:
            shapes["expert_bias"] = (self.n_experts,)
        if self.gated:
            shapes.update(W1=(count, self.n_in, self.n_hidden),
                          W3=(count, self.n_in, self.n_hidden),
                          W2=(count, self.n_hidden, self.n_out))
        else:
            shapes.update(W=(count, self.n_in, self.n_out),
                          b=(count, self.n_out))
        return shapes

    def weight_param_names(self):
        return tuple(n for n in self.param_shapes()
                     if n not in ("b", "expert_bias"))

    def init_params(self, rng, dtype=jnp.float32):
        """An expert's weights come from the seed and its place in the whole
        layer, so a share holds what the uncut layer has there."""
        first, count = self._held()
        k_router, k_experts, k_bias = jax.random.split(rng, 3)
        keys = jax.random.split(k_experts, self.n_experts)[first:first + count]

        def experts(salt, fan_in, fan_out):
            return jnp.stack([
                self._init_w(jax.random.fold_in(k, salt), (fan_in, fan_out),
                             fan_in, fan_out, dtype) for k in keys])

        params = {"Wg": self._init_w(k_router, (self.n_in, self.n_experts),
                                     self.n_in, self.n_experts, dtype)}
        if self.expert_bias:
            params["expert_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(
                k_bias, (self.n_experts,), dtype)
        if self.gated:
            params.update(W1=experts(1, self.n_in, self.n_hidden),
                          W3=experts(3, self.n_in, self.n_hidden),
                          W2=experts(2, self.n_hidden, self.n_out))
        else:
            params.update(W=experts(0, self.n_in, self.n_out),
                          b=jnp.zeros((count, self.n_out), dtype))
        return params

    def init_state(self):
        return {"expert_rows": jnp.zeros((self._held()[1],), jnp.int32),
                "rows_elsewhere": jnp.zeros((), jnp.int32)}

    def share(self, params, x, first=None):
        """:func:`_moe_share` with this layer's options; ``first`` (which may
        be traced: a shard's index) in place of ``experts_held``'s."""
        return _moe_share(
            params, x, top_k=self.top_k, act=self.act_fn(), gate=self.gate,
            norm_topk=self.norm_topk, scaling=self.routed_scaling,
            norm_eps=self.norm_topk_eps, gated=self.gated,
            first=self._held()[0] if first is None else first)

    def forward(self, params, x, *, state=None, train=False, rng=None,
                mask=None):
        x = self._dropout(x, train, rng)
        out, (experts, weights, expert_rows, rows_elsewhere) = \
            self.share(params, x)
        new_state = dict(state or {}, expert_rows=expert_rows,
                         rows_elsewhere=rows_elsewhere)
        if self.collect_gates:
            new_state["gates"] = _dense_gates(experts, weights,
                                              self.n_experts)
        return out, new_state


def load_balancing_loss(gates: jax.Array) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e mean_gate_e * dispatch_frac_e,
    where dispatch fraction counts each token toward its top expert —
    minimized (at 1) when routing is uniform across experts.

    To train WITH this aux term, call ``_moe_apply`` (or the layer) inside a
    custom loss (e.g. a SameDiff-style layer/graph) where the gates are part
    of the differentiated computation; ``collect_gates=True`` state capture
    is for *monitoring* only (layer states are non-differentiated aux
    outputs of the train step)."""
    e = gates.shape[-1]
    flat = gates.reshape(-1, e)
    importance = jnp.mean(flat, axis=0)
    top = jax.nn.one_hot(jnp.argmax(flat, axis=-1), e, dtype=flat.dtype)
    dispatch = jnp.mean(top, axis=0)
    return e * jnp.sum(importance * dispatch)


def ep_forward(layer: MixtureOfExpertsLayer, params, x, mesh: Mesh,
               axis_name: str = EXPERT_AXIS):
    """Expert-parallel execution: expert tensors sharded over ``axis_name``.

    Router weights stay replicated (they're tiny); each device routes every
    token over all experts, computes the share of the experts it holds
    (:func:`_moe_share`, ``first`` from its place on the axis) and a psum
    combines. Numerically identical to the single-device forward. Every
    device sees every token: there is no all-to-all yet.
    """
    from deeplearning4j_tpu.parallel.mesh import shard_map

    n_exp = layer.n_experts
    n_shards = int(mesh.shape[axis_name])
    if n_exp % n_shards:
        raise ValueError(f"n_experts ({n_exp}) must divide over the "
                         f"{axis_name!r} axis ({n_shards})")
    per = n_exp // n_shards
    names = sorted(params)
    specs = tuple(P() if n in ("Wg", "expert_bias") else P(axis_name)
                  for n in names)

    def worker(xx, *shard):
        partial, _ = layer.share(dict(zip(names, shard)), xx,
                                 first=jax.lax.axis_index(axis_name) * per)
        return jax.lax.psum(partial, axis_name)

    mapped = shard_map(worker, mesh=mesh, in_specs=(P(),) + specs,
                       out_specs=P())
    return mapped(jnp.asarray(x), *(params[n] for n in names))
