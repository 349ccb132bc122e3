"""ComputationGraph — DAG model with multiple inputs/outputs, TPU-native.

Reference: ``nn/graph/ComputationGraph.java`` (3.9k LoC): topological
execution (``topologicalOrder:152``), ``fit(DataSetIterator):886`` /
``fit(MultiDataSetIterator):1010``, ``output``, ``rnnTimeStep``, evaluation.

TPU design: params are a dict keyed by vertex name, the whole train step
(forward over the topo order, summed output losses, ``jax.grad``, updaters) is
ONE jitted donated-buffer function. Vertices are pure functions, so the DAG is
just function composition — XLA sees a single fused program, not an object
graph. The step and the fit loop are ``TrainingEngine``'s (``nn/engine.py``),
shared with MultiLayerNetwork; this file holds what a DAG needs of its own.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.engine import TrainingEngine, per_timestep_labels
from deeplearning4j_tpu.nn.layers.base import Layer, cast_params
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer, check_carry_capacity
from deeplearning4j_tpu.nn.updaters import Sgd, Updater
from deeplearning4j_tpu.observe import scope as _scope

Array = jax.Array
Params = Dict[str, Dict[str, Array]]
States = Dict[str, Dict[str, Array]]


def _as_jnp(x, dtype=None):
    if isinstance(x, (np.ndarray, list, tuple)) or np.isscalar(x):
        x = jnp.asarray(x)
    if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype:
        x = x.astype(dtype)
    return x


class ComputationGraph(TrainingEngine):
    """DAG network over a ComputationGraphConfiguration."""

    def __init__(self, conf: ComputationGraphConfiguration):
        conf.finalize()
        self.conf = conf
        self.params: Optional[Params] = None
        self.states: Optional[States] = None
        self.updater_states: Optional[Dict[str, Dict[str, Dict[str, Array]]]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._score_arr = None
        self._rng_key: Optional[jax.Array] = None
        self._jit_cache: Dict[Any, Any] = {}
        self._updaters: Dict[str, Dict[str, Updater]] = {}
        self._rnn_carries: Optional[Dict[str, Any]] = None
        self._rnn_pos = 0
        # cumulative host→device batch payload shipped by fit(); the
        # TraceListener exports deltas as training_transfer_bytes_total
        self.transfer_bytes = 0

    # ----------------------------------------------------------------- init
    def _init_trees(self, seed: Optional[int] = None) -> None:
        """What ``init()`` (``nn/engine.py``) draws for a DAG, by vertex."""
        g = self.conf.global_conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng_key = jax.random.fold_in(key, 0x5EED)
        dtype = g.jnp_dtype()
        self.params, self.states = {}, {}
        self._updaters, self.updater_states = {}, {}
        default_updater = g.updater or Sgd(0.1)
        layer_defs = self.conf.layer_vertices()
        keys = jax.random.split(key, max(1, len(layer_defs)))
        for vd, k in zip(layer_defs, keys):
            layer: Layer = vd.obj  # type: ignore[assignment]
            p = layer.init_params(k, dtype)
            self.params[vd.name] = p
            self.states[vd.name] = layer.init_state()
            layer_upd = layer.updater or default_updater
            bias_upd = layer.bias_updater or g.bias_updater or layer_upd
            umap, smap = {}, {}
            for n, v in p.items():
                u = bias_upd if n == "b" else layer_upd
                umap[n] = u
                smap[n] = u.init_state(v)
            self._updaters[vd.name] = umap
            self.updater_states[vd.name] = smap
        self.iteration = 0
        self.epoch = 0

    # -------------------------------------------------------------- forward
    def _forward_all(self, params: Params, states: States,
                     inputs: Dict[str, Array], *, train: bool,
                     rng: Optional[jax.Array],
                     masks: Optional[Dict[str, Optional[Array]]] = None,
                     carries: Optional[Dict[str, Any]] = None,
                     stop_at_loss: bool = True,
                     ) -> Tuple[Dict[str, Array], States,
                                Dict[str, Optional[Array]], Optional[Dict[str, Any]]]:
        """Execute the DAG in topo order.

        Returns (activations, new_states, masks, new_carries). When
        ``stop_at_loss``, output-layer vertices store their *input* (merged)
        activation under ``name + ':in'`` and their own activation is the
        layer forward (useful for output()).
        """
        conf = self.conf
        cd = conf.global_conf.jnp_compute_dtype()
        if cd is not None:
            # mixed precision: f32 master params, compute-dtype forward
            cast = lambda a: (a.astype(cd)
                              if hasattr(a, "dtype")
                              and jnp.issubdtype(a.dtype, jnp.floating) else a)
            with jax.named_scope(_scope.CAST_PARAMS):
                params = cast_params(lambda n: conf.vertices[n].obj, params, cast)
                inputs = {k: cast(v) for k, v in inputs.items()}
        acts: Dict[str, Array] = dict(inputs)
        m: Dict[str, Optional[Array]] = dict(masks or {})
        for name in conf.inputs:
            m.setdefault(name, None)
        new_states: States = {}
        new_carries: Dict[str, Any] = {}
        n_layers = max(1, len(conf.topo_order))
        rngs = (jax.random.split(rng, n_layers) if rng is not None else [None] * n_layers)
        for vi, name in enumerate(conf.topo_order):
            vd = conf.vertices[name]
            in_acts = [acts[s] for s in vd.inputs]
            in_masks = [m.get(s) for s in vd.inputs]
            with _scope.layer_scope(name, vd.obj):
                if vd.is_layer:
                    layer: Layer = vd.obj  # type: ignore[assignment]
                    p_v, rng_v = params[name], rngs[vi]
                    if (getattr(layer, "weight_noise", None) is not None and train
                            and rng_v is not None):
                        # train-time weight noise (DropConnect.java:19, MLN
                        # parity) — applied before BOTH the single- and
                        # multi-input forward paths
                        rng_wn, rng_v = jax.random.split(rng_v)
                        p_v = layer.weight_noise.apply(layer, p_v, rng_wn, train)
                    if getattr(layer, "consumes_multiple_inputs", False):
                        y, st = layer.forward_multi(
                            p_v, in_acts, state=states[name], train=train,
                            rng=rng_v, masks=in_masks)
                        new_states[name] = st if st else states[name]
                        acts[name] = y
                        m[name] = in_masks[0]
                        continue
                    h = in_acts[0] if len(in_acts) == 1 else jnp.concatenate(in_acts, -1)
                    if name in conf.preprocessors:
                        h = conf.preprocessors[name](h)
                    cur_mask = in_masks[0]
                    if layer.has_loss():
                        acts[name + ":in"] = h
                        acts[name + ":mask"] = cur_mask
                    if carries is not None and isinstance(layer, BaseRecurrentLayer):
                        y, c = layer.forward_seq(p_v, h, carry=carries.get(name),
                                                 mask=cur_mask, train=train, rng=rng_v)
                        new_states[name] = states[name]
                        new_carries[name] = c
                        acts[name] = y
                    else:
                        fwd = lambda p, hh, _l=layer, _n=name, _r=rng_v: _l.forward(
                            p, hh, state=states[_n], train=train, rng=_r,
                            mask=cur_mask)
                        if train and conf.global_conf.gradient_checkpointing:
                            # rematerialize activations in the backward pass
                            fwd = jax.checkpoint(fwd)
                        y, st = fwd(p_v, h)
                        new_states[name] = st if st else states[name]
                        acts[name] = y
                    # per-timestep mask collapses when the time dim disappears;
                    # per-example [N]/[N,1] masks survive (MLN parity)
                    if (cur_mask is not None and acts[name].ndim == 2
                            and cur_mask.ndim == 2 and cur_mask.shape[1] > 1):
                        m[name] = None
                    else:
                        m[name] = cur_mask
                else:
                    acts[name] = vd.obj.forward(in_acts, in_masks)  # type: ignore[union-attr]
                    m[name] = vd.obj.output_mask(in_masks)  # type: ignore[union-attr]
        return acts, new_states, m, (new_carries if carries is not None else None)

    def _regularization(self, params: Params) -> Array:
        reg = jnp.asarray(0.0, jnp.float32)
        for vd in self.conf.layer_vertices():
            l: Layer = vd.obj  # type: ignore[assignment]
            for n, v in params[vd.name].items():
                is_bias = n == "b"
                l1 = (l.l1_bias if is_bias else l.l1) or 0.0
                l2 = (l.l2_bias if is_bias else l.l2) or 0.0
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(v))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(v * v)
        return reg

    def _loss_fn(self, params: Params, states: States,
                 inputs: Dict[str, Array], labels: Sequence[Array],
                 rng, masks, label_masks, train: bool, carries=None):
        acts, new_states, out_masks, new_carries = self._forward_all(
            params, states, inputs, train=train, rng=rng, masks=masks,
            carries=carries)
        loss = jnp.asarray(0.0, jnp.float32)
        for oi, out_name in enumerate(self.conf.outputs):
            vd = self.conf.vertices[out_name]
            layer = vd.obj
            if not (vd.is_layer and layer.has_loss()):
                raise ValueError(f"output vertex {out_name!r} is not a loss layer")
            h = acts[out_name + ":in"]
            if self.conf.global_conf.compute_dtype is not None:
                # loss head in f32 for stable softmax/log under mixed precision
                h = h.astype(jnp.float32)
            lm = None
            if label_masks is not None and label_masks[oi] is not None:
                lm = label_masks[oi]
            elif h.ndim == 3:
                lm = acts.get(out_name + ":mask")
            else:
                fm = acts.get(out_name + ":mask")
                if fm is not None and (fm.ndim == 1 or
                                       (fm.ndim == 2 and fm.shape[-1] == 1)):
                    # per-example feature mask masks the score (MLN parity)
                    lm = fm.reshape(fm.shape[0])
            p_out = params[out_name]
            if (getattr(layer, "weight_noise", None) is not None and train
                    and rng is not None):
                # output layers get weight noise too (MLN parity). Re-derive
                # the SAME key the vertex loop used for this vertex so the
                # loss sees the identical noised weights as any downstream
                # consumer of the output vertex's activation — one noise
                # sample per layer per step.
                topo = self.conf.topo_order
                vi = topo.index(out_name)
                rng_v = jax.random.split(rng, max(1, len(topo)))[vi]
                rng_wn = jax.random.split(rng_v)[0]
                p_out = layer.weight_noise.apply(layer, p_out, rng_wn, train)
            with _scope.layer_scope(out_name, layer), \
                    jax.named_scope(_scope.LOSS):
                loss = loss + layer.compute_loss(p_out, h, labels[oi], mask=lm)
        with jax.named_scope(_scope.REGULARIZATION):
            loss = loss + self._regularization(params)
        return loss, (new_states, new_carries)

    # ------------------------------------------------------------ train step
    def _layer_items(self):
        return [(vd.name, vd.obj) for vd in self.conf.layer_vertices()]

    _tree_of = staticmethod(dict)

    def fit_batches_on_device(self, datasets) -> "ComputationGraph":
        """Train on a window of equal-shape batches in ONE device dispatch
        (``lax.scan`` over the stacked window). Semantically identical to
        calling ``fit`` once per batch; built for dispatch-bound setups
        where per-step host→device latency is significant. Requires uniform
        shapes, no masks, standard backprop.
        """
        from deeplearning4j_tpu.nn.conf.network import normalize_backprop_type
        if self.params is None:
            self.init()
        if normalize_backprop_type(self.conf.backprop_type) != "standard":
            raise ValueError("fit_batches_on_device supports standard "
                             "backprop only (not TBPTT)")
        mds_list = [self._to_mds(ds) for ds in datasets]
        if not mds_list:
            return self
        for m in mds_list:
            if m.features_masks is not None or m.labels_masks is not None:
                raise ValueError("fit_batches_on_device does not carry masks")
        dtype = self.conf.global_conf.jnp_dtype()
        inputs_s = {n: jnp.stack([_as_jnp(m.features[i], dtype)
                                  for m in mds_list])
                    for i, n in enumerate(self.conf.inputs)}
        labels_s = [jnp.stack([_as_jnp(m.labels[i], dtype) for m in mds_list])
                    for i in range(len(mds_list[0].labels))]
        k = len(mds_list)
        multi = self._get_multi_train_step()
        it0 = jnp.asarray(self.iteration, jnp.float32)
        ep = jnp.asarray(self.epoch, jnp.float32)
        (self.params, self.states, self.updater_states, losses) = multi(
            self.params, self.states, self.updater_states, it0, ep,
            inputs_s, labels_s, self._next_rng())
        self.last_batch_size = int(next(iter(inputs_s.values())).shape[1])
        # listeners see every iteration with its own loss, exactly like K
        # sequential fit calls (the device already ran them all)
        for i in range(k):
            self._score_arr = losses[i]
            self.iteration += 1
            self._iteration_done()
        return self

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1,
            prefetch_depth: Optional[int] = None) -> "ComputationGraph":
        """Train on ``(features, labels)`` arrays or lists of them, a DataSet,
        a MultiDataSet or an iterator of either (``prefetch_depth``: see
        ``TrainingEngine._fit_epochs``). The per-batch wait shows up as a
        ``host_wait`` trace span and the shipped payload as
        ``training_transfer_bytes_total``.

        Under ``observe.enable_tracing()`` each step records three spans:
        ``host_wait`` (the wait for its batch), ``step_dispatch`` (the call
        of the jitted step, attribute ``iteration``; a compile it pays for
        nests under it) and ``listeners``. None of them waits for the
        device. The step's name scopes (``observe/scope.py``) are always on:
        the compiled program is ``jit_train_step`` and its operations carry
        their layer's ``Class:name``."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

        if labels is not None:
            iterator = [MultiDataSet(
                data if isinstance(data, (list, tuple)) else [data],
                labels if isinstance(labels, (list, tuple)) else [labels])]
        elif isinstance(data, (DataSet, MultiDataSet)):
            iterator = [data]
        else:
            iterator = data
        self._fit_epochs(iterator, epochs, prefetch_depth)
        return self

    def _to_mds(self, ds):
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
        if isinstance(ds, DataSet):
            return MultiDataSet(
                [ds.features], [ds.labels],
                None if ds.features_mask is None else [ds.features_mask],
                None if ds.labels_mask is None else [ds.labels_mask])
        return ds

    def _to_batch(self, ds):
        mds = self._to_mds(ds)
        dtype = self.conf.global_conf.jnp_dtype()
        inputs = {n: _as_jnp(f, dtype) for n, f in zip(self.conf.inputs, mds.features)}
        labels = [_as_jnp(l, dtype) for l in mds.labels]
        masks = None
        if mds.features_masks is not None:
            masks = {n: (None if m is None else _as_jnp(m))
                     for n, m in zip(self.conf.inputs, mds.features_masks)}
        lmasks = None
        if mds.labels_masks is not None:
            lmasks = [None if m is None else _as_jnp(m) for m in mds.labels_masks]
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import place_batch
            mesh = self._mesh
            inputs, labels, masks, lmasks = jax.tree_util.tree_map(
                lambda a: place_batch(a, mesh), (inputs, labels, masks, lmasks))
        return inputs, labels, masks, lmasks

    def _temporal_inputs(self, inputs) -> set:
        """Input names carrying a time axis: decided by the declared
        InputTypes when present (rnn / image-sequence), else by rank."""
        kinds = ("rnn", "cnn_seq")
        if (self.conf.input_types
                and len(self.conf.input_types) == len(self.conf.inputs)
                and all(t is not None for t in self.conf.input_types)):
            return {n for n, t in zip(self.conf.inputs, self.conf.input_types)
                    if t.kind in kinds}
        return {n for n, a in inputs.items() if a.ndim == 3}

    def _temporal_length(self, inputs):
        ts = {inputs[n].shape[1] for n in self._temporal_inputs(inputs)}
        if len(ts) > 1:
            raise ValueError(f"temporal inputs disagree on sequence length: {ts}")
        return ts.pop() if ts else None

    def _fit_tbptt(self, batch, t_total) -> None:
        """Truncated BPTT over the DAG (ComputationGraph's TBPTT dispatch in
        the reference fit loop): slice the declared-temporal inputs (and
        per-timestep labels/masks) into tbptt_fwd_length chunks, carrying
        recurrent state (KV caches, positional offsets, LSTM carries)
        between the jitted chunk steps. Per-sequence labels (``[N,C]``, class
        ids ``[N]``) are fed whole to every chunk, as in the
        sequential-network TBPTT."""
        inputs, labels, masks, lmasks = batch
        check_carry_capacity(
            ((vd.name, vd.obj) for vd in self.conf.layer_vertices()),
            t_total, "TBPTT")
        temporal = self._temporal_inputs(inputs)
        length = self.conf.tbptt_fwd_length
        n_chunks = max(1, math.ceil(t_total / length))
        batch_size = next(iter(inputs.values())).shape[0]
        dtype = self.conf.global_conf.jnp_dtype()
        carries = {vd.name: vd.obj.init_carry(batch_size, dtype)
                   for vd in self.conf.layer_vertices()
                   if isinstance(vd.obj, BaseRecurrentLayer)}
        for c in range(n_chunks):
            s, e = c * length, min((c + 1) * length, t_total)
            ic = {n: (a[:, s:e] if n in temporal else a)
                  for n, a in inputs.items()}
            lc = [a[:, s:e] if per_timestep_labels(a, t_total) else a
                  for a in labels]
            mc = None if masks is None else {
                n: (a[:, s:e] if a is not None and n in temporal
                    and a.shape[1] == t_total else a)
                for n, a in masks.items()}
            lmc = None if lmasks is None else [
                a[:, s:e] if a is not None
                and per_timestep_labels(labels[i], t_total)
                and a.shape[1] == t_total else a
                for i, a in enumerate(lmasks)]
            carries = self._dispatch_step((ic, lc, mc, lmc), carries)

    # ------------------------------------------------------------- inference
    def _output_fn(self):
        from deeplearning4j_tpu.nn import helpers as _helpers
        key = ("out", _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def out_fn(params, states, inputs, masks):
                acts, _, _, _ = self._forward_all(params, states, inputs,
                                                  train=False, rng=None, masks=masks)
                return [acts[n] for n in self.conf.outputs]
            self._jit_cache[key] = jax.jit(out_fn)
        return self._jit_cache[key]

    def output(self, *xs, masks=None) -> Union[Array, List[Array]]:
        dtype = self.conf.global_conf.jnp_dtype()
        if len(xs) == 1 and isinstance(xs[0], (list, tuple)):
            xs = tuple(xs[0])
        inputs = {n: _as_jnp(x, dtype) for n, x in zip(self.conf.inputs, xs)}
        mask_d = None
        if masks is not None:
            mask_d = {n: (None if m is None else _as_jnp(m))
                      for n, m in zip(self.conf.inputs, masks)}
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import place_batch
            mesh = self._mesh
            inputs, mask_d = jax.tree_util.tree_map(
                lambda a: place_batch(a, mesh), (inputs, mask_d))
        outs = self._output_fn()(self.params, self.states, inputs, mask_d)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *xs, train: bool = False) -> Dict[str, Array]:
        dtype = self.conf.global_conf.jnp_dtype()
        if len(xs) == 1 and isinstance(xs[0], (list, tuple)):
            xs = tuple(xs[0])
        inputs = {n: _as_jnp(x, dtype) for n, x in zip(self.conf.inputs, xs)}
        acts, _, _, _ = self._forward_all(self.params, self.states, inputs,
                                          train=train, rng=None)
        return {k: v for k, v in acts.items() if ":" not in k}

    def predict(self, *xs) -> np.ndarray:
        out = self.output(*xs)
        if isinstance(out, list):
            out = out[0]
        return np.asarray(jnp.argmax(out, axis=-1))

    def score(self, ds=None) -> float:
        if ds is None:
            return self.score_
        mds = self._to_mds(ds)
        dtype = self.conf.global_conf.jnp_dtype()
        inputs = {n: _as_jnp(f, dtype) for n, f in zip(self.conf.inputs, mds.features)}
        labels = [_as_jnp(l, dtype) for l in mds.labels]
        loss, _ = self._loss_fn(self.params, self.states, inputs, labels,
                                None, None, None, train=False)
        return float(loss)

    def compute_gradient_and_score(self, features, labels):
        """Gradient-check hook (GradientCheckUtil parity for graphs)."""
        mds = self._to_mds(self._wrap(features, labels))
        dtype = self.conf.global_conf.jnp_dtype()
        inputs = {n: _as_jnp(f, dtype) for n, f in zip(self.conf.inputs, mds.features)}
        labs = [_as_jnp(l, dtype) for l in mds.labels]

        def lf(p):
            return self._loss_fn(p, self.states, inputs, labs, None, None, None,
                                 train=False)

        (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(self.params)
        return grads, float(loss)

    def _wrap(self, features, labels):
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        return MultiDataSet(
            features if isinstance(features, (list, tuple)) else [features],
            labels if isinstance(labels, (list, tuple)) else [labels])

    # ------------------------------------------------------ stateful RNN API
    def rnn_clear_previous_state(self) -> None:
        self._rnn_carries = None
        self._rnn_pos = 0

    def _rnn_step_fn(self):
        """Jitted stateful step: the whole per-chunk forward (KV-cache
        writes included) compiles to ONE executable per input shape, so
        autoregressive decoding is a jitted step per token, not per-op
        Python dispatch."""
        from deeplearning4j_tpu.nn import helpers as _helpers
        key = ("rnn_step", _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def step_fn(params, states, inputs, carries):
                acts, _, _, new_carries = self._forward_all(
                    params, states, inputs, train=False, rng=None,
                    carries=carries)
                return [acts[n] for n in self.conf.outputs], new_carries
            self._jit_cache[key] = jax.jit(step_fn)
        return self._jit_cache[key]

    def rnn_time_step(self, *xs) -> Union[Array, List[Array]]:
        dtype = self.conf.global_conf.jnp_dtype()
        if len(xs) == 1 and isinstance(xs[0], (list, tuple)):
            xs = tuple(xs[0])
        xs = [_as_jnp(x, dtype) for x in xs]
        squeeze = xs[0].ndim == 2
        if squeeze:
            xs = [x[:, None, :] for x in xs]
        if self._rnn_carries is None:
            batch = xs[0].shape[0]
            self._rnn_carries = {}
            self._rnn_pos = 0
            for vd in self.conf.layer_vertices():
                if isinstance(vd.obj, BaseRecurrentLayer):
                    self._rnn_carries[vd.name] = vd.obj.init_carry(batch, dtype)
        # finite carries (KV caches, positional offsets) cannot raise inside
        # the jitted step — enforce capacity host-side
        t_new = xs[0].shape[1]
        check_carry_capacity(
            ((vd.name, vd.obj) for vd in self.conf.layer_vertices()),
            self._rnn_pos + t_new,
            f"rnn_time_step at position {self._rnn_pos}+{t_new}")
        inputs = dict(zip(self.conf.inputs, xs))
        outs, self._rnn_carries = self._rnn_step_fn()(
            self.params, self.states, inputs, self._rnn_carries)
        self._rnn_pos += t_new
        if squeeze:
            outs = [o[:, -1, :] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------------ evaluation
    # ------------------------------------------------------------- pretrain
    def pretrain_layer(self, vertex_name: str, data, epochs: int = 1
                       ) -> "ComputationGraph":
        """Unsupervised pretraining of one layer vertex
        (``ComputationGraph.pretrainLayer``): the vertex's input activation
        is featurized with the rest of the graph frozen, then its own
        ``pretrain_loss`` is minimized with its configured updater."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if self.params is None:
            self.init()
        vd = self.conf.vertices[vertex_name]
        layer = vd.obj if vd.is_layer else None
        if layer is None or not hasattr(layer, "pretrain_loss"):
            raise ValueError(
                f"vertex {vertex_name!r} is not a pretrainable layer "
                "(needs pretrain_loss — VAE/autoencoder)")
        if hasattr(data, "features") or hasattr(data, "shape"):
            iterator = [data if hasattr(data, "features")
                        else DataSet(data, data)]
        else:
            iterator = data
        dtype = self.conf.global_conf.jnp_dtype()

        def step(p_v, upd_v, it, h, rng):
            loss, grads = jax.value_and_grad(
                lambda p: layer.pretrain_loss(p, h, rng))(p_v)
            new_p, new_upd = {}, {}
            for n, g in grads.items():
                u = self._updaters[vertex_name][n]
                lr = u.lr_at(it, 0.0)
                delta, s = u.update(g, upd_v[n], lr, it + 1.0)
                new_p[n] = p_v[n] - delta.astype(p_v[n].dtype)
                new_upd[n] = s
            return new_p, new_upd, loss

        jstep = jax.jit(step, donate_argnums=(0, 1))
        it_count = 0
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                mds = self._to_mds(ds)
                inputs = {n: _as_jnp(f, dtype)
                          for n, f in zip(self.conf.inputs, mds.features)}
                acts, _, _, _ = self._forward_all(
                    self.params, self.states, inputs, train=False, rng=None)
                ins = [acts[s] for s in vd.inputs]
                h = ins[0] if len(ins) == 1 else jnp.concatenate(ins, -1)
                (self.params[vertex_name],
                 self.updater_states[vertex_name], loss) = jstep(
                    self.params[vertex_name],
                    self.updater_states[vertex_name],
                    jnp.asarray(float(it_count), jnp.float32), h,
                    self._next_rng())
                it_count += 1
                self._score_arr = loss
        return self

    def pretrain(self, data, epochs: int = 1) -> "ComputationGraph":
        """Layer-wise pretraining over every pretrainable vertex in
        topological order (``ComputationGraph.pretrain``)."""
        if self.params is None:
            self.init()
        for name in self.conf.topo_order:
            vd = self.conf.vertices[name]
            if vd.is_layer and hasattr(vd.obj, "pretrain_loss"):
                self.pretrain_layer(name, data, epochs=epochs)
        return self

    def _eval_first_output(self, iterator, consume) -> None:
        """One evaluate loop for every evaluator: reset, convert to
        MultiDataSet, forward the FIRST output with features masks
        applied, then hand (labels, out, label_mask, ds) to ``consume``.
        Keeping a single code path prevents the evaluators from drifting
        apart on mask handling."""
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            mds = self._to_mds(ds)
            out = self.output(*mds.features, masks=mds.features_masks)
            if isinstance(out, list):
                out = out[0]
            lm = (None if mds.labels_masks is None
                  else mds.labels_masks[0])
            consume(np.asarray(mds.labels[0]), np.asarray(out),
                    None if lm is None else np.asarray(lm), ds)

    def evaluate(self, iterator, top_n: int = 1) -> "Evaluation":
        """Evaluate the first output over an iterator
        (``ComputationGraph.evaluate``); ``top_n`` and collected record
        metadata flow through exactly as in MultiLayerNetwork.evaluate."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation(top_n=top_n)
        self._eval_first_output(
            iterator,
            lambda labels, out, lm, ds: e.eval(
                labels, out, mask=lm,
                record_meta_data=getattr(ds, "example_meta_data", None)))
        return e

    def summary(self) -> str:
        """Vertex table with parameter counts
        (``ComputationGraph.summary()``)."""
        if self.params is None:
            self.init()
        rows = []
        total = 0
        for name in self.conf.topo_order:
            vd = self.conf.vertices[name]
            if vd.is_layer:
                p = self.params.get(name, {})
                n = sum(int(np.prod(v.shape)) for v in p.values())
                total += n
                kind = type(vd.obj).__name__
            else:
                n, kind = 0, type(vd.obj).__name__
            rows.append((name, kind, f"{n:,}", ", ".join(vd.inputs)))
        w0 = max(6, max(len(r[0]) for r in rows))
        w1 = max(10, max(len(r[1]) for r in rows))
        w2 = max(8, max(len(r[2]) for r in rows))
        lines = ["=" * 76,
                 f"{'vertex':<{w0}}  {'type':<{w1}}  {'params':>{w2}}  inputs",
                 "-" * 76]
        for r in rows:
            lines.append(f"{r[0]:<{w0}}  {r[1]:<{w1}}  {r[2]:>{w2}}  {r[3]}")
        lines += ["-" * 76, f"Total parameters: {total:,}", "=" * 76]
        return "\n".join(lines)

    def evaluate_regression(self, iterator):
        """Per-column regression metrics over the first output
        (``ComputationGraph.evaluateRegression``)."""
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        e = RegressionEvaluation()
        self._eval_first_output(
            iterator,
            lambda labels, out, lm, ds: e.eval(labels, out, mask=lm))
        return e

    def evaluate_roc(self, iterator, threshold_steps: int = 0):
        """Binary ROC over the first output (``ComputationGraph
        .evaluateROC``)."""
        from deeplearning4j_tpu.eval.roc import ROC
        r = ROC(threshold_steps=threshold_steps)
        self._eval_first_output(
            iterator,
            lambda labels, out, lm, ds: r.eval(labels, out, mask=lm))
        return r

    def evaluate_roc_multi_class(self, iterator, threshold_steps: int = 0):
        """One-vs-all ROC per class over the first output
        (``ComputationGraph.evaluateROCMultiClass``)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        r = ROCMultiClass(threshold_steps=threshold_steps)
        self._eval_first_output(
            iterator,
            lambda labels, out, lm, ds: r.eval(labels, out, mask=lm))
        return r

    def evaluate_roc_binary(self, iterator, threshold_steps: int = 0):
        """Per-output binary ROC over the first output
        (``doEvaluation`` with ROCBinary), features and label masks
        honored."""
        from deeplearning4j_tpu.eval.roc import ROCBinary
        r = ROCBinary(threshold_steps=threshold_steps)
        self._eval_first_output(
            iterator,
            lambda labels, out, lm, ds: r.eval(labels, out, mask=lm))
        return r

    def output_single(self, *xs) -> Array:
        """First output as a single array (``outputSingle``)."""
        out = self.output(*xs)
        return out[0] if isinstance(out, list) else out

    def get_vertex(self, name: str):
        """Vertex definition by name (``getVertex``)."""
        return self.conf.vertices[name]

    def get_layer(self, name: str):
        """Layer object of a layer vertex (``getLayer``)."""
        vd = self.conf.vertices[name]
        if not vd.is_layer:
            raise KeyError(f"vertex {name!r} is not a layer vertex")
        return vd.obj

    def get_vertices(self) -> dict:
        """All vertex definitions by name (``getVertices``)."""
        return dict(self.conf.vertices)

    def get_num_layers(self) -> int:
        """Number of layer vertices (``getNumLayers``)."""
        return len(self.conf.layer_vertices())

    def get_num_input_arrays(self) -> int:
        """``getNumInputArrays``."""
        return len(self.conf.inputs)

    def get_num_output_arrays(self) -> int:
        """``getNumOutputArrays``."""
        return len(self.conf.outputs)

    def get_output_layer(self, index: int = 0):
        """Layer object of the index-th output vertex (``getOutputLayer``)."""
        name = self.conf.outputs[index]
        return self.get_layer(name)

    def topological_sort_order(self) -> list:
        """Vertex names in execution order (``topologicalSortOrder``)."""
        return list(self.conf.topo_order)

    def rnn_get_previous_state(self, name: str):
        """Stored carry of a recurrent layer vertex
        (``rnnGetPreviousState``), or None before any rnn_time_step."""
        if self._rnn_carries is None:
            return None
        return self._rnn_carries.get(name)

    def rnn_get_previous_states(self) -> dict:
        """All stored carries by vertex name (``rnnGetPreviousStates``)."""
        return dict(self._rnn_carries or {})

    def rnn_set_previous_state(self, name: str, state,
                               position: Optional[int] = None) -> None:
        """Overwrite a recurrent vertex's stored carry
        (``rnnSetPreviousState``); ``position`` (total timesteps already
        absorbed) is required when any layer has a finite carry so the
        host-side capacity guard stays in sync with the restored cache."""
        if self._rnn_carries is None:
            raise ValueError(
                "no stored rnn state to overwrite; call rnn_time_step "
                "first to initialize the carries")
        if position is not None:
            self._rnn_pos = int(position)
        else:
            from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
            finite = any(
                vd.is_layer and isinstance(vd.obj, BaseRecurrentLayer)
                and vd.obj.carry_capacity() is not None
                for vd in self.conf.vertices.values())
            if finite:
                raise ValueError(
                    "rnn_set_previous_state needs position= when a layer "
                    "has a finite carry capacity (KV cache)")
        self._rnn_carries[name] = state

    def rnn_set_previous_states(self, states: dict,
                                position: Optional[int] = None) -> None:
        """Overwrite several carries at once (``rnnSetPreviousStates``)."""
        for name, state in states.items():
            self.rnn_set_previous_state(name, state, position=position)

    def get_layers(self) -> list:
        """All layer objects in topological order (``getLayers``)."""
        return [vd.obj for vd in self.conf.layer_vertices()]

    def param_table(self) -> dict:
        """All parameters keyed ``"<vertexName>_<param>"``
        (``paramTable()``), e.g. ``"dense0_W"``."""
        out = {}
        for vname, p in (self.params or {}).items():
            for pname, arr in p.items():
                out[f"{vname}_{pname}"] = arr
        return out

    def get_param(self, key: str) -> Array:
        """One parameter by ``"<vertexName>_<param>"`` key (``getParam``).
        The vertex name is matched longest-first since names may contain
        underscores."""
        vname, pname = self._split_param_key(key)
        return self.params[vname][pname]

    def set_param(self, key: str, value) -> None:
        """Replace one parameter (``setParam``); shape must match."""
        vname, pname = self._split_param_key(key)
        old = self.params[vname][pname]
        arr = jnp.asarray(value, old.dtype)
        if arr.shape != old.shape:
            raise ValueError(
                f"shape mismatch for {key}: {arr.shape} vs {old.shape}")
        self.params[vname] = {**self.params[vname], pname: arr}

    def _split_param_key(self, key: str):
        for vname in sorted(self.params or {}, key=len, reverse=True):
            prefix = f"{vname}_"
            if key.startswith(prefix) and key[len(prefix):] in self.params[vname]:
                return vname, key[len(prefix):]
        raise KeyError(f"no parameter {key!r}")

    def save(self, path: str, save_updater: bool = True) -> None:
        """Write this graph as a checkpoint zip (``ComputationGraph.save``)."""
        from deeplearning4j_tpu.util import model_serializer
        model_serializer.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        """Restore from a checkpoint zip (``ComputationGraph.load``)."""
        from deeplearning4j_tpu.util import model_serializer
        return model_serializer.restore_computation_graph(
            path, load_updater=load_updater)

    def layer_size(self, name: str) -> int:
        """Output size of a layer vertex (``layerSize``)."""
        vd = self.conf.vertices[name]
        n = getattr(vd.obj, "n_out", None) if vd.is_layer else None
        if n:
            return int(n)
        p = (self.params or {}).get(name, {})
        if "W" in p:
            return int(p["W"].shape[-1])
        raise ValueError(f"vertex {name!r} has no defined output size")

    def set_learning_rate(self, lr) -> None:
        """Runtime LR override for every updater (``setLearningRate``);
        rebuilds the frozen updater dataclasses and invalidates the jit
        cache (momentum/state carries over)."""
        import dataclasses as _dc
        rep = lambda u: (_dc.replace(u, learning_rate=lr)
                         if hasattr(u, "learning_rate") else u)
        self._updaters = {
            name: {n: rep(u) for n, u in umap.items()}
            for name, umap in self._updaters.items()}
        for vd in self.conf.layer_vertices():
            if vd.obj.updater is not None and hasattr(
                    vd.obj.updater, "learning_rate"):
                vd.obj.updater = _dc.replace(vd.obj.updater,
                                             learning_rate=lr)
        g = self.conf.global_conf
        if g.updater is not None and hasattr(g.updater, "learning_rate"):
            g.updater = _dc.replace(g.updater, learning_rate=lr)
        self._jit_cache.clear()

    def score_examples(self, ds, add_regularization: bool = False
                       ) -> np.ndarray:
        """Per-example losses over the first labels
        (``ComputationGraph.scoreExamples``), one jitted vmap."""
        mds = self._to_mds(ds)
        dtype = self.conf.global_conf.jnp_dtype()
        inputs = {n: _as_jnp(f, dtype)
                  for n, f in zip(self.conf.inputs, mds.features)}
        labels = [_as_jnp(l, dtype) for l in mds.labels]

        def one(ins, labs):
            loss, _ = self._loss_fn(
                self.params, self.states,
                {k: v[None] for k, v in ins.items()},
                [l[None] for l in labs], None, None, None, train=False)
            return loss

        scores = jax.jit(jax.vmap(one))(inputs, labels)
        reg = self._regularization(self.params)
        scores = scores - reg + (reg if add_regularization else 0.0)
        return np.asarray(scores)

    # ------------------------------------------------------------------ misc
    def num_params(self) -> int:
        if self.params is None:
            return self.conf.num_params()
        return sum(v.size for p in self.params.values() for v in p.values())

    def clone(self) -> "ComputationGraph":
        # jnp.array COPIES the buffers: the original's donating train step
        # must not be able to invalidate the clone's arrays
        copy_arr = lambda a: jnp.array(a) if hasattr(a, "dtype") else a
        other = ComputationGraph(self.conf)
        other.params = jax.tree_util.tree_map(copy_arr, self.params)
        other.states = jax.tree_util.tree_map(copy_arr, self.states)
        other.updater_states = jax.tree_util.tree_map(copy_arr, self.updater_states)
        other._updaters = self._updaters
        other.iteration = self.iteration
        other.epoch = self.epoch
        other._rng_key = self._rng_key
        return other
