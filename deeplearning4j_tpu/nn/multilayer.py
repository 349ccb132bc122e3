"""MultiLayerNetwork — sequential model with DL4J's training API, TPU-native.

Reference: ``nn/multilayer/MultiLayerNetwork.java`` (3.5k LoC): ``init():549``
(flattened param buffer), ``fit(DataSetIterator):1262``, ``output:2006``,
``rnnTimeStep:2800``, ``evaluate:2979``, TBPTT dispatch ``:1309``.

TPU design: params are a pytree (list of per-layer dicts); the whole train
step — forward, loss, ``jax.grad`` backward, gradient normalization, l1/l2,
updater, param update — is ONE jitted function with donated buffers, so XLA
fuses it and params never leave HBM. There is no Solver/ConvexOptimizer object
tree; the optimizer loop IS the compiled function (the reference's
StochasticGradientDescent.optimize():58-98 collapses into it). TBPTT runs the
jitted chunk step in a host loop carrying stopped-gradient RNN state. The step
and the fit loop are ``TrainingEngine``'s (``nn/engine.py``), shared with
ComputationGraph; this file holds what a chain of layers needs of its own.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.engine import TrainingEngine, per_timestep_labels
from deeplearning4j_tpu.nn.layers.base import Layer, cast_params
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer, check_carry_capacity
from deeplearning4j_tpu.nn.updaters import (
    Sgd,
    Updater,
    schedule_value,
)
from deeplearning4j_tpu.observe import scope as _scope

Array = jax.Array
Params = List[Dict[str, Array]]
States = List[Dict[str, Array]]


def _as_jnp(x, dtype=None):
    if isinstance(x, (np.ndarray, list, tuple)) or np.isscalar(x):
        x = jnp.asarray(x)
    if dtype is not None and x.dtype != dtype and jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(dtype)
    return x


class MultiLayerNetwork(TrainingEngine):
    """Sequential network over a MultiLayerConfiguration."""

    def __init__(self, conf: MultiLayerConfiguration):
        conf.finalize()
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.params: Optional[Params] = None
        self.states: Optional[States] = None
        self.updater_states: Optional[List[Dict[str, Dict[str, Array]]]] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._score_arr = None  # device array; float() only on read (no sync/step)
        self._rng_key: Optional[jax.Array] = None
        self._jit_cache: Dict[Any, Any] = {}
        self._rnn_carries: Optional[List[Any]] = None
        self._rnn_pos = 0
        # cumulative host→device batch payload shipped by fit(); the
        # TraceListener exports deltas as training_transfer_bytes_total
        self.transfer_bytes = 0
        # resolve per-layer / per-param updaters once
        self._updaters: List[Dict[str, Updater]] = []

    # ------------------------------------------------------------------ init
    def _init_trees(self, seed: Optional[int] = None) -> None:
        """What ``init()`` (``nn/engine.py``) draws for a chain, by index."""
        g = self.conf.global_conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng_key = jax.random.fold_in(key, 0x5EED)
        dtype = g.jnp_dtype()
        keys = jax.random.split(key, len(self.layers))
        self.params = [l.init_params(k, dtype) for l, k in zip(self.layers, keys)]
        self.states = [l.init_state() for l in self.layers]
        default_updater = g.updater or Sgd(0.1)
        self._updaters = []
        self.updater_states = []
        for l, p in zip(self.layers, self.params):
            layer_upd = l.updater or default_updater
            bias_upd = l.bias_updater or g.bias_updater or layer_upd
            umap, smap = {}, {}
            for n, v in p.items():
                u = bias_upd if n == "b" else layer_upd
                umap[n] = u
                smap[n] = u.init_state(v)
            self._updaters.append(umap)
            self.updater_states.append(smap)
        self.iteration = 0
        self.epoch = 0

    # ------------------------------------------------------------- forward
    def _forward_all(self, params: Params, states: States, x: Array, *,
                     train: bool, rng: Optional[jax.Array], mask: Optional[Array],
                     carries: Optional[List[Any]] = None, upto: Optional[int] = None,
                     ) -> Tuple[Array, States, Optional[List[Any]]]:
        """Run layers [0, upto); returns (activation, new_states, new_carries)."""
        n_layers = len(self.layers) if upto is None else upto
        cd = self.conf.global_conf.jnp_compute_dtype()
        if cd is not None:
            # mixed precision: cast f32 master params + input to the compute
            # dtype; jax.grad through the cast yields master-dtype gradients
            cast = lambda a: (a.astype(cd)
                              if hasattr(a, "dtype")
                              and jnp.issubdtype(a.dtype, jnp.floating) else a)
            with jax.named_scope(_scope.CAST_PARAMS):
                params = cast_params(self.layers.__getitem__, params, cast)
                x = cast(x)
        h = x
        new_states: States = []
        new_carries: List[Any] = []
        rngs = (jax.random.split(rng, len(self.layers)) if rng is not None
                else [None] * len(self.layers))
        cur_mask = mask
        for i in range(len(self.layers)):
            if i >= n_layers:
                new_states.append(states[i])
                new_carries.append(None if carries is None else carries[i])
                continue
            layer = self.layers[i]
            with _scope.layer_scope(i, layer):
                if i in self.conf.preprocessors:
                    h = self.conf.preprocessors[i](h)
                p_i, rng_i = params[i], rngs[i]
                if (getattr(layer, "weight_noise", None) is not None and train
                        and rng_i is not None):
                    # IWeightNoise (DropConnect/WeightNoise): noise the WEIGHTS
                    # at forward time, train only (weightnoise/DropConnect.java:19)
                    rng_wn, rng_i = jax.random.split(rng_i)
                    p_i = layer.weight_noise.apply(layer, p_i, rng_wn, train)
                if carries is not None and isinstance(layer, BaseRecurrentLayer):
                    y, c = layer.forward_seq(p_i, h, carry=carries[i], mask=cur_mask,
                                             train=train, rng=rng_i)
                    new_states.append(states[i])
                    new_carries.append(c)
                    h = y
                else:
                    fwd = lambda p, hh, _l=layer, _i=i, _r=rng_i: _l.forward(
                        p, hh, state=states[_i], train=train, rng=_r,
                        mask=cur_mask)
                    if train and self.conf.global_conf.gradient_checkpointing:
                        # rematerialize this layer's activations in the backward
                        # pass instead of storing them (HBM ↔ FLOPs trade)
                        fwd = jax.checkpoint(fwd)
                    h, st = fwd(p_i, h)
                    new_states.append(st if st else states[i])
                    new_carries.append(None)
            # per-TIMESTEP masks collapse when the time dimension disappears;
            # a per-example [N]/[N,1] mask stays valid on 2d activations
            if (cur_mask is not None and h.ndim == 2 and cur_mask.ndim == 2
                    and cur_mask.shape[1] > 1):
                cur_mask = None
        return h, new_states, new_carries

    def _regularization(self, params: Params) -> Array:
        reg = jnp.asarray(0.0, jnp.float32)
        for l, p in zip(self.layers, params):
            for n, v in p.items():
                is_bias = n == "b"
                l1 = (l.l1_bias if is_bias else l.l1) or 0.0
                l2 = (l.l2_bias if is_bias else l.l2) or 0.0
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(v))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(v * v)
        return reg

    def _loss_fn(self, params: Params, states: States, x, y, rng,
                 mask, label_mask, train: bool,
                 carries: Optional[List[Any]] = None):
        out_layer = self.layers[-1]
        if not out_layer.has_loss():
            raise ValueError("Last layer must be an output/loss layer for fit()")
        h, new_states, new_carries = self._forward_all(
            params, states, x, train=train, rng=rng, mask=mask, carries=carries,
            upto=len(self.layers) - 1)
        last = len(self.layers) - 1
        with _scope.layer_scope(last, out_layer):
            if last in self.conf.preprocessors:
                h = self.conf.preprocessors[last](h)
            if self.conf.global_conf.compute_dtype is not None:
                # loss head in f32 for stable softmax/log under mixed precision
                h = h.astype(jnp.float32)
        if label_mask is not None:
            lm = label_mask
        elif mask is None:
            lm = None
        elif h.ndim == 3:
            lm = mask
        elif mask.ndim == 1 or (mask.ndim == 2 and mask.shape[-1] == 1):
            # per-example feature mask masks the score too (DL4J ScoreUtil)
            lm = mask.reshape(mask.shape[0])
        else:
            lm = None
        p_out = params[-1]
        if (getattr(out_layer, "weight_noise", None) is not None and train
                and rng is not None):
            # output layers get weight noise too (DL4J noises every layer's
            # preOutput); fold_in keeps the key distinct from _forward_all's
            p_out = out_layer.weight_noise.apply(
                out_layer, p_out, jax.random.fold_in(rng, len(self.layers)),
                train)
        with _scope.layer_scope(last, out_layer), \
                jax.named_scope(_scope.LOSS):
            loss = out_layer.compute_loss(p_out, h, y, mask=lm)
        with jax.named_scope(_scope.REGULARIZATION):
            loss = loss + self._regularization(params)
        return loss, (new_states, new_carries)

    # ------------------------------------------------------------ train step
    def _layer_items(self):
        return list(enumerate(self.layers))

    @staticmethod
    def _tree_of(pairs):
        return [v for _, v in pairs]

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1,
            features_mask=None, labels_mask=None,
            prefetch_depth: Optional[int] = None) -> "MultiLayerNetwork":
        """Train. ``data`` is (x, y) arrays, a DataSet, or a DataSetIterator.

        Iterator sources are auto-wrapped in async host→device prefetch
        (``AsyncDataSetIterator`` + device-put stage): a producer thread
        prepares and ships batch N+1 while the device runs batch N, so the
        step never stalls on ETL or the transfer. ``prefetch_depth`` sets
        the queue depth (default 2 — double buffering); 0 disables.
        Iterators with ``async_supported = False`` (AsyncShield) are never
        wrapped. The per-batch wait shows up as a ``host_wait`` trace span
        and the shipped payload as ``training_transfer_bytes_total``.

        Under ``observe.enable_tracing()`` each step records three spans:
        ``host_wait``, ``step_dispatch`` (the call of the jitted step,
        attribute ``iteration``; a compile it pays for nests under it) and
        ``listeners``. None of them waits for the device. The step's name
        scopes (``observe/scope.py``) are always on: the compiled program
        is ``jit_train_step`` and its operations carry their layer's
        ``Class:index``."""
        if self.params is None:
            self.init()
        from deeplearning4j_tpu.datasets.dataset import DataSet  # no cycle

        if labels is not None:
            iterator = [DataSet(data, labels, features_mask, labels_mask)]
        elif isinstance(data, DataSet):
            iterator = [data]
        else:
            iterator = data  # assume iterable of DataSet
        self._fit_epochs(iterator, epochs, prefetch_depth)
        return self

    def fit_batches_on_device(self, datasets) -> "MultiLayerNetwork":
        """Train on a window of equal-shape batches in ONE device dispatch
        (``lax.scan`` over the stacked window) — semantically identical to
        ``fit`` per batch; built for dispatch-bound setups. Requires
        uniform shapes, no masks, standard backprop."""
        from deeplearning4j_tpu.nn.conf.network import normalize_backprop_type
        if self.params is None:
            self.init()
        if normalize_backprop_type(self.conf.backprop_type) != "standard":
            raise ValueError("fit_batches_on_device supports standard "
                             "backprop only (not TBPTT)")
        datasets = list(datasets)
        if not datasets:
            return self
        if any(ds.features_mask is not None or ds.labels_mask is not None
               for ds in datasets):
            raise ValueError("fit_batches_on_device does not carry masks")
        dtype = self.conf.global_conf.jnp_dtype()
        xs = jnp.stack([_as_jnp(ds.features, dtype) for ds in datasets])
        ys = jnp.stack([_as_jnp(ds.labels, dtype) for ds in datasets])
        multi = self._get_multi_train_step()
        it0 = jnp.asarray(self.iteration, jnp.float32)
        ep = jnp.asarray(self.epoch, jnp.float32)
        (self.params, self.states, self.updater_states, losses) = multi(
            self.params, self.states, self.updater_states, it0, ep, xs, ys,
            self._next_rng())
        self.last_batch_size = int(xs.shape[1])
        for i in range(len(datasets)):
            self._score_arr = losses[i]
            self.iteration += 1
            self._iteration_done()
        return self

    def _to_batch(self, ds):
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(ds.features, dtype)
        y = _as_jnp(ds.labels, dtype)
        mask = None if ds.features_mask is None else _as_jnp(ds.features_mask)
        lmask = None if ds.labels_mask is None else _as_jnp(ds.labels_mask)
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import place_batch
            x = place_batch(x, self._mesh)
            y = place_batch(y, self._mesh)
            mask = place_batch(mask, self._mesh)
            lmask = place_batch(lmask, self._mesh)
        return x, y, mask, lmask

    @staticmethod
    def _temporal_length(x):
        return x.shape[1] if x.ndim == 3 else None

    def _fit_tbptt(self, batch, t_total) -> None:
        """Truncated BPTT (MultiLayerNetwork.doTruncatedBPTT:1309 parity):
        process the sequence in chunks of tbptt_fwd_length, carrying RNN state
        (stop-gradient) between chunks."""
        x, y, mask, lmask = batch
        # the chunk steps are jitted, where a finite carry (KV cache,
        # positional offset) cannot raise on overflow — reject here instead
        check_carry_capacity(
            ((f"layer {i} ({type(l).__name__})", l)
             for i, l in enumerate(self.layers)), t_total, "TBPTT")
        length = self.conf.tbptt_fwd_length
        n_chunks = max(1, math.ceil(t_total / length))
        carries = [l.init_carry(x.shape[0], x.dtype) if isinstance(l, BaseRecurrentLayer) else None
                   for l in self.layers]
        for c in range(n_chunks):
            s, e = c * length, min((c + 1) * length, t_total)
            xc = x[:, s:e]
            yc = y[:, s:e] if per_timestep_labels(y, t_total) else y
            mc = None if mask is None else mask[:, s:e]
            lc = None if lmask is None else lmask[:, s:e]
            carries = self._dispatch_step((xc, yc, mc, lc), carries)

    # ------------------------------------------------------------- inference
    def _output_fn(self):
        # one jitted callable; jax.jit itself specializes per input shape.
        # The helper-registry version is part of the key: the registry is
        # consulted at trace time, so registration changes must retrace.
        from deeplearning4j_tpu.nn import helpers as _helpers
        key = ("out", _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def out_fn(params, states, x, mask):
                h, _, _ = self._forward_all(params, states, x, train=False,
                                            rng=None, mask=mask)
                return h
            self._jit_cache[key] = jax.jit(out_fn)
        return self._jit_cache[key]

    def output(self, x, mask=None) -> Array:
        """Inference forward. Also accepts a DataSetIterator (the
        reference's ``output(DataSetIterator)`` overload) — batch outputs
        are concatenated."""
        if hasattr(x, "features") or (hasattr(x, "__iter__")
                                      and not hasattr(x, "shape")
                                      and not isinstance(x, (list, tuple))):
            it = [x] if hasattr(x, "features") else x
            if hasattr(it, "reset"):
                it.reset()
            outs = [np.asarray(self.output(
                ds.features,
                mask=None if ds.features_mask is None else ds.features_mask))
                for ds in it]
            return jnp.concatenate([jnp.asarray(o) for o in outs], axis=0)
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(x, dtype)
        mask = None if mask is None else _as_jnp(mask)
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import place_batch
            x = place_batch(x, self._mesh)
            mask = place_batch(mask, self._mesh)
        return self._output_fn()(self.params, self.states, x, mask)

    def feed_forward(self, x, train: bool = False) -> List[Array]:
        """Per-layer activations (MultiLayerNetwork.feedForward parity)."""
        return self.feed_forward_to_layer(len(self.layers) - 1, x,
                                          train=train)

    def feed_forward_to_layer(self, layer_num: int, x,
                              train: bool = False) -> List[Array]:
        """Activations through layer ``layer_num`` inclusive, stopping
        there (``feedForwardToLayer:949``)."""
        if not 0 <= layer_num < len(self.layers):
            raise ValueError(f"layer_num {layer_num} out of range "
                             f"[0, {len(self.layers)})")
        dtype = self.conf.global_conf.jnp_dtype()
        h = _as_jnp(x, dtype)
        acts = [h]
        for i in range(layer_num + 1):
            if i in self.conf.preprocessors:
                h = self.conf.preprocessors[i](h)
            h, _ = self.layers[i].forward(self.params[i], h,
                                          state=self.states[i],
                                          train=train, rng=None)
            acts.append(h)
        return acts

    # -- layer / parameter access (MultiLayerNetwork getters) ---------------
    @property
    def n_layers(self) -> int:
        """``getnLayers()``."""
        return len(self.layers)

    def get_layer(self, idx) -> Layer:
        """Layer by index or by name (``getLayer``)."""
        if isinstance(idx, str):
            for l in self.layers:
                if l.name == idx:
                    return l
            raise KeyError(f"no layer named {idx!r}")
        return self.layers[idx]

    def get_layers(self) -> List[Layer]:
        return list(self.layers)

    def get_output_layer(self) -> Layer:
        """``getOutputLayer()`` — the final layer."""
        return self.layers[-1]

    def param_table(self) -> Dict[str, Array]:
        """All parameters keyed DL4J-style ``"<layerIdx>_<name>"``
        (``paramTable()``), e.g. ``"0_W"``."""
        out: Dict[str, Array] = {}
        for i, p in enumerate(self.params or []):
            for name, arr in p.items():
                out[f"{i}_{name}"] = arr
        return out

    def get_param(self, key: str) -> Array:
        """One parameter by ``"<layerIdx>_<name>"`` key (``getParam``)."""
        idx, name = key.split("_", 1)
        return self.params[int(idx)][name]

    def set_param(self, key: str, value) -> None:
        """Replace one parameter (``setParam``); shape must match."""
        idx, name = key.split("_", 1)
        i = int(idx)
        old = self.params[i][name]
        arr = jnp.asarray(value, old.dtype)
        if arr.shape != old.shape:
            raise ValueError(
                f"shape mismatch for {key}: {arr.shape} vs {old.shape}")
        self.params[i] = {**self.params[i], name: arr}

    def num_labels(self) -> int:
        """Output dimension of the final layer (``numLabels``)."""
        out = getattr(self.layers[-1], "n_out", None)
        if not out:
            raise ValueError("output layer has no n_out")
        return int(out)

    # -- convenience classifier metrics -------------------------------------
    def f1_score(self, features, labels) -> float:
        """Macro F1 on a batch (``f1Score``)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        e.eval(np.asarray(labels), np.asarray(self.output(features)))
        return float(e.f1())

    def label_probabilities(self, x) -> np.ndarray:
        """Per-class probabilities (``labelProbabilities``) — the output
        activations for a softmax/sigmoid head."""
        return np.asarray(self.output(x))

    # -- rnn stored-state access --------------------------------------------
    def rnn_get_previous_state(self, layer: int):
        """Stored carry of a recurrent layer (``rnnGetPreviousState``),
        or None before any ``rnn_time_step`` call."""
        if self._rnn_carries is None:
            return None
        return self._rnn_carries[layer]

    def rnn_set_previous_state(self, layer: int, state,
                               position: Optional[int] = None) -> None:
        """Overwrite a recurrent layer's stored carry
        (``rnnSetPreviousState``); requires a prior ``rnn_time_step`` so
        the carry list exists.

        ``position``: total timesteps already absorbed by ``state``.
        Mandatory when any layer has a finite carry (KV cache) — the
        host-side capacity guard tracks position separately from the
        opaque carry, and a restored cache whose write offset disagrees
        with the guard would let a jitted ``dynamic_update_slice``
        silently clamp out-of-range writes."""
        if self._rnn_carries is None:
            raise ValueError(
                "no stored rnn state to overwrite; call rnn_time_step "
                "first to initialize the carries")
        if position is not None:
            self._rnn_pos = int(position)
        elif any(isinstance(l, BaseRecurrentLayer)
                 and l.carry_capacity() is not None for l in self.layers):
            raise ValueError(
                "rnn_set_previous_state needs position= when a layer has "
                "a finite carry capacity (KV cache): the restored cache's "
                "write offset must match the capacity guard")
        self._rnn_carries[layer] = state

    # -- save/load facades ----------------------------------------------------
    def save(self, path: str, save_updater: bool = True) -> None:
        """Write this model as a checkpoint zip (``MultiLayerNetwork.save``)."""
        from deeplearning4j_tpu.util import model_serializer
        model_serializer.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        """Restore from a checkpoint zip (``MultiLayerNetwork.load``)."""
        from deeplearning4j_tpu.util import model_serializer
        return model_serializer.restore_multi_layer_network(
            path, load_updater=load_updater)

    def predict(self, x) -> np.ndarray:
        out = self.output(x)
        return np.asarray(jnp.argmax(out, axis=-1))

    def score(self, ds=None) -> float:
        if ds is None:
            return self.score_
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(ds.features, dtype)
        y = _as_jnp(ds.labels, dtype)
        mask = None if ds.features_mask is None else _as_jnp(ds.features_mask)
        lmask = None if ds.labels_mask is None else _as_jnp(ds.labels_mask)
        loss, _ = self._loss_fn(self.params, self.states, x, y, None, mask, lmask,
                                train=False)
        return float(loss)

    def score_examples(self, ds, add_regularization: bool = False) -> np.ndarray:
        """Per-example losses (``MultiLayerNetwork.scoreExamples``): the
        data term of each example's loss, computed in one jitted ``vmap``
        over single-example batches (inference statistics, so examples are
        independent); ``add_regularization`` adds the network's l1/l2 term
        to every score, matching the reference."""
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(ds.features, dtype)
        y = _as_jnp(ds.labels, dtype)
        lmask = None if ds.labels_mask is None else _as_jnp(ds.labels_mask)

        def one(xi, yi, lmi):
            loss, _ = self._loss_fn(self.params, self.states, xi[None],
                                    yi[None], None, None,
                                    None if lmi is None else lmi[None],
                                    train=False)
            return loss

        if lmask is None:
            scores = jax.jit(jax.vmap(lambda a, b: one(a, b, None)))(x, y)
        else:
            scores = jax.jit(jax.vmap(one))(x, y, lmask)
        reg = self._regularization(self.params)
        # _loss_fn includes the regularization term once per (1-example)
        # batch; scoreExamples semantics: data term per example, plus reg
        # only when requested
        scores = scores - reg + (reg if add_regularization else 0.0)
        return np.asarray(scores)

    def compute_gradient_and_score(self, x, y, features_mask=None, labels_mask=None):
        """Returns (gradients pytree, score) without updating params —
        the hook used by gradient checks (GradientCheckUtil parity)."""
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(x, dtype)
        y = _as_jnp(y, dtype)

        def lf(p):
            return self._loss_fn(p, self.states, x, y, None,
                                 features_mask, labels_mask, train=False)

        (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(self.params)
        return grads, float(loss)

    # ------------------------------------------------------ stateful RNN API
    def rnn_clear_previous_state(self) -> None:
        self._rnn_carries = None
        self._rnn_pos = 0

    def _rnn_step_fn(self):
        """Jitted stateful step (see ComputationGraph._rnn_step_fn): one
        executable per input shape for autoregressive decoding."""
        from deeplearning4j_tpu.nn import helpers as _helpers
        key = ("rnn_step", _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def step_fn(params, states, x, carries):
                h, _, new_carries = self._forward_all(
                    params, states, x, train=False, rng=None, mask=None,
                    carries=carries)
                return h, new_carries
            self._jit_cache[key] = jax.jit(step_fn)
        return self._jit_cache[key]

    def rnn_time_step(self, x) -> Array:
        """Stateful single/multi-step inference (rnnTimeStep:2800 parity).
        x: [N, T, C] (or [N, C] for one step)."""
        dtype = self.conf.global_conf.jnp_dtype()
        x = _as_jnp(x, dtype)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        if self._rnn_carries is None:
            batch = x.shape[0]
            self._rnn_pos = 0
            self._rnn_carries = [
                l.init_carry(batch, dtype) if isinstance(l, BaseRecurrentLayer) else None
                for l in self.layers]
        # host-side capacity guard: finite carries cannot raise under jit
        t_new = x.shape[1]
        check_carry_capacity(
            ((f"layer {i}", l) for i, l in enumerate(self.layers)),
            self._rnn_pos + t_new,
            f"rnn_time_step at position {self._rnn_pos}+{t_new}")
        h, self._rnn_carries = self._rnn_step_fn()(
            self.params, self.states, x, self._rnn_carries)
        self._rnn_pos += t_new
        return h[:, -1, :] if squeeze and h.ndim == 3 else h

    # ------------------------------------------------------------ evaluation
    # ------------------------------------------------------------- pretrain
    def pretrain_layer(self, layer_idx: int, data, epochs: int = 1
                       ) -> "MultiLayerNetwork":
        """Unsupervised pretraining of ONE layer
        (``MultiLayerNetwork.pretrainLayer``): inputs are featurized
        through the frozen layers below, then the layer's own
        ``pretrain_loss`` (VAE ELBO / autoencoder reconstruction) is
        minimized with its configured updater in a jitted step."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if self.params is None:
            self.init()
        layer = self.layers[layer_idx]
        if not hasattr(layer, "pretrain_loss"):
            raise ValueError(
                f"layer {layer_idx} ({type(layer).__name__}) has no "
                "pretrain_loss — only VAE/autoencoder layers pretrain")
        if hasattr(data, "features"):
            iterator = [data]
        elif isinstance(data, np.ndarray) or hasattr(data, "shape"):
            iterator = [DataSet(data, data)]
        else:
            iterator = data
        dtype = self.conf.global_conf.jnp_dtype()

        def step(p_i, upd_i, it, x, rng):
            loss, grads = jax.value_and_grad(
                lambda p: layer.pretrain_loss(p, x, rng))(p_i)
            new_p, new_upd = {}, {}
            for n, g in grads.items():
                u = self._updaters[layer_idx][n]
                lr = u.lr_at(it, 0.0)
                delta, s = u.update(g, upd_i[n], lr, it + 1.0)
                new_p[n] = p_i[n] - delta.astype(p_i[n].dtype)
                new_upd[n] = s
            return new_p, new_upd, loss

        jstep = jax.jit(step, donate_argnums=(0, 1))
        it_count = 0
        loss = None
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                x = _as_jnp(ds.features, dtype)
                h, _, _ = self._forward_all(
                    self.params, self.states, x, train=False, rng=None,
                    mask=None, upto=layer_idx)
                (self.params[layer_idx], self.updater_states[layer_idx],
                 loss) = jstep(self.params[layer_idx],
                               self.updater_states[layer_idx],
                               jnp.asarray(float(it_count), jnp.float32),
                               h, self._next_rng())
                it_count += 1
        if loss is not None:
            self._score_arr = loss
        return self

    def pretrain(self, data, epochs: int = 1) -> "MultiLayerNetwork":
        """Layer-wise unsupervised pretraining over every pretrainable
        layer in order (``MultiLayerNetwork.pretrain(DataSetIterator)``)."""
        if self.params is None:
            self.init()
        for i, l in enumerate(self.layers):
            if hasattr(l, "pretrain_loss"):
                self.pretrain_layer(i, data, epochs=epochs)
        return self

    def evaluate(self, iterator, top_n: int = 1) -> "Evaluation":
        """Evaluate over an iterator (``MultiLayerNetwork.evaluate``).
        ``top_n`` > 1 additionally tracks top-N accuracy; when the iterator
        collects record metadata (``collect_meta_data=True``), per-record
        predictions are recorded for error drilldown (``doEvaluation``
        passes ``getExampleMetaData`` through, MultiLayerNetwork.java)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation(top_n=top_n)
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features, mask=None if ds.features_mask is None
                              else _as_jnp(ds.features_mask))
            e.eval(np.asarray(ds.labels), np.asarray(out),
                   mask=None if ds.labels_mask is None else np.asarray(ds.labels_mask),
                   record_meta_data=getattr(ds, "example_meta_data", None))
        return e

    def evaluate_roc(self, iterator, threshold_steps: int = 0) -> "ROC":
        """Binary ROC over an iterator (``MultiLayerNetwork.evaluateROC
        :2999``); ``threshold_steps > 0`` uses the binned mergeable mode."""
        from deeplearning4j_tpu.eval.roc import ROC
        r = ROC(threshold_steps=threshold_steps)
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features,
                              mask=None if ds.features_mask is None
                              else _as_jnp(ds.features_mask))
            r.eval(np.asarray(ds.labels), np.asarray(out),
                   mask=None if ds.labels_mask is None
                   else np.asarray(ds.labels_mask))
        return r

    def evaluate_roc_multi_class(self, iterator,
                                 threshold_steps: int = 0
                                 ) -> "ROCMultiClass":
        """One-vs-all ROC per class (``evaluateROCMultiClass``)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        r = ROCMultiClass(threshold_steps=threshold_steps)
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features,
                              mask=None if ds.features_mask is None
                              else _as_jnp(ds.features_mask))
            r.eval(np.asarray(ds.labels), np.asarray(out),
                   mask=None if ds.labels_mask is None
                   else np.asarray(ds.labels_mask))
        return r

    def evaluate_roc_binary(self, iterator,
                            threshold_steps: int = 0) -> "ROCBinary":
        """Per-output binary ROC for multi-label heads
        (``doEvaluation`` with ROCBinary), masks honored."""
        from deeplearning4j_tpu.eval.roc import ROCBinary
        r = ROCBinary(threshold_steps=threshold_steps)
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features,
                              mask=None if ds.features_mask is None
                              else _as_jnp(ds.features_mask))
            r.eval(np.asarray(ds.labels), np.asarray(out),
                   mask=None if ds.labels_mask is None
                   else np.asarray(ds.labels_mask))
        return r

    def evaluate_regression(self, iterator) -> "RegressionEvaluation":
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        e = RegressionEvaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features)
            e.eval(np.asarray(ds.labels), np.asarray(out))
        return e

    def summary(self) -> str:
        """Layer table with parameter counts
        (``MultiLayerNetwork.summary()``)."""
        if self.params is None:
            self.init()
        rows = []
        total = 0
        for i, (l, p) in enumerate(zip(self.layers, self.params)):
            n = sum(int(np.prod(v.shape)) for v in p.values())
            total += n
            shapes = ", ".join(f"{k}{tuple(v.shape)}"
                               for k, v in sorted(p.items()))
            name = getattr(l, "name", None) or ""
            rows.append((str(i), f"{type(l).__name__}"
                         + (f" ({name})" if name else ""),
                         f"{n:,}", shapes))
        w0 = max(5, max(len(r[0]) for r in rows))
        w1 = max(10, max(len(r[1]) for r in rows))
        w2 = max(8, max(len(r[2]) for r in rows))
        lines = ["=" * 76,
                 f"{'index':<{w0}}  {'layer':<{w1}}  {'params':>{w2}}  shapes",
                 "-" * 76]
        for r in rows:
            lines.append(f"{r[0]:<{w0}}  {r[1]:<{w1}}  {r[2]:>{w2}}  {r[3]}")
        lines += ["-" * 76, f"Total parameters: {total:,}", "=" * 76]
        return "\n".join(lines)

    def set_learning_rate(self, lr) -> None:
        """Override every updater's learning rate at runtime
        (``MultiLayerNetwork.setLearningRate``): updaters are frozen
        dataclasses closed over by the jitted step, so the override
        rebuilds them (state layouts are unchanged — momentum carries
        over) and invalidates the jit cache for a retrace."""
        import dataclasses as _dc
        rep = lambda u: (_dc.replace(u, learning_rate=lr)
                         if hasattr(u, "learning_rate") else u)
        self._updaters = [
            {n: rep(u) for n, u in umap.items()}
            for umap in self._updaters]
        for i, l in enumerate(self.layers):
            if l.updater is not None and hasattr(l.updater,
                                                 "learning_rate"):
                l.updater = _dc.replace(l.updater, learning_rate=lr)
        g = self.conf.global_conf
        if g.updater is not None and hasattr(g.updater, "learning_rate"):
            g.updater = _dc.replace(g.updater, learning_rate=lr)
        self._jit_cache.clear()

    def layer_size(self, layer_idx: int) -> int:
        """``layerSize(int)``: the layer's output size (nOut)."""
        l = self.layers[layer_idx]
        n = getattr(l, "n_out", None)
        if n:
            return int(n)
        p = (self.params or [{}] * len(self.layers))[layer_idx]
        if "W" in p:
            return int(p["W"].shape[-1])
        raise ValueError(f"layer {layer_idx} has no defined output size")

    def get_layer_names(self) -> List[str]:
        """``getLayerNames``: per-layer names (class name when unnamed)."""
        return [getattr(l, "name", None) or type(l).__name__
                for l in self.layers]

    def to_computation_graph(self) -> "Any":
        """Convert to an equivalent single-chain ComputationGraph carrying
        the SAME parameters and states (``toComputationGraph``)."""
        import copy

        from deeplearning4j_tpu.nn.conf.graph_conf import (
            GraphBuilder, VertexDef)
        from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        names = []
        counts = {}
        for l in self.layers:
            base = getattr(l, "name", None) or type(l).__name__.lower()
            counts[base] = counts.get(base, 0) + 1
            names.append(base if counts[base] == 1 else
                         f"{base}_{counts[base]}")
        g = GraphBuilder(copy.deepcopy(self.conf.global_conf))
        g.add_inputs("input")
        prev = "input"
        for nm, l in zip(names, self.layers):
            g.add_layer(nm, copy.deepcopy(l), prev)
            prev = nm
        conf = g.set_outputs(prev).build()
        net = ComputationGraph(conf)
        if self.params is not None:
            net.init()
            net.params = {nm: dict(p) for nm, p in zip(names, self.params)}
            net.states = {nm: dict(s) for nm, s in zip(names, self.states)}
            net.updater_states = {nm: {k: dict(v) for k, v in u.items()}
                                  for nm, u in zip(names,
                                                   self.updater_states)}
            net.iteration = self.iteration
            net.epoch = self.epoch
        return net

    # ------------------------------------------------------------------ misc
    def num_params(self) -> int:
        if self.params is None:
            return self.conf.num_params()
        total = 0
        for p in self.params:
            for v in p.values():
                total += v.size
        return total

    def params_flat(self) -> np.ndarray:
        """Single flattened param vector (DL4J params() parity)."""
        leaves = []
        for p in self.params:
            for n in sorted(p):
                leaves.append(np.asarray(p[n]).ravel())
        return np.concatenate(leaves) if leaves else np.zeros(0)

    def set_params_flat(self, flat: np.ndarray) -> None:
        offset = 0
        new_params = []
        for p in self.params:
            d = {}
            for n in sorted(p):
                size = p[n].size
                d[n] = jnp.asarray(flat[offset:offset + size].reshape(p[n].shape),
                                   p[n].dtype)
                offset += size
            new_params.append(d)
        self.params = new_params

    def clone(self) -> "MultiLayerNetwork":
        # jnp.array COPIES the buffers: the original's donating train step
        # must not be able to invalidate the clone's arrays
        copy_arr = lambda a: jnp.array(a) if hasattr(a, "dtype") else a
        other = MultiLayerNetwork(self.conf)
        other.params = jax.tree_util.tree_map(copy_arr, self.params)
        other.states = jax.tree_util.tree_map(copy_arr, self.states)
        other.updater_states = jax.tree_util.tree_map(copy_arr, self.updater_states)
        other._updaters = self._updaters
        other.iteration = self.iteration
        other.epoch = self.epoch
        other._rng_key = self._rng_key
        return other
