"""ParallelWrapper — mesh-sharded distributed training.

Reference semantics being reproduced (SURVEY.md §2.b):

- ``ParallelWrapper.java:58-137``: single-node data parallelism with
  ``TrainingMode.SHARED_GRADIENTS`` (per-step gradient sync via
  ``EncodedGradientsAccumulator``) and ``TrainingMode.AVERAGING``
  (parameter + updater-state averaging every ``averagingFrequency``
  iterations, ``:250-256,338``).
- ``ParameterAveragingTrainingMaster.java:308``: the multi-node sync variant
  of the same averaging math.

TPU-native design — no thread replication, no message passing:

- **shared_gradients** (default): the global batch is sharded over the mesh
  'data' axis and params are replicated. The model's ordinary jitted train
  step then *is* synchronous data-parallel SGD — XLA GSPMD emits one fused
  all-reduce of the gradients over ICI. This collapses the whole
  accumulator/FancyBlockingQueue machinery into compiler output.
- **averaging**: a ``shard_map`` over the 'data' axis runs
  ``averaging_frequency`` *independent* local steps per device
  (``lax.scan``), then ``pmean``s params and updater state — bit-for-bit the
  reference's semantics (each worker drifts, then syncs), but as one compiled
  program instead of N threads + a host barrier.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.observe import trace as _trace
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, make_mesh, shard_map
from deeplearning4j_tpu.parallel.sharding import batch_sharding, shard_model


from deeplearning4j_tpu.datasets.dataset import batch_nbytes as _batch_nbytes


def make_pure_step(net):
    """The model's train step (``TrainingEngine._step_body``) as a pure
    function ``(params, states, upd, it, ep, x, y, mask, lmask, rng) ->
    (params, states, upd, loss)`` suitable for scan/shard_map composition."""
    def step(params, states, upd, it, ep, x, y, mask, lmask, rng):
        return net._step_body(params, states, upd, it, ep,
                              (x, y, mask, lmask), rng)[:4]
    return step


class ParallelWrapper:
    """Data-parallel trainer over a device mesh (ParallelWrapper parity).

    Usage::

        net = MultiLayerNetwork(conf); net.init()
        pw = ParallelWrapper(net, mode="shared_gradients")
        pw.fit(iterator, epochs=2)
    """

    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 mode: str = "shared_gradients",
                 averaging_frequency: int = 5,
                 tp_axis: Optional[str] = None,
                 data_axis: str = DATA_AXIS,
                 metrics=None, metrics_name: str = "default"):
        if mode not in ("shared_gradients", "averaging"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "averaging" and tp_axis is not None:
            raise ValueError("averaging mode runs workers on replicated params; "
                             "tensor parallelism requires mode='shared_gradients'")
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.mode = mode
        self.averaging_frequency = max(1, int(averaging_frequency))
        self.data_axis = data_axis
        self.tp_axis = tp_axis
        self._avg_step = None
        if model.params is None:
            model.init()
        shard_model(model, self.mesh, tp_axis=tp_axis)
        self.n_workers = self.mesh.shape[data_axis]
        # optional duck-typed registry (observe.metrics): training-side
        # host→device transfer accounting next to the listener's series
        self._metrics_name = metrics_name
        self._m_transfer = None
        if metrics is not None:
            self._m_transfer = metrics.counter(
                "training_transfer_bytes_total",
                "Host to device bytes shipped with training batches",
                ("model",))

    # ------------------------------------------------------------- evaluate
    def evaluate(self, iterator, top_n: int = 1):
        """Data-parallel evaluation over the mesh
        (``SparkDl4jMultiLayer.evaluate`` role): each batch's features are
        sharded over the 'data' axis (params replicated), so the forward
        pass all-gathers nothing and each device scores its shard; metrics
        accumulate in one host-side Evaluation (the eval classes' ``merge``
        covers multi-process topologies). Ragged tail batches run
        unsharded, same policy as training."""
        import numpy as _np

        from deeplearning4j_tpu.eval.evaluation import Evaluation

        e = Evaluation(top_n=top_n)
        if hasattr(iterator, "reset"):
            iterator.reset()
        put = lambda a: jax.device_put(
            jnp.asarray(a),
            batch_sharding(self.mesh, _np.asarray(a).ndim, self.data_axis))
        for ds in iterator:
            x = _np.asarray(ds.features)
            shardable = x.shape[0] % self.n_workers == 0
            feats = put(x) if shardable else x
            fm = ds.features_mask
            if fm is not None:
                fm = put(fm) if shardable else _np.asarray(fm)
            if hasattr(self.model, "_to_mds"):  # ComputationGraph face
                out = self.model.output(
                    feats, masks=None if fm is None else [fm])
            else:
                out = self.model.output(feats, mask=fm)
            if isinstance(out, list):
                out = out[0]
            e.eval(_np.asarray(ds.labels), _np.asarray(out),
                   mask=None if ds.labels_mask is None
                   else _np.asarray(ds.labels_mask),
                   record_meta_data=getattr(ds, "example_meta_data", None))
        return e

    # ------------------------------------------------------------------ fit
    def fit(self, data, labels=None, *, epochs: int = 1,
            prefetch_depth: Optional[int] = None) -> "ParallelWrapper":
        """``prefetch_depth`` (default 2, 0 disables) wraps iterator sources
        in AsyncDataSetIterator so a producer thread hides the host-side
        batch preparation — the ParallelWrapperMain ``--prefetchSize``
        semantics. No device-put stage here: batches are sharded over the
        mesh per step, so placement happens with the sharding applied."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import wrap_for_prefetch

        if labels is not None:
            iterator = [DataSet(data, labels)]
        elif isinstance(data, DataSet):
            iterator = [data]
        else:
            iterator = data
        iterator = wrap_for_prefetch(iterator, prefetch_depth,
                                     device_put=None)

        with _trace.span("parallel_fit", category="train",
                         attrs={"mode": self.mode, "workers": self.n_workers,
                                "epochs": epochs}):
            for _ in range(epochs):
                for listener in self.model.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(self.model)
                if hasattr(iterator, "reset"):
                    iterator.reset()
                if self.mode == "shared_gradients":
                    for ds in iterator:
                        self._fit_step_traced(ds)
                else:
                    self._fit_averaging(iterator)
                self.model.epoch += 1
                for listener in self.model.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(self.model)
        return self

    def _fit_step_traced(self, ds) -> None:
        """One step, wrapped in a ``train_step`` span when tracing is on.
        The traced path syncs on the loss so the span covers the DEVICE
        time of the step (and any compile nests under it — step 0's
        compile shows up loudly); untraced runs keep async dispatch."""
        tracer = _trace.get_active_tracer()
        if tracer is None:
            self._fit_batch_sync(ds)
            return
        net = self.model
        with tracer.span("train_step", category="train",
                         attrs={"mode": self.mode}) as sp:
            self._fit_batch_sync(ds)
            try:
                sp.set_attribute("loss", float(net.score_))  # device sync
            except Exception:  # noqa: BLE001 - score may be deferred
                pass
            sp.set_attribute("iteration", int(net.iteration))
            sp.set_attribute("batch", int(getattr(net, "last_batch_size", 0)
                                          or 0))

    # ------------------------------------------- shared-gradients (per step)
    def _fit_batch_sync(self, ds) -> None:
        """One globally-synchronous step: batch sharded over 'data', params
        replicated → XLA all-reduces gradients over ICI inside the step.

        A final ragged batch (size not divisible by the data-axis size) runs
        unsharded — same math, no DP speedup for that one step (the reference
        ParallelWrapper likewise handles arbitrary tail batches)."""
        net = self.model
        if self._m_transfer is not None:
            self._m_transfer.inc(_batch_nbytes(ds), model=self._metrics_name)
        n = int(np.asarray(ds.features).shape[0])
        if n % self.n_workers:
            net._fit_batch(ds)
            return
        put = lambda a: jax.device_put(
            jnp.asarray(a),
            batch_sharding(self.mesh, np.asarray(a).ndim, self.data_axis))
        from deeplearning4j_tpu.datasets.dataset import DataSet
        sharded = DataSet(
            put(ds.features), put(ds.labels),
            None if ds.features_mask is None else put(ds.features_mask),
            None if ds.labels_mask is None else put(ds.labels_mask))
        net._fit_batch(sharded)

    # ----------------------------------------------------- averaging mode
    def _build_avg_step(self, k: int, x_sds, y_sds, has_fm, has_lm, fm_nd, lm_nd):
        net = self.model
        step = make_pure_step(net)
        daxis = self.data_axis

        def worker(params, states, upd, it0, ep, xs, ys, fms, lms, rng):
            # params/states/upd arrive replicated; xs/ys are this worker's
            # [k, local_batch, ...] shard. Each worker gets a distinct rng.
            rng = jax.random.fold_in(rng, jax.lax.axis_index(daxis))

            def body(carry, inp):
                p, s, u, it = carry
                xi, yi, fmi, lmi, ri = inp
                p, s, u, loss = step(p, s, u, it, ep, xi, yi, fmi, lmi, ri)
                return (p, s, u, it + 1.0), loss

            rngs = jax.random.split(rng, k)
            (params, states, upd, _), losses = jax.lax.scan(
                body, (params, states, upd, it0), (xs, ys, fms, lms, rngs))
            # ParameterAveragingTrainingMaster parity: average params AND
            # updater state (averageUpdatersState, ParallelWrapper.java:338);
            # BN running stats averaged likewise.
            pm = lambda t: jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, daxis), t)
            return pm(params), pm(states), pm(upd), jax.lax.pmean(
                jnp.mean(losses), daxis)

        rep = P()
        spec = lambda nd: P(None, daxis, *([None] * (nd - 2)))
        mapped = shard_map(
            worker, mesh=self.mesh,
            in_specs=(rep, rep, rep, rep, rep, spec(x_sds), spec(y_sds),
                      spec(fm_nd) if has_fm else rep,
                      spec(lm_nd) if has_lm else rep, rep),
            out_specs=(rep, rep, rep, rep))
        return jax.jit(mapped, donate_argnums=(0, 1, 2))

    def _fit_averaging(self, iterator) -> None:
        """Accumulate averaging_frequency batches, then run K local steps per
        worker + param averaging as one compiled program. Batches whose size
        doesn't divide the worker count run unsharded via the model's own
        step (same tail-batch policy as shared_gradients)."""
        net = self.model
        k = self.averaging_frequency
        dtype = net.conf.global_conf.jnp_dtype()
        pending: List[Any] = []

        def stack_masks(masks, arrays):
            """None-mixed masks → all-ones of [batch, T] (DataSet.merge policy)."""
            if all(m is None for m in masks):
                return None
            out = []
            for m, a in zip(masks, arrays):
                if m is None:
                    a = np.asarray(a)
                    m = np.ones(a.shape[:2] if a.ndim >= 3 else a.shape[:1],
                                np.float32)
                out.append(jnp.asarray(np.asarray(m)))
            return jnp.stack(out)

        def flush():
            if not pending:
                return
            tracer = _trace.get_active_tracer()
            if tracer is None:
                _flush_inner()
                return
            with tracer.span("train_step", category="train",
                             attrs={"mode": "averaging",
                                    "local_steps": len(pending)}) as sp:
                _flush_inner()
                try:
                    sp.set_attribute("loss", float(net.score_))  # sync
                except Exception:  # noqa: BLE001
                    pass
                sp.set_attribute("iteration", int(net.iteration))

        def _flush_inner():
            kk = len(pending)
            if self._m_transfer is not None:
                self._m_transfer.inc(sum(_batch_nbytes(d) for d in pending),
                                     model=self._metrics_name)
            xs = jnp.stack([jnp.asarray(d.features, dtype) for d in pending])
            # class ids stay integers; one-hot and soft labels take dtype
            ys = jnp.stack([
                y if jnp.issubdtype(y.dtype, jnp.integer) else y.astype(dtype)
                for y in (jnp.asarray(d.labels) for d in pending)])
            fms = stack_masks([d.features_mask for d in pending],
                              [d.features for d in pending])
            lms = stack_masks([d.labels_mask for d in pending],
                              [d.labels for d in pending])
            from deeplearning4j_tpu.nn import helpers as _helpers
            key = ("avg", kk, xs.shape, ys.shape,
                   None if fms is None else fms.shape,
                   None if lms is None else lms.shape,
                   _helpers.version())  # updater-helper changes must retrace
            if self._avg_step is None or self._avg_step[0] != key:
                self._avg_step = (key, self._build_avg_step(
                    kk, xs.ndim, ys.ndim, fms is not None, lms is not None,
                    0 if fms is None else fms.ndim,
                    0 if lms is None else lms.ndim))
            fn = self._avg_step[1]
            it = jnp.asarray(net.iteration, jnp.float32)
            ep = jnp.asarray(net.epoch, jnp.float32)
            rng = net._next_rng()
            net.params, net.states, net.updater_states, loss = fn(
                net.params, net.states, net.updater_states, it, ep,
                xs, ys, fms, lms, rng)
            net.score_ = loss
            net.iteration += kk
            for listener in net.listeners:
                if hasattr(listener, "iteration_done"):
                    listener.iteration_done(net, net.iteration, net.epoch)
            pending.clear()

        for ds in iterator:
            if int(np.asarray(ds.features).shape[0]) % self.n_workers:
                flush()
                # ragged tail still crosses the host-device boundary: count
                # it (same accounting as the shared_gradients path)
                if self._m_transfer is not None:
                    self._m_transfer.inc(_batch_nbytes(ds),
                                         model=self._metrics_name)
                net._fit_batch(ds)  # ragged tail batch: unsharded
                continue
            if pending and np.asarray(ds.features).shape != np.asarray(
                    pending[-1].features).shape:
                flush()  # shape change (e.g. smaller tail): can't stack
            pending.append(ds)
            if len(pending) == k:
                flush()
        flush()
