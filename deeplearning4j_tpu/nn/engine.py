"""TrainingEngine — the train step and the fit loop, once, under both
network engines.

``ComputationGraph`` reaches a layer by vertex name and holds its trees as
dicts; ``MultiLayerNetwork`` reaches it by index and holds lists. Everything
else about training is the same and lives here: the step's body (forward,
loss, ``jax.grad``, updaters), the jitted programs round it, the epoch loop
of ``fit`` and the dispatch of one step with its spans and its device tick.

An engine supplies ``_loss_fn(params, states, inputs, labels, rng, masks,
label_masks, train, carries)``, ``_layer_items()``, ``_tree_of(pairs)``,
``_to_batch(ds)``, ``_temporal_length(inputs)``, ``_fit_tbptt(batch,
t_total)`` and ``_init_trees(seed)``; a batch is ``(inputs, labels, masks,
label_masks)`` in the engine's own shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import helpers as _helpers
from deeplearning4j_tpu.nn.conf.network import normalize_backprop_type
from deeplearning4j_tpu.nn.constraints import apply_constraints
from deeplearning4j_tpu.nn.tick import device_tick, schedule_tick, store_tick
from deeplearning4j_tpu.nn.updaters import normalize_gradients
from deeplearning4j_tpu.observe import scope as _scope, trace as _trace


def per_timestep_labels(labels, t_total: int) -> bool:
    """Whether TBPTT cuts ``labels`` along time with the inputs: one-hot or
    soft rows ``[N,T,C]``, or integer class ids ``[N,T]``. Per-sequence
    labels (``[N,C]``, ids ``[N]``) go whole to every chunk."""
    timed = labels.ndim == 3 or (
        labels.ndim == 2 and jnp.issubdtype(labels.dtype, jnp.integer))
    return timed and labels.shape[1] == t_total


class TrainingEngine:
    """Base of ComputationGraph and MultiLayerNetwork."""

    # set by parallel.sharding.shard_model_with_rules: when present, fit()/
    # output() place incoming batches over the mesh's data axis so pjit sees
    # a consistent DP x MP layout end to end (GSPMD handles the rest), and
    # the train step pins updated params/opt-state back to the placed specs
    _mesh = None
    _param_shardings = None
    _upd_shardings = None

    # ---------------------------------------------------------------- score
    @property
    def score_(self) -> float:
        """Last minibatch loss. Reading this syncs with the device; the train
        loop itself never blocks on it (PerformanceListener-friendly)."""
        return float("nan") if self._score_arr is None else float(self._score_arr)

    @score_.setter
    def score_(self, v) -> None:
        self._score_arr = v

    # ----------------------------------------------------------------- tick
    def _device_tick(self, batch=None):
        return device_tick(self, batch)

    def _store_tick(self, new_it, new_rng) -> None:
        store_tick(self, new_it, new_rng)

    def _next_rng(self) -> jax.Array:
        self._rng_key, k = jax.random.split(self._rng_key)
        return k

    # ------------------------------------------------------------ listeners
    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listeners(self, *listeners) -> None:
        self.listeners.extend(listeners)

    def _iteration_done(self) -> None:
        for listener in self.listeners:
            if hasattr(listener, "iteration_done"):
                listener.iteration_done(self, self.iteration, self.epoch)

    # ------------------------------------------------------------ train step
    def _pin_placements(self, new_params, new_upd):
        """Inside-jit: constrain step outputs to the rule-placed shardings.
        Without this GSPMD may emit one param with a sharding of its own
        choosing and every subsequent compile re-layouts around the drifted
        leaf (observed: a replicated positional table coming back
        model-sharded cost 18 forward all-gathers)."""
        if self._param_shardings is not None:
            new_params = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_params,
                self._param_shardings)
        if self._upd_shardings is not None and new_upd is not None:
            new_upd = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_upd,
                self._upd_shardings)
        return new_params, new_upd

    def _apply_updates(self, params, grads, upd_states, it, ep):
        # "updater" helper seam: a registered fused kernel (e.g.
        # PallasUpdaterHelper) takes the whole per-param read-modify-write;
        # consulted at trace time, versioned into the train-step cache key
        uhelper = _helpers.get_helper("updater")
        new_params, new_upd = [], []
        for key, l in self._layer_items():
            with jax.named_scope(_scope.OPTIMIZER), \
                    _scope.layer_scope(key, l):
                g_layer = grads[key]
                if l.gradient_normalization:
                    g_layer = normalize_gradients(g_layer, l.gradient_normalization,
                                                  l.gradient_normalization_threshold)
                p_new, s_new = {}, {}
                for n, g in g_layer.items():
                    u = self._updaters[key][n]
                    lr = u.lr_at(it, ep)
                    t = it + 1.0  # 1-based step count for Adam-family bias correction
                    if uhelper is not None and uhelper.supports(u, params[key][n], g):
                        p_new[n], s_new[n] = uhelper.apply(
                            u, params[key][n], g, upd_states[key][n], lr, t)
                        continue
                    upd, s = u.update(g, upd_states[key][n], lr, t)
                    p_new[n] = params[key][n] - upd.astype(params[key][n].dtype)
                    s_new[n] = s
                # post-update parameter constraints (BaseConstraint.applyConstraint
                # runs after each iteration in the reference) — fused into the
                # jitted step, not a separate host pass
                new_params.append((key, apply_constraints(l, p_new)))
                new_upd.append((key, s_new))
        return self._tree_of(new_params), self._tree_of(new_upd)

    def _step_body(self, params, states, upd_states, it, ep, batch, rng,
                   carries=None):
        """One train step as a pure function: forward, loss, ``jax.grad``,
        updaters. Returns ``(params, states, upd_states, loss, carries)``.
        It pins no placement and opens no scope of its own, so a caller may
        put it under ``jit``, ``lax.scan`` or ``shard_map``."""
        inputs, labels, masks, label_masks = batch

        def lf(p):
            return self._loss_fn(p, states, inputs, labels, rng, masks,
                                 label_masks, train=True, carries=carries)
        with schedule_tick(it, ep):  # dropout pSchedule sees the device tick
            (loss, (new_states, new_carries)), grads = \
                jax.value_and_grad(lf, has_aux=True)(params)
        new_params, new_upd = self._apply_updates(params, grads, upd_states, it, ep)
        return new_params, new_states, new_upd, loss, new_carries

    def _evict_stale(self, current_version: int) -> None:
        _helpers.evict_stale_jit_entries(self._jit_cache, current_version)

    def _get_train_step(self, with_carries: bool = False):
        key = ("train", with_carries, self._mesh, _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def train_step(params, states, upd_states, it, ep, inputs, labels,
                           masks, label_masks, rng, carries=None):
                # split on DEVICE and return the next key + iteration: the
                # fit loop then re-feeds them without any per-step host-side
                # device ops (a host rng split + two scalar placements)
                rng_use, rng_next = jax.random.split(rng)
                new_params, new_states, new_upd, loss, new_carries = \
                    self._step_body(params, states, upd_states, it, ep,
                                    (inputs, labels, masks, label_masks),
                                    rng_use, carries if with_carries else None)
                new_params, new_upd = self._pin_placements(new_params, new_upd)
                if with_carries:
                    new_carries = jax.tree_util.tree_map(
                        jax.lax.stop_gradient, new_carries)
                return (new_params, new_states, new_upd, loss, new_carries,
                        it + 1.0, rng_next)

            # the program's name in the device trace and the HLO
            train_step.__name__ = "tbptt_step" if with_carries else "train_step"
            self._jit_cache[key] = _jit_step(train_step, self._mesh,
                                             donate_argnums=(0, 1, 2, 3, 9))
        return self._jit_cache[key]

    def _get_multi_train_step(self):
        """K train steps as ONE compiled ``lax.scan`` over stacked batches —
        a single dispatch executes the whole window on device. This is the
        TPU training-loop idiom: per-step host dispatch disappears, and
        XLA pipelines the step boundary (see ``fit_batches_on_device``)."""
        key = ("train_scan", self._mesh, _helpers.version())
        if key not in self._jit_cache:
            self._evict_stale(_helpers.version())

            def train_steps_scan(params, states, upd_states, it0, ep, inputs_s,
                                 labels_s, rng0):
                def body(carry, xs):
                    params, states, upd, it, rng = carry
                    inputs, labels = xs
                    rng, sub = jax.random.split(rng)
                    new_params, new_states, new_upd, loss, _ = self._step_body(
                        params, states, upd, it, ep,
                        (inputs, labels, None, None), sub)
                    new_params, new_upd = self._pin_placements(new_params,
                                                               new_upd)
                    return (new_params, new_states, new_upd, it + 1.0, rng), loss

                (params, states, upd, _, _), losses = jax.lax.scan(
                    body, (params, states, upd_states, it0, rng0),
                    (inputs_s, labels_s))
                return params, states, upd, losses

            self._jit_cache[key] = _jit_step(train_steps_scan, self._mesh,
                                             donate_argnums=(0, 1, 2))
        return self._jit_cache[key]

    # ------------------------------------------------------------------- fit
    def _fit_epochs(self, iterator, epochs: int, prefetch_depth) -> None:
        """The loop of ``fit``: iterator sources are wrapped in async
        host→device prefetch (``prefetch_depth`` queue slots, default 2; 0
        disables; ``async_supported = False`` opts out)."""
        from deeplearning4j_tpu.datasets.dataset import batch_nbytes
        from deeplearning4j_tpu.datasets.iterators import wrap_for_prefetch
        iterator = wrap_for_prefetch(iterator, prefetch_depth)
        for _ in range(epochs):
            for listener in self.listeners:
                if hasattr(listener, "on_epoch_start"):
                    listener.on_epoch_start(self)
            if hasattr(iterator, "reset"):
                iterator.reset()
            batches = iter(iterator)
            while True:
                # host_wait = time the training thread blocks on the input
                # pipeline; ~zero when prefetch keeps the queue warm
                with _trace.span("host_wait", category="train"):
                    ds = next(batches, None)
                if ds is None:
                    break
                self.transfer_bytes += batch_nbytes(ds)
                self._fit_batch(ds)
            self.epoch += 1
            for listener in self.listeners:
                if hasattr(listener, "on_epoch_end"):
                    listener.on_epoch_end(self)

    def _fit_batch(self, ds) -> None:
        batch = self._to_batch(ds)
        self.last_batch_size = int(
            jax.tree_util.tree_leaves(batch[0])[0].shape[0])
        t_total = None
        if normalize_backprop_type(self.conf.backprop_type) == "truncated_bptt":
            t_total = self._temporal_length(batch[0])
        if t_total is None:
            self._dispatch_step(batch)
        else:
            self._fit_tbptt(batch, t_total)
        tracer = _trace.get_active_tracer()
        if tracer is None:
            self._iteration_done()
        else:
            with tracer.span("listeners", category="train"):
                self._iteration_done()

    def _dispatch_step(self, batch, carries=None):
        """Call the jitted step on one batch (one TBPTT chunk when
        ``carries`` is given) and adopt what it returns; hands back the new
        carries."""
        step = self._get_train_step(carries is not None)
        it, ep, rng = self._device_tick(batch[0])
        # A span under tracing, and with it off no span and no context
        # manager. Its body reads no device value, so it does not drain the
        # device; a compile the call pays for nests under it. The step is
        # called from this one line either way and for both engines: a
        # Pallas kernel's compiled form carries its call stack, so a second
        # call site would be a second program in the compile cache.
        tracer = _trace.get_active_tracer()
        opened = None if tracer is None else tracer.enter_span(
            "step_dispatch", category="train",
            attrs={"iteration": self.iteration})
        try:
            (self.params, self.states, self.updater_states, loss, carries,
             new_it, new_rng) = step(
                self.params, self.states, self.updater_states, it, ep,
                *batch, rng, carries)
        finally:
            if opened is not None:
                tracer.exit_span(*opened)
        self._score_arr = loss
        self.iteration += 1
        self._store_tick(new_it, new_rng)
        return carries

    # ----------------------------------------------------------------- init
    # Below the step on purpose: a Pallas kernel's compiled form carries the
    # line numbers of its call path (``_step_body``, ``_dispatch_step``),
    # so what is added to this file is added at its end.
    def init(self, seed=None):
        """Draw parameters, layer states and updater state from ``seed``
        (the configuration's by default) and reset the counters; returns
        ``self``. The engine's ``_init_trees`` does the drawing, eagerly,
        one small program a shape: under tracing a ``model_init`` span
        holds it, with the programs it fetched nested inside."""
        tracer = _trace.get_active_tracer()
        if tracer is None:
            self._init_trees(seed)
            return self
        with tracer.span("model_init", category="setup") as span:
            self._init_trees(seed)
            leaves = jax.tree_util.tree_leaves
            span.set_attribute("parameters", sum(
                int(leaf.size) for leaf in leaves(self.params)))
            span.set_attribute("bytes", sum(
                int(leaf.size) * leaf.dtype.itemsize
                for leaf in leaves((self.params, self.updater_states))))
        return self


def _jit_step(step, mesh, **jit_kwargs):
    """``jax.jit`` of a train step. Under a mesh of several TPUs it is
    compiled with ``parallel.mesh.step_compiler_options`` (its collectives
    asynchronous), and each trace of it is counted as
    ``placement.async_collective_steps`` on the span open then: the first
    ``step_dispatch``; where the options bound the gradient sums over
    ``data`` (``DATA_SUM_OPTIONS``) it is counted as
    ``placement.data_sum_overlap_steps`` too. Elsewhere the call is exactly
    ``jax.jit(step, **jit_kwargs)``. Defined here, at the end, for the
    reason ``init`` gives: the step's call path keeps its line numbers, and
    so the imports are local (a line added at the top would move every line
    below it)."""
    import functools
    from deeplearning4j_tpu.parallel import mesh as _mesh
    options = _mesh.step_compiler_options(mesh)
    if options is None:
        return jax.jit(step, **jit_kwargs)
    counters = ["placement.async_collective_steps"]
    if _mesh.DATA_SUM_OPTIONS.items() <= options.items():
        counters.append("placement.data_sum_overlap_steps")

    @functools.wraps(step)
    def counted(*args, **kwargs):
        tracer = _trace.get_active_tracer()
        if tracer is not None:
            for name in counters:
                tracer.count(name)
        return step(*args, **kwargs)
    return jax.jit(counted, compiler_options=options, **jit_kwargs)
