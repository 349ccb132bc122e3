"""TrainingMaster layer: cluster-style training drivers over the mesh.

Capability parity with the reference's Spark scale-out layer
(`dl4j-spark/.../api/TrainingMaster.java:28`,
`ParameterAveragingTrainingMaster.java` — split sizing ``:287-298``, training
``:308``, tree aggregation / ``aggregationDepth``;
`dl4j-spark-parameterserver/.../SharedTrainingMaster.java:493` — threshold-
compressed gradient sharing over Aeron; export-based iteration
`impl/paramavg/util/ExportSupport.java`; per-phase timing
`api/stats/CommonSparkTrainingStats.java`) — redesigned for the TPU stack:

- Spark executors → mesh axis shards. The "cluster" is a ``jax.sharding.Mesh``;
  multi-host runs enter through ``jax.distributed`` (`init_distributed`) with
  per-host input pipelines, exactly the single-controller JAX model.
- broadcast + treeAggregate → XLA collectives over ICI/DCN. ``aggregationDepth``
  is accepted but XLA's all-reduce already uses optimal reduction topology.
- Aeron threshold messages → in-step quantization: each worker applies the
  Strom-style threshold sign-quantization to its update, keeps the residual,
  and a ``psum`` shares the quantized updates (`EncodingHandler.java`
  semantics; the wire-format sparse codec lives in
  ``deeplearning4j_tpu.parallel.compression``).
"""

from __future__ import annotations

import os
import time
from typing import Any, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS,
    is_multiprocess,
    make_global,
    make_mesh,
    shard_map,
)
from deeplearning4j_tpu.parallel.trainer import ParallelWrapper


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     coordinator_bind_address: Optional[str] = None) -> None:
    """Multi-host entry: join the JAX coordination service (replaces the
    reference's Aeron introduction/shard protocol,
    `SharedTrainingWrapper.java:214-244`). No-op when single-process.

    ``coordinator_bind_address`` lets process 0 listen on a different
    interface than the one peers dial (``coordinator_address`` is the
    ADVERTISED address) — NAT/container pods where 0.0.0.0 must be bound
    but a routable name advertised. ``None`` keeps jax's default (bind
    the advertised address)."""
    if num_processes is None or num_processes <= 1:
        return
    kwargs = {}
    if coordinator_bind_address is not None:
        kwargs["coordinator_bind_address"] = coordinator_bind_address
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)


class TrainingStats:
    """Per-phase wall-clock timings (`CommonSparkTrainingStats.java`), with
    each event also stamped by the process-wide TimeSource — plug in
    :class:`~deeplearning4j_tpu.parallel.time_source.NTPTimeSource` and
    events from different hosts line up on one timeline (the reference's
    NTP-corrected `BaseEventStats` timestamps)."""

    def __init__(self, time_source=None):
        self.phase_times: dict = {}
        self.events: list = []  # (phase, start_millis, duration_millis)
        self._ts = time_source  # None → resolve per add(), so a
        # set_time_source() AFTER the master was built still takes effect

    def add(self, phase: str, seconds: float) -> None:
        from deeplearning4j_tpu.parallel.time_source import get_time_source
        self.phase_times.setdefault(phase, []).append(seconds)
        ts = self._ts if self._ts is not None else get_time_source()
        end_ms = ts.current_time_millis()
        self.events.append((phase, int(end_ms - seconds * 1000),
                            int(seconds * 1000)))

    def total(self, phase: str) -> float:
        return sum(self.phase_times.get(phase, []))

    def as_dict(self) -> dict:
        return {k: {"count": len(v), "total_s": sum(v)}
                for k, v in self.phase_times.items()}


class TrainingMaster:
    """SPI: how distributed fitting is orchestrated
    (`api/TrainingMaster.java:28`)."""

    def execute_training(self, network, data_iterator: Iterable) -> None:
        raise NotImplementedError

    def get_training_stats(self) -> TrainingStats:
        return getattr(self, "stats", TrainingStats())


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Synchronous periodic parameter averaging
    (`ParameterAveragingTrainingMaster.java`).

    Splits the incoming stream into chunks of
    ``num_workers * batch_size_per_worker * averaging_frequency`` examples
    (split sizing ``:287-298``); each split runs ``averaging_frequency``
    local steps per worker followed by parameter + updater-state averaging —
    executed as ONE compiled shard_map program per split
    (:class:`ParallelWrapper` averaging mode) instead of Spark map + tree
    aggregation.
    """

    def __init__(self, batch_size_per_worker: int = 16,
                 averaging_frequency: int = 5,
                 num_workers: Optional[int] = None,
                 aggregation_depth: int = 2,
                 repartition: str = "always",
                 export_directory: Optional[str] = None,
                 mesh: Optional[Mesh] = None,
                 data_axis: str = DATA_AXIS):
        self.batch_size_per_worker = batch_size_per_worker
        self.averaging_frequency = max(1, averaging_frequency)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.data_axis = data_axis
        self.num_workers = num_workers or int(self.mesh.shape[data_axis])
        # accepted for parity; XLA's all-reduce already picks the reduction
        # topology, so depth is advisory only
        self.aggregation_depth = aggregation_depth
        self.repartition = repartition
        self.export_directory = export_directory
        self.stats = TrainingStats()
        self._pw: Optional[ParallelWrapper] = None

    class Builder:
        def __init__(self, batch_size_per_worker: int = 16):
            self._kw = {"batch_size_per_worker": batch_size_per_worker}

        def averaging_frequency(self, f):
            self._kw["averaging_frequency"] = f
            return self

        def aggregation_depth(self, d):
            self._kw["aggregation_depth"] = d
            return self

        def workers(self, n):
            self._kw["num_workers"] = n
            return self

        def export_directory(self, d):
            self._kw["export_directory"] = d
            return self

        def build(self):
            return ParameterAveragingTrainingMaster(**self._kw)

    # -- data staging ------------------------------------------------------
    def _repartition(self, data_iterator) -> List:
        """Regroup the stream into worker-divisible batches of
        batch_size_per_worker * num_workers examples (the reference
        repartitions the RDD so every executor sees equal counts)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        t0 = time.perf_counter()
        per_round = self.batch_size_per_worker * self.num_workers
        feats, labs, n_buf = [], [], 0
        out: List[DataSet] = []
        for ds in data_iterator:
            if ds.features_mask is not None or ds.labels_mask is not None:
                # masked sequence data is not re-chunked; pass through
                out.append(ds)
                continue
            feats.append(np.asarray(ds.features))
            labs.append(np.asarray(ds.labels))
            n_buf += feats[-1].shape[0]
            while n_buf >= per_round:
                f = np.concatenate(feats) if len(feats) > 1 else feats[0]
                l = np.concatenate(labs) if len(labs) > 1 else labs[0]
                out.append(DataSet(f[:per_round], l[:per_round]))
                feats, labs = [f[per_round:]], [l[per_round:]]
                n_buf = feats[0].shape[0]
        if n_buf:
            out.append(DataSet(np.concatenate(feats) if len(feats) > 1 else feats[0],
                               np.concatenate(labs) if len(labs) > 1 else labs[0]))
        if self.export_directory:
            os.makedirs(self.export_directory, exist_ok=True)
            for i, ds in enumerate(out):
                arrays = {"features": np.asarray(ds.features),
                          "labels": np.asarray(ds.labels)}
                if ds.features_mask is not None:
                    arrays["features_mask"] = np.asarray(ds.features_mask)
                if ds.labels_mask is not None:
                    arrays["labels_mask"] = np.asarray(ds.labels_mask)
                # zero-padded index: lexicographic == numeric replay order
                np.savez(os.path.join(self.export_directory,
                                      f"split{i:06d}.npz"), **arrays)
        self.stats.add("split", time.perf_counter() - t0)
        return out

    @staticmethod
    def load_exported(directory: str) -> List:
        """Replay a staged export directory (`ExportSupport.java` parity) in
        the original split order."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        names = [f for f in os.listdir(directory) if f.endswith(".npz")]
        # numeric sort handles legacy unpadded names too
        names.sort(key=lambda f: (len(f), f))
        out = []
        for f in names:
            z = np.load(os.path.join(directory, f))
            out.append(DataSet(
                z["features"], z["labels"],
                z["features_mask"] if "features_mask" in z else None,
                z["labels_mask"] if "labels_mask" in z else None))
        return out

    # -- training ----------------------------------------------------------
    def execute_training(self, network, data_iterator: Iterable) -> None:
        batches = self._repartition(data_iterator)
        # cache the wrapper so the compiled shard_map step survives epochs
        pw = self._pw
        if pw is None or pw.model is not network:
            pw = self._pw = ParallelWrapper(
                network, self.mesh, mode="averaging",
                averaging_frequency=self.averaging_frequency,
                data_axis=self.data_axis)
        t0 = time.perf_counter()
        pw.fit(batches)
        network.epoch -= 1  # pw.fit counts an epoch; the master's caller owns epochs
        self.stats.add("fit", time.perf_counter() - t0)


class SharedTrainingMaster(TrainingMaster):
    """Per-step threshold-compressed gradient sharing
    (`SharedTrainingMaster.java` + `EncodedGradientsAccumulator.java:33`).

    Each worker: local gradients → local updater → update + residual →
    Strom threshold sign-quantization (magnitudes below ``threshold`` stay in
    the residual; survivors are quantized to ±threshold) → ``psum`` over the
    mesh → everyone applies the same summed quantized update. The adaptive
    threshold decay/boost of `EncodingHandler.java:69-94` is applied between
    steps from the on-device sparsity measurement.
    """

    def __init__(self, batch_size_per_worker: int = 16,
                 threshold: float = 1e-3, min_threshold: float = 1e-5,
                 threshold_step: float = 1e-5, step_trigger: float = 0.05,
                 step_delay: int = 50, shake_frequency: int = 0,
                 mesh: Optional[Mesh] = None, data_axis: str = DATA_AXIS):
        self.batch_size_per_worker = batch_size_per_worker
        self.threshold = float(threshold)
        self.min_threshold = float(min_threshold)
        self.threshold_step = float(threshold_step)
        self.step_trigger = float(step_trigger)  # target sparsity ratio
        self.step_delay = step_delay
        self.shake_frequency = shake_frequency
        self.mesh = mesh if mesh is not None else make_mesh()
        self.data_axis = data_axis
        self.num_workers = int(self.mesh.shape[data_axis])
        self.stats = TrainingStats()
        self._step_fn = None
        self._net_ref = None
        self._residual = None
        self._steps_done = 0
        self._shake_restore: Optional[float] = None

    class Builder:
        def __init__(self, batch_size_per_worker: int = 16):
            self._kw = {"batch_size_per_worker": batch_size_per_worker}

        def update_threshold(self, t):
            self._kw["threshold"] = t
            return self

        def min_update_threshold(self, t):
            self._kw["min_threshold"] = t
            return self

        def workers_per_node(self, n):
            return self  # mesh decides worker count; accepted for parity

        def build(self):
            return SharedTrainingMaster(**self._kw)

    def _build_step(self, net):
        daxis = self.data_axis

        def worker(params, states, upd, residual, it, ep, x, y, rng, thr):
            # Workers compute local grads/updates on their batch shard; the
            # quantized updates are summed across the mesh (the Aeron
            # broadcast path, now one ICI collective). ``residual`` leaves
            # arrive as this worker's [1, *param_shape] slice of the stacked
            # per-worker residual state.
            rng = jax.random.fold_in(rng, jax.lax.axis_index(daxis))
            # local updater: update magnitudes, not raw grads, are shared
            # (StochasticGradientDescent.java:66-73 stores the UPDATE)
            stepped, new_states, new_upd, loss, _ = net._step_body(
                params, states, upd, it, ep, (x, y, None, None), rng)
            update = jax.tree_util.tree_map(lambda a, b: a - b, params, stepped)
            acc = jax.tree_util.tree_map(lambda r, u: r + u[None], residual, update)
            quant = jax.tree_util.tree_map(
                lambda a: jnp.where(jnp.abs(a) >= thr,
                                    jnp.sign(a) * thr, 0.0).astype(a.dtype), acc)
            new_residual = jax.tree_util.tree_map(lambda a, q: a - q, acc, quant)
            # every node applies the SUM of all workers' quantized updates
            # (EncodedGradientsAccumulator applies each received message)
            shared = jax.tree_util.tree_map(
                lambda q: jax.lax.psum(q, daxis), quant)
            new_params = jax.tree_util.tree_map(
                lambda p, s: p - s[0], params, shared)
            # sparsity: fraction of elements encoded (EncodingHandler feedback)
            counts = jax.tree_util.tree_map(
                lambda q: (jnp.sum(q != 0), q.size), quant,
                is_leaf=lambda a: hasattr(a, "shape"))
            leaves = jax.tree_util.tree_leaves(counts)
            nz = sum(leaves[0::2])
            total = sum(leaves[1::2])
            avg = lambda t: jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, daxis), t)
            sparsity = jax.lax.pmean(nz / total, daxis)
            return (new_params, avg(new_states), avg(new_upd), new_residual,
                    jax.lax.pmean(loss, daxis), sparsity)

        rep = P()
        shard0 = P(daxis)

        mapped = shard_map(
            worker, mesh=self.mesh,
            in_specs=(rep, rep, rep, shard0, rep, rep, shard0, shard0,
                      rep, rep),
            out_specs=(rep, rep, rep, shard0, rep, rep))
        return jax.jit(mapped, donate_argnums=(0, 1, 2, 3))

    # -- compression-state checkpointing ---------------------------------
    # A preemption checkpoint that carries only model + updater state
    # resumes ALMOST exactly: the adaptive threshold re-warms and the
    # un-transmitted residuals are lost (they re-accumulate, shifting a
    # few low-order bits of every later update). Exact resume needs this
    # state too — the reference has no analog (its accumulator dies with
    # the worker; membership is fixed — SharedTrainingWrapper.java:131).

    def state_snapshot(self) -> dict:
        """This PROCESS's compression state (threshold machinery + its
        local residual shard) as host numpy arrays — the rank-local
        checkpoint shard, decoupled from the live training state so an
        async save thread can write it while the next step mutates the
        residual (:func:`write_state_snapshot`)."""
        snap = {
            "threshold": np.float64(self.threshold),
            "steps_done": np.int64(self._steps_done),
            "shake_restore": np.float64(
                -1.0 if self._shake_restore is None else self._shake_restore),
        }
        if self._residual is not None:
            leaves = jax.tree_util.tree_leaves(self._residual)
            for i, leaf in enumerate(leaves):
                if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                    # ALL local shards, in worker order — a process usually
                    # owns several devices, each holding one worker slice
                    # of the worker-stacked residual (axis 0)
                    shards = sorted(leaf.addressable_shards,
                                    key=lambda s: s.index[0].start or 0)
                    snap[f"res{i}"] = np.concatenate(
                        [np.asarray(s.data) for s in shards], axis=0)
                else:
                    snap[f"res{i}"] = np.asarray(leaf).copy()
        return snap

    @staticmethod
    def write_state_snapshot(snapshot: dict, path: str) -> None:
        """Write a :meth:`state_snapshot` npz atomically. The elastic
        commit protocol (elastic.py) treats this file's EXISTENCE as
        "shard landed" — a torn write from a mid-save kill must never be
        stampable as committed."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:  # handle, not path: savez would
            np.savez(fh, **snapshot)  # append .npz to the name
        os.replace(tmp, path)

    def save_state(self, path: str) -> None:
        """Write this PROCESS's compression state (threshold machinery +
        its local residual shard) as an npz. In a multi-process run every
        process must save its own file — residual shards differ."""
        self.write_state_snapshot(self.state_snapshot(), path)

    def load_state(self, path: str) -> None:
        """Restore state written by :meth:`save_state`.

        Single-process meshes tolerate a WORKER-COUNT change (the
        elastic-shrink restore path): the saved per-worker residual stack
        is summed and spread evenly over the new worker stack, so the
        un-transmitted gradient mass and the adapted threshold both
        survive an N→N-1 world change. A mismatch in the per-parameter
        shapes themselves (different architecture) still fails loudly.
        Multi-process runs stay strict — residual shards are rank-local
        and a shrunk world cannot see the dead rank's shard; skip
        load_state there and re-accumulate. The residual is re-placed
        lazily on the next ``execute_training`` call."""
        data = np.load(path)
        self.threshold = float(data["threshold"])
        self._steps_done = int(data["steps_done"])
        sr = float(data["shake_restore"])
        self._shake_restore = None if sr < 0 else sr
        res = [data[k] for k in sorted(
            (k for k in data.files if k.startswith("res")),
            key=lambda k: int(k[3:]))]
        self._residual_restore = res or None
        if self._residual is not None and self._residual_restore is not None:
            # master already bound to a network: place the residual NOW —
            # deferring to the next step-fn rebuild would silently keep the
            # current residual while the threshold scalars rolled back
            self._residual = self._place_restored_residual(
                self._residual, mp=is_multiprocess(self.mesh),
                shard_spec=P(self.data_axis))

    _residual_restore = None

    def _place_restored_residual(self, zeros_tree, mp: bool, shard_spec):
        leaves, treedef = jax.tree_util.tree_flatten(zeros_tree)
        saved = self._residual_restore
        self._residual_restore = None
        if len(saved) != len(leaves):
            raise ValueError(
                f"restored residual has {len(saved)} leaves, model needs "
                f"{len(leaves)} — was the checkpoint from this architecture?")
        placed = []
        for z, s in zip(leaves, saved):
            if mp:
                # validate BEFORE constructing the global array — the jax
                # constructor's own mismatch error would bury the remedy
                expect_local = (z.shape[0] // jax.process_count(),) + \
                    tuple(z.shape[1:])
                if tuple(s.shape) != expect_local:
                    raise ValueError(
                        f"restored residual shard {s.shape} does not tile "
                        f"to {z.shape} over {jax.process_count()} processes "
                        "— resuming on a different worker count drops "
                        "residuals: skip load_state and re-accumulate")
                sharding = jax.sharding.NamedSharding(
                    self.mesh, shard_spec)
                arr = jax.make_array_from_process_local_data(
                    sharding, np.asarray(s, z.dtype))
            else:
                if tuple(s.shape) != tuple(z.shape):
                    if tuple(s.shape[1:]) == tuple(z.shape[1:]):
                        # mesh reshape (worker count changed, e.g. an
                        # elastic shrink): conserve the un-transmitted
                        # mass — sum the saved per-worker stack and
                        # spread it evenly over the new one
                        total = np.asarray(s, np.float64).sum(axis=0)
                        s = np.broadcast_to(total / z.shape[0], z.shape)
                    else:
                        raise ValueError(
                            f"restored residual shape {s.shape} != "
                            f"{z.shape} — the checkpoint is from a "
                            "different architecture, not just a different "
                            "worker count: skip load_state and "
                            "re-accumulate")
                arr = jnp.asarray(np.asarray(s, z.dtype))
            placed.append(arr)
        return jax.tree_util.tree_unflatten(treedef, placed)

    def _adapt_threshold(self, sparsity: float) -> None:
        """EncodingHandler.java:69-94: decay threshold toward min when too few
        elements pass (residual starving), raise it when too many pass."""
        self._steps_done += 1
        if self._shake_restore is not None:
            # previous step was a shake: restore the working threshold
            self.threshold = self._shake_restore
            self._shake_restore = None
        if self._steps_done < self.step_delay:
            return
        if sparsity < 1e-4:  # almost nothing transmitted → lower threshold
            self.threshold = max(self.min_threshold,
                                 self.threshold - self.threshold_step)
        elif sparsity > self.step_trigger:  # too dense → raise threshold
            self.threshold = self.threshold + self.threshold_step
        if self.shake_frequency and self._steps_done % self.shake_frequency == 0:
            # periodic "shake": lower for ONE step to flush residuals, then
            # restore (EncodingHandler's temporary shake semantics)
            self._shake_restore = self.threshold
            self.threshold = max(self.min_threshold, self.threshold * 0.5)

    def execute_training(self, network, data_iterator: Iterable) -> None:
        if network.params is None:
            network.init()
        dtype = network.conf.global_conf.jnp_dtype()
        mp = is_multiprocess(self.mesh)
        rep, shard0 = P(), P(self.data_axis)
        if self._step_fn is None or self._net_ref is not network:
            # the compiled worker closes over the network: rebuild on switch
            self._net_ref = network
            self._step_fn = self._build_step(network)
            # stacked per-worker residuals, sharded over the data axis
            self._residual = jax.tree_util.tree_map(
                lambda p: np.zeros((self.num_workers,) + p.shape,
                                   np.asarray(p).dtype),
                network.params)
            if mp:
                # cross-process run (jax.distributed): every host holds the
                # same full values; lift them to GLOBAL arrays over the mesh
                if self._residual_restore is not None:
                    self._residual = self._place_restored_residual(
                        self._residual, mp=True, shard_spec=shard0)
                else:
                    self._residual = make_global(self._residual, self.mesh,
                                                 shard0)
                network.params = make_global(network.params, self.mesh, rep)
                network.states = make_global(network.states, self.mesh, rep)
                network.updater_states = make_global(
                    network.updater_states, self.mesh, rep)
            else:
                if self._residual_restore is not None:
                    self._residual = self._place_restored_residual(
                        self._residual, mp=False, shard_spec=shard0)
                else:
                    self._residual = jax.tree_util.tree_map(jnp.asarray,
                                                            self._residual)
                # a restored model's params arrive COMMITTED to one device
                # (orbax device_puts on load); the sharded step needs them
                # replicated over the whole mesh — uncommitted fresh-init
                # arrays pass through device_put for free
                rep_sh = jax.sharding.NamedSharding(self.mesh, rep)
                network.params = jax.device_put(network.params, rep_sh)
                network.states = jax.device_put(network.states, rep_sh)
                if network.updater_states is not None:
                    network.updater_states = jax.device_put(
                        network.updater_states, rep_sh)
        t0 = time.perf_counter()
        for ds in data_iterator:
            x = np.asarray(ds.features)
            y = np.asarray(ds.labels)
            if (x.shape[0] % self.num_workers
                    or ds.features_mask is not None
                    or ds.labels_mask is not None):
                if mp:
                    raise ValueError(
                        "multi-process SharedTrainingMaster requires batch "
                        f"sizes divisible by {self.num_workers} workers and "
                        "no masks (got batch "
                        f"{x.shape[0]}, masks={ds.features_mask is not None})")
                # ragged tail or masked sequence data: the sharded step
                # doesn't carry masks — run unsharded (same math, no DP)
                network._fit_batch(ds)
                continue
            it = jnp.asarray(network.iteration, jnp.float32)
            ep = jnp.asarray(network.epoch, jnp.float32)
            rng = network._next_rng()
            xb = np.asarray(x, dtype)
            yb = np.asarray(y, dtype)
            if mp:
                xb, yb = make_global((xb, yb), self.mesh, shard0)
                it, ep, rng = make_global((it, ep, rng), self.mesh, rep)
            else:
                xb, yb = jnp.asarray(xb), jnp.asarray(yb)
            (network.params, network.states, network.updater_states,
             self._residual, loss, sparsity) = self._step_fn(
                network.params, network.states, network.updater_states,
                self._residual, it, ep, xb, yb, rng,
                np.float32(self.threshold))
            network.score_ = loss
            network.iteration += 1
            self._adapt_threshold(float(sparsity))
            for listener in network.listeners:
                if hasattr(listener, "iteration_done"):
                    listener.iteration_done(network, network.iteration,
                                            network.epoch)
        self.stats.add("fit", time.perf_counter() - t0)


class DistributedMultiLayerNetwork:
    """Front end pairing a network with a TrainingMaster
    (`SparkDl4jMultiLayer.java:71` role: ``fit(RDD)`` → master)."""

    def __init__(self, network, training_master: TrainingMaster):
        self.network = network
        self.master = training_master

    def fit(self, data_iterator, epochs: int = 1):
        if self.network.params is None:
            self.network.init()
        # settle the NTP offset BEFORE the first phase stamp so the timeline
        # never jumps when a background sync lands mid-run (one blocking
        # exchange at startup; no-op for already-synced / plain clocks)
        from deeplearning4j_tpu.parallel.time_source import get_time_source
        get_time_source().ensure_synced()
        for _ in range(epochs):
            if hasattr(data_iterator, "reset"):
                data_iterator.reset()
            self.master.execute_training(self.network, data_iterator)
            self.network.epoch += 1
        return self.network

    def evaluate(self, iterator):
        return self.network.evaluate(iterator)

    def get_training_stats(self) -> TrainingStats:
        return self.master.get_training_stats()
