"""Orbax-backed checkpointing — the TPU-scale checkpoint path.

The zip checkpoints (``util/model_serializer.py``) are the
DL4J-compatible interchange (``ModelSerializer.java:51`` role). This
module adds the idiomatic JAX path on top: the same model state (params
+ updater state + training counters + config JSON) stored through
``orbax.checkpoint``, which brings sharding-aware, per-host-parallel,
optionally async writes and step-managed retention — what checkpointing
a multi-host mesh actually needs (CheckpointListener rotation at pod
scale). Restore returns a fully wired MultiLayerNetwork /
ComputationGraph, like the zip restore does.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "save_model",
    "restore_model",
    "snapshot_state",
    "AsyncSaveHandle",
    "OrbaxCheckpointManager",
]

_CONFIG_FILE = "model_config.json"


def _canonical_dir(directory: str) -> str:
    """Absolutize local paths; leave URL-style paths (``gs://…``)
    untouched — ``os.path.abspath('gs://b/ckpt')`` would mangle them
    into ``<cwd>/gs:/b/ckpt`` and silently redirect cloud saves to a
    bogus local directory. Scheme paths flow through etils ``epath``,
    which handles both existence checks and mkdir for remote stores."""
    if "://" in directory:
        return directory
    return os.path.abspath(directory)


# -- shared helpers ----------------------------------------------------------

def _write_meta(model, directory: str) -> None:
    # primary-host-gated like orbax's own writes (every process calling
    # save on a pod must not race on the shared meta file), and through
    # epath so gs:// checkpoint directories work like local ones
    import jax
    if jax.process_index() != 0:
        return
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from etils import epath
    from deeplearning4j_tpu.nn.layers.attention import QKV_LAYOUT
    kind = "mln" if isinstance(model, MultiLayerNetwork) else "graph"
    d = epath.Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    (d / _CONFIG_FILE).write_text(
        json.dumps({"kind": kind, "conf": json.loads(model.conf.to_json()),
                    "qkv_layout": QKV_LAYOUT}))


def _build_model(directory: str):
    from etils import epath
    meta = json.loads((epath.Path(directory) / _CONFIG_FILE).read_text())
    if meta["kind"] == "mln":
        from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        model = MultiLayerNetwork(
            MultiLayerConfiguration.from_dict(meta["conf"]))
    else:
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            ComputationGraphConfiguration,
        )
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        model = ComputationGraph(
            ComputationGraphConfiguration.from_dict(meta["conf"]))
    model.init()  # allocates the target pytree structure + updaters
    # pre-round-5 checkpoints carry no qkv_layout stamp: their fused
    # attention columns are block-major and must be repacked after the
    # state is applied (_apply_state reads this flag)
    from deeplearning4j_tpu.nn.layers.attention import QKV_LAYOUT
    model._legacy_qkv_checkpoint = meta.get("qkv_layout") != QKV_LAYOUT
    return model


def _multiprocess_safe(tree):
    """In a multi-process (``jax.distributed``) job, orbax refuses to
    serialize HOST-LOCAL jax.Arrays (replicated lockstep state, like the
    deterministic-broadcast training masters keep) — only numpy or global
    sharded arrays. Convert fully-addressable arrays to host numpy;
    genuinely global (multi-host sharded) arrays pass through to orbax's
    proper sharded path."""
    import jax
    if jax.process_count() <= 1:
        return tree

    def conv(x):
        if isinstance(x, jax.Array):
            if x.is_fully_addressable:
                return np.asarray(x)
            if x.is_fully_replicated:
                # global replicated array (e.g. params after training over
                # a multi-process mesh): every process holds the full
                # value in its local shard
                return np.asarray(x.addressable_data(0))
        return x
    return jax.tree_util.tree_map(conv, tree)


def _state_pytree(model, with_updater: bool) -> Dict[str, Any]:
    # the published shapes, whatever mesh padded the vocabulary to
    from deeplearning4j_tpu.parallel.sharding import published_trees
    params, updater_states = published_trees(model)
    state: Dict[str, Any] = {"params": params, "states": model.states}
    if with_updater and updater_states is not None:
        state["updater_states"] = updater_states
    state["counters"] = {"iteration": np.asarray(model.iteration),
                         "epoch": np.asarray(model.epoch)}
    return _multiprocess_safe(state)


def snapshot_state(model, with_updater: bool = True) -> Dict[str, Any]:
    """Decouple a checkpoint from the live model: the state pytree with
    every addressable array copied to host numpy at call time. Training
    may then mutate the model while a background thread feeds the
    snapshot to :meth:`OrbaxCheckpointManager.save` — the overlapped
    (async) elastic checkpoint path. Non-addressable global arrays (a
    genuinely multi-host-sharded model) pass through untouched; those
    must go through orbax's own sharded async machinery instead."""
    import jax

    def conv(x):
        if isinstance(x, jax.Array):
            if x.is_fully_addressable:
                return np.asarray(x).copy()
            return x
        if isinstance(x, np.ndarray):
            return x.copy()
        return x
    return jax.tree_util.tree_map(
        conv, _state_pytree(model, with_updater=with_updater))


def _template_for(model, metadata) -> Dict[str, Any]:
    """Restore template matching what the checkpoint actually contains
    (a template/on-disk structure mismatch is a hard orbax error)."""
    has_updater = True
    try:
        tree = getattr(metadata, "item_metadata", metadata)
        tree = getattr(tree, "tree", tree)
        if hasattr(tree, "keys"):
            has_updater = "updater_states" in tree
    except Exception:  # noqa: BLE001 - fall back to assuming present
        pass
    return _state_pytree(model, with_updater=has_updater)


def _sharded_template(model, template: Dict[str, Any], mesh,
                      rules=None) -> Dict[str, Any]:
    """Rewrite the params/updater_states halves of a restore template as
    ``ShapeDtypeStruct``s carrying the rule-derived target shardings, so
    orbax restores each leaf DIRECTLY into its mesh placement — the
    reshard-on-restore path (a 2×4 checkpoint restored onto a 1×4 mesh
    re-slices shards; no full-host materialization on the pod path).
    ``states``/``counters`` stay as-is (replicated small state)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sharding import (
        DEFAULT_2D_RULES, _leaf_sharding_ok, _path_name,
        match_partition_rules)

    specs = match_partition_rules(
        DEFAULT_2D_RULES if rules is None else rules, model.params)
    placed: Dict[str, Any] = {}

    def conv_param(path, v, spec):
        if not _leaf_sharding_ok(v.shape, spec, mesh):
            spec = P()
        placed[_path_name(path)] = (tuple(v.shape), spec)
        return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                    sharding=NamedSharding(mesh, spec))

    out = dict(template)
    out["params"] = jax.tree_util.tree_map_with_path(
        conv_param, template["params"], specs)
    if "updater_states" in out:
        def conv_upd(path, s):
            shape_spec = placed.get(_path_name(path[:-1]))
            spec = (shape_spec[1] if shape_spec is not None
                    and tuple(s.shape) == shape_spec[0] else P())
            return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                        sharding=NamedSharding(mesh, spec))
        out["updater_states"] = jax.tree_util.tree_map_with_path(
            conv_upd, template["updater_states"])
    return out


def _apply_state(model, state: Dict[str, Any], load_updater: bool):
    model.params = state["params"]
    model.states = state["states"]
    if load_updater and "updater_states" in state:
        model.updater_states = state["updater_states"]
    counters = state.get("counters", {})
    model.iteration = int(np.asarray(counters.get("iteration", 0)))
    model.epoch = int(np.asarray(counters.get("epoch", 0)))
    if getattr(model, "_legacy_qkv_checkpoint", False):
        from deeplearning4j_tpu.nn.layers.attention import (
            repack_legacy_fused_qkv)
        repack_legacy_fused_qkv(model)
        model._legacy_qkv_checkpoint = False
    return model


# -- one-shot save / restore -------------------------------------------------

class AsyncSaveHandle:
    """Returned by ``save_model(..., async_write=True)``: the write runs
    in the background; call :meth:`wait_until_finished` (or use as a
    context manager) before reading the checkpoint or exiting."""

    def __init__(self, checkpointer):
        self._ckptr = checkpointer

    def wait_until_finished(self) -> None:
        if self._ckptr is not None:
            self._ckptr.wait_until_finished()
            self._ckptr.close()
            self._ckptr = None

    def __enter__(self) -> "AsyncSaveHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.wait_until_finished()


def save_model(model, directory: str, *, save_updater: bool = True,
               async_write: bool = False) -> Optional[AsyncSaveHandle]:
    """Write a model checkpoint into ``directory`` via orbax.

    ``async_write=True`` returns an :class:`AsyncSaveHandle` as soon as
    the device arrays are snapshotted — training continues while bytes
    hit disk; call ``handle.wait_until_finished()`` before relying on
    the files. Synchronous saves return None.
    """
    import orbax.checkpoint as ocp

    directory = _canonical_dir(directory)
    _write_meta(model, directory)

    state = _state_pytree(model, with_updater=save_updater)
    target = os.path.join(directory, "state")
    if async_write:
        ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        ckptr.save(target, args=ocp.args.StandardSave(state), force=True)
        return AsyncSaveHandle(ckptr)
    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        ckptr.save(target, args=ocp.args.StandardSave(state), force=True)
    return None


def restore_model(directory: str, *, load_updater: bool = True,
                  mesh=None, sharding_rules=None):
    """Restore a model saved by :func:`save_model`. Works regardless of
    whether the checkpoint contains updater state.

    ``mesh`` (+ optional ``sharding_rules``) restores STRAIGHT INTO a
    rule-sharded placement on that mesh — the checkpoint's own mesh
    shape is irrelevant (reshard-on-restore: a 2×4 save restores onto a
    1×4 mesh), and the returned model has ``fit``/``output`` honoring
    the mesh exactly as after
    :func:`deeplearning4j_tpu.parallel.sharding.shard_model_with_rules`."""
    import orbax.checkpoint as ocp

    directory = _canonical_dir(directory)
    model = _build_model(directory)
    target = os.path.join(directory, "state")
    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        template = _template_for(model, ckptr.metadata(target))
        if mesh is not None:
            template = _sharded_template(model, template, mesh,
                                         sharding_rules)
        state = ckptr.restore(target,
                              args=ocp.args.StandardRestore(template))
    model = _apply_state(model, state, load_updater)
    if mesh is not None:
        from deeplearning4j_tpu.parallel.sharding import (
            shard_model_with_rules)
        shard_model_with_rules(model, mesh, sharding_rules)
    return model


# -- step-managed rotation ---------------------------------------------------

class OrbaxCheckpointManager:
    """Step-managed rotation over orbax (CheckpointListener's
    keepLast/saveEvery semantics at pod scale, via
    ``ocp.CheckpointManager``)."""

    def __init__(self, directory: str, *, max_to_keep: Optional[int] = 3,
                 save_interval_steps: int = 1,
                 active_processes: Optional[set] = None,
                 barrier_sync_key_prefix: Optional[str] = None):
        """``active_processes`` restricts orbax's multihost coordination to
        a subset of a ``jax.distributed`` job (e.g. ``{0}`` so only the
        coordinator checkpoints replicated state) — without it, a save
        from one process of a multi-process job hangs on a barrier the
        other processes never enter. ``barrier_sync_key_prefix`` keeps
        two concurrent managers' barriers from colliding."""
        import orbax.checkpoint as ocp
        from etils import epath
        self.directory = _canonical_dir(directory)
        epath.Path(self.directory).mkdir(parents=True, exist_ok=True)
        mp_options = None
        if active_processes is not None or barrier_sync_key_prefix is not None:
            primary = (min(active_processes) if active_processes else 0)
            mp_options = ocp.options.MultiprocessingOptions(
                primary_host=primary,
                active_processes=active_processes,
                barrier_sync_key_prefix=barrier_sync_key_prefix)
        extra = {}
        if mp_options is not None:
            # orbax treats an explicit None differently from the kwarg
            # being absent, and refuses create=True with active_processes;
            # the epath mkdir above has already made the root either way
            extra = {"multiprocessing_options": mp_options, "create": False}
        self._options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=max(1, save_interval_steps),
            **extra)
        self._mgr = ocp.CheckpointManager(self.directory,
                                          options=self._options)
        self._meta_written = False

    def save(self, step: int, model, *, save_updater: bool = True,
             overwrite_existing: bool = False,
             state: Optional[Dict[str, Any]] = None) -> bool:
        """Save at ``step`` (skipped when the interval says so; returns
        whether a save happened).

        ``overwrite_existing=True``: orbax returns False (writing
        NOTHING) when a finalized dir for ``step`` already exists — e.g.
        a corrupt leftover a fallback restore walked past. The elastic
        commit path must not re-advertise those bytes as freshly saved,
        so this deletes the stale step dir and saves again.

        ``state``: a pre-built state pytree (see :func:`snapshot_state`)
        written INSTEAD of reading the live model — the async save path,
        where ``model`` is only consulted for its immutable config meta
        while training keeps mutating its arrays."""
        import orbax.checkpoint as ocp

        def _save():
            return self._mgr.save(
                step, args=ocp.args.StandardSave(
                    state if state is not None
                    else _state_pytree(model, with_updater=save_updater)))

        if not self._meta_written:
            _write_meta(model, self.directory)
            self._meta_written = True
        ok = _save()
        if not ok and overwrite_existing \
                and int(step) in set(self.all_steps()):
            import shutil
            shutil.rmtree(os.path.join(self.directory, str(int(step))),
                          ignore_errors=True)
            if hasattr(self._mgr, "reload"):
                self._mgr.reload()  # drop the cached step list
            ok = _save()
        return ok

    def all_steps(self) -> List[int]:
        """Steps currently retained by the rotation, ascending."""
        return sorted(int(s) for s in self._mgr.all_steps())

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    #: step actually restored by the last :meth:`restore` call — differs
    #: from the requested step when ``fallback`` walked to an older one
    restored_step: Optional[int] = None

    def restore(self, step: Optional[int] = None, *,
                load_updater: bool = True, fallback: bool = False,
                fallback_steps: Optional[Sequence[int]] = None,
                mesh=None, sharding_rules=None):
        """Restore the model at ``step`` (default: latest).

        ``mesh``/``sharding_rules`` restore straight into a rule-sharded
        placement regardless of the mesh the checkpoint was saved under
        (see :func:`restore_model` — the elastic reshard-on-shrink path).

        ``fallback=True`` is the integrity-tolerant path: when the chosen
        step is truncated/corrupt (a preemption mid-write, a fault-
        injected torn checkpoint), restore walks back through the older
        retained steps instead of failing — the rotation (``max_to_keep``)
        exists precisely so the previous good step survives. The step
        actually used is recorded in :attr:`restored_step`. Without
        fallback a damaged checkpoint fails fast with a clear error.

        ``fallback_steps`` restricts the walk to an allow-list (the
        elastic supervisor passes its fence-eligible steps: an orbax dir
        may hold steps a zombie generation wrote after its fence, and the
        fallback must not resurrect them)."""
        steps = sorted(self._mgr.all_steps())
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            raise ValueError(f"no checkpoints in {self.directory}")
        candidates = [step]
        if fallback:
            pool = steps if fallback_steps is None else \
                [s for s in steps if s in set(int(x) for x in fallback_steps)]
            candidates += [s for s in reversed(pool) if s < step]
        errors = []
        for s in candidates:
            try:
                model = self._restore_step(s, load_updater, mesh=mesh,
                                           sharding_rules=sharding_rules)
            except Exception as e:  # noqa: BLE001 - orbax raises many kinds
                errors.append(f"step {s}: {type(e).__name__}: {e}")
                if not fallback:
                    raise ValueError(
                        f"checkpoint step {s} in {self.directory} is "
                        f"unrestorable (truncated or corrupt?): {e}") from e
                continue
            self.restored_step = s
            if errors:
                import logging
                logging.getLogger(__name__).warning(
                    "Restored checkpoint step %s after newer step(s) "
                    "failed integrity: %s", s, "; ".join(errors))
            return model
        raise ValueError(
            f"no restorable checkpoint in {self.directory}: "
            + "; ".join(errors))

    def _restore_step(self, step: int, load_updater: bool, *,
                      mesh=None, sharding_rules=None):
        import orbax.checkpoint as ocp
        model = _build_model(self.directory)
        template = _template_for(model, self._mgr.item_metadata(step))
        if mesh is not None:
            template = _sharded_template(model, template, mesh,
                                         sharding_rules)
        state = self._mgr.restore(
            step, args=ocp.args.StandardRestore(template))
        model = _apply_state(model, state, load_updater)
        if mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import (
                shard_model_with_rules)
            shard_model_with_rules(model, mesh, sharding_rules)
        return model

    def wait_until_finished(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "OrbaxCheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
