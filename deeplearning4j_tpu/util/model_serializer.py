"""ModelSerializer — checkpoint save/restore.

Reference: ``util/ModelSerializer.java``: a zip holding JSON config +
flattened params + updater state (+ optional normalizer) with
``writeModel:51``, ``restoreMultiLayerNetwork:182``,
``restoreComputationGraph:389``, ``addNormalizerToModel:654``.

Format here: a zip with
- ``configuration.json``  — the network config (self-describing: sequential
  vs graph via its ``format`` field)
- ``params.npz``          — param arrays named ``<layer>/<param>``
- ``updater.npz``         — updater state ``<layer>/<param>/<slot>`` (optional)
- ``states.npz``          — layer runtime state (BN running stats) (optional)
- ``normalizer.json``     — fitted normalizer (optional)
- ``meta.json``           — iteration/epoch counters

Arrays are saved in the model's dtype; restore places them back on the
default device (re-shard with ``parallel.shard_model`` afterwards for
distributed resume).
"""

from __future__ import annotations

import io
import json
import zipfile
import zlib
from pathlib import Path
from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

CONFIG_NAME = "configuration.json"
PARAMS_NAME = "params.npz"
UPDATER_NAME = "updater.npz"
STATES_NAME = "states.npz"
NORMALIZER_NAME = "normalizer.json"
META_NAME = "meta.json"


def _layer_keys(params):
    """(key, params_dict) pairs — list-indexed for MLN, name-keyed for graphs."""
    if isinstance(params, dict):
        return list(params.items())
    return [(str(i), p) for i, p in enumerate(params)]


def _npz_bytes(arrays: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def replace_zip_entry(path, entry_name: str, payload: bytes) -> None:
    """Atomically rewrite the zip at ``path`` with every entry except
    ``entry_name`` (matched case-insensitively, as ``ModelSerializer.java:
    670`` does), then append ``payload`` under that name. Preserves the
    original file's permissions and cleans up the temp file on error."""
    import os
    import tempfile

    path = str(path)
    mode = os.stat(path).st_mode & 0o7777
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)),
                               suffix=".zip")
    os.close(fd)
    try:
        with zipfile.ZipFile(path) as zin, \
                zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as zout:
            for entry in zin.namelist():
                if entry.lower() == entry_name.lower():
                    continue
                zout.writestr(entry, zin.read(entry))
            zout.writestr(entry_name, payload)
        os.chmod(tmp, mode)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def write_model(model, path: Union[str, Path], *, save_updater: bool = True,
                normalizer=None) -> None:
    """ModelSerializer.writeModel parity."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)

    # the published shapes, whatever mesh padded the vocabulary to
    from deeplearning4j_tpu.parallel.sharding import published_trees
    model_params, us = published_trees(model)
    params = {f"{k}/{n}": np.asarray(v)
              for k, pd in _layer_keys(model_params) for n, v in pd.items()}

    upd = {}
    if save_updater and us is not None:
        items = us.items() if isinstance(us, dict) else ((str(i), u) for i, u in enumerate(us))
        for k, per_param in items:
            for pn, slots in per_param.items():
                for sn, v in slots.items():
                    upd[f"{k}/{pn}/{sn}"] = np.asarray(v)

    states = {}
    st = model.states
    if st is not None:
        items = st.items() if isinstance(st, dict) else ((str(i), s) for i, s in enumerate(st))
        for k, sd in items:
            for n, v in (sd or {}).items():
                states[f"{k}/{n}"] = np.asarray(v)

    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr(CONFIG_NAME, model.conf.to_json())
        z.writestr(PARAMS_NAME, _npz_bytes(params))
        if upd:
            z.writestr(UPDATER_NAME, _npz_bytes(upd))
        if states:
            z.writestr(STATES_NAME, _npz_bytes(states))
        if normalizer is not None:
            z.writestr(NORMALIZER_NAME, normalizer.to_json())
        from deeplearning4j_tpu.nn.layers.attention import QKV_LAYOUT
        z.writestr(META_NAME, json.dumps(
            {"iteration": model.iteration, "epoch": model.epoch,
             "framework": "deeplearning4j_tpu",
             # round-5 layout stamp: fused attention columns are head-major
             "qkv_layout": QKV_LAYOUT}))


def _load_npz(z: zipfile.ZipFile, name: str) -> Optional[dict]:
    if name not in z.namelist():
        return None
    with z.open(name) as f:
        data = np.load(io.BytesIO(f.read()))
        return {k: data[k] for k in data.files}


def validate_model_zip(path: Union[str, Path]) -> list:
    """Integrity check for a zip checkpoint; returns a list of problems
    (empty = valid). Catches the torn-write failure modes a preemption
    (or the fault injector's ``corrupt_checkpoint``) produces: not a zip
    at all, truncated central directory, CRC damage in a required member,
    or required members missing entirely."""
    problems = []
    try:
        with zipfile.ZipFile(path, "r") as z:
            for required in (CONFIG_NAME, PARAMS_NAME):
                if required not in z.namelist():
                    problems.append(f"missing required entry {required!r}")
            try:
                bad = z.testzip()
            except Exception as e:  # noqa: BLE001 - zlib.error, EOFError...
                # testzip only RETURNS names for CRC mismatches; damage to
                # the compressed stream itself raises from the inflater
                bad, problems = None, problems + [f"undecodable entry: {e}"]
            if bad is not None:
                problems.append(f"CRC mismatch in entry {bad!r}")
    except (zipfile.BadZipFile, OSError) as e:
        problems.append(f"unreadable zip: {e}")
    return problems


def _restore(path: Union[str, Path], *, load_updater: bool = True):
    from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    try:
        # zipfile verifies each member's CRC as it is read, so damage
        # surfaces here for free — the full validate_model_zip scan
        # (a second decompression of every member) runs only on the
        # failure path, to name the problem in the error
        with zipfile.ZipFile(path, "r") as z:
            conf_d = json.loads(z.read(CONFIG_NAME))
            params = _load_npz(z, PARAMS_NAME)
            upd = _load_npz(z, UPDATER_NAME) if load_updater else None
            states = _load_npz(z, STATES_NAME)
            meta = json.loads(z.read(META_NAME)) \
                if META_NAME in z.namelist() else {}
    except (zipfile.BadZipFile, zlib.error, EOFError, KeyError,
            OSError) as e:
        problems = validate_model_zip(path)
        raise ValueError(
            f"checkpoint {path} failed integrity validation: "
            + ("; ".join(problems) if problems else str(e))
            + " — the file is truncated/corrupt or not a model zip") from e

    is_graph = "ComputationGraph" in conf_d.get("format", "")
    if is_graph:
        conf = ComputationGraphConfiguration.from_dict(conf_d)
        model = ComputationGraph(conf)
    else:
        conf = MultiLayerConfiguration.from_dict(conf_d)
        model = MultiLayerNetwork(conf)
    model.init()

    def put(container, key, pn, arr):
        tgt = container[key] if isinstance(container, dict) else container[int(key)]
        if pn in tgt and tuple(tgt[pn].shape) != tuple(arr.shape):
            raise ValueError(
                f"checkpoint {path}: array {key}/{pn} has shape "
                f"{tuple(arr.shape)} but the configuration allocates "
                f"{tuple(tgt[pn].shape)} — checkpoint and config disagree "
                f"(wrong file, or corrupt)")
        tgt[pn] = jnp.asarray(arr)

    for full, arr in params.items():
        key, pn = full.split("/", 1)
        put(model.params, key, pn, arr)
    if states:
        for full, arr in states.items():
            key, pn = full.split("/", 1)
            put(model.states, key, pn, arr)
    if upd:
        for full, arr in upd.items():
            key, pn, sn = full.split("/", 2)
            tgt = (model.updater_states[key] if isinstance(model.updater_states, dict)
                   else model.updater_states[int(key)])
            tgt[pn][sn] = jnp.asarray(arr)
    model.iteration = int(meta.get("iteration", 0))
    model.epoch = int(meta.get("epoch", 0))
    from deeplearning4j_tpu.nn.layers.attention import (QKV_LAYOUT,
                                                        repack_legacy_fused_qkv)
    if meta.get("qkv_layout") != QKV_LAYOUT:
        # pre-round-5 checkpoint: fused attention weights were saved in the
        # [3,H,Dh] block-major column order — repack to head-major
        repack_legacy_fused_qkv(model)
    return model


def restore_multi_layer_network(path, *, load_updater: bool = True):
    """ModelSerializer.restoreMultiLayerNetwork:182 parity."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    m = _restore(path, load_updater=load_updater)
    if not isinstance(m, MultiLayerNetwork):
        raise ValueError(f"{path} holds a ComputationGraph, not a MultiLayerNetwork")
    return m


def restore_computation_graph(path, *, load_updater: bool = True):
    """ModelSerializer.restoreComputationGraph:389 parity."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    m = _restore(path, load_updater=load_updater)
    if not isinstance(m, ComputationGraph):
        raise ValueError(f"{path} holds a MultiLayerNetwork, not a ComputationGraph")
    return m


def restore_model(path, *, load_updater: bool = True):
    """Type-agnostic restore."""
    return _restore(path, load_updater=load_updater)


def add_normalizer_to_model(path, normalizer) -> None:
    """ModelSerializer.addNormalizerToModel:654 parity (rewrites the zip)."""
    replace_zip_entry(path, NORMALIZER_NAME,
                      normalizer.to_json().encode("utf-8"))


def restore_normalizer(path):
    from deeplearning4j_tpu.datasets.normalizers import Normalizer
    with zipfile.ZipFile(path, "r") as z:
        if NORMALIZER_NAME not in z.namelist():
            return None
        return Normalizer.from_json(z.read(NORMALIZER_NAME).decode())
