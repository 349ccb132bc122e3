"""Sharding rules: how model pytrees map onto a Mesh.

This replaces the reference's model replication (`ParallelWrapper.java:78`
clones the net per worker thread) with sharding annotations: a replicated
param lives once per device HBM but is updated by a single SPMD program; a
tensor-parallel param is *split* across the 'model' axis and XLA inserts the
matching collectives (all-gather / reduce-scatter) around the matmuls.
"""

from __future__ import annotations

import functools
import json
import re

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.observe import trace as _trace
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dimension over the data axis."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def place_batch(x, mesh: Optional[Mesh], axis: str = DATA_AXIS):
    """Shard one batch array's leading dim over the mesh's data axis —
    the end-to-end input half of a DP×MP step (params carry the model
    axis; the batch carries data). No-op for ``None`` leaves, meshes
    without the axis, and ragged batches that don't divide it (those
    run on the replicated path, same contract as ParallelWrapper's
    tail-batch handling)."""
    if x is None or mesh is None:
        return x
    d = int(mesh.shape.get(axis, 1))
    ndim = getattr(x, "ndim", 0)
    if d <= 1 or ndim == 0 or x.shape[0] % d:
        return x
    return jax.device_put(x, batch_sharding(mesh, ndim, axis))


_COLUMN = "column"
_ROW = "row"


def _dense_like(layer) -> bool:
    """Layers holding one [n_in, n_out] matmul W (+ bias b): the building
    blocks of Megatron column/row pairs. OutputLayer subclasses DenseLayer."""
    from deeplearning4j_tpu.nn.layers.core import DenseLayer
    return isinstance(layer, DenseLayer)


def _is_output_layer(layer) -> bool:
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    return isinstance(layer, OutputLayer)


def _is_attention(layer) -> bool:
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    return isinstance(layer, SelfAttentionLayer)


def _require_inferred_preprocessors(net) -> None:
    """Pair-breaking reads the conf's preprocessor maps, and the INFERRED
    half (automatic reshape boundaries) only exists after
    ``conf.finalize()`` runs shape inference (ADVICE round 5: specs
    computed before that could pair across a reshape and silently gather
    the activation path). Both network constructors finalize, so this
    only trips for hand-built configuration objects — loudly."""
    if getattr(net.conf, "_finalized", True) is False:
        raise RuntimeError(
            "tp_param_specs/shard_model need the conf's inferred input "
            "preprocessors, which are computed by shape inference: call "
            "net.init() (or conf.finalize()) before requesting "
            "tensor-parallel specs — otherwise column/row pairs could "
            "form across a reshape boundary and the all-gather-free "
            "activation path is silently lost")


def _layer_topology(net):
    """(key, layer, consumers) in forward order for both network kinds.

    MLN: keys are layer indices, consumer of i is [i+1]. ComputationGraph:
    keys are vertex names, consumers from the vertex-input edges (layer
    vertices only — elementwise vertices break pairs, which is correct:
    a residual add merges two activation shardings)."""
    if isinstance(net.params, dict):  # ComputationGraph
        vertices = net.conf.vertices
        consumers = {k: [] for k in vertices}
        n_inputs = {}
        for name, vd in vertices.items():
            n_inputs[name] = len(vd.inputs)
            for src in vd.inputs:
                if src in consumers:
                    consumers[src].append(name)
        # like the MLN branch: a per-vertex input preprocessor reshapes the
        # activation between the pair and would gather the column sharding
        graph_pre = set(getattr(net.conf, "preprocessors", {}) or {})

        def pairable_consumers(name):
            # ANY non-layer or multi-input consumer (residual tap, merge)
            # disqualifies pairing: the column-sharded activation would be
            # gathered on that edge, defeating the pair
            out = []
            for c in consumers[name]:
                if not (vertices[c].is_layer and n_inputs[c] == 1
                        and c not in graph_pre):
                    return []
                out.append(c)
            return out

        return [(name, vd.obj, pairable_consumers(name))
                for name, vd in vertices.items() if vd.is_layer]
    layers = list(net.layers)
    # an input preprocessor (explicit spec or inferred reshape) between two
    # layers breaks the pair, like a non-layer vertex does in a graph: the
    # column-sharded activation would be gathered at the reshape
    pre = set(getattr(net.conf, "preprocessors", {}) or {})
    pre |= set(getattr(net.conf, "input_pre_processors", {}) or {})
    return [(i, layer,
             [i + 1] if i + 1 < len(layers) and (i + 1) not in pre else [])
            for i, layer in enumerate(layers)]


def tp_param_specs(net, axis: str = MODEL_AXIS, mesh: Optional[Mesh] = None):
    """Megatron-pattern tensor-parallel PartitionSpecs (designed, round 5).

    Replaces the round-1 every-layer output-dim rule, which forced a GSPMD
    reshard between every consecutive pair of layers. The designed rule
    shards in *paired* column→row units so the activation between the pair
    stays sharded on the hidden dimension and the only collective is one
    all-reduce after the row matmul (the Megatron-LM MLP/attention
    pattern; SURVEY.md §2.b "Model/tensor parallelism" — the capability
    the reference lacks):

    - **Dense→Dense chains** (position-wise FFN, classifier heads): the
      first layer is column-parallel (``W: P(None, axis)``, ``b: P(axis)``),
      its unique dense consumer row-parallel (``W: P(axis, None)``,
      ``b: P()``). Pairs form greedily along the forward order; an
      OutputLayer may END a pair (its row all-reduce yields full logits
      for the loss) but never starts one (column-sharded logits would
      force a gather at the loss).
    - **Self-attention**: QKV projection column-split / output projection
      row-split within the layer (``Wqkv: P(None, axis)``,
      ``bqkv: P(axis)``, ``Wo: P(axis, None)``, ``bo: P()``) — one
      all-reduce per attention block.
    - Everything else (LayerNorm/BN scale-shift, embeddings, recurrent
      cells, conv) stays replicated: their params are small or their
      access pattern (vocab gather, scan carry) would trade one
      all-reduce for several.

    Measured on the 8-device CPU mesh (dp=2 × tp=4, 3-layer FFN forward:
    ``tests/test_parallel.py::test_megatron_specs_fewer_collectives``):
    the old rule compiles to **12 collectives (6 all-gather + 6
    all-reduce)**; the paired rule compiles to **3 all-reduce** — the
    canonical one-all-reduce-per-pair shape, a 4× reduction in collective
    count with zero all-gathers on the activation path.

    When ``mesh`` is given, a pair whose shared hidden dimension does not
    divide the model-axis size degrades JOINTLY to replicated (a half
    -degraded pair is worse than none: the sharded half's activation
    would be gathered anyway).
    """
    _require_inferred_preprocessors(net)
    topo = _layer_topology(net)
    by_key = {k: layer for k, layer, _ in topo}
    roles: Dict[object, str] = {}

    def tp_size():
        return mesh.shape[axis] if mesh is not None else None

    for key, layer, consumers in topo:
        if key in roles or not _dense_like(layer) or _is_output_layer(layer):
            continue
        if len(consumers) != 1:
            continue
        nxt = consumers[0]
        nxt_layer = by_key.get(nxt)
        if nxt_layer is None or nxt in roles or not _dense_like(nxt_layer):
            continue
        # the pair's shared hidden dim must divide the model axis
        if tp_size() is not None and layer.n_out % tp_size():
            continue
        roles[key] = _COLUMN
        roles[nxt] = _ROW

    def specs_for(key, layer, p: Dict) -> Dict[str, P]:
        if _is_attention(layer):
            # head-major Wqkv propagates through the (n,t,h,3,dh) reshape
            # iff tp divides n_heads (attention.py param_shapes)
            if tp_size() is not None and layer.n_heads % tp_size():
                return {n: P() for n in p}
            d = {"Wqkv": P(None, axis), "bqkv": P(axis)}
            if "Wo" in p:
                d["Wo"] = P(axis, None)
                d["bo"] = P()
            return {n: d.get(n, P()) for n in p}
        role = roles.get(key)
        if role == _COLUMN:
            return {n: (P(None, axis) if n == "W"
                        else P(axis) if n == "b" else P()) for n in p}
        if role == _ROW:
            return {n: (P(axis, None) if n == "W" else P()) for n in p}
        return {n: P() for n in p}

    if isinstance(net.params, dict):
        return {key: specs_for(key, by_key[key], p)
                for key, p in net.params.items() if key in by_key}
    return [specs_for(i, layer, p)
            for (i, layer), p in zip(enumerate(net.layers), net.params)]


# -- rule-based sharding: regex-over-param-path → PartitionSpec --------------
#
# The config-driven layer above tp_param_specs: one rule line shards any
# model without touching layer code. A rule is (regex, PartitionSpec);
# rules are tried in order against the '/'-joined param path ("vertex/W"
# for graphs, "0/W" for MultiLayerNetwork layer lists) and the FIRST
# match wins. Scalar / size-1 leaves are never partitioned; a param no
# rule matches fails loudly — a silently-replicated tensor is how a
# "sharded" job quietly stops fitting in HBM.

Rule = Tuple[str, P]

#: Shipped default rule set for the framework's transformer naming
#: convention (``transformer_encoder_block``/``transformer_decoder_block``
#: vertex names, ``embed``/``out`` heads). Reproduces the Megatron
#: column→row pairs ``tp_param_specs`` derives from topology, PLUS the
#: vocab path the pairing rule refuses on principle: the embedding table
#: is vocab-ROW-sharded (``jnp.take`` over a sharded axis-0 compiles to
#: masked local takes + one all-reduce, no gather) and the LM head is
#: vocab-COLUMN-sharded — its logits stay sharded through the
#: log-sum-exp cross-entropy (``losses.mcxent_logits`` routes softmax
#: losses through ``log_softmax``), so the whole path compiles with ZERO
#: all-gathers (asserted in tests/test_sharding_rules.py against HLO).
DEFAULT_2D_RULES: Tuple[Rule, ...] = (
    # vocab path: row-sharded embedding take …
    (r"(^|/)embed[^/]*/W$", P(MODEL_AXIS, None)),
    # … and column-sharded logits (+LSE loss keeps them sharded)
    (r"(^|/)(out|output|logits|lm_head)[^/]*/W$", P(None, MODEL_AXIS)),
    (r"(^|/)(out|output|logits|lm_head)[^/]*/b$", P(MODEL_AXIS)),
    # Megatron attention block: QKV column-split, output row-split
    (r"/Wqkv$", P(None, MODEL_AXIS)),
    (r"/bqkv$", P(MODEL_AXIS)),
    (r"/Wo$", P(MODEL_AXIS, None)),
    (r"/bo$", P()),
    # Megatron paired FFN: first matmul column, second row
    (r"ff1[^/]*/W$", P(None, MODEL_AXIS)),
    (r"ff1[^/]*/b$", P(MODEL_AXIS)),
    (r"ff2[^/]*/W$", P(MODEL_AXIS, None)),
    # everything else (LayerNorm/BN scale-shift, positional tables,
    # recurrent cells, conv) replicates
    (r".*", P()),
)


def _path_name(path) -> str:
    """'/'-joined name for a tree_util key path: dict keys and sequence
    indices both render bare (``0/W``, ``block0-att/Wqkv``)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - future key kinds
            parts.append(str(k))
    return "/".join(parts)


def _as_spec(spec) -> P:
    if isinstance(spec, P):
        return spec
    if spec is None:
        return P()
    if isinstance(spec, (list, tuple)):
        return P(*[None if (s is None or s == "null") else str(s)
                   for s in spec])
    raise ValueError(f"bad partition spec {spec!r} (want PartitionSpec, "
                     f"None, or a list of axis names / null)")


def normalize_rules(rules: Sequence) -> List[Rule]:
    """Validate + canonicalize a rule list: each entry becomes
    ``(compiled-ok regex string, PartitionSpec)``."""
    out: List[Rule] = []
    for i, entry in enumerate(rules):
        try:
            pattern, spec = entry
        except (TypeError, ValueError):
            raise ValueError(
                f"rule[{i}] must be a (regex, spec) pair, got {entry!r}"
            ) from None
        if not isinstance(pattern, str):
            raise ValueError(f"rule[{i}] pattern must be a string, "
                             f"got {type(pattern).__name__}")
        try:
            re.compile(pattern)
        except re.error as e:
            raise ValueError(f"rule[{i}] regex {pattern!r} invalid: {e}") \
                from None
        out.append((pattern, _as_spec(spec)))
    if not out:
        raise ValueError("empty sharding rule list")
    return out


def load_sharding_rules(source) -> List[Rule]:
    """Load rules from a JSON file path / file object / parsed dict.

    Schema: ``{"rules": [[regex, [axis-or-null, ...]], ...]}`` — the
    spec array gives one entry per tensor dimension (trailing dims may
    be omitted = unsharded), ``null`` meaning replicated on that dim.
    """
    if isinstance(source, dict):
        doc = source
    elif hasattr(source, "read"):
        doc = json.load(source)
    else:
        with open(source, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
    if not isinstance(doc, dict) or "rules" not in doc:
        raise ValueError("sharding rules file must be an object with a "
                         "'rules' array")
    return normalize_rules(doc["rules"])


def _is_scalar_leaf(leaf) -> bool:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return True
    return len(shape) == 0 or int(np.prod(shape)) == 1


def match_partition_rules(rules: Sequence, params):
    """Map a param pytree to a same-structure PartitionSpec pytree by
    first-match regex over each leaf's '/'-joined path (the
    fmengine/EasyLM ``match_partition_rules`` pattern). Scalar and
    size-1 leaves are never partitioned (always ``P()``); a leaf no rule
    matches raises — add a catch-all ``(".*", P())`` rule to opt into
    replicate-by-default."""
    rules = normalize_rules(rules)

    def match(path, leaf):
        name = _path_name(path)
        if _is_scalar_leaf(leaf):
            return P()
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        raise ValueError(f"Partition rule not found for param: {name}")

    return jax.tree_util.tree_map_with_path(match, params)


def lint_partition_rules(rules: Sequence, params) -> List[str]:
    """Dry-run lint against a sample model's param tree: returns
    warnings (empty = clean) for unmatched params (would raise at
    placement time), dead rules (match nothing), and shadowed rules
    (every leaf they match is claimed by an earlier rule)."""
    rules = normalize_rules(rules)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    names = [_path_name(p) for p, leaf in leaves
             if not _is_scalar_leaf(leaf)]
    problems: List[str] = []
    hits: List[set] = [set() for _ in rules]
    first_hit: Dict[str, int] = {}
    for name in names:
        matched = False
        for i, (pattern, _spec) in enumerate(rules):
            if re.search(pattern, name):
                hits[i].add(name)
                if not matched:
                    first_hit[name] = i
                matched = True
        if not matched:
            problems.append(f"param {name!r} matches no rule (placement "
                            f"would fail loudly)")
    for i, (pattern, _spec) in enumerate(rules):
        if not hits[i]:
            problems.append(f"rule[{i}] {pattern!r} matches no param of "
                            f"the sample model (dead rule?)")
        elif all(first_hit[n] != i for n in hits[i]):
            winners = sorted({first_hit[n] for n in hits[i]})
            problems.append(
                f"rule[{i}] {pattern!r} is fully shadowed by earlier "
                f"rule(s) {winners} — it can never win a match")
    return problems


def _place_params_span(place):
    """``place(net, mesh, ...)`` under a ``place_params`` span while tracing
    is on: a model is built on one device and moved leaf by leaf, and the
    span says how long that took for how many leaves, bytes and devices."""
    @functools.wraps(place)
    def placing(net, mesh: Mesh, *args, **kwargs):
        tracer = _trace.get_active_tracer()
        if tracer is None:
            return place(net, mesh, *args, **kwargs)
        with tracer.span("place_params", category="setup",
                         attrs={"devices": int(mesh.devices.size)}) as span:
            place(net, mesh, *args, **kwargs)
            leaves = jax.tree_util.tree_leaves(
                (net.params, net.states, net.updater_states))
            span.set_attribute("leaves", len(leaves))
            span.set_attribute("bytes",
                               sum(int(leaf.nbytes) for leaf in leaves))
    return placing


@_place_params_span
def shard_model_with_rules(net, mesh: Mesh, rules: Optional[Sequence] = None
                           ) -> None:
    """Place a model on a DP×MP mesh from a rule list, in-place (the
    config-line counterpart of :func:`shard_model`): params by
    first-match rule, updater-state leaves sharing the param's spec when
    shapes match, layer states replicated. Records the mesh on the net
    (``net._mesh``) so ``fit``/``output`` shard incoming batches over
    the ``data`` axis end to end.

    ``rules=None`` uses :data:`DEFAULT_2D_RULES`. A matched leaf whose
    dims do not divide the named axes degrades to replicated (same
    contract as ``shard_model``'s Megatron path)."""
    specs = match_partition_rules(
        DEFAULT_2D_RULES if rules is None else rules, net.params)
    repl = replicated(mesh)
    placed: Dict[str, Tuple[tuple, P]] = {}

    def place_param(path, v, spec):
        if not _leaf_sharding_ok(v.shape, spec, mesh):
            spec = P()
        placed[_path_name(path)] = (tuple(v.shape), spec)
        return jax.device_put(v, NamedSharding(mesh, spec))

    new_params = jax.tree_util.tree_map_with_path(place_param, net.params,
                                                  specs)
    if net.updater_states is not None:
        def upd_sharding(path, s):
            # updater moments live at <param-path>/<slot-name> and share
            # the param's spec when shapes match (momentum etc.)
            shape_spec = placed.get(_path_name(path[:-1]))
            if shape_spec is not None and tuple(s.shape) == shape_spec[0]:
                return NamedSharding(mesh, shape_spec[1])
            return repl
        upd_sh = jax.tree_util.tree_map_with_path(upd_sharding,
                                                  net.updater_states)
        net.updater_states = jax.tree_util.tree_map(
            jax.device_put, net.updater_states, upd_sh)
        net._upd_shardings = upd_sh
    net.params = new_params
    net.states = jax.device_put(net.states, repl)
    net._mesh = mesh
    # the train step pins its updated params/opt-state to these (GSPMD
    # would otherwise pick its own output shardings — one drifted leaf
    # re-layouts every later compile and re-introduces all-gathers)
    net._param_shardings = jax.tree_util.tree_map(
        lambda v: v.sharding, new_params)
    # steps compiled before placement know nothing about the pins
    net._jit_cache.clear()


def _leaf_sharding_ok(shape, spec: P, mesh: Mesh) -> bool:
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            # an axis the mesh does not have (e.g. 2-D rules on a
            # data-only mesh) degrades to replicated, same as a
            # non-dividing dim
            if a not in mesh.shape or dim % mesh.shape[a]:
                return False
    return True


@_place_params_span
def shard_model(net, mesh: Mesh, tp_axis: Optional[str] = None) -> None:
    """Place a model's params / states / updater states on the mesh, in-place.
    Works for both MultiLayerNetwork (list params) and ComputationGraph
    (dict params keyed by vertex name).

    ``tp_axis=None`` → fully replicated (pure data parallel).
    ``tp_axis='model'`` → Megatron paired specs from :func:`tp_param_specs`;
    any leaf whose dims don't divide the axis falls back to replicated.
    """
    repl = replicated(mesh)
    if tp_axis is None:
        net.params = jax.device_put(net.params, repl)
        net.states = jax.device_put(net.states, repl)
        net.updater_states = jax.device_put(net.updater_states, repl)
        return

    specs = tp_param_specs(net, tp_axis, mesh)
    is_graph = isinstance(net.params, dict)
    keys = list(net.params.keys()) if is_graph else range(len(net.params))

    def place(key):
        pd = net.params[key]
        sd = (specs.get(key, {}) if is_graph else specs[key])
        pl, ul = {}, {}
        for n, v in pd.items():
            spec = sd.get(n, P())
            if not _leaf_sharding_ok(v.shape, spec, mesh):
                spec = P()
            sh = NamedSharding(mesh, spec)
            pl[n] = jax.device_put(v, sh)
            # updater state leaves (momentum etc.) share the param's shape/spec
            ul[n] = {
                k: jax.device_put(s, sh if s.shape == v.shape else repl)
                for k, s in net.updater_states[key][n].items()
            }
        return pl, ul

    if is_graph:
        new_params, new_upd = {}, {}
        for key in keys:
            new_params[key], new_upd[key] = place(key)
    else:
        new_params, new_upd = [], []
        for key in keys:
            pl, ul = place(key)
            new_params.append(pl)
            new_upd.append(ul)
    net.params = new_params
    net.updater_states = new_upd
    net.states = jax.device_put(net.states, repl)
