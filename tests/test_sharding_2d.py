"""GSPMD 2-D parallelism: rule-based sharding, the zero-all-gather vocab
path, resharded restore, sharded serving.

The oracle throughout is the compiled HLO itself (the same surface
``bench.py --sharding-2d`` records into ``MULTICHIP_r07.json``): on a
DP×MP mesh the Megatron rule set must produce a forward with ZERO
all-gathers — a row-sharded embedding ``take`` in, column-sharded logits
with LSE cross-entropy out. Rule semantics follow the fmengine/EasyLM
``match_partition_rules`` pattern: first regex match over the
'/'-joined param path wins, scalars never partition, unmatched paths
fail loudly.
"""

import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.parallel.mesh import (format_mesh_axes, make_mesh,
                                              parse_mesh_axes)
from deeplearning4j_tpu.parallel.sharding import (
    DEFAULT_2D_RULES, P, lint_partition_rules, load_sharding_rules,
    match_partition_rules, place_batch, shard_model_with_rules)
from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

VOCAB, T, BATCH = 64, 8, 8

_COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all-to-all)\b")


def tiny_lm_2d(mesh=None, rules=None, seed=7, vocab=VOCAB):
    """1-layer LM whose dims divide every mesh used here (model axis up
    to 4: vocab 64, d_model 16, heads 4, d_ff 32); an odd ``vocab`` is
    the one dimension that does not."""
    net = TransformerLM(vocab_size=vocab, max_length=T, n_layers=1,
                        d_model=16, n_heads=4, d_ff=32, seed=seed).init()
    if mesh is not None:
        shard_model_with_rules(net, mesh, rules)
    return net


def lm_batch(seed=3, vocab=VOCAB):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, size=(BATCH, T))
    return DataSet(toks.astype(np.float32),
                   np.asarray(lm_labels(jnp.asarray(toks), vocab)))


def collective_counts(hlo):
    counts = {}
    for m in _COLLECTIVE.finditer(hlo):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def forward_hlo(net, ds, mesh):
    import jax.numpy as jnp
    xj = place_batch(jnp.asarray(np.asarray(ds.features)), mesh)
    return net._output_fn().lower(net.params, net.states, {"tokens": xj},
                                  None).compile().as_text()


def step_hlo(net, ds, mesh):
    import jax.numpy as jnp
    step = net._get_train_step()
    it, ep, rng_k = net._device_tick()
    xj = place_batch(jnp.asarray(np.asarray(ds.features)), mesh)
    yj = place_batch(jnp.asarray(np.asarray(ds.labels)), mesh)
    return step.lower(net.params, net.states, net.updater_states, it, ep,
                      {"tokens": xj}, [yj], None, None,
                      rng_k).compile().as_text()


def leaf_paths(params):
    import jax
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}


# ----------------------------------------------------------- rule matching
class TestMatchPartitionRules:
    def test_first_match_wins(self):
        params = {"block/ff1": {"W": np.zeros((4, 8))}}
        specs = match_partition_rules(
            [("ff1/W", P(None, "model")), (".*", P())], params)
        assert specs["block/ff1"]["W"] == P(None, "model")
        # reversed order: the catch-all shadows the specific rule
        specs = match_partition_rules(
            [(".*", P()), ("ff1/W", P(None, "model"))], params)
        assert specs["block/ff1"]["W"] == P()

    def test_scalar_leaves_never_partitioned(self):
        params = {"layer": {"W": np.zeros((4, 4)), "step": np.float32(3.0),
                            "one": np.zeros((1,))}}
        specs = match_partition_rules([(".*", P("model"))], params)
        assert specs["layer"]["W"] == P("model")
        assert specs["layer"]["step"] == P()   # 0-d: never partitioned
        assert specs["layer"]["one"] == P()    # size-1: never partitioned

    def test_unmatched_path_fails_loudly(self):
        params = {"embed": {"W": np.zeros((8, 4))}}
        with pytest.raises(ValueError, match="Partition rule not found"):
            match_partition_rules([("ff1/W", P())], params)

    def test_default_rules_cover_transformer_lm(self):
        net = tiny_lm_2d()
        specs = leaf_paths(match_partition_rules(DEFAULT_2D_RULES,
                                                 net.params))
        embed = [s for n, s in specs.items() if "embed" in n and
                 n.endswith("/W")]
        out_w = [s for n, s in specs.items()
                 if re.search(r"(out|output|logits|lm_head)[^/]*/W$", n)]
        assert embed and all(s == P("model", None) for s in embed)
        assert out_w and all(s == P(None, "model") for s in out_w)

    def test_lint_flags_unmatched_dead_and_shadowed(self):
        params = {"embed": {"W": np.zeros((8, 4))},
                  "out": {"W": np.zeros((4, 8))}}
        warnings = lint_partition_rules(
            [("embed/W", P("model", None)),   # live
             ("qkv/W", P(None, "model")),     # dead: matches nothing
             ("embed/.*", P())],              # fully shadowed by rule 0
            params)
        text = "\n".join(warnings)
        assert "'out/W' matches no rule" in text
        assert "matches no param" in text and "qkv/W" in text
        assert "fully shadowed" in text
        # the shipped default set lints clean against the LM it targets
        assert lint_partition_rules(DEFAULT_2D_RULES,
                                    tiny_lm_2d().params) == []

    def test_load_sharding_rules_schema(self, tmp_path):
        spec = {"rules": [["embed/W$", ["model", None]], [".*", []]]}
        rules = load_sharding_rules(spec)
        assert rules[0][1] == P("model", None)
        assert rules[1][1] == P()
        path = tmp_path / "rules.json"
        path.write_text(json.dumps(spec))
        assert load_sharding_rules(str(path)) == rules
        with pytest.raises(ValueError):
            load_sharding_rules({"rules": [["(unclosed", []]]})
        with pytest.raises((ValueError, KeyError, TypeError)):
            load_sharding_rules({"rules": "not-a-list"})


# ------------------------------------------------------------ mesh grammar
class TestMeshGrammar:
    def test_parse_format_round_trip(self):
        axes = parse_mesh_axes("data=4,model=2")
        assert axes == {"data": 4, "model": 2}
        assert format_mesh_axes(axes) == "data=4,model=2"
        assert parse_mesh_axes("data=-1,model=2") == {"data": -1,
                                                      "model": 2}

    @pytest.mark.parametrize("bad", ["", "data", "data=x", "data=0",
                                     "data=4,data=2", "data=-1,model=-1",
                                     "data=-2"])
    def test_rejects_bad_specs(self, bad):
        with pytest.raises(ValueError):
            parse_mesh_axes(bad)

    def test_make_mesh_infers_one_axis(self):
        mesh = make_mesh(parse_mesh_axes("data=-1,model=2"))
        assert dict(mesh.shape) == {"data": 4, "model": 2}

    def test_2d_rules_on_data_only_mesh_degrade_to_replicated(self):
        # naming an absent axis must not KeyError — the leaf replicates,
        # same as a non-dividing dim (bench's dp8 baseline relies on it)
        mesh = make_mesh({"data": 8})
        net = tiny_lm_2d(mesh=mesh)  # DEFAULT_2D_RULES name "model"
        for v in leaf_paths(net.params).values():
            assert v.sharding.spec == P()


# --------------------------------------------- vocab-path HLO oracle tests
class TestVocabPathHLO:
    """The acceptance oracle: compiled-HLO collective counts on the
    8-device CPU mesh (conftest forces it)."""

    def test_forward_zero_all_gathers(self):
        mesh = make_mesh({"data": 4, "model": 2})
        net, ds = tiny_lm_2d(mesh=mesh), lm_batch()
        counts = collective_counts(forward_hlo(net, ds, mesh))
        assert counts.get("all-gather", 0) == 0
        # ...and the model really is sharded: row-parallel partial sums
        # surface as all-reduces, not as a gather of replicated params
        assert counts.get("all-reduce", 0) > 0

    def test_forward_zero_all_gathers_after_fit(self):
        # placement-pinning regression: one train step must leave params
        # exactly where the rules put them (GSPMD picking its own output
        # shardings for the updated params would re-introduce gathers)
        mesh = make_mesh({"data": 4, "model": 2})
        net, ds = tiny_lm_2d(mesh=mesh), lm_batch()
        net.fit(ds)
        emb = [v for n, v in leaf_paths(net.params).items()
               if "embed" in n and n.endswith("/W")]
        assert emb and emb[0].sharding.spec == P("model", None)
        counts = collective_counts(forward_hlo(net, ds, mesh))
        assert counts.get("all-gather", 0) == 0

    def test_train_step_zero_all_gathers(self):
        mesh = make_mesh({"data": 2, "model": 4})
        net, ds = tiny_lm_2d(mesh=mesh), lm_batch()
        counts = collective_counts(step_hlo(net, ds, mesh))
        assert counts.get("all-gather", 0) == 0
        assert counts.get("all-reduce", 0) > 0  # grad sync over data


# --------------------------------------------------- end-to-end DP×MP fit
class TestEndToEnd2D:
    def test_graph_2d_fit_matches_replicated(self):
        ds = lm_batch()
        ref = tiny_lm_2d(seed=11)
        net = tiny_lm_2d(mesh=make_mesh({"data": 4, "model": 2}), seed=11)
        for _ in range(2):
            ref.fit(ds)
            net.fit(ds)
        assert np.isfinite(float(net.score_))
        assert float(net.score_) == pytest.approx(float(ref.score_),
                                                  abs=1e-4)
        ref_p, net_p = leaf_paths(ref.params), leaf_paths(net.params)
        assert set(ref_p) == set(net_p)
        for name in ref_p:
            np.testing.assert_allclose(np.asarray(net_p[name]),
                                       np.asarray(ref_p[name]), atol=2e-5,
                                       err_msg=name)
        out = np.asarray(net.output(ds.features))
        np.testing.assert_allclose(out, np.asarray(ref.output(ds.features)),
                                   atol=1e-4)

    def test_mln_2d_fit_honors_rules(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = (NeuralNetConfiguration.builder().seed(5).list()
                .layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                                   loss="negativeloglikelihood"))
                .build())
        net = MultiLayerNetwork(conf).init()
        mesh = make_mesh({"data": 4, "model": 2})
        # Megatron pair over the hidden layer: column then row
        shard_model_with_rules(net, mesh, [
            ("(^|/)0/W$", P(None, "model")), ("(^|/)0/b$", P("model")),
            ("(^|/)1/W$", P("model", None)), (".*", P())])
        placed = leaf_paths(net.params)
        assert placed["0/W"].sharding.spec == P(None, "model")
        assert placed["1/W"].sharding.spec == P("model", None)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((BATCH, 12)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, BATCH)]
        net.fit(DataSet(x, y))
        assert np.isfinite(float(net.score_))
        # pinning holds on MLN too
        assert leaf_paths(net.params)["0/W"].sharding.spec == \
            P(None, "model")
        assert np.asarray(net.output(x)).shape == (BATCH, 4)


# ------------------------------------- a vocabulary the mesh does not divide
#: leaves the vocabulary pads: embed/W, out/W, out/b and Adam's m and v of each
VOCAB_LEAVES = [("embed", "W", 0), ("out", "W", 1), ("out", "b", 0)]
ODD = 63


def vocab_leaves(net):
    """(name, param, dim, array, moments) of the vocabulary's leaves."""
    return [(k, n, dim, net.params[k][n], net.updater_states[k][n])
            for k, n, dim in VOCAB_LEAVES]


def device0_bytes(arrays):
    import jax
    dev = jax.devices()[0]
    return sum(shard.data.nbytes for a in arrays
               for shard in a.addressable_shards if shard.device == dev)


class TestPaddedVocab:
    """GPT-2's 50,257 is odd: the vocabulary leaves are padded to the mesh
    and placed split, with the same mathematics as the published model.
    64 is the control, which divides and pads nothing."""

    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_vocab_leaves_split_and_padded(self, vocab):
        net = tiny_lm_2d(make_mesh({"data": 2, "model": 2}), vocab=vocab)
        for k, n, dim, v, moments in vocab_leaves(net):
            assert v.shape[dim] == 64, (k, n)
            assert v.sharding.spec[dim] == "model", (k, n)
            assert set(moments) == {"m", "v"}
            for s in moments.values():
                assert s.shape == v.shape and s.sharding == v.sharding

    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_padding_stays_exact_over_two_steps(self, vocab):
        from deeplearning4j_tpu.parallel.sharding import PADDED_LOGIT_BIAS
        net = tiny_lm_2d(make_mesh({"data": 2, "model": 2}), vocab=vocab)
        ds = lm_batch(vocab=vocab)
        for _ in range(2):
            net.fit(ds)
        for k, n, dim, v, moments in vocab_leaves(net):
            pad = np.take(np.asarray(v), range(vocab, 64), axis=dim)
            want = PADDED_LOGIT_BIAS if (k, n) == ("out", "b") else 0.0
            assert np.all(pad == np.float32(want)), (k, n)
            for s in moments.values():
                assert not np.take(np.asarray(s), range(vocab, 64),
                                   axis=dim).any(), (k, n)

    @pytest.mark.parametrize("labels", ["class_ids", "one_hot"])
    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_loss_and_gradients_match_the_unpadded_net(self, vocab, labels):
        ds = lm_batch(vocab=vocab)
        y = np.asarray(ds.labels)
        if labels == "one_hot":
            y = np.eye(vocab, dtype=np.float32)[y]
        ref = tiny_lm_2d(seed=11, vocab=vocab)
        net = tiny_lm_2d(make_mesh({"data": 2, "model": 2}), seed=11,
                         vocab=vocab)
        g_ref, loss_ref = ref.compute_gradient_and_score(ds.features, y)
        g_net, loss_net = net.compute_gradient_and_score(ds.features, y)
        assert loss_net == pytest.approx(loss_ref, abs=1e-5)
        ref_g, net_g = leaf_paths(g_ref), leaf_paths(g_net)
        assert set(ref_g) == set(net_g)
        for name, want in ref_g.items():
            got = np.asarray(net_g[name])
            got = got[tuple(slice(0, n) for n in want.shape)]
            np.testing.assert_allclose(got, np.asarray(want), atol=2e-6,
                                       err_msg=name)
        for k, n, dim, _v, _m in vocab_leaves(net):
            assert not np.take(np.asarray(g_net[k][n]), range(vocab, 64),
                               axis=dim).any(), (k, n)

    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_vocab_path_compiles_with_zero_all_gathers(self, vocab):
        """The train step gathers nothing. The forward gathers nothing
        either, except, where the head is padded, the one output that
        ``output()`` hands back cut to the published width: its
        probabilities ``[batch shard, T, 64]``, never a parameter."""
        mesh = make_mesh({"data": 2, "model": 2})
        net, ds = tiny_lm_2d(mesh, vocab=vocab), lm_batch(vocab=vocab)
        step = collective_counts(step_hlo(net, ds, mesh))
        assert step.get("all-gather", 0) == 0 and step["all-reduce"] > 0
        forward = forward_hlo(net, ds, mesh)
        gathers = re.findall(r"= (\S+) all-gather\(", forward)
        assert gathers == ([] if vocab == VOCAB
                           else [f"f32[{BATCH // 2},{T},64]{{1,0,2}}"])
        root = [line for line in forward.splitlines()
                if "ROOT" in line and "ENTRY" not in line][-1]
        assert collective_counts(forward)["all-reduce"] > 0
        if gathers:
            assert f"f32[{BATCH // 2},{T},{vocab}]" in root

    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_output_has_the_published_width(self, vocab):
        ds = lm_batch(vocab=vocab)
        ref = tiny_lm_2d(seed=5, vocab=vocab)
        net = tiny_lm_2d(make_mesh({"data": 2, "model": 2}), seed=5,
                         vocab=vocab)
        out = np.asarray(net.output(ds.features))
        assert out.shape == (BATCH, T, vocab)
        np.testing.assert_allclose(out, np.asarray(ref.output(ds.features)),
                                   atol=1e-6)
        np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-5)

    @pytest.mark.parametrize("loss", ["mse", "xent"])
    def test_a_head_of_another_loss_stays_replicated(self, loss):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers import (DenseLayer,
                                                  EmbeddingLayer,
                                                  OutputLayer)
        conf = (NeuralNetConfiguration.builder().seed(3).updater("adam")
                .graph_builder().add_inputs("ids")
                .add_layer("embed", EmbeddingLayer(n_in=ODD, n_out=16), "ids")
                .add_layer("hidden", DenseLayer(n_in=16, n_out=16), "embed")
                .add_layer("out", OutputLayer(n_in=16, n_out=ODD, loss=loss,
                                              activation="sigmoid"), "hidden")
                .set_outputs("out").build())
        net = ComputationGraph(conf).init()
        shard_model_with_rules(net, make_mesh({"data": 2, "model": 2}))
        placed = leaf_paths(net.params)
        assert placed["embed/W"].shape == (64, 16)      # the embedding pads
        assert placed["embed/W"].sharding.spec == P("model", None)
        for n in ("out/W", "out/b"):                    # this head does not
            assert placed[n].shape[-1] == ODD
            assert placed[n].sharding.spec == P()
        x = np.arange(BATCH, dtype=np.float32)[:, None]
        y = np.zeros((BATCH, ODD), np.float32)
        net.fit(DataSet(x, y))
        assert np.isfinite(float(net.score_))
        assert np.asarray(net.output(x)).shape == (BATCH, ODD)

    #: run in a fresh interpreter: jax.live_arrays() is the whole process's,
    #: and arrays that other tests' threads make would enter the count
    LIVE_BYTES = """
import gc, json, sys
import jax
sys.path.insert(0, "tests")
from test_sharding_2d import (DEFAULT_2D_RULES, ODD, P, device0_bytes,
                              make_mesh, tiny_lm_2d)
mesh = make_mesh({"data": 2, "model": 2})
replicate_vocab = [(r"(^|/)(embed|out)[^/]*/(W|b)$", P())] + list(
    DEFAULT_2D_RULES)
out = {}
for how, rules in (("replicated", replicate_vocab), ("padded", None)):
    gc.collect()
    before = device0_bytes(jax.live_arrays())
    net = tiny_lm_2d(mesh, rules, vocab=ODD)
    gc.collect()
    out[how] = [device0_bytes(jax.live_arrays()) - before,
                device0_bytes(jax.tree_util.tree_leaves(
                    (net.params, net.states, net.updater_states,
                     net._rng_key)))]
    del net
print(json.dumps(out))
"""

    def test_device0_holds_no_more_than_the_replicated_placement(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        proc = subprocess.run([sys.executable, "-c", self.LIVE_BYTES],
                              cwd=repo, env=env, capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        held = json.loads(proc.stdout.strip().splitlines()[-1])
        for how, (live, trees) in held.items():
            # nothing of the net's outlives placement but its own trees
            assert live == trees, how
        # the six [63,16] f32 leaves are 4 KB each on device 0 replicated
        # and 2 KB each split
        assert held["padded"][0] <= held["replicated"][0] - 6 * 1900

    def test_each_padded_leaf_replaces_its_source_before_the_next(
            self, monkeypatch):
        import weakref
        from deeplearning4j_tpu.parallel import sharding
        net = tiny_lm_2d(vocab=ODD)
        sources = [weakref.ref(leaf) for k, n, _d, v, moments
                   in vocab_leaves(net) for leaf in (v, *moments.values())]
        alive_at_call = []
        padded = sharding._padded

        def watched(*args, **kwargs):
            alive_at_call.append([ref() is not None for ref in sources])
            return padded(*args, **kwargs)

        monkeypatch.setattr(sharding, "_padded", watched)
        shard_model_with_rules(net, make_mesh({"data": 2, "model": 2}))
        assert len(alive_at_call) == 9
        for i, alive in enumerate(alive_at_call):
            assert alive == [False] * i + [True] * (9 - i), i

    @pytest.mark.parametrize("vocab", [VOCAB, ODD])
    def test_place_params_span_counts_the_padding(self, vocab):
        from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                                enable_tracing)
        tracer = enable_tracing(Tracer())
        try:
            tiny_lm_2d(make_mesh({"data": 2, "model": 2}), vocab=vocab)
        finally:
            disable_tracing()
        span, = [s for s in tracer.recorder.spans()
                 if s.name == "place_params"]
        table, bias = vocab * 16 * 4, vocab * 4
        want = (9, 3 * (2 * table + bias)) if vocab % 2 else (0, 0)
        assert (span.attrs["padded_leaves"],
                span.attrs["released_bytes"]) == want

    @pytest.mark.parametrize("updater", [True, False])
    def test_zip_checkpoint_holds_the_published_shapes(self, tmp_path,
                                                       updater):
        from deeplearning4j_tpu.util.model_serializer import (restore_model,
                                                              write_model)
        ds = lm_batch(vocab=ODD)
        net = tiny_lm_2d(make_mesh({"data": 2, "model": 2}), vocab=ODD)
        net.fit(ds)
        path = tmp_path / "lm.zip"
        write_model(net, path, save_updater=updater)
        back = restore_model(path, load_updater=updater)
        for k, n, _dim, v, moments in vocab_leaves(back):
            want = tiny_lm_2d(vocab=ODD).params[k][n].shape
            assert v.shape == want
            if updater:
                assert all(s.shape == want for s in moments.values())
        np.testing.assert_allclose(np.asarray(back.output(ds.features)),
                                   np.asarray(net.output(ds.features)),
                                   atol=1e-6)


# ----------------------------------------------- Keras-imported BERT, 2-D
class TestKerasBert2D:
    def _bert(self, keras, vocab=128, t=12, d=64, heads=16, ff=256,
              blocks=2):
        """BERT-large's architecture family (post-LN encoder: fused-QKV
        MHA + GELU 4x FFN + token-embedding in, vocab-projection out) at
        CI dims; layer names target the shipped DEFAULT_2D_RULES."""
        kl = keras.layers
        inp = kl.Input((t,), name="tokens")
        h = kl.Embedding(vocab, d, name="embed")(inp)
        for i in range(blocks):
            att = kl.MultiHeadAttention(num_heads=heads,
                                        key_dim=d // heads,
                                        name=f"mha{i}")(h, h)
            h = kl.LayerNormalization(name=f"ln_a{i}")(
                kl.Add(name=f"res_a{i}")([h, att]))
            f = kl.Dense(ff, activation="gelu", name=f"ff1_{i}")(h)
            f = kl.Dense(d, name=f"ff2_{i}")(f)
            h = kl.LayerNormalization(name=f"ln_f{i}")(
                kl.Add(name=f"res_f{i}")([h, f]))
        out = kl.Dense(vocab, activation="softmax", name="lm_head")(h)
        return keras.Model(inp, out)

    def test_imported_bert_trains_2d_zero_all_gather_vocab(self, tmp_path):
        keras = pytest.importorskip("keras")
        from deeplearning4j_tpu.modelimport.keras import KerasModelImport
        m = self._bert(keras)
        m.compile(loss="categorical_crossentropy", optimizer="sgd")
        path = str(tmp_path / "bert.h5")
        m.save(path)
        net = KerasModelImport.import_keras_model_and_weights(path)
        mesh = make_mesh({"data": 4, "model": 2})
        shard_model_with_rules(net, mesh)  # the shipped Megatron rules
        placed = leaf_paths(net.params)
        emb = [v for n, v in placed.items()
               if "embed" in n and n.endswith("/W")]
        head = [v for n, v in placed.items()
                if "lm_head" in n and n.endswith("/W")]
        assert emb[0].sharding.spec == P("model", None)   # row: take
        assert head[0].sharding.spec == P(None, "model")  # column: logits

        rng = np.random.default_rng(4)
        toks = rng.integers(0, 128, size=(BATCH, 12)).astype(np.float32)
        y = np.eye(128, dtype=np.float32)[
            rng.integers(0, 128, size=(BATCH, 12))]
        ds = DataSet(toks, y)
        net.fit(ds)
        first = float(net.score_)
        for _ in range(3):
            net.fit(ds)
        assert np.isfinite(first) and float(net.score_) < first

        # the vocab path of the IMPORTED model compiles gather-free,
        # after training (placement pinning) — the acceptance oracle
        import jax.numpy as jnp
        xj = place_batch(jnp.asarray(toks), mesh)
        hlo = net._output_fn().lower(net.params, net.states,
                                     {"tokens": xj},
                                     None).compile().as_text()
        counts = collective_counts(hlo)
        assert counts.get("all-gather", 0) == 0
        assert counts.get("all-reduce", 0) > 0


# ------------------------------------------------------- resharded restore
class TestReshardedRestore:
    def test_2x4_save_restores_onto_1x4(self, tmp_path):
        """A host-failure shrink: save on data=2×model=4, restore onto
        data=1×model=4; one further step must equal a clean resume."""
        from deeplearning4j_tpu.util.orbax_checkpoint import (restore_model,
                                                              save_model)
        ds = lm_batch()
        net = tiny_lm_2d(mesh=make_mesh({"data": 2, "model": 4}), seed=13)
        for _ in range(2):
            net.fit(ds)
        save_model(net, str(tmp_path / "ckpt"))

        clean = restore_model(str(tmp_path / "ckpt"))  # replicated resume
        shrunk_mesh = make_mesh({"data": 1, "model": 4})
        shrunk = restore_model(str(tmp_path / "ckpt"), mesh=shrunk_mesh,
                               sharding_rules=None)
        # restored STRAIGHT INTO the rule placement on the shrunk mesh
        emb = [v for n, v in leaf_paths(shrunk.params).items()
               if "embed" in n and n.endswith("/W")][0]
        assert emb.sharding.spec == P("model", None)
        assert dict(emb.sharding.mesh.shape) == {"data": 1, "model": 4}

        clean.fit(ds)
        shrunk.fit(ds)
        c_p, s_p = leaf_paths(clean.params), leaf_paths(shrunk.params)
        assert set(c_p) == set(s_p)
        for name in c_p:
            np.testing.assert_allclose(np.asarray(s_p[name]),
                                       np.asarray(c_p[name]), atol=2e-5,
                                       err_msg=name)


# --------------------------------------------------------- sharded serving
class TestShardedServing:
    def _dense(self, seed):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        conf = (NeuralNetConfiguration.builder().seed(seed).list()
                .layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                                   loss="negativeloglikelihood"))
                .build())
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()

    def test_sharded_register_warmup_hot_swap_zero_compiles(self):
        """Register a version GSPMD-sharded, warm it, hot-swap to a
        second sharded version UNDER LOAD — buckets round to the data
        axis and the steady state after the swap compiles nothing."""
        from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                                enable_tracing)
        from deeplearning4j_tpu.serving import ModelRegistry
        mesh = make_mesh({"data": 4, "model": 2})
        rules = [("(^|/)0/W$", P(None, "model")),
                 ("(^|/)1/W$", P("model", None)), (".*", P())]
        tr = enable_tracing(Tracer())
        reg = ModelRegistry(max_batch_size=8, warmup="sync")
        try:
            v1 = reg.register("clf", self._dense(1), mesh=mesh,
                              sharding_rules=rules, input_shape=(12,))
            served = reg._models["clf"]
            # buckets rounded to the data-axis size
            assert all(b % 4 == 0 for b in served.inference.buckets)
            assert served.describe()["versions"][0]["mesh"] == \
                {"data": 4, "model": 2}
            x = np.zeros((3, 12), np.float32)
            assert reg.predict("clf", x).shape == (3, 4)

            stop = threading.Event()
            errors = []

            def hammer():
                while not stop.is_set():
                    try:
                        reg.predict("clf", x)
                    except Exception as e:  # pragma: no cover - fail loud
                        errors.append(e)
                        return

            t = threading.Thread(target=hammer)
            t.start()
            try:
                # sync warmup compiles v2's buckets BEFORE activation —
                # the swap lands on an already-compiled forward
                v2 = reg.register("clf", self._dense(2), mesh=mesh,
                                  sharding_rules=rules, input_shape=(12,))
            finally:
                stop.set()
                t.join(30.0)
            assert not errors
            assert v2 == v1 + 1
            assert served.describe()["current_version"] == v2
            # steady state: every bucket is warm, nothing compiles
            baseline = tr.compile_count
            for n in (1, 3, 4, 8):
                out = reg.predict("clf", np.zeros((n, 12), np.float32))
                assert out.shape == (n, 4)
            assert tr.compile_count == baseline
        finally:
            reg.shutdown()
            disable_tracing()

    def test_sharded_register_rejects_quantized_policy(self):
        from deeplearning4j_tpu.serving import ModelRegistry
        reg = ModelRegistry(warmup="off")
        try:
            with pytest.raises(ValueError, match="float32"):
                reg.register("q", self._dense(3),
                             mesh=make_mesh({"data": 4, "model": 2}),
                             dtype_policy="int8")
        finally:
            reg.shutdown()


# ---------------------------------------------------- pod-mesh plumbing
class TestPodMeshSpec:
    def test_worker_spec_mesh_slice(self):
        from deeplearning4j_tpu.parallel.elastic import WorkerSpec
        spec = WorkerSpec(argv=["x"], mesh_axes={"model": 2},
                          env={"XLA_FLAGS":
                               "--xla_force_host_platform_device_count=8 "
                               "--xla_dump_to=/tmp/d"})
        assert spec.local_mesh_devices() == 2
        flags = spec.environment()["XLA_FLAGS"]
        # the parent's 8-device multiplier is replaced by the slice size;
        # unrelated operator flags survive
        assert "--xla_force_host_platform_device_count=2" in flags
        assert flags.count("device_count") == 1
        assert "--xla_dump_to=/tmp/d" in flags
        # classic one-device worker: the multiplier is stripped outright
        one = WorkerSpec(argv=["x"], env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
        assert one.local_mesh_devices() == 1
        assert "XLA_FLAGS" not in one.environment()

    def test_worker_context_pod_mesh_axes(self, tmp_path):
        from deeplearning4j_tpu.parallel import elastic
        from deeplearning4j_tpu.parallel.elastic import ElasticWorkerContext
        env = {
            elastic.ENV_COORDINATOR: "127.0.0.1:999",
            elastic.ENV_NUM_PROCESSES: "3",
            elastic.ENV_PROCESS_ID: "1",
            elastic.ENV_SLOT: "1",
            elastic.ENV_GENERATION: "2",
            elastic.ENV_TOKEN: "g2-abc",
            elastic.ENV_CKPT_DIR: str(tmp_path),
            elastic.ENV_HEARTBEAT: str(tmp_path / "hb"),
            elastic.ENV_MESH: "model=2",
            elastic.ENV_SHARDING_RULES: "/tmp/rules.json",
        }
        ctx = ElasticWorkerContext.from_env(env)
        assert ctx.mesh_axes == {"model": 2}
        assert ctx.sharding_rules_path == "/tmp/rules.json"
        # data spans the generation's processes; model lives in-host
        assert ctx.pod_mesh_axes() == {"data": 3, "model": 2}
        env.pop(elastic.ENV_MESH)
        env.pop(elastic.ENV_SHARDING_RULES)
        ctx = ElasticWorkerContext.from_env(env)
        assert ctx.mesh_axes is None
        assert ctx.pod_mesh_axes() == {"data": 3}


# ------------------------------------------------- committed bench record
@pytest.mark.smoke
class TestMultichipR07Check:
    """The committed MULTICHIP_r07 series must keep passing its own
    --check (same pattern as BENCH_TRAIN in the smoke tier): schema +
    collective-count consistency, plus the zero-all-gather vocab-path
    invariant re-proven LIVE before and after a train step."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    COMMITTED = os.path.join(REPO, "MULTICHIP_r07.json")

    def test_committed_record_schema(self):
        with open(self.COMMITTED, encoding="utf-8") as fh:
            rec = json.load(fh)
        assert rec["metric"] == "sharding_2d"
        assert rec["series"] == "MULTICHIP_r07"
        cfgs = rec["configs"]
        assert set(cfgs) == {"dp8", "dp4_mp2", "dp2_mp4"}
        for name, cfg in cfgs.items():
            assert cfg["wall_ms_per_step"] > 0
            assert cfg["forward"]["all_gather"] == 0
            if name != "dp8":  # 2-D: grads sync AND rows partial-sum
                assert cfg["train_step"]["all_reduce"] > 0

    def test_check_passes(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(self.REPO, "bench.py"),
             "--sharding-2d", "--check", self.COMMITTED],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=self.REPO,
            capture_output=True, text=True, timeout=560)
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "sharding-2d check OK" in proc.stdout


# ------------------------------------------------------------ CLI contract
class TestCliValidation:
    ARGS = ["--modelPath", "/nonexistent/m.zip",
            "--dataPath", "/nonexistent/d.npz",
            "--modelOutputPath", "/nonexistent/out.zip"]

    def _train(self, extra):
        from deeplearning4j_tpu.cli import parallel_wrapper_main
        with pytest.raises(SystemExit) as exc:
            parallel_wrapper_main(self.ARGS + extra)
        assert exc.value.code == 2

    def test_train_rejects_bad_mesh_grammar(self, capsys):
        self._train(["--mesh", "data=4,model"])
        assert "--mesh" in capsys.readouterr().err

    def test_train_rejects_workers_plus_mesh(self, capsys):
        self._train(["--mesh", "data=4", "--workers", "4"])
        assert "both size the data axis" in capsys.readouterr().err

    def test_train_rejects_rules_without_mesh(self, capsys):
        self._train(["--sharding-rules", "/tmp/rules.json"])
        assert "needs --mesh" in capsys.readouterr().err

    def test_train_rejects_unreadable_rules(self, capsys):
        self._train(["--mesh", "data=4,model=2",
                     "--sharding-rules", "/nonexistent/rules.json"])
        assert "--sharding-rules" in capsys.readouterr().err

    def test_elastic_rejects_pinned_data_axis(self, tmp_path, capsys):
        self._train(["--elastic", "2", "--ckpt-dir", str(tmp_path),
                     "--mesh", "data=4,model=2"])
        assert "cannot be pinned" in capsys.readouterr().err

    def test_serve_rejects_mesh_plus_quantization(self, capsys):
        from deeplearning4j_tpu.cli import serve_main
        with pytest.raises(SystemExit) as exc:
            serve_main(["--model", "m=/nonexistent/m.zip",
                        "--mesh", "data=4,model=2",
                        "--dtype-policy", "m=int8"], block=False)
        assert exc.value.code == 2
        assert "float32-only" in capsys.readouterr().err
