"""Example 23 — round-5 feature tour: normalizer.bin migration, designed
tensor parallelism, Chinese lattice segmentation, typed unknown words.

Four additions in one runnable script:

1. ``normalizer.bin`` both ways — ship a model WITH its fitted normalizer
   in one DL4J-format zip (``ModelSerializer.java:165-168``), restore both
   on the consumer side (``restoreNormalizerFromFile:707``), reproduce the
   producer's outputs from raw data alone.
2. Designed (Megatron) tensor parallelism — paired column→row Dense specs
   and head-sharded attention over a dp×tp mesh; TP outputs equal the
   replicated model.
3. Chinese lattice segmentation — the bigram-cost Viterbi decoder beats
   greedy longest-match on the classic ambiguity traps.
4. kuromoji-style unknown-word handling — out-of-lexicon spans come back
   as single TYPED tokens (grouped katakana/alpha/numeric runs), not
   per-character soup.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
     python examples/23_round5_features_tour.py
"""

import os
import tempfile

import jax
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.normalizers import NormalizerStandardize
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam

# --- 1. normalizer.bin rides the checkpoint zip ----------------------------
print("== 1. normalizer.bin migration (both directions)")

rng = np.random.default_rng(5)
y_idx = rng.integers(0, 3, 512)
x_raw = (rng.normal(size=(512, 8)).astype(np.float32) * 40 + 250)
for i, c in enumerate(y_idx):
    x_raw[i, c] += 90.0
y = np.eye(3, dtype=np.float32)[y_idx]

norm = NormalizerStandardize().fit(DataSet(x_raw, y))
x_norm = np.asarray(norm.transform(DataSet(x_raw, y)).features)

conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2)).list()
        .layer(DenseLayer(n_in=8, n_out=32, activation="relu"))
        .layer(OutputLayer(n_in=32, n_out=3)).build())
producer = MultiLayerNetwork(conf).init()
for _ in range(15):
    producer.fit(x_norm, y)

from deeplearning4j_tpu.modelimport.dl4j import (
    restore_multi_layer_network,
    restore_normalizer,
)
from deeplearning4j_tpu.modelimport.dl4j_export import (
    export_multi_layer_network,
)

with tempfile.TemporaryDirectory() as td:
    zip_path = os.path.join(td, "shipped.zip")
    export_multi_layer_network(producer, zip_path, normalizer=norm)
    consumer_net = restore_multi_layer_network(zip_path)
    consumer_norm = restore_normalizer(zip_path)

x_new = rng.normal(size=(16, 8)).astype(np.float32) * 40 + 250
a = np.asarray(producer.output(
    np.asarray(norm.transform(DataSet(x_new, None)).features)))
b = np.asarray(consumer_net.output(
    np.asarray(consumer_norm.transform(DataSet(x_new, None)).features)))
np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)
print("   restored model + normalizer reproduce producer outputs exactly")

# --- 2. designed tensor parallelism ----------------------------------------
print("== 2. Megatron tensor parallelism (dp x tp mesh)")

n_dev = len(jax.devices())
if n_dev >= 4:
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel.sharding import shard_model
    from deeplearning4j_tpu.zoo.models import TransformerEncoder

    tp = 4 if n_dev % 4 == 0 else 2
    dp = n_dev // tp
    mesh = make_mesh({"data": dp, "model": tp}, jax.devices()[:dp * tp])

    def enc():
        return ComputationGraph(TransformerEncoder(
            num_labels=4, vocab_size=64, max_length=8, n_layers=1,
            d_model=8 * tp, n_heads=tp, d_ff=16 * tp, seed=7).conf()).init()

    replicated, sharded = enc(), enc()
    shard_model(sharded, mesh, tp_axis="model")  # QKV column / Wo row,
    # ff1 column / ff2 row — one all-reduce per pair, no all-gathers
    toks = rng.integers(0, 64, size=(2 * dp, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(sharded.output_single(toks)),
        np.asarray(replicated.output_single(toks)), rtol=2e-4, atol=1e-5)
    print(f"   TP TransformerEncoder on {dp}x{tp} mesh == replicated")
else:
    print(f"   skipped ({n_dev} devices; run with the 8-device CPU mesh)")

# --- 3. Chinese lattice segmentation ---------------------------------------
print("== 3. Chinese lattice Viterbi vs greedy longest-match")

from deeplearning4j_tpu.nlp.dictionary_tokenizer import (
    derive_dictionary_from_tagged_corpus,
    greedy_segment,
    viterbi_segment,
)

zh_corpus = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures", "zh_tagged_corpus.tsv")
zh = derive_dictionary_from_tagged_corpus(zh_corpus)
trap = "他研究生命的起源。"
print("   viterbi:", "|".join(e.surface for e in viterbi_segment(trap, zh)))
print("   greedy :", "|".join(greedy_segment(trap, zh)),
      "   <- falls into the 研究生 trap")

# --- 4. typed unknown words ------------------------------------------------
print("== 4. kuromoji-style unknown-word handling")

ja_corpus = os.path.join(os.path.dirname(zh_corpus),
                         "ja_tagged_corpus.tsv")
ja = derive_dictionary_from_tagged_corpus(ja_corpus)
for e in viterbi_segment("私はテレビゲームとABC123を学ぶ", ja):
    tag = f"  ({e.features[1]})" if e.features[:1] == ("UNK",) else ""
    print(f"   {e.surface}{tag}")

print("round-5 tour complete")
