"""BERT-base at real scale THROUGH the Keras import path (BASELINE
``configs[2]``), benched against the framework-native zoo
``TransformerEncoder`` — proving import adds no graph-quality tax.

Two stages (run in separate processes; Keras/TF must not share the TPU
process):

  make  — build a genuine BERT-base (12L/768/12H/3072, vocab 30522,
          T=128) in the installed Keras as a two-input functional model
          (token ids + position ids), compile, save h5 (~0.5 GB).
  bench — import the h5, bf16 compute, train B=32/T=128 on the TPU with
          PROFILED device time; then the zoo TransformerEncoder with the
          same shapes in the same session (A/B pair). Done criterion
          (round-3 verdict): imported step within 10% of the zoo step.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=. python tools/r4_bert_import_bench.py make
  PYTHONPATH=.:tools python tools/r4_bert_import_bench.py bench
Writes R4_BERT_IMPORT_BENCH.json.
"""

import json
import os
import sys

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

H5 = os.environ.get("DL4J_TPU_BERT_H5", "/tmp/bert_base_import.h5")
T, V, D, NH, FF, L = 128, 30522, 768, 12, 3072, 12
BATCH = 32


def make():
    import keras
    from keras import layers as kl

    tok = kl.Input((T,), dtype="int32", name="tokens")
    pos = kl.Input((T,), dtype="int32", name="positions")
    e = kl.Embedding(V, D, name="tok_emb")(tok)
    p = kl.Embedding(T, D, name="pos_emb")(pos)
    x = kl.Add(name="embed_add")([e, p])
    for i in range(L):
        att = kl.MultiHeadAttention(num_heads=NH, key_dim=D // NH,
                                    name=f"mha_{i}")(x, x)
        x = kl.LayerNormalization(name=f"ln1_{i}")(
            kl.Add(name=f"add1_{i}")([x, att]))
        ff = kl.Dense(FF, activation="gelu", name=f"ff1_{i}")(x)
        ff = kl.Dense(D, name=f"ff2_{i}")(ff)
        x = kl.LayerNormalization(name=f"ln2_{i}")(
            kl.Add(name=f"add2_{i}")([x, ff]))
    g = kl.GlobalAveragePooling1D(name="pool")(x)
    out = kl.Dense(2, activation="softmax", name="cls")(g)
    m = keras.Model([tok, pos], out)
    m.compile(loss="categorical_crossentropy", optimizer="adam")
    m.save(H5)
    print("params:", m.count_params(), "->", H5,
          f"{os.path.getsize(H5) / 1e9:.2f} GB", flush=True)


def profiled_ms_per_step(fit_once, log_dir, warmup=3, steps=4):
    import shutil

    import jax

    from tpu_perf_session import parse_xplane

    for _ in range(warmup):
        fit_once()
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    try:
        for _ in range(steps):
            fit_once()
    finally:
        jax.profiler.stop_trace()
    times = parse_xplane(log_dir)
    return 1e3 * sum(t for t, _ in times.values()) / steps


def bench():
    import jax

    from deeplearning4j_tpu.modelimport.keras.importer import KerasModelImport

    print("backend:", jax.default_backend(), flush=True)
    results = {}
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, size=(BATCH, T)).astype(np.float32)
    poss = np.tile(np.arange(T, dtype=np.float32), (BATCH, 1))
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, BATCH)]

    net = KerasModelImport.import_keras_model_and_weights(H5)
    net.conf.global_conf.compute_dtype = "bfloat16"
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    mds = MultiDataSet([toks, poss], [y])

    def fit_imported():
        net.fit(mds)
        return net.score_

    ms = profiled_ms_per_step(fit_imported, "/tmp/r4_bert_imported")
    results["imported_bert_base"] = {
        "device_ms_per_step": ms,
        "tokens_per_s": BATCH * T / ms * 1e3,
    }
    print(f"imported BERT-base: {ms:.2f} ms/step device "
          f"({BATCH * T / ms * 1e3:.0f} tok/s)", flush=True)
    del net

    # A/B: the framework-native zoo encoder, same shapes, same session
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.models import TransformerEncoder

    zconf = TransformerEncoder(num_labels=2, vocab_size=V, max_length=T).conf()
    zconf.global_conf.compute_dtype = "bfloat16"
    znet = ComputationGraph(zconf)
    znet.init()

    def fit_zoo():
        znet.fit(toks, y)
        return znet.score_

    ms_z = profiled_ms_per_step(fit_zoo, "/tmp/r4_bert_zoo")
    results["zoo_transformer_encoder"] = {
        "device_ms_per_step": ms_z,
        "tokens_per_s": BATCH * T / ms_z * 1e3,
    }
    print(f"zoo encoder:        {ms_z:.2f} ms/step device "
          f"({BATCH * T / ms_z * 1e3:.0f} tok/s)", flush=True)
    results["import_tax_ratio"] = ms / ms_z

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "R4_BERT_IMPORT_BENCH.json")
    with open(out, "w") as fh:
        json.dump(results, fh, indent=1)
    print("wrote", out, flush=True)


if __name__ == "__main__":
    make() if sys.argv[1] == "make" else bench()
