"""Causal language model: training, KV-cached decoding, device-side sampling.

The decoder-side twin of example 10: a GPT-style `TransformerLM` (causal
self-attention with a fixed-capacity KV cache riding the same recurrent-carry
protocol as the LSTMs) trained on a next-token task, then sampled three ways:

1. `generate`      — host loop over `rnn_time_step` (one jitted step/token);
2. `generate_on_device` — the WHOLE decode compiled to one executable
   (prefill + `lax.scan` + on-device sampling): the per-token host round
   trip disappears. No timing of either loop has been taken on this
   installation (PERF.md);
3. truncated BPTT — the same model trained in chunks with carried caches
   (Transformer-XL-style), via the graph's `t_bptt_length`.

Also shows SameDiff-style control flow is unrelated to decoding: the KV
cache makes stepwise decode O(T·cache) instead of O(T^2) re-forwards.

Run: python examples/11_transformer_lm_generation.py   (CPU-friendly)
"""

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.zoo.models import (
    TransformerLM,
    generate,
    generate_on_device,
    lm_labels,
)

VOCAB = 11


def cycle(rng, n, t, step=3):
    start = rng.integers(0, VOCAB, size=(n, 1))
    return ((start + step * np.arange(t)[None, :]) % VOCAB).astype(np.float32)


def main():
    rng = np.random.default_rng(0)

    # -- train a tiny decoder on the +3 successor rule ----------------------
    m = TransformerLM(vocab_size=VOCAB, max_length=32, n_layers=2,
                      d_model=32, n_heads=4, d_ff=64, seed=3)
    net = ComputationGraph(m.conf()).init()
    x = cycle(rng, 64, 32)
    y = lm_labels(x, VOCAB)                  # int32 class ids [N,T]
    lmask = np.ones(x.shape[:2], np.float32)
    lmask[:, -1] = 0.0                       # final step has no next token
    ds = DataSet(x, y, labels_mask=lmask)
    s0 = net.score(ds)
    for _ in range(150):
        net.fit(ds)
    print(f"LM loss: {s0:.3f} -> {net.score_:.3f} after 150 steps")

    # -- decode: host loop vs single-dispatch device loop -------------------
    prompt = cycle(np.random.default_rng(1), 2, 6)
    host = generate(net, prompt, 8)                      # rnn_time_step loop
    dev = generate_on_device(net, prompt, 8)             # one lax.scan
    want = (prompt[:, -1:] + 3 * np.arange(1, 9)[None, :]) % VOCAB
    print(f"host loop continues the cycle:   {(host == want).mean():.2f}")
    print(f"device loop identical to host:   {(host == dev).all()}")
    sampled = generate_on_device(net, prompt, 8, temperature=0.8, seed=4)
    print(f"temperature sampling (device):   {sampled[0].tolist()}")

    # -- truncated BPTT over the DAG: chunked training, carried KV caches ---
    conf = TransformerLM(vocab_size=VOCAB, max_length=32, n_layers=1,
                         d_model=16, n_heads=2, d_ff=32, seed=5).conf()
    conf.backprop_type = "truncated_bptt"
    conf.tbptt_fwd_length = 8                # 4 chunks per 32-step sequence
    tb = ComputationGraph(conf).init()
    for _ in range(20):
        tb.fit(ds)
    print(f"TBPTT (4 chunks/batch): loss {tb.score_:.3f}, "
          f"iterations {tb.iteration} (one per chunk)")


if __name__ == "__main__":
    main()
