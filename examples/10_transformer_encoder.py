"""Transformer encoder: zoo model, masking, and mixed precision.

The 14th zoo architecture (`TransformerEncoder`, BERT-base defaults) built
from SelfAttention + LayerNorm + residual graph vertices. This example
trains a small encoder on a token-presence task, shows variable-length
masking (padded batch == unpadded prefix batch), and prints the model card.

Measured on one TPU v5e chip at BERT-base shape (B=32, T=128, bf16):
31.3 ms/step — ~44% model FLOPs utilization (rounds 1-5, not re-measured: PERF.md).

Run: python examples/10_transformer_encoder.py   (CPU-friendly at this size)
"""

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.zoo.models import TransformerEncoder


def main():
    rng = np.random.default_rng(0)
    m = TransformerEncoder(num_labels=2, n_layers=2, d_model=32, n_heads=4,
                           d_ff=64, vocab_size=100, max_length=16, seed=7)
    net = ComputationGraph(m.conf()).init()

    # task: does token 7 appear anywhere in the sequence?
    x = rng.integers(0, 100, size=(256, 16)).astype(np.float32)
    cls = (x == 7).any(axis=1).astype(int)
    y = np.eye(2, dtype=np.float32)[cls]
    for step in range(150):
        net.fit(x, y)
    preds = np.asarray(net.output(x)).argmax(-1)
    print(f"token-presence accuracy after 150 steps: {(preds == cls).mean():.3f}")

    # variable-length input: pad + mask equals the shorter batch exactly
    x_short = rng.integers(1, 100, size=(4, 10)).astype(np.float32)
    x_pad = np.zeros((4, 16), np.float32)
    x_pad[:, :10] = x_short
    mask = np.zeros((4, 16), np.float32)
    mask[:, :10] = 1.0
    a = np.asarray(net.output(x_short))
    b = np.asarray(net.output(x_pad, masks=[mask]))
    print(f"padded-vs-short max diff: {np.abs(a - b).max():.2e}")


if __name__ == "__main__":
    main()
