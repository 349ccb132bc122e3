"""Token ids with a planted next-token rule, from a seed.

Copied from `chip_smoke.py:PlantedLMBatches`: a small alphabet of ids
spread over the whole vocabulary, among them 30521 and `vocab - 1`, which
bfloat16 cannot hold (a float id would be rounded on the way in), each
always followed by the same other one. A model that trains learns the rule
within tens of steps, so a falling loss is a check that the step trains.
Ids are int32 and are made for the whole run at once, in set-up.
"""

from __future__ import annotations

import numpy as np

ALPHABET = 64


class PlantedRule:
    def __init__(self, vocab: int, seed: int):
        rng = np.random.default_rng(seed)
        must = [i for i in (30521, vocab - 1) if 0 <= i < vocab]
        drawn = rng.choice(vocab, size=min(vocab, ALPHABET) - len(must),
                           replace=False)
        self.alphabet = np.unique(np.concatenate([must, drawn]))
        self.successor = rng.permutation(len(self.alphabet))
        self.vocab = vocab

    def sequences(self, n: int, length: int, seed: int) -> np.ndarray:
        """`[n, length]` int32 ids; every id is followed by its successor."""
        rng = np.random.default_rng(seed)
        idx = np.empty((n, length), np.int64)
        idx[:, 0] = rng.integers(0, len(self.alphabet), size=n)
        for t in range(1, length):
            idx[:, t] = self.successor[idx[:, t - 1]]
        return self.alphabet[idx].astype(np.int32)

    def follows_rule(self, tokens: np.ndarray) -> bool:
        pos = np.searchsorted(self.alphabet, tokens)
        if not np.array_equal(self.alphabet[np.minimum(
                pos, len(self.alphabet) - 1)], tokens):
            return False
        return bool(np.array_equal(self.successor[pos[:, :-1]], pos[:, 1:]))
