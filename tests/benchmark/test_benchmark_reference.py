"""The GPT-2 family's plain reference against the system, at a tiny size on
the CPU. On the chip the same comparison is made at the published widths,
outside the timed window, by every run of every cell."""

import functools

import jax
import numpy as np
import pytest

from benchmarks.harness import manifest
from benchmarks.harness.planted_tokens import PlantedRule


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), "gpt2s-resident-t2048",
                         rehearse=True)


def tokens_for(cell, n=3, seed=4):
    config = cell.config
    rng = np.random.default_rng(seed)
    planted = PlantedRule(config["vocab_size"], seed).sequences(
        n, config["n_positions"], seed)
    anywhere = rng.integers(0, config["vocab_size"], planted.shape,
                            dtype=np.int32)
    return np.concatenate([planted, anywhere])


# float32 on both sides and the same arithmetic: what is left is the order
# of sums. bfloat16 compute keeps 8 bits of mantissa in every activation,
# so a loss near ln(512) = 6.2 moves in its third digit.
@pytest.mark.parametrize("compute_dtype, rtol", [("float32", 2e-5),
                                                 ("bfloat16", 2e-2)])
def test_reference_loss_matches_net_score(cell, compute_dtype, rtol):
    config = dict(cell.config, compute_dtype=compute_dtype)
    model = cell.family.Model(config, seed=3, devices=jax.devices()[:1])
    tokens = tokens_for(cell)
    # weights away from their initial values, where every loss is ln(V)
    batch = model.make_batch(tokens)
    for _ in range(40):
        model.net.fit(batch)
    got, want = model.score(tokens), model.reference(tokens)
    assert np.isfinite(got) and np.isfinite(want)
    assert abs(want - np.log(config["vocab_size"])) > 0.05
    assert got == pytest.approx(want, rel=rtol)


def test_reference_notices_a_wrong_mask_and_a_wrong_head_order(cell):
    """It is a check only if a model that computes something else fails it:
    the same weights with attention that sees the future, or with Wqkv read
    as [q|k|v] blocks and not head-major, give another loss."""
    config = dict(cell.config, compute_dtype="float32")
    model = cell.family.Model(config, seed=3, devices=jax.devices()[:1])
    tokens = tokens_for(cell)
    batch = model.make_batch(tokens)
    for _ in range(40):
        model.net.fit(batch)
    want = model.reference(tokens)
    fn = functools.partial(cell.family.reference_loss,
                           n_head=config["n_head"],
                           eps=config["layer_norm_epsilon"])
    params = jax.tree_util.tree_map(np.asarray, model.net.params)

    reordered = {k: dict(v) for k, v in params.items()}
    w = reordered["block0-att"]["Wqkv"]
    h, dh = config["n_head"], config["n_embd"] // config["n_head"]
    reordered["block0-att"]["Wqkv"] = w.reshape(-1, h, 3, dh) \
        .transpose(0, 2, 1, 3).reshape(w.shape)
    assert abs(float(fn(reordered, tokens)) - want) > 1e-3

    reversed_tokens = tokens[:, ::-1].copy()
    assert abs(float(fn(params, reversed_tokens)) - want) > 1e-3


def test_sharded_model_scores_like_the_reference():
    cell = manifest.Cell(manifest.load(), "gpt2l-2x2-resident-t1024",
                         rehearse=True)
    model = cell.family.Model(cell.config, seed=5,
                              devices=jax.devices()[:4])
    assert model.devices_holding_params() == 4
    tokens = tokens_for(cell, n=2)
    placed = model.resident(model.make_batch(tokens))
    assert len(placed.labels.sharding.device_set) == 4
    model.net.fit(placed)
    assert model.score(tokens) == pytest.approx(model.reference(tokens),
                                                rel=2e-2)
