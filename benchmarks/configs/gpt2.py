"""The GPT-2 family as this repository ships it (`zoo.models.TransformerLM`),
built the way a user builds it, and its plain reference.

**What the yardstick holds of the program** (the seam). The benchmark is
frozen and the program is not, so this file reaches the program only through
names a user reaches:

- `zoo.models.TransformerLM(...).conf()`, `conf.global_conf.compute_dtype`
- `nn.graph.ComputationGraph(conf).init()`
- `zoo.models.lm_labels(tokens, V)`, `datasets.dataset.DataSet`
- `net.fit(iterator, epochs=1)` (in the traffic kind), `net.score(ds)`
- `net.params` (a dict keyed by vertex and parameter name), `net.listeners`
- `parallel.make_mesh`, `parallel.sharding.shard_model_with_rules`

The one private call is `compiled_step_text`, which lowers the train step
to count kernels and collectives, in a traced run only.

**Departures of the zoo model from the published GPT-2**, which the
reference follows, because it has to compute what the system computes: the
output head is not tied to the embedding, GELU is the exact (erf) form and
not the tanh approximation, LayerNorm's epsilon is 1e-3 (the layer's
default; `TransformerLM` has no argument for it), weights are Xavier, and
the last position's target repeats its own token (`lm_labels`).
"""

from __future__ import annotations

import functools

import numpy as np


# ------------------------------------------------------------ what it costs
def matmul_params(config: dict) -> int:
    """Parameters that a token is multiplied by: per block 3d^2 (QKV) + d^2
    (output projection) + 2*d*d_ff, and the d*V head. The embedding is a
    lookup, and biases and LayerNorm are not matrix multiplications."""
    d, ff = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (4 * d * d + 2 * d * ff)
            + d * config["vocab_size"])


def required_flops_per_item(config: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one token of a
    sequence of `seq_len`: 6 per multiplied parameter, and causal attention
    at half of the full 12*L*d*T (QK^T and PV, 2 operations a
    multiply-add, forward plus twice that backward). Nothing recomputed is
    counted."""
    attention = (6 * config["n_layer"] * config["n_embd"]
                 * traffic["seq_len"])
    return 6.0 * matmul_params(config) + attention


# ------------------------------------------------------------ the reference
def reference_loss(params, tokens, *, n_head: int, eps: float):
    """Mean next-token cross-entropy of a pre-LN GPT-2 decoder in plain
    float32 `jax.numpy`, independent of `deeplearning4j_tpu`: it takes the
    parameters by their names and nothing else. On a TPU a float32 matmul
    runs in lower precision unless told otherwise, so the caller wraps this
    in `jax.default_matmul_precision("highest")`."""
    import jax
    import jax.numpy as jnp

    def layer_norm(x, p):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]

    def f32(name):
        return {k: jnp.asarray(v, jnp.float32) for k, v in params[name].items()}

    n, t = tokens.shape
    x = f32("embed")["W"][tokens] + f32("pos")["P"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    block = 0
    while f"block{block}-att" in params:
        att, pre = f32(f"block{block}-att"), f"block{block}-"
        h = layer_norm(x, f32(pre + "ln1"))
        # columns of Wqkv are head-major: [head, (q, k, v), head_dim]
        qkv = (h @ att["Wqkv"] + att["bqkv"]).reshape(n, t, n_head, 3, -1)
        q, k, v = (qkv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(3))
        scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / np.sqrt(q.shape[-1])
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        mixed = jnp.einsum("nhqk,nhkd->nhqd", weights, v)
        mixed = mixed.transpose(0, 2, 1, 3).reshape(n, t, -1)
        x = x + mixed @ att["Wo"] + att["bo"]
        ff1, ff2 = f32(pre + "ff1"), f32(pre + "ff2")
        h = layer_norm(x, f32(pre + "ln2"))
        h = jax.nn.gelu(h @ ff1["W"] + ff1["b"], approximate=False)
        x = x + h @ ff2["W"] + ff2["b"]
        block += 1
    out = f32("out")
    logits = layer_norm(x, f32("ln_f")) @ out["W"] + out["b"]
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], 1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


# ---------------------------------------------------------------- the model
def network_conf(config: dict, seed: int):
    """The zoo's configuration at the file's sizes, as a user writes it."""
    from deeplearning4j_tpu.zoo.models import TransformerLM

    conf = TransformerLM(vocab_size=config["vocab_size"],
                         max_length=config["n_positions"],
                         n_layers=config["n_layer"],
                         d_model=config["n_embd"],
                         n_heads=config["n_head"],
                         d_ff=config["n_inner"], seed=seed).conf()
    conf.global_conf.compute_dtype = config["compute_dtype"]
    return conf


class Model:
    """One configuration of the family, built on `devices` from `seed`."""

    def __init__(self, config: dict, seed: int, devices):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        self.config = config
        self.devices = list(devices)
        self.net = ComputationGraph(network_conf(config, seed)).init()
        self.mesh = None
        axes = config["deployment"].get("mesh")
        if axes:
            from deeplearning4j_tpu.parallel import make_mesh
            from deeplearning4j_tpu.parallel.sharding import (
                shard_model_with_rules)
            self.mesh = make_mesh(dict(axes), self.devices)
            shard_model_with_rules(self.net, self.mesh)

    def make_batch(self, tokens: np.ndarray):
        """Host arrays in, the `DataSet` a user hands to `fit()` out: int32
        ids and the labels `lm_labels` builds for them."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.zoo.models import lm_labels

        return DataSet(tokens, lm_labels(tokens, self.config["vocab_size"]))

    def resident(self, ds):
        """The same batch held on the device(s): split over the mesh's
        `data` axis where there is a mesh, as `fit()` would place it."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        where = (self.devices[0] if self.mesh is None
                 else NamedSharding(self.mesh, PartitionSpec("data")))
        ds.features = jax.device_put(ds.features, where)
        ds.labels = jax.device_put(ds.labels, where)
        return ds

    def score(self, tokens: np.ndarray) -> float:
        return float(self.net.score(self.make_batch(tokens)))

    def reference(self, tokens: np.ndarray) -> float:
        """`reference_loss` on the weights the net holds now, where they
        are. On a mesh the compiler partitions the plain function over the
        shards: after the window a chip of the 2x2 holds 13.9 of its 16.9 GB
        (the step's temporaries stay reserved while its program is loaded),
        so the 3.4 GB of gathered float32 weights would not fit on one."""
        import jax

        fn = jax.jit(functools.partial(
            reference_loss, n_head=self.config["n_head"],
            eps=self.config["layer_norm_epsilon"]))
        with jax.default_matmul_precision("highest"):
            return float(fn(self.net.params, tokens))

    def devices_holding_params(self) -> int:
        import jax

        return len({d.id for leaf in jax.tree_util.tree_leaves(self.net.params)
                    for d in leaf.sharding.device_set})

    def compiled_step_text(self, ds) -> str:
        """HLO of the train step as the compiler left it, for `ds` as
        `fit()` feeds it. The jitted step has been compiled by then, so this
        is served from the compile cache."""
        import jax.numpy as jnp

        net = self.net
        it, ep, rng = net._device_tick()
        lowered = net._get_train_step().lower(
            net.params, net.states, net.updater_states, it, ep,
            {"tokens": jnp.asarray(ds.features)}, [jnp.asarray(ds.labels)],
            None, None, rng)
        return lowered.compile().as_text()
