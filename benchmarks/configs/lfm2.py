"""The LFM2-MoE family (LiquidAI LFM2-8B-A1B) as this repository ships it
(`zoo.models.HybridConvMoELM`), built the way a user builds it, and its plain
reference.

**What the yardstick holds of the program** (the seam), beyond what
`gpt2.py` lists: `zoo.models.HybridConvMoELM(...).conf()`, the vertex and
parameter names the reference reads (`embed`, `block<l>-norm1|conv|att|
norm2|ff1|ff3|ff2|moe`, `norm_f`, `out`), and `net.states[<expert layer>]`
with `expert_rows` and `rows_elsewhere` (`counters()`), and for the step
check `net.updater_states[<vertex>][<name>]["m"]` (Adam's first moment).
`Model` takes `make_batch`, `resident`, `devices_holding_params` and
`compiled_step_text` (the one private call) from `gpt2.Model`.

**The layers, as the reference computes them** (config keys in brackets),
for layer `l` with input `x` `[T, hidden_size]`:

    h = x + Op_l(RMSNorm(x))            y = h + FFN_l(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2, -1) + norm_eps) * w

- `conv` [conv_L_cache, conv_bias false]: `[B, C, X] = split3(x W_in)`;
  `u = B * X`; `v_t = sum_j k[:, j] * u_{t-(L-1)+j}` (depthwise, causal,
  zeros to the left); `Op = (C * v) W_out`.
- `full_attention` [num_attention_heads, num_key_value_heads, rope_theta]:
  q, k, v without biases; an RMSNorm over each head of q and of k; rotary
  positions on the whole head, rotate-half; causal softmax(q k^T /
  sqrt(Dh)) v, each K/V head serving its group of query heads; `W_o`.
- dense feed-forward, layers `< num_dense_layers` [intermediate_size]:
  `W2(silu(W1 x) * W3 x)`.
- expert feed-forward [num_experts, num_experts_per_tok,
  moe_intermediate_size, use_expert_bias, norm_topk_prob,
  routed_scaling_factor]: `s = sigmoid(x W_r)`; the top k of `s + b` are
  selected; weights `s` on the selected over their sum (+1e-6), times the
  scaling; the sum of the weighted experts **held here**
  (`experts_held_first`, `num_experts_held`): what the absent ones would
  add is left out, as in the program.
- embedding, a last RMSNorm, an untied head; the last position's target
  repeats its own token (`lm_labels`).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.configs import gpt2

QUERY_BLOCK = 256       # rows of the score matrix the reference holds at once


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


# ------------------------------------------------------------ what it costs
def matmul_params(config: dict) -> float:
    """Parameters that a token is multiplied by, the expert layers in
    expectation: of the `num_experts_per_tok` experts a token picks,
    `num_experts_held / num_experts` are held here if the router spreads
    its picks evenly. The embedding is a lookup and the norms are not matrix
    multiplications."""
    d, dh = config["hidden_size"], _head_dim(config)
    operator = {
        "conv": 3 * d * d + config["conv_L_cache"] * d + d * d,
        "full_attention": 2 * d * config["num_attention_heads"] * dh
        + 2 * d * config["num_key_value_heads"] * dh}
    dense = 3 * d * config["intermediate_size"]
    picks_held = (config["num_experts_per_tok"] * config["num_experts_held"]
                  / config["num_experts"])
    experts = (d * config["num_experts"]
               + picks_held * 3 * d * config["moe_intermediate_size"])
    total = d * config["vocab_size"]
    for layer, kind in enumerate(config["layer_types"]):
        total += operator[kind] + (dense if layer < config["num_dense_layers"]
                                   else experts)
    return total


def required_flops_per_item(config: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one token of a
    sequence of `seq_len`: 6 per multiplied parameter (`matmul_params`: an
    expectation where experts are concerned; the exact rows are in
    `counters()`), and causal attention at half of the full product in each
    attention layer, `6 * hidden_size * seq_len`. Nothing recomputed is
    counted."""
    attention_layers = sum(k == "full_attention"
                           for k in config["layer_types"])
    return (6.0 * matmul_params(config) + 6.0 * attention_layers
            * config["hidden_size"] * traffic["seq_len"])


# ------------------------------------------------------------ the reference
def reference_loss(params, tokens, *, config: dict, router_dtype=None,
                   product_dtype=None):
    """Mean next-token cross-entropy of the model in plain float32
    `jax.numpy`, independent of `deeplearning4j_tpu`: it takes the
    parameters by their names and the sizes from `config`. Attention is
    computed `QUERY_BLOCK` query rows at a time, so that a long sequence
    never holds its whole score matrix. On a TPU a float32 matmul runs in
    lower precision unless told otherwise, so the caller wraps this in
    `jax.default_matmul_precision("highest")`.

    `router_dtype` and `product_dtype` are for one reading only (PERF.md §6,
    what a lower precision does to this number): the router's product in
    that dtype, and both operands of every other product rounded to that
    dtype first."""
    import jax
    import jax.numpy as jnp

    eps, dh = config["norm_eps"], _head_dim(config)
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    taps, k_picks = config["conv_L_cache"], config["num_experts_per_tok"]
    first, held = config["experts_held_first"], config["num_experts_held"]

    def f32(name):
        return {k: jnp.asarray(v, jnp.float32)
                for k, v in params[name].items()}

    def mm(a, b):
        if product_dtype is not None:
            a = a.astype(product_dtype).astype(jnp.float32)
            b = b.astype(product_dtype).astype(jnp.float32)
        return a @ b

    def rms_norm(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def short_conv(x, p):
        gate_b, gate_c, inner = jnp.split(mm(x, p["Win"]), 3, axis=-1)
        u = gate_b * inner
        padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        v = sum(p["K"][:, j] * padded[j:j + len(u)] for j in range(taps))
        return mm(gate_c * v, p["Wout"])

    def rotate(x, positions):            # x [heads, T, dh]
        inv_freq = config["rope_theta"] ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        angles = positions[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)
        sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)
        turned = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
        return x * cos + turned * sin

    def attention(x, p):
        t = len(x)
        positions = jnp.arange(t, dtype=jnp.float32)
        q = mm(x, p["Wq"]).reshape(t, h, dh).transpose(1, 0, 2)
        # columns of Wkv are head-major: [kv head, (k, v), head_dim]
        kv = mm(x, p["Wkv"]).reshape(t, hkv, 2, dh)
        k, v = kv[:, :, 0].transpose(1, 0, 2), kv[:, :, 1].transpose(1, 0, 2)
        q = rotate(rms_norm(q, p["q_norm"]), positions)
        k = rotate(rms_norm(k, p["k_norm"]), positions)
        # each K/V head serves h / hkv query heads in a row
        k, v = jnp.repeat(k, h // hkv, 0), jnp.repeat(v, h // hkv, 0)
        block = min(QUERY_BLOCK, t)

        def rows(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
            scores = jnp.einsum("hqd,hkd->hqk", q_rows, k) / np.sqrt(dh)
            seen = (jnp.arange(t)[None, :]
                    <= (start + jnp.arange(block))[:, None])
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,hkd->hqd", weights, v)

        # checkpoints here and below: where a gradient of this function is
        # taken (`reference_moment_change`), the backward pass computes a block, an
        # expert or a layer again and keeps none of their insides
        mixed = jax.lax.map(jax.checkpoint(rows),
                            jnp.arange(0, t, block))    # [blocks,h,b,dh]
        mixed = mixed.transpose(0, 2, 1, 3).reshape(t, h * dh)
        return mm(mixed, p["Wo"])

    def gated(x, w1, w3, w2):
        return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)

    def experts(x, p):
        rd = router_dtype or jnp.float32
        scores = jax.nn.sigmoid((x.astype(rd) @ p["Wg"].astype(rd))
                                .astype(jnp.float32))
        biased = scores + p["expert_bias"] if "expert_bias" in p else scores
        _, picked = jax.lax.top_k(biased, k_picks)
        weights = jnp.take_along_axis(scores, picked, -1)
        if config["norm_topk_prob"]:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
        weights = weights * config["routed_scaling_factor"]
        out = jnp.zeros_like(x)
        for e in range(held):
            weight_e = jnp.sum(jnp.where(picked == first + e, weights, 0.0),
                               -1, keepdims=True)
            out += weight_e * jax.checkpoint(gated)(x, p["W1"][e], p["W3"][e],
                                                    p["W2"][e])
        return out

    def block(layer, kind, x):
        pre = f"block{layer}-"
        normed = rms_norm(x, f32(pre + "norm1")["gamma"])
        x = x + (short_conv(normed, f32(pre + "conv")) if kind == "conv"
                 else attention(normed, f32(pre + "att")))
        normed = rms_norm(x, f32(pre + "norm2")["gamma"])
        if layer < config["num_dense_layers"]:
            return x + gated(normed, f32(pre + "ff1")["W"],
                             f32(pre + "ff3")["W"], f32(pre + "ff2")["W"])
        return x + experts(normed, f32(pre + "moe"))

    def one_sequence(ids):
        x = f32("embed")["W"][ids]
        for layer, kind in enumerate(config["layer_types"]):
            x = jax.checkpoint(functools.partial(block, layer, kind))(x)
        logits = mm(rms_norm(x, f32("norm_f")["gamma"]), f32("out")["W"])
        targets = jnp.concatenate([ids[1:], ids[-1:]])
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    # one sequence at a time: [T, vocab] float32 logits are the largest thing
    return jnp.mean(jax.lax.map(one_sequence, jnp.asarray(tokens)))


#: the decay of Adam's first moment as `HybridConvMoELM.conf()` sets it
#: (`Adam(3e-4)`: the default)
BETA1 = 0.9
#: the step is compared on the parameters smaller than this (norm weights,
#: the depthwise kernels, the routers): each one's gradient passes through
#: every layer behind it and before it, and their copies fit beside the step
SMALL_PARAMETER = 1 << 20


def reference_moment_change(params, first_moments, tokens, *, config: dict,
                            **lower):
    """What one step of Adam on `reference_loss` changes the first moments
    of the small parameters by: `{(vertex, name): change}` for those of
    `first_moments`, which holds them before the step. The new moment is
    `BETA1 * m + (1 - BETA1) * g` with the reference's float32 gradient `g`,
    so the change is linear in the gradient: nothing of Adam's division,
    which turns a small gradient into its sign, enters."""
    import jax

    def loss(small):
        merged = {v: dict(owned) for v, owned in params.items()}
        for (vertex, name), value in small.items():
            merged[vertex][name] = value
        return reference_loss(merged, tokens, config=config, **lower)

    grads = jax.grad(loss)({key: params[key[0]][key[1]]
                            for key in first_moments})
    return {key: (1 - BETA1) * (g - first_moments[key])
            for key, g in grads.items()}


def relative_difference(got: dict, want: dict) -> float:
    """`|got - want| / |want|` over all the entries together: 0 where they
    agree, 1 where `got` is no change at all."""
    diff = sum(float(np.sum((np.asarray(got[k], np.float64)
                             - np.asarray(want[k], np.float64)) ** 2))
               for k in want)
    norm = sum(float(np.sum(np.asarray(want[k], np.float64) ** 2))
               for k in want)
    return float(np.sqrt(diff / norm))


# ---------------------------------------------------------------- the model
def network_conf(config: dict, seed: int):
    """The zoo's configuration at the file's sizes, as a user writes it."""
    from deeplearning4j_tpu.zoo.models import HybridConvMoELM

    if len(config["layer_types"]) != config["num_layers"]:
        raise ValueError("layer_types names another depth than num_layers")
    conf = HybridConvMoELM(
        vocab_size=config["vocab_size"],
        max_length=config["max_position_embeddings"],
        layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        experts_held=(config["experts_held_first"],
                      config["num_experts_held"]),
        use_expert_bias=config["use_expert_bias"],
        norm_topk=config["norm_topk_prob"],
        routed_scaling=config["routed_scaling_factor"],
        conv_kernel=config["conv_L_cache"], rope_theta=config["rope_theta"],
        norm_eps=config["norm_eps"], seed=seed).conf()
    conf.global_conf.compute_dtype = config["compute_dtype"]
    return conf


class Model(gpt2.Model):
    """One configuration of the family, built on `devices` from `seed`;
    batches, placement and the lowered step as the GPT-2 family has them."""

    def __init__(self, config: dict, seed: int, devices):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        self.config = config
        self.devices = list(devices)
        self.net = ComputationGraph(network_conf(config, seed)).init()
        self.mesh = None

    def score(self, tokens: np.ndarray) -> float:
        """`net.score` a sequence at a time (they are of one length, so the
        mean of their means is the mean): two sequences' float32 logits at
        once are 1.07 GB beside the step's reserved memory."""
        return float(np.mean([self.net.score(self.make_batch(tokens[i:i + 1]))
                              for i in range(len(tokens))]))

    def reference(self, tokens: np.ndarray, **lower) -> float:
        """`reference_loss` on the weights the net holds now, where they
        are; `lower` for the reading of a lower precision (see there)."""
        import jax

        fn = jax.jit(functools.partial(reference_loss, config=self.config,
                                       **lower))
        with jax.default_matmul_precision("highest"):
            return float(fn(self.net.params, tokens))

    def small_parameters(self) -> list:
        """`(vertex, name)` of the parameters the step is compared on; the
        expert bias takes no gradient and is left out."""
        return [(vertex, name) for vertex, owned in self.net.params.items()
                for name, value in owned.items()
                if value.size < SMALL_PARAMETER and name != "expert_bias"]

    def first_moments(self) -> dict:
        import jax

        return jax.device_get({(v, n): self.net.updater_states[v][n]["m"]
                               for v, n in self.small_parameters()})

    def reference_moment_change(self, tokens: np.ndarray, **lower) -> dict:
        """`reference_moment_change` from the state the net holds now."""
        import jax

        fn = jax.jit(functools.partial(reference_moment_change,
                                       config=self.config, **lower))
        with jax.default_matmul_precision("highest"):
            return jax.device_get(fn(self.net.params, self.first_moments(),
                                     tokens))

    def step_change_error(self, ds) -> float:
        """One more step of `fit()` on `ds`, the step the window timed, set
        against the reference's step from the same parameters, moments and
        tokens: `relative_difference` of the change of the small
        parameters' first moments."""
        want = self.reference_moment_change(np.asarray(ds.features))
        before = self.first_moments()
        self.net.fit(ds)
        after = self.first_moments()
        return relative_difference({k: after[k] - before[k] for k in want},
                                   want)

    def counters(self) -> dict:
        """From the last step, by expert layer: `expert_rows` (the pairs
        each held expert computed) and `rows_elsewhere` (the pairs whose
        expert is not held). Exact."""
        found = {name: state for name, state in self.net.states.items()
                 if "expert_rows" in state}
        return {"expert_rows": {n: np.asarray(s["expert_rows"]).tolist()
                                for n, s in found.items()},
                "rows_elsewhere": {n: int(s["rows_elsewhere"])
                                   for n, s in found.items()}}
