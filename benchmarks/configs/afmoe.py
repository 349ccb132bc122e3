"""The AFMoE family (arcee-ai Trinity-Mini) as this repository ships it
(`zoo.models.GatedWindowMoELM`), built the way a user builds it, and its
plain reference, written from the family's equations
(`transformers/models/afmoe/modeling_afmoe.py`) and not from the program.

**What the yardstick holds of the program** (the seam), beyond what
`gpt2.py` and `lfm2.py` list: `zoo.models.GatedWindowMoELM(...).conf()`, and
the vertex and parameter names the reference reads: `embed`,
`block<l>-norm1|norm1p|norm2|norm2p` (`gamma`), `block<l>-swa` (a
`sliding_attention` layer) or `block<l>-att` (a `full_attention` one) with
`Wq`, `Wkv`, `Wgate`, `q_norm`, `k_norm`, `Wo`, `block<l>-ff1|ff3|ff2` (`W`),
`block<l>-moe` (`Wg`, `expert_bias`, `W1`, `W3`, `W2`),
`block<l>-shared1|shared3|shared2` (`W`), `norm_f`, `out`. `Model` takes
`score`, `small_parameters`, `first_moments`, `step_change_error` and
`counters` from `lfm2.Model`, and `make_batch`, `resident`,
`devices_holding_params` and `compiled_step_text` from `gpt2.Model`; neither
file is edited.

**The model, as the reference computes it** (config keys in brackets); `x`
is `[T, hidden_size]`, `norm` an RMSNorm with a weight [rms_norm_eps]:

    h0 = embed[ids] * sqrt(hidden_size)                      [mup_enabled]
    h = h + norm1p(Attention_l(norm1(h)));  h = h + norm2p(FF_l(norm2(h)))
    logits = norm_f(h) @ W_out

- `Attention_l` [num_attention_heads, num_key_value_heads, head_dim]: q, k, v
  and a gate `g = x Wgate` without biases; an RMSNorm over each head of q and
  of k; in a `sliding_attention` layer rotary positions on the whole head
  [rope_theta], rotate-half, and a query sees keys `0 <= i - j <
  sliding_window`; in a `full_attention` layer no positions at all and
  `j <= i`; softmax(q k^T / sqrt(head_dim)) v, each K/V head serving its
  group of query heads; `(o * sigmoid(g)) Wo`.
- dense feed-forward, layers `< num_dense_layers` [intermediate_size]:
  `W2(silu(W1 x) * W3 x)`.
- expert feed-forward [num_experts, num_experts_per_tok,
  moe_intermediate_size, num_shared_experts, route_norm, route_scale]:
  `s = sigmoid(x Wg)` in float32; the top k of `s + expert_bias` are chosen;
  weights `s` on the chosen over their sum (+1e-20), times `route_scale`;
  the shared expert whole plus the weighted experts **held here**
  (`experts_held_first`, `num_experts_held`): what the absent ones would add
  is left out, as in the program.
- the last position's target repeats its own token (`lm_labels`).

**The expert bias.** The program draws it and nothing moves it (ROADMAP
R-M3a). `Model` sets it once, before the first step, to where the family's
balancing rule would have brought it at the seed's weights on the batch the
cell trains on (`balancing_bias`), so that every seed starts with the same
rows on the held experts: a step's time follows those rows.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.configs import lfm2
from benchmarks.configs.lfm2 import BETA1
from benchmarks.harness.window_costs import window_pairs

QUERY_BLOCK = 256       # rows of the score matrix the reference holds at once
#: `balancing_bias`: the sizes of its first and last step and how many it takes
BALANCE_FIRST_STEP, BALANCE_LAST_STEP, BALANCE_STEPS = 0.05, 1e-5, 256
#: what `reference_loss(leave_out=...)` can get wrong on purpose, one at a
#: time: the tests show that each part matters, and the cell's control
#: (`window`) that a program attending over everything is not `correct`
FAULTS = frozenset(("gate", "embedding_scale", "norm1", "norm1p", "norm2",
                    "norm2p", "full_layers_unrotated", "window"))


# ------------------------------------------------------------ what it costs
def matmul_params(config: dict) -> float:
    """Parameters that a token is multiplied by, the routed experts in
    expectation: of the `num_experts_per_tok` experts a token picks,
    `num_experts_held / num_experts` are held here if the router spreads
    its picks evenly; the shared expert whole. The embedding is a lookup
    and the norms are not matrix multiplications."""
    d = config["hidden_size"]
    inner = config["num_attention_heads"] * config["head_dim"]
    attention = (3 * d * inner                      # Wq, Wgate, Wo
                 + 2 * d * config["num_key_value_heads"] * config["head_dim"])
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    picks_held = (config["num_experts_per_tok"] * config["num_experts_held"]
                  / config["num_experts"])
    experts = (d * config["num_experts"]
               + (config["num_shared_experts"] + picks_held) * expert)
    dense_layers = min(config["num_dense_layers"], config["num_layers"])
    return (config["num_layers"] * attention + dense_layers * dense
            + (config["num_layers"] - dense_layers) * experts
            + d * config["vocab_size"])


def required_flops_per_item(config: dict, traffic: dict) -> float:
    """Operations the forward and backward passes need for one token of a
    sequence of `seq_len`: 6 per multiplied parameter (`matmul_params`) and,
    in each attention layer, QK^T and PV over the pairs its mask allows
    (`window_pairs`: the keys inside the window in a `sliding_attention`
    layer, not the causal half): 2 products of `2 * head_dim` operations a
    pair and head forward, twice that backward. Nothing recomputed is
    counted."""
    t = traffic["seq_len"]
    pairs = sum(window_pairs(t, config["sliding_window"]
                             if kind == "sliding_attention" else t)
                for kind in config["layer_types"])
    attention = (12.0 * config["head_dim"] * config["num_attention_heads"]
                 * pairs / t)
    return 6.0 * matmul_params(config) + attention


# ------------------------------------------------------------ the reference
def balancing_bias(scores, start, picks: int):
    """The bias at which the top `picks` of `scores + bias` send every expert
    the same number of rows, found as the family's training framework moves
    it: from `start`, `BALANCE_STEPS` times a step by the sign of each
    expert's load error, the steps shrinking from `BALANCE_FIRST_STEP` to
    `BALANCE_LAST_STEP`. `scores` is `[T, experts]` float32."""
    import jax
    import jax.numpy as jnp

    even = scores.shape[0] * picks / scores.shape[1]
    sizes = BALANCE_FIRST_STEP * (BALANCE_LAST_STEP / BALANCE_FIRST_STEP) ** (
        jnp.arange(BALANCE_STEPS) / (BALANCE_STEPS - 1))

    def move(bias, size):
        biased = scores + bias
        least_chosen = jax.lax.top_k(biased, picks)[0][:, -1:]
        load = jnp.sum(biased >= least_chosen, 0)
        return bias + size * jnp.sign(even - load), None

    return jax.lax.scan(move, start, sizes)[0]


def reference_loss(params, tokens, *, config: dict, router_dtype=None,
                   product_dtype=None, leave_out=()):
    """Mean next-token cross-entropy of the model in plain float32
    `jax.numpy`, independent of `deeplearning4j_tpu`: it takes the
    parameters by their names and the sizes from `config`. Attention is
    computed `QUERY_BLOCK` query rows at a time, so that a long sequence
    never holds its whole score matrix. On a TPU a float32 matmul runs in
    lower precision unless told otherwise, so the caller wraps this in
    `jax.default_matmul_precision("highest")`.

    `router_dtype` and `product_dtype` are for one reading only (what a
    lower precision does to this number): the router's product in that
    dtype, and both operands of every other product rounded to that dtype
    first. `leave_out` names `FAULTS`: the model with that part wrong."""
    return _loss_and_biases(params, tokens, config, router_dtype,
                            product_dtype, leave_out, balance=False)[0]


def balanced_expert_biases(params, tokens, *, config: dict) -> dict:
    """`{vertex: bias}` for every expert layer: the `balancing_bias` of the
    layer's scores on a sequence of `tokens`, each layer routed with its new
    bias before the next one is reached (the mean over the sequences where
    there are several). The model is the reference's, at whatever matmul
    precision the caller sets."""
    biases = _loss_and_biases(params, tokens, config, None, None, (),
                              balance=True)[1]
    return {vertex: bias.mean(0) for vertex, bias in biases.items()}


def _loss_and_biases(params, tokens, config, router_dtype, product_dtype,
                     leave_out, balance):
    """`reference_loss`, and the bias each expert layer routed with, by
    vertex and sequence: the stored one, or with `balance` the one
    `balancing_bias` moves it to."""
    import jax
    import jax.numpy as jnp

    unknown = set(leave_out) - FAULTS
    if unknown:
        raise ValueError(f"leave_out {sorted(unknown)} is none of "
                         f"{sorted(FAULTS)}")
    d, eps, dh = config["hidden_size"], config["rms_norm_eps"], config["head_dim"]
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    window, k_picks = config["sliding_window"], config["num_experts_per_tok"]
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

    def norm(name, x):
        if name.rpartition("-")[2] in leave_out:
            return x
        return rms_norm(x, f32(name)["gamma"])

    def rotate(x, positions):            # x [heads, T, dh]
        inv_freq = config["rope_theta"] ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
        angles = positions[:, None] * inv_freq[None, :]
        cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)
        sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)
        turned = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
        return x * cos + turned * sin

    def attention(x, p, sliding):
        t = len(x)
        q = mm(x, p["Wq"]).reshape(t, h, dh).transpose(1, 0, 2)
        # columns of Wkv are head-major: [kv head, (k, v), head_dim]
        kv = mm(x, p["Wkv"]).reshape(t, hkv, 2, dh)
        k, v = kv[:, :, 0].transpose(1, 0, 2), kv[:, :, 1].transpose(1, 0, 2)
        q, k = rms_norm(q, p["q_norm"]), rms_norm(k, p["k_norm"])
        if sliding or "full_layers_unrotated" in leave_out:
            positions = jnp.arange(t, dtype=jnp.float32)
            q, k = rotate(q, positions), rotate(k, positions)
        # query head i reads key-value head i // (h / hkv)
        k, v = jnp.repeat(k, h // hkv, 0), jnp.repeat(v, h // hkv, 0)
        block = min(QUERY_BLOCK, t)
        banded = sliding and "window" not in leave_out

        def rows(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
            scores = jnp.einsum("hqd,hkd->hqk", q_rows, k) / np.sqrt(dh)
            behind = ((start + jnp.arange(block))[:, None]
                      - jnp.arange(t)[None, :])            # i - j
            allowed = behind >= 0
            if banded:
                allowed = allowed & (behind < window)
            weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
            return jnp.einsum("hqk,hkd->hqd", weights, v)

        # checkpoints here and below: where a gradient of this function is
        # taken (`reference_moment_change`), the backward pass computes a
        # block, an expert or a layer again and keeps none of their insides
        mixed = jax.lax.map(jax.checkpoint(rows),
                            jnp.arange(0, t, block))    # [blocks,h,b,dh]
        mixed = mixed.transpose(0, 2, 1, 3).reshape(t, h * dh)
        if "gate" not in leave_out:
            mixed = mixed * jax.nn.sigmoid(mm(x, p["Wgate"]))
        return mm(mixed, p["Wo"])

    def gated(x, w1, w3, w2):
        return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)

    def experts(x, p, shared):
        rd = router_dtype or jnp.float32
        scores = jax.nn.sigmoid((x.astype(rd) @ p["Wg"].astype(rd))
                                .astype(jnp.float32))
        bias = p["expert_bias"]
        if balance:
            bias = balancing_bias(scores, bias, k_picks)
        _, chosen = jax.lax.top_k(scores + bias, k_picks)
        weights = jnp.take_along_axis(scores, chosen, -1)
        if config["route_norm"]:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        weights = weights * config["route_scale"]
        out = jax.checkpoint(gated)(x, *shared)
        for e in range(held):
            weight_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0),
                               -1, keepdims=True)
            out += weight_e * jax.checkpoint(gated)(x, p["W1"][e], p["W3"][e],
                                                    p["W2"][e])
        return out, bias

    def block(layer, kind, x):
        pre = f"block{layer}-"
        sliding = kind == "sliding_attention"
        mixed = attention(norm(pre + "norm1", x),
                          f32(pre + ("swa" if sliding else "att")), sliding)
        x = x + norm(pre + "norm1p", mixed)
        normed = norm(pre + "norm2", x)
        if layer < config["num_dense_layers"]:
            ff = gated(normed, f32(pre + "ff1")["W"], f32(pre + "ff3")["W"],
                       f32(pre + "ff2")["W"])
            biases = {}
        else:
            ff, bias = experts(normed, f32(pre + "moe"),
                               [f32(pre + f"shared{i}")["W"] for i in (1, 3, 2)])
            biases = {pre + "moe": bias}
        return x + norm(pre + "norm2p", ff), biases

    def one_sequence(ids):
        x = f32("embed")["W"][ids]
        if "embedding_scale" not in leave_out:
            x = x * np.sqrt(d)
        biases = {}
        for layer, kind in enumerate(config["layer_types"]):
            x, routed_with = jax.checkpoint(
                functools.partial(block, layer, kind))(x)
            biases.update(routed_with)
        logits = mm(rms_norm(x, f32("norm_f")["gamma"]), f32("out")["W"])
        targets = jnp.concatenate([ids[1:], ids[-1:]])
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked), biases

    # one sequence at a time: [T, vocab] float32 logits are the largest thing
    losses, biases = jax.lax.map(one_sequence, jnp.asarray(tokens))
    return jnp.mean(losses), biases


def reference_moment_change(params, first_moments, tokens, *, config: dict,
                            **lower):
    """What one step of Adam on `reference_loss` changes the first moments
    of the small parameters by, as `lfm2.reference_moment_change` has it
    for its own loss: `(1 - BETA1) * (g - m)` with the reference's float32
    gradient `g`, linear in the gradient."""
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


# ---------------------------------------------------------------- the model
def learning_rate(config: dict):
    """Adam's learning rate as the file states it: `learning_rate` at its
    peak, reached linearly over `lr_warmup_steps` steps (then a cosine down
    to a tenth of it at `lr_total_steps`)."""
    from deeplearning4j_tpu.nn.updaters import WarmupCosineSchedule

    return WarmupCosineSchedule(
        peak_value=config["learning_rate"],
        warmup_steps=config["lr_warmup_steps"],
        total_steps=config["lr_total_steps"],
        final_value=0.1 * config["learning_rate"])


def network_conf(config: dict, seed: int):
    """The zoo's configuration at the file's sizes, as a user writes it."""
    from deeplearning4j_tpu.zoo.models import GatedWindowMoELM

    if len(config["layer_types"]) != config["num_layers"]:
        raise ValueError("layer_types names another depth than num_layers")
    conf = GatedWindowMoELM(
        vocab_size=config["vocab_size"],
        max_length=config["max_position_embeddings"],
        layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"], d_ff=config["intermediate_size"],
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        experts_held=(config["experts_held_first"],
                      config["num_experts_held"]),
        sliding_window=config["sliding_window"],
        rope_theta=config["rope_theta"], route_norm=config["route_norm"],
        route_scale=config["route_scale"],
        scale_embedding=config["mup_enabled"],
        norm_eps=config["rms_norm_eps"], learning_rate=learning_rate(config),
        seed=seed).conf()
    conf.global_conf.compute_dtype = config["compute_dtype"]
    return conf


class Model(lfm2.Model):
    """One configuration of the family, built on `devices` from `seed`;
    the score, the small parameters, the step check and the experts'
    counters as the LFM2 family has them, against this family's
    reference."""

    def __init__(self, config: dict, seed: int, devices):
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        self.config = config
        self.devices = list(devices)
        self.net = ComputationGraph(network_conf(config, seed)).init()
        self.mesh = None
        self.biases_balanced = False

    def resident(self, ds):
        """`gpt2.Model.resident`; the first batch placed is the one the cell
        trains on, and before any step the expert biases are balanced on it
        (`balance_expert_biases`)."""
        ds = super().resident(ds)
        if not self.biases_balanced:
            self.biases_balanced = True
            self.balance_expert_biases(ds.features)
        return ds

    def balance_expert_biases(self, tokens) -> None:
        """Every expert layer's constant bias set to where the family's
        balancing rule would have brought it at these weights on these ids
        (`balanced_expert_biases`), so that each expert is sent `tokens x
        num_experts_per_tok / num_experts` rows at the first step whatever
        the seed. The draw the program makes leaves the rows that land on
        the held experts a factor of two apart from seed to seed, and a
        step's time follows them (PERF.md section 6, PR 36); a deployment's
        bias is what keeps them even."""
        import jax

        fn = jax.jit(functools.partial(balanced_expert_biases,
                                       config=self.config))
        for vertex, bias in jax.device_get(fn(self.net.params,
                                              tokens)).items():
            self.net.set_param(f"{vertex}_expert_bias", bias)

    def reference(self, tokens: np.ndarray, **lower) -> float:
        """`reference_loss` on the weights the net holds now, where they
        are; `lower` for the readings of a lower precision and of a fault
        (see there)."""
        import jax

        fn = jax.jit(functools.partial(reference_loss, config=self.config,
                                       **lower))
        with jax.default_matmul_precision("highest"):
            return float(fn(self.net.params, tokens))

    def reference_moment_change(self, tokens: np.ndarray, **lower) -> dict:
        """`reference_moment_change` from the state the net holds now."""
        import jax

        fn = jax.jit(functools.partial(reference_moment_change,
                                       config=self.config, **lower))
        with jax.default_matmul_precision("highest"):
            return jax.device_get(fn(self.net.params, self.first_moments(),
                                     tokens))
