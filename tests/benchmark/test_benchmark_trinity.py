"""The yardstick's side of the Trinity-Mini configuration and its cell: the
file against the published sizes, the costs against counts worked out by
hand, the readers that tell a window layer from a full one, and the cell
rehearsed end to end on the CPU."""

import os
import types

import pytest

from benchmarks.harness import kernel_costs, manifest, window_costs
from tests.benchmark.test_benchmark_lfm2 import fake_run
from tests.benchmark.test_benchmark_rehearse import (
    check_result, last_line, run_cell)

CELL = "trinity-mini-resident-t8192"
NEW_METRICS = ["window_attention_ms_per_step", "full_attention_ms_per_step",
               "window_attn_kernel_ms_per_step", "window_attention_roofline",
               "shared_expert_ms_per_step", "window_kernels_in_step"]
#: the catalog's row of the model (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


def test_manifest_is_sound_with_the_new_entries():
    doc = manifest.load()
    assert manifest.problems(doc) == []
    assert len(doc["workloads"]) == 6
    assert [w["name"] for w in doc["workloads"] if w["chips"] == 4] == [
        "gpt2l-2x2-resident-t1024"]
    assert doc["workloads"][-1]["name"] == CELL
    assert doc["configs"][-1]["name"] == "trinity-mini"
    assert [m["name"] for m in doc["per_layer"][-6:]] == NEW_METRICS


def test_config_keeps_every_published_size_but_the_reduced_ones(cell):
    config = cell.config
    assert config["reduced"] == ["num_layers", "layer_types",
                                 "num_dense_layers", "num_experts_held",
                                 "vocab_size"]
    for key, value in PUBLISHED.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # the cut: one leading dense layer and one whole period after it
    assert config["num_layers"] == len(config["layer_types"]) == 5
    assert config["num_dense_layers"] == 1
    assert sorted(config["layer_types"][1:]) == [
        "full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"][0] == "sliding_attention"
    # the guide's floors: 8 routed experts, an eighth of the vocabulary
    assert config["num_experts_held"] == 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["deployment"] == dict(config["deployment"], chips=1,
                                        layers_shared_by=16)
    for key in ("attention_gate", "positions", "four_norms",
                "embedding_scale", "route_norm_epsilon", "expert_bias",
                "head", "optimizer", "source_of_equations"):
        assert key in config["assumed"]
    assert set(config["changed"]) == set(config["reduced"])
    tolerance = config["reference_tolerance"]
    assert 0 < tolerance["rtol"] <= 0.01 and len(tolerance["why"]) > 40
    assert 0 < tolerance["step_change"] < 0.5
    # a rehearsal's window is shorter than its sequences
    assert config["rehearse"]["sliding_window"] < \
        cell.traffic["rehearse"]["seq_len"]


def test_cell_and_its_metrics_are_entries_of_their_own():
    doc = manifest.load()
    entry = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "trinity-mini"
    assert entry["traffic"] == "resident-b1-t8192-wide-ids"
    mine = [m for m in doc["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    assert {m["name"]: m["layer"] for m in mine} == {
        "window_attention_ms_per_step": "train_step",
        "full_attention_ms_per_step": "train_step",
        "window_attn_kernel_ms_per_step": "kernels",
        "window_attention_roofline": "kernels",
        "shared_expert_ms_per_step": "train_step",
        "window_kernels_in_step": "kernels"}
    # no list of an accepted metric was widened for it
    assert not [m["name"] for m in doc["per_layer"]
                if CELL in m.get("workloads", ()) and m not in mine]
    for name in NEW_METRICS + ["window_costs"]:
        where = "metrics" if name != "window_costs" else "harness"
        path = os.path.join(manifest.BENCH_DIR, where, name + ".py")
        with open(path, encoding="utf-8") as fh:
            assert fh.read().startswith('"""'), name


def test_parameters_and_required_flops_by_hand(cell):
    config, family = cell.config, cell.family
    d = 2048
    attention = 3 * d * 4096 + d * 1024          # Wq, Wgate, Wo; Wkv
    dense, expert, router = 3 * d * 6144, 3 * d * 1024, d * 128
    head = d * 25024
    assert (attention, dense, expert) == (27_262_976, 37_748_736, 6_291_456)
    # a token picks 8 of 128 experts, 8 are held: half an expert in
    # expectation, beside the shared one
    multiplied = (5 * attention + dense + 4 * (router + 1.5 * expert) + head)
    assert family.matmul_params(config) == multiplied == 264_110_080
    # pairs a head computes over 8,192 positions
    window = 2048 * 2049 // 2 + 6144 * 2048
    full = 8192 * 8193 // 2
    assert (window, full) == (14_681_088, 33_558_528)
    assert window_costs.window_pairs(8192, 2048) == window
    assert window_costs.window_pairs(8192, 8192) == full
    assert window_costs.window_pairs(8192, 9000) == full
    assert window / full == pytest.approx(0.4375, abs=1e-3)
    flops = family.required_flops_per_item(config, {"seq_len": 8192})
    attention_flops = 12 * 128 * 32 * (4 * window + full) / 8192
    assert flops == 6 * multiplied + attention_flops
    # a step of 8,192 tokens: 17.5 TFLOP, 89 ms at the chip's peak
    assert flops * 8192 == pytest.approx(17.52e12, rel=1e-3)
    # with the window ignored the same layers would need 3.71 TFLOP more
    every_layer_full = family.required_flops_per_item(
        dict(config, sliding_window=8192), {"seq_len": 8192})
    assert (every_layer_full - flops) * 8192 == pytest.approx(3.711e12,
                                                              rel=1e-3)
    # what the chip holds: 504.1M parameters
    held = (5 * (attention + 256 + 4 * d) + dense
            + 4 * (router + 128 + 9 * expert) + 2 * head + d)
    assert held == 504_147_712


@pytest.mark.parametrize("experts, picks, tokens", [(8, 2, 64), (128, 8, 2048)])
def test_balancing_bias_evens_the_load(cell, experts, picks, tokens):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(experts)
    # every token shares an offset by expert: some experts are sent several
    # times their share, some nothing
    logits = rng.normal(size=(tokens, experts)) + rng.normal(size=(1, experts))
    scores = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
    even = tokens * picks // experts

    def loads(bias):
        chosen = jax.lax.top_k(scores + bias, picks)[1]
        return np.bincount(np.asarray(chosen).ravel(), minlength=experts)

    start = jnp.asarray(0.02 * rng.normal(size=experts), jnp.float32)
    assert loads(start).max() > 1.5 * even
    balanced = loads(cell.family.balancing_bias(scores, start, picks))
    assert balanced.sum() == tokens * picks
    assert abs(balanced - even).max() <= max(1, even // 50)


def test_first_step_sends_every_held_expert_its_share():
    import jax
    import numpy as np

    rehearsal = manifest.Cell(manifest.load(), CELL, rehearse=True)
    config, mix = rehearsal.config, rehearsal.traffic
    even = mix["seq_len"] * config["num_experts_per_tok"] // config["num_experts"]
    spreads = {}
    for balanced in (False, True):
        model = rehearsal.family.Model(config, 7, jax.devices()[:1])
        if not balanced:
            model.biases_balanced = True            # the program's own draw
        ids = rehearsal.kind.WideIds(config["vocab_size"], 7,
                                     mix["restart_every"])
        placed = model.resident(model.make_batch(
            ids.sequences(1, mix["seq_len"], 8)))
        model.net.fit(placed)
        rows = np.asarray(list(model.counters()["expert_rows"].values()))
        spreads[balanced] = int(abs(rows - even).max())
    # the program computes in bfloat16, the balancing in float32: a token
    # or two at the edge of the eighth place go the other way
    assert spreads[True] <= 2 < spreads[False], spreads


def test_learning_rate_warms_up_as_the_file_states(cell):
    from deeplearning4j_tpu.nn.updaters import schedule_value
    from deeplearning4j_tpu.zoo.models import GatedWindowMoELM

    config = cell.config
    assert (config["learning_rate"], config["lr_warmup_steps"]) == (3e-4, 100_000)
    rate = cell.family.learning_rate(config)
    assert float(schedule_value(rate, 0, 0)) == 0.0
    assert float(schedule_value(rate, 50, 0)) == pytest.approx(1.5e-7)
    assert float(schedule_value(rate, 100_000, 0)) == pytest.approx(3e-4)
    assert float(schedule_value(rate, config["lr_total_steps"], 0)) == (
        pytest.approx(3e-5))
    # every layer of the built network steps at that rate; the zoo's own
    # default stays a constant 3e-4
    rehearsal = manifest.Cell(manifest.load(), CELL, rehearse=True)
    conf = rehearsal.family.network_conf(rehearsal.config, 1)
    rates = {vertex.obj.updater.learning_rate
             for vertex in conf.vertices.values()
             if getattr(vertex.obj, "updater", None) is not None}
    assert rates == {rate}
    assert GatedWindowMoELM().learning_rate == 3e-4


def test_window_cost_by_hand():
    # B=1, 32 heads of 128, T=8192, window 2048: 14,681,088 pairs a head,
    # a product is 2 * 128 operations a pair: 120,267,472,896 a layer, and
    # the splash kernels with a fused backward make seven of them
    fused = ["splash_mha_fwd_residuals.5", "splash_mha_dkv_no_residuals.10"]
    cost = window_costs.windowed_attention(fused, 1, 32, 8192, 128, 2048)
    assert window_costs.window_pairs(8192, 2048) == 14_681_088
    assert cost["flops"] == 7 * 120_267_472_896
    # bytes as the causal kernels': q, k, v, o and the gradients whole
    causal = kernel_costs.fused_backward_attention_causal(1, 32, 8192, 128)
    assert cost["bytes"] == causal["bytes"] == 11 * 67_108_864 + 3 * 1_048_576
    seconds, bound = kernel_costs.min_seconds(cost, PEAKS)
    assert bound == "compute" and seconds == pytest.approx(4.273e-3, rel=1e-3)
    # a window that covers the sequence costs the causal pairs, diagonal in
    whole = window_costs.windowed_attention(fused, 1, 32, 8192, 128, 8192)
    assert whole["flops"] == 7 * 2 * 128 * 32 * (8192 * 8193 // 2)
    # three kernels: nine products
    split = fused + ["splash_mha_dq_no_residuals.3"]
    assert window_costs.windowed_attention(
        split, 1, 32, 8192, 128, 2048)["flops"] == 9 * 120_267_472_896
    assert window_costs.windowed_attention(["fusion.3"], 1, 32, 8192, 128,
                                           2048) is None


STEP = """
HloModule jit_train_step

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %dot.1 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %a), metadata={op_name="jit(train_step)/jvp(GroupedQueryAttentionLayer:block0-swa)/dot_general"}
  %splash_mha_fwd_residuals.5 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %dot.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GroupedQueryAttentionLayer:block0-swa)/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
  %splash_mha_fwd_residuals.7 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %splash_mha_fwd_residuals.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GroupedQueryAttentionLayer:block2-att)/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call"}
  %dot.2 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %splash_mha_fwd_residuals.7, bf16[8,8]{1,0} %a), metadata={op_name="jit(train_step)/jvp(DenseLayer:block2-shared1)/dot_general"}
  %dot.3 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %dot.2, bf16[8,8]{1,0} %a), metadata={op_name="jit(train_step)/jvp(DenseLayer:block0-ff1)/dot_general"}
  %splash_mha_dkv_no_residuals.9 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %dot.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(GroupedQueryAttentionLayer:block2-att))/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
  %splash_mha_dkv_no_residuals.10 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %splash_mha_dkv_no_residuals.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(GroupedQueryAttentionLayer:block0-swa))/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/pallas_call"}
  %dot.4 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %splash_mha_dkv_no_residuals.10, bf16[8,8]{1,0} %a), metadata={op_name="jit(train_step)/transpose(jvp(DenseLayer:block2-shared2))/dot_general"}
  ROOT %add.9 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %dot.4, bf16[8,8]{1,0} %dot.4), metadata={op_name="jit(train_step)/optimizer/GroupedQueryAttentionLayer:block0-swa/add"}
}
"""
SECONDS = {"dot.1": 0.004, "splash_mha_fwd_residuals.5": 0.010,
           "splash_mha_fwd_residuals.7": 0.020, "dot.2": 0.002,
           "dot.3": 0.1, "splash_mha_dkv_no_residuals.9": 0.040,
           "splash_mha_dkv_no_residuals.10": 0.030, "dot.4": 0.006,
           "add.9": 0.05}


def test_readers_tell_a_window_layer_from_a_full_one(cell):
    run = fake_run(STEP, SECONDS)               # two steps
    run.cell, run.peaks = cell, PEAKS
    read = {m["name"]: r for m, r in cell.metrics("per_layer")}
    # forward and backward under the scope; Adam's update of it is not
    assert read["window_attention_ms_per_step"](run) == pytest.approx(22.0)
    assert read["full_attention_ms_per_step"](run) == pytest.approx(30.0)
    assert read["window_attn_kernel_ms_per_step"](run) == pytest.approx(20.0)
    assert read["shared_expert_ms_per_step"](run) == pytest.approx(4.0)
    assert read["window_kernels_in_step"](run) == 2
    assert read["flash_kernels_in_step"](run) == 4
    # the least time for the cell's four window layers over 20 ms a step
    least = kernel_costs.min_seconds(window_costs.windowed_attention(
        ["splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"], 1, 32,
        8192, 128, 2048), PEAKS)[0]
    assert read["window_attention_roofline"](run) == pytest.approx(
        100 * 4 * least / 20e-3)
    assert 80 < read["window_attention_roofline"](run) < 90


def test_readers_find_nothing_in_a_program_without_such_layers(cell):
    """The parent's side: no `-swa` scope, no shared expert, or no traced
    run at all: every new reader returns None and does not raise."""
    read = {m["name"]: r for m, r in cell.metrics("per_layer")}
    plain = fake_run(STEP.replace("-swa", "-att").replace("-shared", "-ff"),
                     SECONDS)
    plain.cell, plain.peaks = cell, PEAKS
    for name in ("window_attention_ms_per_step", "shared_expert_ms_per_step",
                 "window_attn_kernel_ms_per_step",
                 "window_attention_roofline"):
        assert read[name](plain) is None, name
    assert read["window_kernels_in_step"](plain) == 0
    assert read["full_attention_ms_per_step"](plain) == pytest.approx(52.0)
    nothing = types.SimpleNamespace(step_text=None, device_trace=None,
                                    xplane_path=None, counters={}, cell=cell,
                                    peaks=PEAKS)
    for name in NEW_METRICS:
        assert read[name](nothing) is None, name


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(trace):
    done = run_cell("--workload", CELL, "--seed", "2147483659", "--seconds",
                    "1", "--trace", str(trace), "--rehearse")
    metrics = check_result(last_line(done), CELL,
                           "per_layer" if trace else "end_to_end", 1)
    assert "check no_token_dropped: ok" in done.stdout
    assert "check score_matches_reference: ok" in done.stdout
    assert "check step_matches_reference: ok" in done.stdout
    if trace:
        # on the CPU every attention layer takes the einsum path
        assert metrics["flash_kernels_in_step"]["value"] == 0
        assert metrics["window_kernels_in_step"]["value"] == 0
        assert set(NEW_METRICS) <= set(metrics)
        out = os.path.join(manifest.ROOT, "chiprun_out", "benchmarks", CELL)
        assert os.path.isfile(os.path.join(out, "step.hlo.txt.gz"))
    else:
        assert set(metrics) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
