"""The yardstick's side of the LFM2-8B-A1B configuration and its cell: the
file against the published sizes, the costs against counts worked out by
hand, the ids' rule, the readers of the expert layer's scopes, and the cell
rehearsed end to end on the CPU."""

import os
import types

import numpy as np
import pytest

from benchmarks.harness import expert_costs, kernel_costs, manifest
from tests.benchmark.test_benchmark_rehearse import (
    check_result, last_line, run_cell)

CELL = "lfm2-8b-a1b-share4-resident-t8192"
#: the catalog's row of the model (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(manifest.load(), CELL)


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
    assert config["layer_types"][config["num_dense_layers"]:] == [
        "full_attention", "conv", "conv", "conv"]
    assert config["num_experts_held"] == 8
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert config["deployment"] == dict(config["deployment"], chips=1,
                                        layers_shared_by=4)
    for key in ("head", "norm_topk_epsilon", "expert_bias", "parameters",
                "optimizer", "compute_dtype"):
        assert key in config["assumed"]
    assert set(config["changed"]) == set(config["reduced"])
    tolerance = config["reference_tolerance"]
    assert 0 < tolerance["rtol"] <= 0.01 and len(tolerance["why"]) > 40
    assert 0 < tolerance["step_change"] < 1


def test_cell_and_its_metrics_are_entries_of_their_own():
    doc = manifest.load()
    entry = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "lfm2-8b-a1b"
    assert "lfm2-8b-a1b" in [c["name"] for c in doc["configs"]]
    mine = [m for m in doc["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "expert_ffn_ms_per_step", "expert_dispatch_ms_per_step",
        "expert_matmul_ms_per_step", "expert_matmul_roofline",
        "short_conv_ms_per_step", "gqa_attention_ms_per_step",
        "expert_rows_per_step", "expert_load_max_over_mean"]
    assert all(m["moves"] == "tokens_per_s" for m in mine)
    # of the metrics that list their cells, only those whose spans are made
    # in one place for every model (`nn/engine.py`) took this one in
    assert [m["name"] for m in doc["per_layer"]
            if CELL in m.get("workloads", ()) and m not in mine] == [
        "dispatch_ms_per_step", "steps_that_compiled"]


def test_parameters_and_required_flops_by_hand(cell):
    config, family = cell.config, cell.family
    d = 2048
    conv = d * 6144 + d * 3 + d * d                     # 16,783,360
    attention = 2 * d * d + 2 * d * 512                 # 10,485,760
    dense, expert = 3 * d * 7168, 3 * d * 1792          # 44,040,192; 11,010,048
    router, head = d * 32, d * 16384
    # a token picks 4 of 32 experts, 8 are held: one expert in expectation
    multiplied = ((conv + dense) + (attention + router + expert)
                  + 3 * (conv + router + expert) + head)
    assert conv == 16_783_360 and attention == 10_485_760
    assert family.matmul_params(config) == multiplied == 199_516_160
    flops = family.required_flops_per_item(config, {"seq_len": 8192})
    assert flops == 6 * multiplied + 6 * d * 8192 == 1_297_760_256
    # a step of 8,192 tokens: 10.6 TFLOP
    assert flops * 8192 == pytest.approx(10.63e12, rel=1e-3)
    # what the chip holds: 541.4M parameters
    held = ((conv + dense) + (attention + router + 32 + 8 * expert)
            + 3 * (conv + router + 32 + 8 * expert) + 2 * head
            + 11 * d + 2 * 64)
    assert held == pytest.approx(541.4e6, rel=1e-3)


def test_expert_ffn_cost_by_hand():
    # 8,192 rows over 8 experts of 2048 x 1792: one product is
    # 2 * 8192 * 2048 * 1792 = 60,129,542,144 operations, and there are
    # three forward and six backward
    cost = expert_costs.gated_expert_ffn(8192, 2048, 1792, 8)
    assert cost["flops"] == 9 * 60_129_542_144
    # bf16: the 8 experts' matrix is 58,720,256 bytes, read in 6 products
    # and written by 3; a row in and a row out are (2048 + 1792) * 2 bytes
    assert cost["bytes"] == 9 * 58_720_256 + 9 * 8192 * 7680
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = kernel_costs.min_seconds(cost, peaks)
    assert bound == "compute" and seconds == pytest.approx(2.747e-3, rel=1e-3)
    # few rows: the weights' bytes bound it
    assert kernel_costs.min_seconds(
        expert_costs.gated_expert_ffn(64, 2048, 1792, 8), peaks)[1] == "memory"


def test_wide_ids_cover_the_vocabulary_and_follow_their_rule(cell):
    rule = cell.kind.WideIds(16384, seed=2_147_483_659, restart_every=64)
    a = rule.sequences(2, 8192, seed=9)
    assert a.dtype == np.int32 and a.shape == (2, 8192)
    assert 0 <= a.min() and a.max() < 16384
    assert rule.follows_rule(a)
    assert len(np.unique(a)) > 6000         # planted_tokens has 64
    again = cell.kind.WideIds(16384, 2_147_483_659, 64)
    assert np.array_equal(a, again.sequences(2, 8192, 9))
    assert not np.array_equal(a, rule.sequences(2, 8192, seed=10))
    assert not np.array_equal(
        a, cell.kind.WideIds(16384, 5, 64).sequences(2, 8192, 9))
    broken = a.copy()
    broken[0, 5] = (broken[0, 5] + 1) % 16384
    assert not rule.follows_rule(broken)
    # a stretch starts anywhere, so position 64 need not follow position 63
    assert not np.array_equal(rule.successor[a[:, 63]], a[:, 64])


def test_no_token_dropped_counts_every_pair(cell):
    run = types.SimpleNamespace(cell=cell, checks={})
    good = {"expert_rows": {"block1-moe": [8000, 192], "block2-moe": [1, 0]},
            "rows_elsewhere": {"block1-moe": 24576, "block2-moe": 32767}}
    cell.kind.check_no_token_dropped(run, good)
    assert run.checks["no_token_dropped"][0] is True
    good["rows_elsewhere"]["block2-moe"] -= 1
    cell.kind.check_no_token_dropped(run, good)
    assert run.checks["no_token_dropped"][0] is False
    cell.kind.check_no_token_dropped(
        run, {"expert_rows": {}, "rows_elsewhere": {}})
    assert run.checks["no_token_dropped"][0] is False


STEP = """
HloModule jit_train_step

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %gather.1 = bf16[8,8]{1,0} gather(bf16[8,8]{1,0} %p0), metadata={op_name="jit(train_step)/jvp(MixtureOfExpertsLayer:block1-moe)/dispatch/gather"}
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kLoop, calls=%fused_computation.1
  %gmm.4 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(MixtureOfExpertsLayer:block1-moe)/experts/jit(gmm)/pallas_call"}
  %tgmm.2 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %gmm.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(MixtureOfExpertsLayer:block1-moe))/experts/jit(tgmm)/pallas_call"}
  %multiply.3 = bf16[8,8]{1,0} multiply(bf16[8,8]{1,0} %tgmm.2, bf16[8,8]{1,0} %tgmm.2), metadata={op_name="jit(train_step)/transpose(jvp(MixtureOfExpertsLayer:block1-moe))/experts/mul"}
  %sort.1 = bf16[8,8]{1,0} sort(bf16[8,8]{1,0} %multiply.3), metadata={op_name="jit(train_step)/jvp(MixtureOfExpertsLayer:block1-moe)/route/top_k"}
  %add.9 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %sort.1, bf16[8,8]{1,0} %sort.1), metadata={op_name="jit(train_step)/optimizer/MixtureOfExpertsLayer:block1-moe/add"}
  ROOT %dot.5 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %add.9, bf16[8,8]{1,0} %a), metadata={op_name="jit(train_step)/jvp(DenseLayer:block0-ff1)/dot_general"}
}
"""


def fake_run(step_text, seconds_by_event, steps=2):
    from benchmarks.harness import xplane

    names, at = list(seconds_by_event), 0.0
    start, end = [], []
    for name in names:
        start.append(at)
        at += seconds_by_event[name]
        end.append(at)
    ops = xplane.Line(names, start, end)
    plane = types.SimpleNamespace(ops=ops, steps=[None] * steps)
    return types.SimpleNamespace(
        step_text=step_text, xplane_path=f"fake-{id(ops)}",
        device_trace=types.SimpleNamespace(first=plane))


def test_expert_scopes_split_the_layer():
    run = fake_run(STEP, {"fusion.1": 0.002, "gmm.4": 0.010, "tgmm.2": 0.006,
                          "multiply.3": 0.001, "sort.1": 0.004,
                          "add.9": 0.008, "dot.5": 0.1})
    around = expert_costs.in_scopes("route", "dispatch", "combine")
    assert expert_costs.scope_ms(run, around) == pytest.approx(3.0)
    assert expert_costs.scope_ms(run, expert_costs.grouped_products) \
        == pytest.approx(8.0)
    assert expert_costs.scope_ms(run, expert_costs.in_scopes("experts")) \
        == pytest.approx(8.5)
    # a step with no expert layer: nothing to read, and no error
    plain = fake_run(STEP.replace("MixtureOfExpertsLayer", "DenseLayer"),
                     {"gmm.4": 0.01})
    assert expert_costs.scope_ms(plain, around) is None
    nothing = types.SimpleNamespace(step_text=None, device_trace=None,
                                    xplane_path=None, counters={})
    assert expert_costs.scope_ms(nothing, around) is None
    assert expert_costs.counted_rows(nothing) is None


def test_expert_readers_on_counted_rows(cell):
    run = fake_run(STEP, {"gmm.4": 0.020, "tgmm.2": 0.010})
    run.cell, run.rehearse = cell, False
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run.counters = {"expert_rows": {f"block{i}-moe": [1024] * 8
                                    for i in range(1, 5)}}
    read = {m["name"]: r for m, r in cell.metrics("per_layer")}
    assert read["expert_rows_per_step"](run) == 4 * 8192
    assert read["expert_load_max_over_mean"](run) == 1.0
    run.counters["expert_rows"]["block3-moe"][2] = 2048
    assert read["expert_load_max_over_mean"](run) == pytest.approx(
        2048 * 8 / 9216)
    assert read["expert_matmul_ms_per_step"](run) == pytest.approx(15.0)
    # least time for 33,792 rows over 32 held matrices, over 15 ms a step
    least = kernel_costs.min_seconds(
        expert_costs.gated_expert_ffn(33_792, 2048, 1792, 32), run.peaks)[0]
    assert read["expert_matmul_roofline"](run) == pytest.approx(
        100 * least / 15e-3)
    assert 70 < read["expert_matmul_roofline"](run) < 80
    # the program before this PR has neither counters nor scopes
    run.counters = {}
    for name in ("expert_rows_per_step", "expert_load_max_over_mean",
                 "expert_matmul_roofline"):
        assert read[name](run) is None


def test_step_check_reads_what_it_says():
    """The comparison of the step: a float32 program reads 0 (its first
    moments move as the reference's gradient says), a state left unchanged
    reads 1, and the reference with 8-bit products reads far from its
    float32 self."""
    import jax
    import jax.numpy as jnp

    cell = manifest.Cell(manifest.load(), CELL, rehearse=True)
    config = dict(cell.config, compute_dtype=None)
    model = cell.family.Model(config, 5, jax.devices()[:1])
    rule = cell.kind.WideIds(config["vocab_size"], 5, 8)
    batch = model.resident(model.make_batch(rule.sequences(2, 32, 6)))
    for _ in range(12):
        model.net.fit(batch)
    small = model.small_parameters()
    assert ("block1-moe", "Wg") in small and ("block0-conv", "K") in small
    assert ("block1-moe", "expert_bias") not in small
    assert model.step_change_error(batch) < 1e-3
    assert model.net.iteration == 13
    tokens = np.asarray(batch.features)
    want = model.reference_moment_change(tokens)
    assert set(want) == set(small)
    none = {k: np.zeros_like(v) for k, v in want.items()}
    assert cell.family.relative_difference(none, want) == 1.0
    assert cell.family.relative_difference(want, want) == 0.0
    low = model.reference_moment_change(tokens,
                                        product_dtype=jnp.float8_e4m3fn)
    assert cell.family.relative_difference(low, want) > 0.1
    run = types.SimpleNamespace(cell=cell, checks={}, counters={})
    cell.kind.check_step_matches_reference(run, model, batch)
    assert run.checks["step_matches_reference"][0] is True
    assert run.counters["step_change_error"] < 1e-3


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
        # 2 sequences of 32 tokens, 2 experts a token, 4 of 8 experts held
        assert 0 < metrics["expert_rows_per_step"]["value"] <= 2 * 2 * 64
        assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
        assert metrics["flash_kernels_in_step"]["value"] == 0
        out = os.path.join(manifest.ROOT, "chiprun_out", "benchmarks", CELL)
        assert os.path.isfile(os.path.join(out, "step.hlo.txt.gz"))
    else:
        assert set(metrics) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
