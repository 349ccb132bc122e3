"""The arithmetic of the yardstick, against counts worked out by hand."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import kernel_costs, manifest
from benchmarks.harness.hlo_text import collective_counts
from benchmarks.harness.planted_tokens import PlantedRule


def family_and_config(name):
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == name)
    with open(os.path.join(manifest.ROOT, entry["file"]),
              encoding="utf-8") as fh:
        config = json.load(fh)
    family = manifest._load_module(manifest.family_path(entry, config))
    return family, config


@pytest.mark.parametrize("name, seq_len, multiplied, flops", [
    # 12 * (4*768^2 + 2*768*3072) + 768*50257 = 84,934,656 + 38,597,376
    ("gpt2-small", 2048, 123_532_032, 854_438_400),
    ("gpt2-small", 1024, 123_532_032, 797_815_296),
    # 36 * (4*1280^2 + 2*1280*5120) + 1280*50257 = 707,788,800 + 64,328,960
    ("gpt2-large", 1024, 772_117_760, 4_915_822_080),
])
def test_required_flops_per_token(name, seq_len, multiplied, flops):
    family, config = family_and_config(name)
    assert family.matmul_params(config) == multiplied
    # 6 per multiplied parameter + 6 * L * d * T of causal attention
    by_hand = 6 * multiplied + 6 * config["n_layer"] * config["n_embd"] \
        * seq_len
    got = family.required_flops_per_item(config, {"seq_len": seq_len})
    assert got == by_hand == flops


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_attention_cost_by_hand():
    # B=4, H=12, T=2048, dh=64: one full product is 2*T*T*dh*B*H =
    # 25,769,803,776 operations, the causal half 12,884,901,888, and the
    # three kernels need 2 + 4 + 3 of them
    cost = kernel_costs.flash_attention_causal(4, 12, 2048, 64)
    assert cost["flops"] == 9 * 12_884_901_888
    # q, k, v, o, dO, dq, dk, dv are 4*12*2048*64 bf16 = 12,582,912 bytes
    assert cost["bytes"] == 17 * 12_582_912
    seconds, bound = kernel_costs.min_seconds(cost, PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(5.8865e-4, rel=1e-4)
    assert kernel_costs.min_seconds({"flops": 1.0, "bytes": 819e9},
                                    PEAKS) == (1.0, "memory")


@pytest.mark.parametrize("shape, product, tensor, row, least_s", [
    # B=4, H=12, T=2048, dh=64 (gpt2s-resident-t2048): the causal half of
    # 2*T*T*dh*B*H; q is 4*12*2048*64 bf16; a float32 per query row is
    # 4*12*2048*4 bytes; 7 * 12,884,901,888 / 197e12
    ((4, 12, 2048, 64), 12_884_901_888, 12_582_912, 393_216, 4.5784e-4),
    # B=8, H=12, T=1024, dh=64 (gpt2s-resident-t1024): half the products
    # for the same tokens, the same bytes
    ((8, 12, 1024, 64), 6_442_450_944, 12_582_912, 393_216, 2.2892e-4),
])
def test_fused_backward_attention_cost_by_hand(shape, product, tensor, row,
                                               least_s):
    # forward QK^T, PV; backward QK^T again, dP, dV, dK, dQ: 7 products in
    # 2 kernels. Forward reads q, k, v and writes o and the log-sum-exp
    # row; backward reads q, k, v, dO and two rows, writes dQ, dK, dV.
    cost = kernel_costs.fused_backward_attention_causal(*shape)
    assert cost["flops"] == 7 * product
    assert cost["bytes"] == (4 + 7) * tensor + (1 + 2) * row
    seconds, bound = kernel_costs.min_seconds(cost, PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(least_s, rel=1e-4)
    # the stock split of the same shape: two products and a kernel more
    stock = kernel_costs.flash_attention_causal(*shape)
    assert stock["flops"] == 9 * product
    assert stock["bytes"] == 17 * tensor
    # twelve layers of it a step, against the issue's 5.49 / 2.75 ms
    assert 12 * seconds == pytest.approx(
        {2048: 5.494e-3, 1024: 2.747e-3}[shape[2]], rel=1e-3)


#: names as a trace's `XLA Ops` line and a compiled step hold them, by the
#: generation of kernels: what the recorded fixtures hold (my chip run,
#: PR 22), what the cells run since PR 26 (my chip run, PR 32), and what
#: `splash_attention_kernel.get_kernel_name` gives a backward in two
#: kernels, grouped queries and segment ids
GENERATIONS = {
    "stock": (["jvp_jit_flash_attention__.12",
               "flash_mha_bwd_dkv_block_q_major_512.3",
               "flash_mha_bwd_dq_block_q_major_512.7"], 9),
    "splash_fused_backward": (["splash_mha_fwd_residuals.12",
                               "splash_mha_dkv_no_residuals.3"], 7),
    "splash_backward_in_two": (["splash_mqa_fwd_segmented_residuals.1",
                                "splash_mqa_dkv_segmented_no_residuals.2",
                                "splash_mqa_dq_segmented_no_residuals.3"],
                               9),
}
OTHERS = ["fusion.12", "all-reduce.3", "gmm.4", "tgmm.1", "copy-done.127",
          "splash_mask_info", "flashy_fusion"]


@pytest.mark.parametrize("generation", sorted(GENERATIONS))
def test_the_pattern_finds_each_generation_of_attention_kernels(generation):
    names, products = GENERATIONS[generation]
    assert all(kernel_costs.FLASH_ATTENTION_OPS.search(n) for n in names)
    assert not any(kernel_costs.FLASH_ATTENTION_OPS.search(n) for n in OTHERS)
    # the cost follows the kernels found, whatever else the line holds
    cost = kernel_costs.attention_causal(OTHERS + names, 4, 12, 2048, 64)
    assert cost["flops"] == products * 12_884_901_888
    assert kernel_costs.attention_causal(OTHERS, 4, 12, 2048, 64) is None


def compiled_step_with(names):
    """A short compiled step: each name a `tpu_custom_call`, among a fusion,
    a custom call that is no kernel and the grouped matmul's kernel."""
    calls = "".join(
        f'  %{name} = bf16[8,12,1024,64]{{3,2,1,0}} custom-call(%q, %k, %v), '
        f'custom_call_target="tpu_custom_call", frontend_attributes='
        f'{{kernel_metadata={{"xprof_metadata":"{{}}"}}}}, metadata='
        f'{{op_name="jit(train_step)/jvp(A:att)/{name}/pallas_call"}}\n'
        for name in names)
    return f"""
HloModule jit_train_step, is_scheduled=true

ENTRY %main.1 (q: bf16[8,12,1024,64]) -> bf16[8,12,1024,64] {{
  %q = bf16[8,12,1024,64]{{3,2,1,0}} parameter(0)
  %fusion.3 = bf16[8,12,1024,64]{{3,2,1,0}} fusion(%q), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="jit(train_step)/jvp(A:att)/splash_mha_fwd_residuals/mul"}}
  %gmm.4 = bf16[8,64]{{1,0}} custom-call(%q), custom_call_target="tpu_custom_call", metadata={{op_name="jit(train_step)/jvp(M:moe)/experts/jit(gmm)/pallas_call"}}
  %custom-call.9 = bf16[8,64]{{1,0}} custom-call(%q), custom_call_target="Sharding"
{calls}  ROOT %out.1 = bf16[8,12,1024,64]{{3,2,1,0}} copy(%q)
}}
"""


@pytest.mark.parametrize("generation", sorted(GENERATIONS))
def test_flash_kernels_in_step_counts_each_generation(generation):
    count = manifest._load_module(
        manifest.metric_path("flash_kernels_in_step")).count
    names, _ = GENERATIONS[generation]
    two_layers = [f"{n.rsplit('.', 1)[0]}.{10 * layer + i}"
                  for layer in range(2) for i, n in enumerate(names)]
    assert count(compiled_step_with(two_layers)) == 2 * len(names)
    assert count(compiled_step_with([])) == 0


def test_collectives_are_counted_once_each():
    text = """
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}
  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %q), replica_groups={}
  %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
  %ag = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %r), dimensions={0}
  %agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag)
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.1), kind=kLoop
  ROOT %cp = f32[8]{0} collective-permute(f32[8]{0} %x), source_target_pairs={{0,1}}
"""
    assert collective_counts(text) == {
        "all-reduce": 2, "all-gather": 1, "reduce-scatter": 0,
        "collective-permute": 1, "all-to-all": 0}


def test_planted_tokens_follow_their_rule_and_their_seed():
    rule = PlantedRule(50257, seed=5)
    assert {30521, 50256} <= set(rule.alphabet.tolist())
    assert len(rule.alphabet) == 64
    a = rule.sequences(6, 128, seed=9)
    assert a.dtype == np.int32 and a.shape == (6, 128)
    assert rule.follows_rule(a)
    assert np.array_equal(a, PlantedRule(50257, seed=5).sequences(6, 128, 9))
    assert not np.array_equal(a, rule.sequences(6, 128, seed=10))
    assert not np.array_equal(
        a, PlantedRule(50257, seed=6).sequences(6, 128, seed=9))
    broken = a.copy()
    broken[0, 5] = broken[0, 4]
    assert not rule.follows_rule(broken)
    small = PlantedRule(512, seed=1)  # the rehearsal's vocabulary
    assert small.alphabet.max() == 511 and len(small.alphabet) == 64


class FakeDevice:
    def __init__(self, **stats):
        self.stats = stats or None

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("before, stats, peak", [
    # resident, one chip: the window raised nothing; what is held now plus
    # the step's reservation (numbers of gpt2s-resident-t2048, PR 22)
    (3_734_595_584, dict(bytes_in_use=3_734_595_584,
                         peak_bytes_in_use=3_734_595_584,
                         peak_bytes_reserved=5_596_839_936), 9_331_435_520),
    # stream: prefetched batches raised the allocator's peak in the window
    (2_096_878_080, dict(bytes_in_use=2_096_878_080,
                         peak_bytes_in_use=10_322_010_112,
                         peak_bytes_reserved=5_596_839_936), 15_918_850_048),
    # 2x2, device 0: the whole model sat here in set-up, before sharding
    (14_398_343_680, dict(bytes_in_use=6_640_100_352,
                          peak_bytes_in_use=14_398_343_680,
                          peak_bytes_reserved=6_795_460_608), 14_398_343_680),
    # a backend that reserves nothing
    (100, dict(bytes_in_use=100, peak_bytes_in_use=300), 300),
])
def test_memory_peak_takes_allocator_and_reservation_together(before, stats,
                                                              peak):
    from benchmarks.harness import devices
    small = FakeDevice(bytes_in_use=1, peak_bytes_in_use=1)
    assert devices.memory_peak_bytes([small, FakeDevice(**stats)],
                                     [1, before]) == peak
    assert devices.allocator_peaks([FakeDevice(**stats), FakeDevice()]) \
        == [stats["peak_bytes_in_use"], None]
    assert devices.memory_peak_bytes([FakeDevice()], [None]) is None
