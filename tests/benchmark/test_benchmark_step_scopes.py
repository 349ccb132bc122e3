"""`harness/step_scopes.py` on a compiled step written by hand, where every
label can be worked out; the eight metrics that read it and the program's
spans, in a rehearsal; and the manifest with their entries."""

import json

import pytest

from benchmarks.harness import manifest, step_scopes
from benchmarks.harness.xplane import Line
from test_benchmark_rehearse import last_line, run_cell

FWD = "jit(train_step)/jvp(DenseLayer:fc)"
BWD = "jit(train_step)/transpose(jvp(DenseLayer:fc))"
OPT = "jit(train_step)/optimizer/DenseLayer:fc"
ATT_BWD = "jit(train_step)/transpose(jvp(CausalSelfAttentionLayer:att))"

HLO = f"""
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,4], param_1.1: bf16[4,4]) -> bf16[8,4] {{
  %param_0.1 = bf16[8,4]{{1,0:T(8,128)(2,1)}} parameter(0)
  %param_1.1 = bf16[4,4]{{1,0}} parameter(1)
  %zero.1 = bf16[] constant(0), metadata={{op_name="{BWD}/mul"}}
  ROOT %dot.1 = bf16[8,4]{{1,0}} convolution(%param_0.1, %param_1.1), dim_labels=bf_io->bf, metadata={{op_name="{FWD}/dot_general"}}
}}

%fused_computation.2 (param_0.2: bf16[8,4], param_1.2: bf16[8,4], param_2.2: f32[4,4]) -> (f32[4,4], f32[4,4]) {{
  %param_0.2 = bf16[8,4]{{1,0}} parameter(0)
  %param_1.2 = bf16[8,4]{{1,0}} parameter(1)
  %param_2.2 = f32[4,4]{{1,0}} parameter(2)
  %dw.2 = f32[4,4]{{1,0}} convolution(%param_0.2, %param_1.2), dim_labels=fb_io->bf, metadata={{op_name="{BWD}/dot_general"}}
  %m.2 = f32[4,4]{{1,0}} multiply(%dw.2, %dw.2), metadata={{op_name="{OPT}/mul"}}
  %p.2 = f32[4,4]{{1,0}} subtract(%param_2.2, %m.2), metadata={{op_name="{OPT}/sub"}}
  ROOT %tuple.2 = (f32[4,4]{{1,0}}, f32[4,4]{{1,0}}) tuple(%p.2, %m.2)
}}

%fused_computation.3 (param_0.3: bf16[8,4], param_1.3: bf16[4,4]) -> bf16[8,4] {{
  %param_0.3 = bf16[8,4]{{1,0}} parameter(0)
  %param_1.3 = bf16[4,4]{{1,0}} parameter(1)
  %gelu.3 = bf16[8,4]{{1,0}} tanh(%param_0.3), metadata={{op_name="{FWD}/tanh"}}
  %inner.3 = bf16[8,4]{{1,0}} fusion(%gelu.3, %param_1.3), kind=kOutput, calls=%fused_computation.4
  ROOT %dx.3 = bf16[8,4]{{1,0}} multiply(%inner.3, %gelu.3), metadata={{op_name="{BWD}/mul"}}
}}

%fused_computation.4 (param_0.4: bf16[8,4], param_1.4: bf16[4,4]) -> bf16[8,4] {{
  %param_0.4 = bf16[8,4]{{1,0}} parameter(0)
  %param_1.4 = bf16[4,4]{{1,0}} parameter(1)
  ROOT %dot.4 = bf16[8,4]{{1,0}} dot(%param_0.4, %param_1.4), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}, metadata={{op_name="{BWD}/dot_general"}}
}}

%fused_computation.5 (param_0.5: f32[8,4]) -> f32[8] {{
  %param_0.5 = f32[8,4]{{1,0}} parameter(0)
  %exp.5 = f32[8,4]{{1,0}} exponential(%param_0.5), metadata={{op_name="jit(train_step)/jvp(OutputLayer:out)/loss/exp"}}
  %sum.5 = f32[8]{{0}} reduce(%exp.5, %param_0.5), dimensions={{1}}, to_apply=%add, metadata={{op_name="jit(train_step)/jvp(OutputLayer:out)/loss/reduce_sum"}}
  ROOT %back.5 = f32[8]{{0}} reduce(%exp.5, %sum.5), dimensions={{1}}, to_apply=%add, metadata={{op_name="jit(train_step)/transpose(jvp(OutputLayer:out))/loss/reduce_sum"}}
}}

%fused_computation.6 (param_0.6: bf16[8,4], param_1.6: f32[8,4]) -> bf16[8,4] {{
  %param_0.6 = bf16[8,4]{{1,0}} parameter(0)
  %param_1.6 = f32[8,4]{{1,0}} parameter(1)
  %moved.6 = f32[8,4]{{0,1}} copy(%param_1.6), metadata={{op_name="labels[0]"}}
  %dot.6 = bf16[8,4]{{1,0}} dot(%param_0.6, %param_0.6), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}, metadata={{op_name="{BWD}/dot_general"}}
  ROOT %scaled.6 = bf16[8,4]{{1,0}} multiply(%dot.6, %dot.6), metadata={{op_name="jit(train_step)/mul"}}
}}

ENTRY %main.9 (x.1: bf16[8,4], w.1: f32[4,4]) -> f32[4,4] {{
  %x.1 = bf16[8,4]{{1,0}} parameter(0), metadata={{op_name="x"}}
  %w.1 = f32[4,4]{{1,0}} parameter(1), metadata={{op_name="params[0]['W']"}}
  %cast.1 = bf16[4,4]{{1,0}} convert(%w.1), metadata={{op_name="jit(train_step)/jvp(cast_params)/convert_element_type" stack_frame_id=3}}
  %copy-start.1 = (bf16[8,4]{{1,0:T(8,128)(2,1)S(1)}}, bf16[8,4]{{1,0}}, u32[]{{:S(2)}}) copy-start(%x.1)
  %copy-done.1 = bf16[8,4]{{1,0:T(8,128)(2,1)S(1)}} copy-done(%copy-start.1)
  %fusion.1 = bf16[8,4]{{1,0}} fusion(%copy-done.1, %cast.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{FWD}/add"}}
  %loss_fusion = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(train_step)/jvp(OutputLayer:out)/loss/reduce_sum"}}
  %custom-call.7 = bf16[8,4]{{1,0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{ATT_BWD}/jit(flash_attention)/flash_mha_bwd_dq_block_q_major=512/pallas_call"}}
  %all-reduce.3 = bf16[8,4]{{1,0}} all-reduce(%custom-call.7), replica_groups={{}}, to_apply=%add, metadata={{op_name="{BWD}/dot_general"}}
  %recompute_fusion = bf16[8,4]{{1,0}} fusion(%all-reduce.3, %cast.1), kind=kOutput, calls=%fused_computation.3, metadata={{op_name="{BWD}/mul"}}
  %joined.1 = bf16[8,4]{{1,0}} add(%recompute_fusion, %recompute_fusion), metadata={{op_name="jit(train_step)/add;{BWD}/add_any"}}
  %tick.1 = f32[] add(%w.1, %w.1), metadata={{op_name="jit(train_step)/add"}}
  %unscoped_fusion = bf16[8,4]{{1,0}} fusion(%joined.1, %x.1), kind=kOutput, calls=%fused_computation.6, metadata={{op_name="{BWD}/dot_general"}}
  %divide_subtract_fusion = (f32[4,4]{{1,0}}, f32[4,4]{{1,0}}) fusion(%x.1, %joined.1, %w.1), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{OPT}/sub"}}
  ROOT %out.1 = f32[4,4]{{1,0}} get-tuple-element(%divide_subtract_fusion), index=0
}}
"""

#: event name -> (seconds, phase, class, name, mixed)
EVENTS = {
    "cast.1": (1.0, "forward", "cast_params", "cast_params", ""),
    # no name of its own: what uses it says what it is for
    "copy-start.1": (0.5, "forward", "DenseLayer", "DenseLayer:fc", ""),
    "copy-done.1": (1.5, "forward", "DenseLayer", "DenseLayer:fc", ""),
    # the constant's backward name does not make the forward matmul a mix
    "fusion.1": (8.0, "forward", "DenseLayer", "DenseLayer:fc", ""),
    # a loss that sums forward and backward in one pass is a mix
    "loss_fusion": (4.0, "forward", "OutputLayer", "OutputLayer:out/loss",
                    "forward+backward"),
    # a kernel's event named by its kernel, not by `custom-call.7`
    "flash_mha_bwd_dq_block_q_major_512.7": (
        16.0, "backward", "CausalSelfAttentionLayer",
        "flash_mha_bwd_dq_block_q_major_512", ""),
    # whose operation it was made for says the phase; the class is its own
    "all-reduce.3": (2.0, "backward", "all-reduce", "DenseLayer:fc", ""),
    # the backward matmul (in a nested fusion) recomputes the forward tanh
    "recompute_fusion": (6.0, "backward", "DenseLayer", "DenseLayer:fc", ""),
    # the first of the joined names that has a phase
    "joined.1": (1.0, "backward", "DenseLayer", "DenseLayer:fc", ""),
    "tick.1": (0.25, "other", "-", "-", ""),
    # the weight gradient with the update fused into it: the matmul's label
    "divide_subtract_fusion": (10.0, "backward", "DenseLayer",
                               "DenseLayer:fc", "backward+optimizer"),
    # an operation of the step that no scope names, fused into a named one:
    # a mix (how a dropped scope shows); an argument's name on a copy is not
    "unscoped_fusion": (3.0, "backward", "DenseLayer", "DenseLayer:fc",
                        "backward+other"),
    "who_knows.4": (0.75, "other", "-", "who_knows", ""),
}


def ops_line():
    names, start, end, at = [], [], [], 0.0
    for name, (seconds, *_) in EVENTS.items():
        names.append(name)
        start.append(at)
        end.append(at + seconds)
        at += seconds + 0.125       # the device idles between them
    return Line(names, start, end)


def test_parse_finds_every_instruction_and_what_a_fusion_calls():
    instructions, computations = step_scopes.parse(HLO)
    assert set(computations) == {f"fused_computation.{i}" for i in range(1, 7)} \
        | {"main.9"}
    assert [i.name for i in computations["main.9"]][-2:] == [
        "divide_subtract_fusion", "out.1"]
    fusion = instructions["divide_subtract_fusion"]
    assert (fusion.opcode, fusion.calls) == ("fusion", "fused_computation.2")
    assert fusion.operands == ("x.1", "joined.1", "w.1")    # a tuple's shape
    assert instructions["copy-start.1"].op_name == ""
    assert instructions["cast.1"].op_name.endswith("convert_element_type")
    assert instructions["custom-call.7"].opcode == "custom-call"


def test_parse_joins_an_instruction_that_the_text_breaks_over_lines():
    """jax 0.9.0 writes a Pallas kernel's metadata as JSON with a newline
    after every brace and comma; the custom call then ends on a line that
    starts with `}}`, which is no end of the computation."""
    one_line = (
        '  %splash_mha_fwd_residuals.12 = bf16[8,4]{1,0} custom-call('
        '%fusion.1), custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={"xprof_metadata": '
        '"{\\"block_q\\": 1024, \\"use_fused_bwd_kernel\\": true}"}}, '
        'metadata={op_name="' + FWD.replace("DenseLayer:fc", "A:att")
        + '/splash_mha_fwd_residuals/pallas_call"}')
    broken = one_line.replace('kernel_metadata={', 'kernel_metadata={\n') \
        .replace('"}}, metadata', '"\n}}, metadata')
    assert broken.count("\n") == 2 and "\n}}, metadata=" in broken
    before = "  %custom-call.7 = "
    at = HLO.index(before)
    parsed = {}
    for name, kernel in (("one_line", one_line), ("broken", broken)):
        text = HLO[:at] + kernel + "\n" + HLO[at:]
        instructions, computations = step_scopes.parse(text)
        parsed[name] = instructions
        kernel = instructions["splash_mha_fwd_residuals.12"]
        assert (kernel.opcode, kernel.operands) == ("custom-call",
                                                    ("fusion.1",))
        assert kernel.op_name.endswith("splash_mha_fwd_residuals/pallas_call")
        # nothing after the kernel is lost, and nothing is added
        assert [i.name for i in computations["main.9"]][-2:] == [
            "divide_subtract_fusion", "out.1"]
        assert set(instructions) == set(step_scopes.parse(HLO)[0]) \
            | {"splash_mha_fwd_residuals.12"}
        assert step_scopes.Labels(text).of_event(
            "splash_mha_fwd_residuals.12") == step_scopes.Label(
                "forward", "A", "A:att")
    assert {k: vars(v) for k, v in parsed["broken"].items()} \
        == {k: vars(v) for k, v in parsed["one_line"].items()}


def test_label_of_each_event():
    labels = step_scopes.Labels(HLO)
    for event, (_, phase, cls, name, mixed) in EVENTS.items():
        assert labels.of_event(event) \
            == step_scopes.Label(phase, cls, name, mixed), event


def test_account_sums_to_the_line_and_splits_it_by_phase_and_class():
    ops = ops_line()
    steps = 2
    table = step_scopes.account(step_scopes.Labels(HLO), ops, steps)
    total = sum(s for s, *_ in EVENTS.values())
    assert float(ops.self_seconds.sum()) == total
    assert table["total_ms"] == pytest.approx(total / steps * 1e3, rel=1e-12)
    assert sum(ms for *_, ms in table["by_phase_and_class"]) \
        == pytest.approx(table["total_ms"], rel=1e-12)
    assert sum(table["by_phase_ms"].values()) \
        == pytest.approx(table["total_ms"], rel=1e-12)
    per_step = 1e3 / steps
    assert table["by_phase_ms"] == pytest.approx({
        "forward": 15.0 * per_step, "backward": 38.0 * per_step,
        "optimizer": 0.0, "other": 1.0 * per_step})
    assert table["mixed_ms"] == pytest.approx({
        "backward+optimizer": 10.0 * per_step,
        "backward+other": 3.0 * per_step,
        "forward+backward": 4.0 * per_step})
    # covered: everything but the three mixes and the two without a phase
    assert table["coverage_percent"] == pytest.approx(
        100.0 * (total - 10.0 - 4.0 - 3.0 - 0.25 - 0.75) / total)
    assert table["by_phase_and_class"][0] == pytest.approx(
        ["backward", "DenseLayer", 20.0 * per_step])
    assert [k for k, _ in table["other_or_mixed_kinds"]] == [
        "divide_subtract_fusion (mixed)", "loss_fusion (mixed)",
        "unscoped_fusion (mixed)", "who_knows", "tick"]
    attention = step_scopes.class_ms(table, lambda c: "Attention" in c,
                                     ("forward", "backward"))
    assert attention == pytest.approx(16.0 * per_step)
    assert step_scopes.class_ms(table, lambda c: c == "Embedding") is None


def test_traced_rehearsal_prints_the_new_metrics_and_counts_compiling_steps():
    done = run_cell("--workload", "gpt2s-resident-t2048", "--seed", "11",
                    "--seconds", "1", "--trace", "1", "--rehearse")
    metrics = last_line(done)["metrics"]
    for name in ("forward_ms_per_step", "backward_ms_per_step",
                 "optimizer_ms_per_step", "attention_ms_per_step",
                 "vocab_path_ms_per_step", "step_scope_coverage",
                 "dispatch_ms_per_step"):
        assert metrics[name]["value"] is None, name     # timed: never a CPU's
    assert metrics["steps_that_compiled"]["value"] >= 1
    noted, = [line for line in done.stdout.splitlines()
              if line.startswith("steps_that_compiled ")]
    compiled = json.loads(noted.split(" ", 1)[1])
    assert len(compiled) == metrics["steps_that_compiled"]["value"]
    assert compiled[0]["iteration"] == 0 and compiled[0]["compile_s"] > 0


def test_manifest_is_sound_with_the_eight_entries():
    doc = manifest.load()
    assert manifest.problems(doc) == []
    eight = ["forward_ms_per_step", "backward_ms_per_step",
             "optimizer_ms_per_step", "attention_ms_per_step",
             "vocab_path_ms_per_step", "step_scope_coverage",
             "dispatch_ms_per_step", "steps_that_compiled"]
    # all there, in this order among themselves; later PRs append their own
    assert [m["name"] for m in doc["per_layer"] if m["name"] in eight] == eight
