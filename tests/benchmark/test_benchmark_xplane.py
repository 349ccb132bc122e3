"""`harness/xplane.py`, the one reduction from a trace to device times:
first on lines made by hand, where every answer can be worked out, then on
a trace recorded on the chip."""

import glob
import json
import os
import types

import pytest

from benchmarks.harness import kernel_costs, manifest, xplane
from benchmarks.harness.xplane import DevicePlane, DeviceTrace, Line

FIXTURES = os.path.join(manifest.BENCH_DIR, "fixtures")


def test_interval_sets():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2), (6, 5)]) \
        == [(0, 2), (3, 4)]
    assert xplane.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert xplane.total([(0, 2), (3, 4)]) == 3
    assert xplane.complement([(1, 2), (3, 4)], 0, 5) \
        == [(0, 1), (2, 3), (4, 5)]
    assert xplane.complement([], 0, 5) == [(0, 5)]


def test_nested_events_are_counted_once():
    #   outer 0..10 holds a 1..4 (which holds b 2..3) and c 5..9; d follows
    line = Line(["c", "outer", "b", "a", "d"],
                [5, 0, 2, 1, 10], [9, 10, 3, 4, 12])
    assert line.names == ["outer", "a", "b", "c", "d"]
    assert line.self_seconds.tolist() == [3, 2, 1, 4, 2]
    assert sum(line.seconds_by_name().values()) == 12  # the union, exactly


def two_step_plane(skip=0):
    # three steps of one program, 10 s each, with a 2 s gap after the first
    # and a 5 s gap after the second; another, short program in between
    modules = Line(["jit_step", "jit_other", "jit_step", "jit_step"],
                   [0, 10.5, 12, 27], [10, 11, 22, 37])
    ops = Line(["fusion.1", "all-reduce.7", "fusion.1", "flash_attention_fwd",
                "fusion.1", "all-reduce-start.2"],
               [0, 6, 12, 18, 27, 33], [6, 10, 18, 22, 33, 37])
    return DevicePlane(0, modules, ops, skip)


def test_steady_window_steps_and_busy():
    plane = two_step_plane()
    assert plane.step_name == "jit_step"
    assert plane.window == (0, 37) and len(plane.steps) == 3
    assert plane.step_seconds().tolist() == [10, 10, 10]
    assert plane.busy_s == 30 and plane.window_s == 37
    assert plane.idle_gaps() == [(10, 12), (22, 27)]
    assert plane.op_seconds() == 30
    assert plane.op_seconds(xplane.COLLECTIVE) == 8
    assert plane.op_seconds(kernel_costs.FLASH_ATTENTION_OPS) == 4
    # what the old reduction got wrong: device time per step can never be
    # more than the window holds
    assert plane.step_seconds().sum() <= plane.window_s


def test_skipped_steps_move_the_window():
    plane = two_step_plane(skip=1)
    assert plane.window == (12, 37) and len(plane.steps) == 2
    assert plane.busy_s == 20
    assert plane.idle_gaps() == [(22, 27)]
    with pytest.raises(ValueError, match="3 step event"):
        two_step_plane(skip=3)


def test_idle_time_is_named_by_the_host_spans_open_meanwhile():
    trace = DeviceTrace({0: two_step_plane()},
                        {xplane.CLOCK_SYNC: [(1.0, 1.1), (30.0, 30.1)]})
    # perf_counter ran 100 s ahead of the trace's clock
    offset = trace.clock_offset([101.0, 130.0])
    assert offset == 100.0
    spans = [("host_wait", 110.0, 111.5),        # covers 10..11.5 of gap 1
             ("host_wait", 122.5, 126.0),        # 22.5..26 of gap 2
             ("make_batch", 123.0, 140.0),       # 23.. : over host_wait too
             ("iteration_done", 90.0, 95.0)]     # before the window
    idle = trace.idle_by_host_span(spans, offset)
    assert idle == pytest.approx({
        "host_wait": 1.5 + 0.5,                  # 10..11.5 and 22.5..23
        "": 0.5 + 0.5,                           # 11.5..12 and 22..22.5
        "host_wait+make_batch": 3.0,             # 23..26
        "make_batch": 1.0})                      # 26..27
    assert sum(idle.values()) == pytest.approx(
        xplane.total(trace.first.idle_gaps()))
    with pytest.raises(ValueError, match="2 bench.clock_sync"):
        trace.clock_offset([101.0])


def test_a_trace_without_a_device_plane_reads_as_none(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.clock_sync"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert xplane.load(path) is None


# ------------------------------------------------- recorded on the chip
# benchmarks/fixtures/gpt2s-resident-t2048.3steps.xplane.pb.gz: the first
# three steps of the traced slice of gpt2s-resident-t2048 on a TPU v5e (my
# chip run, PR 22), cut by benchmarks/tools/cut_xplane.py and gzipped. The
# profiler was on for 1.046 s of wall time (between its two clock marks) and
# nine steps.
def test_recorded_one_chip_trace_reduces_to_sane_device_times():
    trace = xplane.load(os.path.join(
        FIXTURES, "gpt2s-resident-t2048.3steps.xplane.pb.gz"))
    assert sorted(trace.planes) == [0]
    plane = trace.first
    assert plane.step_name.startswith("jit_step")
    assert len(plane.steps) == 3
    steps = plane.step_seconds()
    assert steps == pytest.approx([0.11596] * 3, rel=1e-3)
    # the check the old reduction never had: the steps' device time fits
    # into the window, and the window into the profiled wall time
    assert steps.sum() <= plane.window_s
    sync = sorted(s for s, _ in trace.marks[xplane.CLOCK_SYNC])
    assert plane.window_s <= sync[-1] - sync[0]
    assert plane.busy_s <= plane.window_s
    assert plane.busy_s == pytest.approx(plane.window_s, rel=1e-3)  # resident
    # no operation nests in another on a TPU's line, so the sum over
    # operations is the busy time, and no name is a whole instruction
    assert plane.op_seconds() == pytest.approx(plane.busy_s, rel=1e-9)
    assert not any(" = " in n or n.startswith("%") for n in plane.ops.names)
    # the three flash kernels are found by name, once per layer and step
    flash = [n for n in plane.ops.names
             if kernel_costs.FLASH_ATTENTION_OPS.search(n)]
    assert len(flash) == 3 * 12 * 3
    assert {xplane.kind_of(n).split("_block")[0] for n in flash} == {
        "jvp_jit_flash_attention__", "flash_mha_bwd_dkv", "flash_mha_bwd_dq"}
    per_step = plane.op_seconds(kernel_costs.FLASH_ATTENTION_OPS) / 3
    assert per_step == pytest.approx(0.03529, rel=1e-3)
    assert plane.op_seconds(xplane.COLLECTIVE) == 0
    # the host's marks are on the device's clock: the first step starts
    # after the profiler's first mark and within a millisecond of it
    assert 0 < plane.window[0] - sync[0] < 1e-3
    offset = trace.clock_offset([100.0 + s for s in sync])
    assert offset == pytest.approx(100.0)


# benchmarks/fixtures/gpt2l-2x2-resident-t1024.1step.xplane.pb.gz: one step of
# the traced slice of gpt2l-2x2-resident-t1024 on four chips, the first
# chip's plane only (my chip run, PR 22).
def test_recorded_four_chip_trace_finds_the_collectives_by_name():
    trace = xplane.load(os.path.join(
        FIXTURES, "gpt2l-2x2-resident-t1024.1step.xplane.pb.gz"))
    plane = trace.first
    assert plane.step_name.startswith("jit_step") and len(plane.steps) == 1
    assert plane.step_seconds() == pytest.approx([0.19606], rel=1e-3)
    assert plane.busy_s <= plane.window_s == plane.step_seconds()[0]
    collectives = [n for n in plane.ops.names if xplane.COLLECTIVE.search(n)]
    # as many as the compiled step holds (collectives_in_step, same run)
    assert len(collectives) == 153
    assert {xplane.kind_of(n) for n in collectives} == {"all-reduce"}
    assert plane.op_seconds(xplane.COLLECTIVE) == pytest.approx(0.060247,
                                                                rel=1e-3)
    # under GSPMD the flash gate stands aside: no kernel in this step
    assert plane.op_seconds(kernel_costs.FLASH_ATTENTION_OPS) == 0


# benchmarks/fixtures/gpt2s-resident-t1024.3steps.xplane.pb.gz: the first
# three steps of the traced slice of gpt2s-resident-t1024 on a TPU v5e (my
# chip run, PR 32, seed 2147483621), cut by benchmarks/tools/cut_xplane.py
# and gzipped (179,267 bytes). The kernels are the splash kernels, forward
# and one fused backward.
def test_recorded_splash_trace_holds_two_kernels_a_layer_and_step():
    trace = xplane.load(os.path.join(
        FIXTURES, "gpt2s-resident-t1024.3steps.xplane.pb.gz"))
    plane = trace.first
    assert plane.step_name.startswith("jit_train_step")
    assert len(plane.steps) == 3
    assert plane.step_seconds() == pytest.approx([0.081244] * 3, rel=1e-3)
    assert plane.busy_s == pytest.approx(plane.window_s, rel=1e-3)
    found = [n for n in plane.ops.names
             if kernel_costs.FLASH_ATTENTION_OPS.search(n)]
    assert len(found) == 2 * 12 * 3
    assert {xplane.kind_of(n) for n in found} == {
        "splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}
    by_kind = plane.ops.seconds_by_name(xplane.kind_of)
    assert by_kind["splash_mha_fwd_residuals"] / 3 == pytest.approx(
        0.004983, rel=1e-3)
    assert by_kind["splash_mha_dkv_no_residuals"] / 3 == pytest.approx(
        0.008982, rel=1e-3)
    # the names decide the count: seven products in two kernels
    cost = kernel_costs.attention_causal(plane.ops.names, 8, 12, 1024, 64)
    assert cost["flops"] == 7 * 6_442_450_944
    assert plane.op_seconds(xplane.COLLECTIVE) == 0


@pytest.mark.parametrize("fixture, cell, ms_per_step, share", [
    # stock flash kernels (PR 22): 12 x 9 x 12.885e9 / 197e12 = 7.064 ms of
    # the 35.29 the three kernels took
    ("gpt2s-resident-t2048.3steps", "gpt2s-resident-t2048", 35.292, 20.015),
    # splash, fused backward (PR 32): 12 x 7 x 6.442e9 / 197e12 = 2.747 ms
    # of 13.97
    ("gpt2s-resident-t1024.3steps", "gpt2s-resident-t1024", 13.965, 19.670),
])
def test_the_attention_readers_on_a_trace_of_each_generation(
        fixture, cell, ms_per_step, share):
    with open(os.path.join(manifest.BENCH_DIR, "harness", "peaks.json"),
              encoding="utf-8") as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    run = types.SimpleNamespace(
        device_trace=xplane.load(os.path.join(FIXTURES,
                                              fixture + ".xplane.pb.gz")),
        peaks=peaks, cell=manifest.Cell(manifest.load(), cell))

    def read(metric):
        return manifest._load_module(manifest.metric_path(metric)).read(run)

    assert read("flash_attn_ms_per_step") == pytest.approx(ms_per_step,
                                                           rel=1e-4)
    roofline = read("flash_attention_roofline")
    assert roofline == pytest.approx(share, rel=1e-4) and roofline < 100
    # a trace that holds no attention kernel leaves both out of the line
    run.device_trace = xplane.load(os.path.join(
        FIXTURES, "gpt2l-2x2-resident-t1024.1step.xplane.pb.gz"))
    assert read("flash_attn_ms_per_step") is None
    assert read("flash_attention_roofline") is None
