"""The seven readers of the program's set-up spans (PR 38,
`benchmarks/harness/setup_spans.py`) against a recorder built by hand, whose
sums are known: spans before and after `t0`, a trace nested in a trace and
one inside a lowering, a gap with no named span over it, a `listeners` span
that `t0` cuts. Then the two rehearsed cells, which must report every one
of them, and the entries in `BENCHMARK.json`."""

import json
import types

import pytest

from benchmarks.harness import manifest, setup_spans
from deeplearning4j_tpu.observe import (TraceRecorder, Tracer,
                                        disable_tracing, enable_tracing)
from tests.benchmark.test_benchmark_rehearse import (
    check_result, last_line, run_cell)

SEVEN = ["model_init_s", "param_placement_s", "step_trace_s", "cache_load_s",
         "programs_in_setup", "setup_span_coverage",
         "slow_path_calls_in_step"]
S = 1_000_000_000
RUN = types.SimpleNamespace(t_start=40.0, setup_s=69.0)     # t0 at 109 s

STEP_COUNTS = {"attention.kernel_calls": 12, "attention.einsum_calls": 1,
               "attention.sharded_kernel_calls": 12, "loss.class_id_calls": 1,
               "activation.gelu_one_branch_calls": 10,
               "activation.gelu_erfc_calls": 2}


def read(name, run=RUN):
    return manifest._load_module(manifest.metric_path(name)).read(run)


def build(tracer, with_traces=True):
    """The timeline of the module docstring, in seconds on the tracer's
    clock; returns the first `step_dispatch`."""
    def add(name, start, end, parent=None, counts=None, **attrs):
        span = tracer.record(name, int(start * S), int(end * S),
                             parent=parent and parent.context, attrs=attrs)
        span.counts.update(counts or {})
        return span

    def trace(*args, **kw):
        if with_traces:
            add("jax_trace", *args, **kw)

    init = add("model_init", 100.0, 102.0, parameters=1000, bytes=12000,
               counts={"compile_cache.hits": 2, "compile_cache.misses": 1})
    trace(100.1, 100.2, init)
    add("jax_lowering", 100.2, 100.3, init)
    add("xla_compile", 100.3, 100.8, init, fun_name="jit(_normal)")
    add("cache_load", 100.3, 100.5, init)
    add("xla_compile", 101.0, 101.5, init, fun_name="jit(_uniform)")
    add("cache_load", 101.0, 101.1, init)
    # 102 to 103: the benchmark's own batch, under no span of the program
    add("xla_compile", 102.2, 102.6, fun_name="jit(balance)")
    add("cache_load", 102.2, 102.3)
    add("place_params", 103.0, 104.0, leaves=30, bytes=12000, devices=4)
    add("host_wait", 104.0, 104.1)
    add("state_commit", 104.1, 104.3, bytes=12000)
    step = add("step_dispatch", 104.3, 108.3, iteration=0,
               counts=dict(STEP_COUNTS, **{"compile_cache.hits": 1}))
    trace(104.4, 105.4, step)                   # the step's own trace
    trace(104.5, 104.7, step)                   # an inner jit's, inside it
    trace(104.8, 105.0, step)
    add("jax_lowering", 105.4, 106.0, step)
    trace(105.5, 105.6, step)                   # lowering traces too
    add("xla_compile", 106.0, 108.2, step, fun_name="jit(train_step)")
    add("cache_load", 106.0, 107.0, step)
    add("listeners", 108.3, 108.4)
    # 108.4 to 108.5: nothing
    add("host_wait", 108.5, 108.6)
    add("step_dispatch", 108.6, 108.7, iteration=1)
    add("listeners", 108.7, 109.2)              # t0 = 109.0 falls in it
    # after t0: the window, and the checks, which build and compile again
    add("step_dispatch", 109.3, 109.4, iteration=2,
        counts={"attention.einsum_calls": 99})
    late = add("model_init", 110.0, 111.0, parameters=1, bytes=1)
    add("xla_compile", 110.1, 110.9, late)
    add("cache_load", 110.1, 110.2, late)
    trace(110.0, 110.1, late)
    add("place_params", 111.0, 112.0)
    return step


@pytest.fixture
def built():
    tracer = enable_tracing(Tracer(TraceRecorder()), jax_hook=False)
    build(tracer)
    yield tracer
    disable_tracing()


def said(capsys, name):
    """The one line a reader printed before its value."""
    line, = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith(name + " ")]
    return json.loads(line[len(name) + 1:])


@pytest.mark.parametrize("name, want", [
    ("model_init_s", 2.0),              # not the one the checks built later
    ("param_placement_s", 1.0),
    ("step_trace_s", 1.6),              # 104.4 to 106.0, each second once
    ("cache_load_s", 0.2 + 0.1 + 0.1 + 1.0),
    ("programs_in_setup", 4),
    ("setup_span_coverage", 100 * 7.9 / 9.0),
    ("slow_path_calls_in_step", 3),     # 1 einsum call and 2 erfc GELUs
])
def test_each_reader_against_sums_made_by_hand(built, capsys, name, want):
    got = read(name)
    assert got == pytest.approx(want, rel=1e-9)
    assert isinstance(got, int) == isinstance(want, int)
    assert said(capsys, name)


def test_what_each_reader_says_before_its_value(built, capsys):
    read("model_init_s")
    line, = said(capsys, "model_init_s")
    assert line == {
        "span": "model_init", "parent": None, "at_s": 0.0, "seconds": 2.0,
        "attrs": {"parameters": 1000, "bytes": 12000},
        "counts": {"compile_cache.hits": 2, "compile_cache.misses": 1},
        "jax_trace": {"spans": 1, "seconds": 0.1},
        "jax_lowering": {"spans": 1, "seconds": 0.1},
        "xla_compile": {"spans": 2, "seconds": 1.0},
        "cache_load": {"spans": 2, "seconds": 0.3}}
    assert built.recorder.spans()[3].attrs == {"fun_name": "jit(_normal)"}

    read("step_trace_s")
    line = said(capsys, "step_trace_s")
    assert (line["span"], line["at_s"], line["seconds"]) == (
        "step_dispatch", 4.3, 4.0)
    assert line["attrs"] == {"iteration": 0}
    # four trace spans, 1.6 s if summed: the two nested in the step's own
    # are counted once, and the one inside the lowering lies beside it
    assert line["jax_trace"] == {"spans": 4, "seconds": 1.1}
    assert line["jax_lowering"] == {"spans": 1, "seconds": 0.6}

    read("cache_load_s")
    assert [(l["parent"], l["seconds"]) for l in said(capsys, "cache_load_s")
            ] == [("model_init", 0.2), ("model_init", 0.1), (None, 0.1),
                  ("step_dispatch", 1.0)]

    read("programs_in_setup")
    assert said(capsys, "programs_in_setup") == {
        "model_init": {"programs": 2, "seconds": 1.0, "cache_loads": 2,
                       "cache_load_s": 0.3, "compile_cache.hits": 2,
                       "compile_cache.misses": 1,
                       "slowest": [["jit(_normal)", 0.5],
                                   ["jit(_uniform)", 0.5]]},
        "none": {"programs": 1, "seconds": 0.4, "cache_loads": 1,
                 "cache_load_s": 0.1, "compile_cache.hits": None,
                 "compile_cache.misses": None,
                 "slowest": [["jit(balance)", 0.4]]},
        "step_dispatch": {"programs": 1, "seconds": 2.2, "cache_loads": 1,
                          "cache_load_s": 1.0, "compile_cache.hits": 1,
                          "compile_cache.misses": 0,
                          "slowest": [["jit(train_step)", 2.2]]}}

    read("setup_span_coverage")
    line = said(capsys, "setup_span_coverage")
    assert (line["first_span_to_t0_s"], line["named_s"],
            line["spans_dropped"]) == (9.0, 7.9, 0)
    assert line["longest_gaps"] == [
        {"at_s": 2.0, "seconds": 1.0, "after": "model_init",
         "before": "place_params",
         "holds": {"xla_compile": {"spans": 1, "seconds": 0.4,
                                   "longest": ["jit(balance)", 0.4]},
                   "cache_load": {"spans": 1, "seconds": 0.1,
                                  "longest": [None, 0.1]}}},
        {"at_s": 8.4, "seconds": 0.1, "after": "listeners",
         "before": "host_wait", "holds": {}}]

    read("slow_path_calls_in_step")
    line = said(capsys, "slow_path_calls_in_step")
    assert line["counts"] == dict(STEP_COUNTS, **{"compile_cache.hits": 1})
    assert line["counts"]["attention.sharded_kernel_calls"] == 12


def test_many_cache_loads_are_said_as_a_tally(built, capsys):
    for i in range(50):
        built.record("cache_load", int((100.0 + i / 100) * S),
                     int((100.005 + i / 100) * S),
                     parent=[s for s in built.recorder.spans()
                             if s.name == "model_init"][0].context)
    assert read("cache_load_s") == pytest.approx(1.4 + 50 * 0.005)
    line = said(capsys, "cache_load_s")
    assert line["spans"] == 54 and len(line["longest"]) == 10
    assert line["by_parent"]["model_init"][0] == 52
    assert line["longest"][0]["seconds"] == 1.0


@pytest.mark.parametrize("name", SEVEN)
@pytest.mark.parametrize("why", ["tracing_off", "no_jax_trace_spans"])
def test_a_program_without_the_spans_reads_none_and_says_nothing(
        capsys, name, why):
    """The parent of PR 38: its hook records no `jax_trace`, its spans carry
    no `counts`. A reader returns None there and does not raise."""
    disable_tracing()
    try:
        if why == "no_jax_trace_spans":
            tracer = enable_tracing(Tracer(TraceRecorder()), jax_hook=False)
            build(tracer, with_traces=False)
            for span in tracer.recorder.spans():
                del span.counts             # a Span of before PR 38
        assert read(name) is None
        assert capsys.readouterr().out == ""
    finally:
        disable_tracing()


def test_spans_without_counts_silence_only_the_reader_of_counts(capsys):
    tracer = enable_tracing(Tracer(TraceRecorder()), jax_hook=False)
    try:
        build(tracer)
        for span in tracer.recorder.spans():
            del span.counts
        assert read("slow_path_calls_in_step") is None
        assert read("programs_in_setup") == 4
        tally = said(capsys, "programs_in_setup")
        assert tally["model_init"]["compile_cache.hits"] == 0
    finally:
        disable_tracing()


def test_no_step_and_an_empty_set_up(capsys):
    tracer = enable_tracing(Tracer(TraceRecorder()), jax_hook=False)
    try:
        tracer.record("jax_trace", 120 * S, 121 * S)    # after t0 only
        assert read("step_trace_s") is None
        assert read("slow_path_calls_in_step") is None
        assert read("setup_span_coverage") is None
        assert read("model_init_s") == 0.0
        assert read("programs_in_setup") == 0
    finally:
        disable_tracing()


def test_union_counts_each_second_once():
    span = lambda a, b: types.SimpleNamespace(start_ns=a, end_ns=b)
    setup = setup_spans.Setup([], t0_ns=100)
    assert setup.union([span(0, 10), span(2, 5), span(10, 12), span(20, 30),
                        span(25, 200)]) == [(0, 12), (20, 100)]
    assert setup.union_seconds([span(0, S), span(0, S)]) == \
        pytest.approx(100e-9)                       # clipped to t0, once
    assert setup.union([]) == []


def test_the_seven_are_entries_at_the_end_and_files_of_their_own():
    doc = manifest.load()
    assert manifest.problems(doc) == []
    assert [m["name"] for m in doc["per_layer"][-7:]] == SEVEN
    mine = {m["name"]: m for m in doc["per_layer"][-7:]}
    assert {n: (m["layer"], m["source"], m["unit"], m["better"], m["moves"])
            for n, m in mine.items()} == {
        "model_init_s": ("train_step", "program_span", "s", "lower",
                         "setup_s"),
        "param_placement_s": ("placement", "program_span", "s", "lower",
                              "setup_s"),
        "step_trace_s": ("compile_cache", "program_span", "s", "lower",
                         "setup_s"),
        "cache_load_s": ("compile_cache", "program_span", "s", "lower",
                         "setup_s"),
        "programs_in_setup": ("compile_cache", "program_counter", "count",
                              "lower", "setup_s"),
        "setup_span_coverage": ("compile_cache", "program_span", "%",
                                "higher", "setup_s"),
        "slow_path_calls_in_step": ("train_step", "program_counter", "count",
                                    "lower", "tokens_per_s")}
    # one lists its cells: only a mesh's cell places parameters
    assert {n: m.get("workloads") for n, m in mine.items()} == dict(
        dict.fromkeys(SEVEN), param_placement_s=["gpt2l-2x2-resident-t1024"])
    for name in SEVEN:
        with open(manifest.metric_path(name), encoding="utf-8") as fh:
            assert fh.read().startswith('"""'), name
    for name in SEVEN:
        assert f"`{name}`" in setup_spans.__doc__


@pytest.mark.parametrize("cell, chips", [
    ("gpt2s-resident-t1024", 1), ("gpt2l-2x2-resident-t1024", 4)])
def test_a_rehearsed_traced_cell_reports_every_one(cell, chips):
    done = run_cell("--workload", cell, "--seed", "11", "--seconds", "1",
                    "--trace", "1", "--rehearse")
    metrics = check_result(last_line(done), cell, "per_layer", chips)
    mine = [n for n in SEVEN if chips == 4 or n != "param_placement_s"]
    assert set(mine) <= set(metrics)
    assert ("param_placement_s" in metrics) == (chips == 4)
    # a rehearsal prints a time as null; that a reader found what it reads
    # shows in the line it says before its value
    lines = {l.split(" ", 1)[0]: json.loads(l.split(" ", 1)[1])
             for l in done.stdout.splitlines()
             if l.split(" ", 1)[0] in SEVEN}
    assert sorted(lines) == sorted(mine)
    assert lines["model_init_s"][0]["attrs"]["parameters"] > 0
    assert lines["model_init_s"][0]["xla_compile"]["spans"] > 1
    assert lines["step_trace_s"]["jax_trace"]["spans"] > 1
    assert lines["step_trace_s"]["jax_lowering"]["spans"] == 1
    assert lines["step_trace_s"]["xla_compile"]["spans"] == 1
    programs = lines["programs_in_setup"]
    assert programs["step_dispatch"]["programs"] == 1
    assert metrics["programs_in_setup"]["value"] == sum(
        g["programs"] for g in programs.values())
    assert lines["setup_span_coverage"]["spans_dropped"] == 0
    assert 0 < lines["setup_span_coverage"]["named_s"] \
        <= lines["setup_span_coverage"]["first_span_to_t0_s"]
    # tiny sequences sit on the einsum side of the gate, one call a layer
    layers = lines["slow_path_calls_in_step"]["counts"][
        "attention.einsum_calls"]
    assert layers >= 1
    assert metrics["slow_path_calls_in_step"]["value"] >= layers
    if chips == 4:
        placed, = lines["param_placement_s"]
        assert placed["attrs"]["devices"] == 4 and placed["attrs"]["leaves"]
        assert "place_params" in programs or placed["seconds"] > 0
