"""Set-up from inside (ISSUE 38): the spans on the path from ``init()`` to
the first step, what the JAX hook hears besides compile and lowering, and
counts that land on the span that caused them.

``fit()`` of a two-layer net under ``enable_tracing()`` records
``model_init``, ``state_commit`` and one ``step_dispatch`` that holds
``jax_trace``, ``jax_lowering`` and ``xla_compile``; with a persistent
compile cache a second process also records ``cache_load`` and counts
``compile_cache.hits`` on the span that paid; ``Tracer.count`` keeps its
process-wide tally; ``thread_compile_seconds`` does not start counting the
new spans; with tracing off nothing is recorded and nothing imported.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observe import (TraceRecorder, Tracer,
                                        disable_tracing, enable_tracing,
                                        text_timeline, to_chrome_trace)
from deeplearning4j_tpu.observe import jaxhook
from deeplearning4j_tpu.parallel import make_mesh
from deeplearning4j_tpu.parallel.sharding import (shard_model,
                                                  shard_model_with_rules)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOOK_SPANS = ("jax_trace", "jax_lowering", "xla_compile")


@pytest.fixture
def tracer():
    tr = enable_tracing(Tracer(TraceRecorder(capacity=16384)))
    yield tr
    disable_tracing()


def _chain():
    return (NeuralNetConfiguration.builder().seed(7).list()
            .layer(DenseLayer(n_in=12, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=3, activation="softmax",
                               loss="negativeloglikelihood")).build())


def _graph():
    return (NeuralNetConfiguration.builder().seed(7).graph_builder()
            .add_inputs("in")
            .add_layer("fc", DenseLayer(n_in=12, n_out=24,
                                        activation="relu"), "in")
            .add_layer("out", OutputLayer(n_in=24, n_out=3,
                                          activation="softmax",
                                          loss="negativeloglikelihood"),
                       "fc")
            .set_outputs("out").build())


ENGINES = {"graph": lambda: ComputationGraph(_graph()),
           "chain": lambda: MultiLayerNetwork(_chain())}


def _data(n=8):
    x = np.linspace(-1, 1, n * 12, dtype=np.float32).reshape(n, 12)
    y = np.eye(3, dtype=np.float32)[np.arange(n) % 3]
    return x, y


class _Resident:
    """Yields one placed batch again and again, as a resident cell does."""

    def __init__(self, ds, steps):
        self.ds, self.steps = ds, steps

    def reset(self):
        pass

    def __iter__(self):
        return iter([self.ds] * self.steps)


def _below(spans, ancestor):
    by_id = {s.span_id: s for s in spans}
    out = []
    for s in spans:
        up = by_id.get(s.parent_id)
        while up is not None and up is not ancestor:
            up = by_id.get(up.parent_id)
        if up is ancestor:
            out.append(s)
    return out


def _named(tr, name):
    return [s for s in tr.recorder.spans() if s.name == name]


# --------------------------------------------------- the spans of a fit()
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_fit_records_set_up_where_it_happens(tracer, engine):
    jax.clear_caches()      # as a fresh process: init()'s programs are new
    net = ENGINES[engine]().init()
    x, y = _data()
    # a batch placed on a device commits the model's trees before step one
    ds = DataSet(jax.device_put(x, jax.devices()[0]),
                 jax.device_put(y, jax.devices()[0]))
    net.fit(_Resident(ds, 2), epochs=1, prefetch_depth=0)

    spans = tracer.recorder.spans()
    init, = _named(tracer, "model_init")
    assert init.category == "setup" and init.parent_id is None
    n_params = 12 * 24 + 24 + 24 * 3 + 3
    assert init.attrs["parameters"] == n_params
    assert init.attrs["bytes"] >= 4 * n_params      # and the updater state
    # init() draws eagerly: every small program it fetched nests under it
    assert {s.name for s in _below(spans, init)} >= set(HOOK_SPANS)

    commit, = _named(tracer, "state_commit")        # a cached tick: no more
    assert commit.category == "setup" and commit.attrs["bytes"] > 0
    assert commit.start_ns >= init.end_ns

    steps = sorted(_named(tracer, "step_dispatch"), key=lambda s: s.start_ns)
    assert len(steps) == 2
    first = {s.name for s in _below(spans, steps[0])}
    assert first >= set(HOOK_SPANS), first
    assert not _below(spans, steps[1])              # the second step is paid
    assert commit.end_ns <= steps[0].start_ns


def test_init_returns_self_and_resets_counters(tracer):
    for make in ENGINES.values():
        net = make()
        net.iteration = net.epoch = 5
        assert net.init(seed=3) is net
        assert (net.iteration, net.epoch) == (0, 0)
        assert net.params is not None and net.updater_states is not None
    assert len(_named(tracer, "model_init")) == 2


@pytest.mark.parametrize("place", ["rules", "replicated", "megatron"])
def test_placing_a_model_is_a_span(tracer, place):
    net = ENGINES["chain"]().init()
    mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    if place == "rules":
        assert shard_model_with_rules(net, mesh) is None
    else:
        shard_model(net, mesh, "model" if place == "megatron" else None)
    span, = _named(tracer, "place_params")
    assert span.category == "setup" and span.attrs["devices"] == 4
    leaves = jax.tree_util.tree_leaves(
        (net.params, net.states, net.updater_states))
    assert span.attrs["leaves"] == len(leaves)
    assert span.attrs["bytes"] == sum(leaf.nbytes for leaf in leaves)
    assert all(len(leaf.sharding.device_set) == 4
               for leaf in jax.tree_util.tree_leaves(net.params))
    assert shard_model_with_rules.__name__ == "shard_model_with_rules"


def test_tracing_off_records_nothing_and_imports_nothing():
    disable_tracing()
    idle = Tracer()                 # made, never enabled
    before = set(sys.modules)
    net = ENGINES["graph"]().init()
    mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    shard_model_with_rules(net, mesh)
    x, y = _data()
    net.fit(ListDataSetIterator(DataSet(x, y), 8), epochs=1)
    assert len(idle.recorder) == 0 and idle.counters == {}
    new = {m for m in set(sys.modules) - before
           if m.startswith("deeplearning4j_tpu.observe")}
    assert not new, new


# ------------------------------------------------------- the hook's events
def test_nested_jits_report_overlapping_traces_recorded_as_siblings(tracer):
    @jax.jit
    def inner(a):
        return jnp.tanh(a) * 2.0

    @jax.jit
    def outer(a):
        return inner(a) + inner(a * 3.0)

    with tracer.span("caller") as caller:
        outer(jnp.ones((4, 4))).block_until_ready()
    traces = [s for s in _named(tracer, "jax_trace")
              if s.parent_id == caller.span_id]
    assert len(traces) >= 2         # the outer jit's, and the inner's in it
    widest = max(traces, key=lambda s: s.end_ns - s.start_ns)
    held = [s for s in traces if s is not widest
            and s.start_ns >= widest.start_ns - 1_000_000
            and s.end_ns <= widest.end_ns]
    assert held, "no inner trace lies inside the outer one's interval"
    # which is why a reader takes the union and never the sum
    assert sum(s.end_ns - s.start_ns for s in traces) > \
        widest.end_ns - widest.start_ns
    assert {s.name for s in tracer.recorder.spans()
            if s.parent_id == caller.span_id} >= set(HOOK_SPANS)
    # each carries the name jax gives the function: a timeline says which
    assert widest.attrs == {"fun_name": "outer"}
    assert {"fun_name": "inner"} in [s.attrs for s in held]
    compiled = [s.attrs["fun_name"] for s in _named(tracer, "xla_compile")
                if s.parent_id == caller.span_id]
    assert "jit(outer)" in compiled


@pytest.mark.parametrize("span_name, counted", [
    ("xla_compile", True), ("jax_lowering", True),
    ("jax_trace", False), ("cache_load", False)])
def test_thread_compile_seconds_counts_compile_and_lowering_only(
        tracer, span_name, counted):
    tracer.note_compile_event(span_name, 0.25)
    span, = _named(tracer, span_name)
    assert span.category == "compile" and span.attrs == {}
    assert abs((span.end_ns - span.start_ns) / 1e9 - 0.25) < 1e-6
    assert tracer.thread_compile_seconds() == (0.25 if counted else 0.0)
    assert tracer.compile_count == (1 if span_name == "xla_compile" else 0)


def test_the_hook_maps_what_jax_says():
    assert jaxhook._EVENT_SPANS == {
        "/jax/core/compile/backend_compile_duration": "xla_compile",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lowering",
        "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}
    # jax still emits under these names
    from jax._src import dispatch
    assert dispatch.JAXPR_TRACE_EVENT in jaxhook._EVENT_SPANS
    assert dispatch.BACKEND_COMPILE_EVENT in jaxhook._EVENT_SPANS
    assert dispatch.JAXPR_TO_MLIR_MODULE_EVENT in jaxhook._EVENT_SPANS


@pytest.mark.parametrize("event, count_name", [
    ("/jax/compilation_cache/cache_hits", "compile_cache.hits"),
    ("/jax/compilation_cache/cache_misses", "compile_cache.misses")])
def test_cache_events_are_counts_on_the_open_span(tracer, event, count_name):
    import jax.monitoring
    with tracer.span("paying") as paying:
        jax.monitoring.record_event(event)
        jax.monitoring.record_event("/jax/compilation_cache/other")
    jax.monitoring.record_event(event)              # under no span
    assert paying.counts == {count_name: 1}
    assert tracer.counters == {count_name: 2}
    disable_tracing()
    jax.monitoring.record_event(event)              # the listener is a no-op
    assert tracer.counters == {count_name: 2}


CACHE_SCRIPT = """
import collections, json, sys
import numpy as np
from deeplearning4j_tpu import observe
from deeplearning4j_tpu.util.compile_cache import (
    enable_persistent_compile_cache)
enable_persistent_compile_cache(sys.argv[1])
tracer = observe.enable_tracing()
from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
conf = (NeuralNetConfiguration.builder().seed(7).list()
        .layer(DenseLayer(n_in=12, n_out=24, activation="relu"))
        .layer(OutputLayer(n_in=24, n_out=3, activation="softmax",
                           loss="negativeloglikelihood")).build())
net = MultiLayerNetwork(conf).init()
x = np.ones((8, 12), np.float32)
y = np.eye(3, dtype=np.float32)[np.arange(8) % 3]
net.fit(ListDataSetIterator(DataSet(x, y), 8), epochs=1)
spans = tracer.recorder.spans()
out = {"counters": tracer.counters}
for s in spans:
    if s.name in ("model_init", "step_dispatch"):
        out[s.name] = {"counts": s.counts, "below": collections.Counter(
            k.name for k in spans if k.parent_id == s.span_id)}
out["compile_s"] = tracer.thread_compile_seconds()
out["in_compile_and_lowering"] = sum(
    (s.end_ns - s.start_ns) / 1e9 for s in spans
    if s.name in ("xla_compile", "jax_lowering"))
print("RESULT " + json.dumps(out))
"""


def test_a_second_process_loads_from_the_cache_and_says_where(tmp_path):
    """Two processes, one cache directory: the first counts misses, the
    second records a ``cache_load`` inside each ``xla_compile`` and counts
    ``compile_cache.hits`` on the span that paid (``init()``'s small
    programs on ``model_init``, the step on ``step_dispatch``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", CACHE_SCRIPT, str(tmp_path / "cache")],
            env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-3000:]
        line, = [l for l in done.stdout.splitlines()
                 if l.startswith("RESULT ")]
        runs.append(json.loads(line[len("RESULT "):]))
    cold, warm = runs
    for run in runs:        # the cost plane's source is what it was
        assert run["compile_s"] == pytest.approx(
            run["in_compile_and_lowering"], rel=1e-6)
    programs = cold["model_init"]["below"]["xla_compile"]
    assert programs > 1
    assert cold["model_init"]["counts"] == {"compile_cache.misses": programs}
    assert cold["step_dispatch"]["counts"]["compile_cache.misses"] == 1
    assert "cache_load" not in cold["model_init"]["below"]
    assert "compile_cache.hits" not in cold["counters"]

    assert warm["model_init"]["counts"] == {"compile_cache.hits": programs}
    assert warm["model_init"]["below"]["cache_load"] == programs
    assert warm["model_init"]["below"]["xla_compile"] == programs
    assert warm["step_dispatch"]["counts"]["compile_cache.hits"] == 1
    assert warm["step_dispatch"]["below"]["cache_load"] == 1
    assert warm["counters"]["compile_cache.hits"] == programs + 1
    assert "compile_cache.misses" not in warm["counters"]
    # the path counters keep their names and land beside them
    assert warm["step_dispatch"]["counts"]["loss.one_hot_calls"] == 1
    assert warm["counters"]["loss.one_hot_calls"] == 1


# ------------------------------------------ a count lands where it was made
def test_count_lands_on_the_innermost_open_span_and_on_the_tally(tracer):
    tracer.count("outside")                         # no span: the tally only
    with tracer.span("outer") as outer:
        tracer.count("a")
        with tracer.span("inner") as inner:
            tracer.count("a")
            tracer.count("b")
        tracer.count("a")
    assert tracer.counters == {"outside": 1, "a": 3, "b": 1}
    assert outer.counts == {"a": 2}
    assert inner.counts == {"a": 1, "b": 1}
    assert all("outside" not in s.counts for s in tracer.recorder.spans())


def test_count_from_a_second_thread_lands_on_that_threads_span(tracer):
    seen = {}

    def work():
        tracer.count("early")       # contextvars do not leak into a thread
        with tracer.span("worker") as sp:
            tracer.count("x")
            tracer.count("x")
        seen["worker"] = sp

    with tracer.span("main") as main:
        t = threading.Thread(target=work)
        t.start()
        t.join()
        tracer.count("x")
    assert main.counts == {"x": 1}
    assert seen["worker"].counts == {"x": 2}
    assert seen["worker"].parent_id is None
    assert tracer.counters == {"early": 1, "x": 3}


def test_counts_from_many_threads_lose_nothing(tracer):
    """More threads than cores, a short switch interval: every count is on
    the tally and on its own thread's span, none on another's."""
    threads, each = 16, 500
    spans = []

    def work():
        with tracer.span("worker") as sp:
            for _ in range(each):
                tracer.count("n")
        spans.append(sp)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in workers)
    assert tracer.counters == {"n": threads * each}
    assert [sp.counts for sp in spans] == [{"n": each}] * threads


def test_record_and_start_span_do_not_become_the_open_span(tracer):
    with tracer.span("open") as open_span:
        manual = tracer.start_span("manual")        # sets no context
        tracer.count("n")
        tracer.end_span(manual)
        recorded = tracer.record("after_the_fact", 1, 2)
        tracer.count("n")
    assert open_span.counts == {"n": 2}
    assert manual.counts == {} and recorded.counts == {}
    assert manual.parent_id == recorded.parent_id == open_span.span_id


def test_counts_are_exported_with_the_span(tracer):
    with tracer.span("step", attrs={"iteration": 0}):
        tracer.count("attention.kernel_calls")
        tracer.count("attention.kernel_calls")
    with tracer.span("quiet"):
        pass
    events = {e["name"]: e for e in
              to_chrome_trace(tracer.recorder.spans())["traceEvents"]
              if e["ph"] == "X"}
    assert events["step"]["args"]["counts"] == {"attention.kernel_calls": 2}
    assert events["step"]["args"]["iteration"] == 0
    assert "counts" not in events["quiet"]["args"]
    json.dumps(events)                              # still plain JSON
    text = text_timeline(tracer.recorder.spans())
    assert "iteration=0  #attention.kernel_calls=2" in text
    assert "#" not in text_timeline(tracer.recorder.spans(), attrs=False)


def test_the_context_keeps_its_public_shape(tracer):
    from deeplearning4j_tpu.observe.trace import current_span_ids
    assert current_span_ids() == (None, None)
    assert tracer.current_context() is None
    with tracer.span("s") as sp:
        assert current_span_ids() == (sp.trace_id, sp.span_id)
        ctx = tracer.current_context()
        assert (ctx.trace_id, ctx.span_id) == (sp.trace_id, sp.span_id)
        assert tracer.current_traceparent() == sp.context.traceparent()
    assert current_span_ids() == (None, None)
