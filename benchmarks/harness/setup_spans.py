"""Set-up as the program's own spans tell it (PR 38).

Until `t0` a run builds its model, places it, traces and lowers its step and
gets every executable from the compiler or the compile cache. The program's
tracer names those parts (`deeplearning4j_tpu/observe`): the spans
`model_init`, `place_params`, `state_commit` and the first `step_dispatch`
from the program's own code, and under them what jax reports, `jax_trace`,
`jax_lowering`, `xla_compile` and `cache_load`, with the cache's hits and
misses and the step's path counters as `counts` on the span that caused
them. Seven readers in `metrics/` reduce them, each to one number, and each
prints one line `"<metric> <json>"` of what it summed before it returns:

- `model_init_s`: seconds in `model_init` spans;
- `param_placement_s`: seconds in `place_params` spans (a mesh's cells);
- `step_trace_s`: seconds of the first `step_dispatch` that jax spent
  tracing and lowering, which no cache saves;
- `cache_load_s`: seconds in `cache_load` spans, all programs;
- `programs_in_setup`: `xla_compile` spans, by the named span above them;
- `setup_span_coverage`: the share of the program's set-up under a named
  span, and the longest gaps with no name;
- `slow_path_calls_in_step`: the first step's counts on the slow side of
  the program's gates.

All seven read spans that start before `t0 = run.t_start + run.setup_s`
(`perf_counter` and the tracer's `perf_counter_ns` are one clock), and all
seven read `None`, and print nothing, where tracing is off or the program's
hook does not record `jax_trace` spans: a program from before PR 38.

jax reports the duration of a trace when it ends, and of an inner `jit`'s
trace inside an outer one, so the tracer's `jax_trace` spans are siblings
whose intervals overlap; lowering traces too. Seconds of such spans are the
length of the union of their intervals, never their sum.
"""

from __future__ import annotations

import json
from typing import Optional

# spans the program opens round its own work, before the window
NAMED = ("model_init", "place_params", "state_commit", "step_dispatch",
         "listeners", "host_wait")
# the spans the hook records from what jax reports
HOOK = ("jax_trace", "jax_lowering", "xla_compile", "cache_load")


class Setup:
    """The spans of one run's set-up, oldest first, clipped to `t0`."""

    def __init__(self, spans: list, t0_ns: int, dropped: int = 0):
        self.t0_ns = t0_ns
        self.dropped = dropped      # spans the ring buffer no longer holds
        self.by_id = {s.span_id: s for s in spans}
        self.spans = sorted((s for s in spans if s.start_ns < t0_ns),
                            key=lambda s: s.start_ns)
        self.first_ns = self.spans[0].start_ns if self.spans else t0_ns
        self.first_step = next((s for s in self.spans
                                if s.name == "step_dispatch"), None)

    def interval(self, span) -> tuple:
        return span.start_ns, min(span.end_ns, self.t0_ns)

    def seconds(self, span) -> float:
        start, end = self.interval(span)
        return (end - start) / 1e9

    def named(self, *names) -> list:
        return [s for s in self.spans if s.name in names]

    def ancestors(self, span):
        """The spans above `span`, nearest first."""
        up = self.by_id.get(span.parent_id)
        while up is not None:
            yield up
            up = self.by_id.get(up.parent_id)

    def above(self, span, names=NAMED) -> Optional[object]:
        """The nearest span above `span` with one of `names`."""
        return next((up for up in self.ancestors(span) if up.name in names),
                    None)

    def under(self, ancestor, *names) -> list:
        """Set-up spans with one of `names` somewhere below `ancestor`."""
        return [s for s in self.named(*names)
                if any(up is ancestor for up in self.ancestors(s))]

    def union(self, spans) -> list:
        """`[(start_ns, end_ns)]`: the intervals of `spans`, merged."""
        merged = []
        for start, end in sorted(self.interval(s) for s in spans):
            if merged and start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], end)
            else:
                merged.append([start, end])
        return [tuple(m) for m in merged]

    def union_seconds(self, spans) -> float:
        return sum(end - start for start, end in self.union(spans)) / 1e9

    def offset_s(self, ns: int) -> float:
        return (ns - self.first_ns) / 1e9


def collect(run) -> Optional[Setup]:
    """The run's set-up, or `None` where nothing can be read: tracing off,
    or a program whose hook records no `jax_trace` span."""
    from deeplearning4j_tpu.observe import get_active_tracer

    tracer = get_active_tracer()
    if tracer is None:
        return None
    spans = [s for s in tracer.recorder.spans() if s.end_ns is not None]
    if not any(s.name == "jax_trace" for s in spans):
        return None
    return Setup(spans, int((run.t_start + run.setup_s) * 1e9),
                 tracer.recorder.dropped)


def counts_of(span) -> dict:
    return dict(getattr(span, "counts", None) or {})


def describe(setup: Setup, span, below: bool = False) -> dict:
    """One span as a line of the log tells it; with `below`, also what jax
    reported underneath it, by kind: how many spans, and their union."""
    above = setup.by_id.get(span.parent_id)
    out = {"span": span.name, "parent": above.name if above else None,
           "at_s": round(setup.offset_s(span.start_ns), 6),
           "seconds": round(setup.seconds(span), 6)}
    if span.attrs:
        out["attrs"] = {k: v for k, v in span.attrs.items()
                        if isinstance(v, (int, float, str))}
    if counts_of(span):
        out["counts"] = counts_of(span)
    if below:
        for name in HOOK:
            spans = setup.under(span, name)
            if spans:
                out[name] = {"spans": len(spans), "seconds": round(
                    setup.union_seconds(spans), 6)}
    return out


def say(metric: str, what) -> None:
    print(metric + " " + json.dumps(what), flush=True)


def seconds_in(run, metric: str, name: str) -> Optional[float]:
    """Seconds in set-up's spans called `name`, each printed: the three
    readers that are one span's sum (`model_init_s`, `param_placement_s`,
    `cache_load_s`). Spans of one name do not nest."""
    setup = collect(run)
    if setup is None:
        return None
    spans = setup.named(name)
    lines = [describe(setup, s, below=name not in HOOK) for s in spans]
    if len(lines) > 40:     # a cache load a program: the longest, and a tally
        by_parent = {}
        for line in lines:
            tally = by_parent.setdefault(str(line["parent"]), [0, 0.0])
            tally[0] += 1
            tally[1] = round(tally[1] + line["seconds"], 6)
        lines = {"spans": len(lines), "by_parent": by_parent,
                 "longest": sorted(lines, key=lambda l: -l["seconds"])[:10]}
    say(metric, lines)
    return sum(setup.seconds(s) for s in spans)
