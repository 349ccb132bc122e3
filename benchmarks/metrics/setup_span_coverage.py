"""What of the program's set-up has a name, in percent: the union of the
program's own spans before `t0` (`model_init`, `place_params`,
`state_commit`, every `step_dispatch`, `listeners` and `host_wait` of the
warm-up) over the time from the tracer's first span to `t0`. The rest is
time the program, or the benchmark round it, spent with no span open: the
line before the value lists the five longest such gaps, each with the named
span before it and after it and what jax reported inside it (`jax_trace`,
`jax_lowering`, `xla_compile`, `cache_load`: how many, the length of
their union, and the longest by the name jax gives it). What lies before the tracer's first span (`import jax`, the
devices, the harness's imports) is no part of either side."""

from benchmarks.harness import setup_spans

LONGEST = 5


def read(run):
    setup = setup_spans.collect(run)
    if setup is None or setup.t0_ns <= setup.first_ns:
        return None
    named = setup.named(*setup_spans.NAMED)
    covered = setup.union(named)
    edges = [setup.first_ns] + [t for pair in covered for t in pair] \
        + [setup.t0_ns]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    lines = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:LONGEST]:
        before = [s for s in named if s.end_ns <= start]
        after = [s for s in named if s.start_ns >= end]
        holds = {}
        for name in setup_spans.HOOK:
            inside = [s for s in setup.named(name)
                      if s.start_ns < end and s.end_ns > start
                      and setup.above(s) is None]
            if inside:
                longest = max(inside, key=setup.seconds)
                holds[name] = {
                    "spans": len(inside),
                    "seconds": round(setup.union_seconds(inside), 6),
                    "longest": [longest.attrs.get("fun_name"),
                                round(setup.seconds(longest), 6)]}
        lines.append({
            "at_s": round(setup.offset_s(start), 6),
            "seconds": round((end - start) / 1e9, 6),
            "after": (max(before, key=lambda s: s.end_ns).name if before
                      else "the tracer's first span"),
            "before": (min(after, key=lambda s: s.start_ns).name if after
                       else "t0"),
            "holds": holds})
    total = (setup.t0_ns - setup.first_ns) / 1e9
    named_s = sum(end - start for start, end in covered) / 1e9
    setup_spans.say("setup_span_coverage", {
        "first_span_to_t0_s": round(total, 6), "named_s": round(named_s, 6),
        "spans_dropped": setup.dropped, "longest_gaps": lines})
    return 100.0 * named_s / total
