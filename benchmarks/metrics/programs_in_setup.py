"""Programs jax had to get during set-up, from the compiler or from the
compile cache: `xla_compile` spans that start before `t0` (an exact count).
The step is one; the rest are the small programs that eager code fetches
one by one (`init()`'s draws, a `device_put`, the benchmark's own batch).
The line before the value tallies them by the nearest named span above each
(`model_init`, `place_params`, `state_commit`, `step_dispatch`, none): how
many, their seconds, the `cache_load` spans among them with their seconds,
the `compile_cache.hits` / `.misses` counted on those named spans (`null`
for the programs under no span: a count needs a span to land on), and the
three that took longest, by the name jax gives them (`fun_name`)."""

from benchmarks.harness import setup_spans

GROUPS = ("model_init", "place_params", "state_commit", "step_dispatch")
SLOWEST = 3


def read(run):
    setup = setup_spans.collect(run)
    if setup is None:
        return None
    tally = {}
    for span in setup.named("xla_compile", "cache_load"):
        above = setup.above(span, GROUPS)
        group = tally.setdefault(above.name if above else "none", {
            "programs": 0, "seconds": 0.0, "cache_loads": 0,
            "cache_load_s": 0.0, "slowest": []})
        if span.name == "xla_compile":
            group["programs"] += 1
            group["seconds"] += setup.seconds(span)
            group["slowest"].append([span.attrs.get("fun_name"),
                                     round(setup.seconds(span), 6)])
        else:
            group["cache_loads"] += 1
            group["cache_load_s"] += setup.seconds(span)
    for name, group in tally.items():
        for key in ("compile_cache.hits", "compile_cache.misses"):
            group[key] = (None if name == "none" else sum(
                setup_spans.counts_of(s).get(key, 0)
                for s in setup.named(name)))
        group["slowest"] = sorted(group["slowest"],
                                  key=lambda kv: -kv[1])[:SLOWEST]
        group["seconds"] = round(group["seconds"], 6)
        group["cache_load_s"] = round(group["cache_load_s"], 6)
    setup_spans.say("programs_in_setup", tally)
    return sum(group["programs"] for group in tally.values())
