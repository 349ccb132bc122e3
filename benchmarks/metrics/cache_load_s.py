"""Seconds of set-up in `cache_load` spans, all programs: what jax reports as
`/jax/compilation_cache/cache_retrieval_time_sec` for each executable the
persistent compile cache held, the file read and the deserialisation. Each
lies inside the `xla_compile` span of its program, so this is the part of
`compile_s` that a warm start still pays; 0 where nothing was loaded. The
line before the value gives each load with the span that paid for it (or,
past 40, the ten longest and a tally by that span)."""

from benchmarks.harness import setup_spans


def read(run):
    return setup_spans.seconds_in(run, "cache_load_s", "cache_load")
