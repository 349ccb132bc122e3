"""Persistent compile-cache misses during set-up (`jax.monitoring`, an
exact count): 0 on every run of a cell in a checkout after its first."""


def read(run):
    return run.counters.get("cache_misses")
