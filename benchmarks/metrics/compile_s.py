"""Seconds in `backend_compile` events (`jax.monitoring`) during set-up.
A program loaded from the persistent cache still reports a short one."""


def read(run):
    return run.counters.get("compile_s")
