"""Seconds of set-up in the program's `model_init` spans (`init()` of either
engine, `nn/engine.py`): drawing every parameter and every updater moment,
eagerly, one small program a shape, each fetched from the compiler or the
compile cache. The line before the value gives each span with its
`parameters` and `bytes`. None where the program records no such span."""

from benchmarks.harness import setup_spans


def read(run):
    return setup_spans.seconds_in(run, "model_init_s", "model_init")
