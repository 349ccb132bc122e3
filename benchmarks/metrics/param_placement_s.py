"""Seconds of set-up in the program's `place_params` spans
(`parallel/sharding.py`: `shard_model_with_rules`, `shard_model`): a model
built on one device is moved over the mesh leaf by leaf. The line before the
value gives each span with its `leaves`, `bytes` and `devices`. Listed for
the cells that have a mesh."""

from benchmarks.harness import setup_spans


def read(run):
    return setup_spans.seconds_in(run, "param_placement_s", "place_params")
