"""Shared code of the benchmark: what no single configuration, traffic mix
or metric owns."""
