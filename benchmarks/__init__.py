"""The benchmark: `BENCHMARK.json` at the root names every cell, and this
package measures one of them per process (`benchmarks/run.py`). See
`benchmarks/README.md` for how a configuration, a traffic mix, a cell or a
metric is added as files."""
