#!/usr/bin/env python3
"""One process, one cell of `BENCHMARK.json`, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses any backend but a TPU whose `device_kind` has published peaks
(`harness/peaks.json`) and any device count but the cell's; turns the compile
cache on where the program's entry points keep it; lets the cell's traffic
kind build the model from the seed, warm up the cell's own shapes, measure
for `--seconds` and check correctness outside the window; prints the
contract's JSON object as the last line of stdout. With `--trace 0` its
metrics are the cell's end-to-end metrics, with `--trace 1` (a run of its
own, with the program's tracing on and the profiler over a short slice) its
per-layer metrics, the device's busy time and a breakdown.

`--rehearse` runs the same control flow at the tiny sizes the configuration
and the mix name under `rehearse`, on whatever backend there is, and prints
every timed metric as `null`: it proves paths, arguments and control flow
without the chip, and measures nothing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import devices, manifest, result, xplane
    from benchmarks.harness.compile_meter import CompileMeter
    from benchmarks.harness.run_state import Run

    cell = manifest.Cell(manifest.load(), args.workload, args.rehearse)
    try:
        from deeplearning4j_tpu.util.compile_cache import (
            enable_persistent_compile_cache)
    except ImportError as e:
        print(f"benchmarks/run.py: the program is not in this checkout "
              f"({e}); the benchmark measures it and cannot run without it",
              file=sys.stderr)
        return 2
    try:
        found, facts, peaks = devices.require(cell.chips, args.rehearse)
    except devices.DeviceError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    result.say(f"cell {cell.name}: platform={facts['platform']} "
               f"device_kind={facts['kind']!r} count={facts['count']}"
               f"{' (rehearsal: nothing here is a measurement)' if args.rehearse else ''}")
    meter = CompileMeter().install()
    result.say(f"compile cache: {enable_persistent_compile_cache()}")

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), rehearse=args.rehearse, devices=found,
              device=facts, peaks=peaks, meter=meter, t_start=T_START,
              out_dir=os.path.join(ROOT, "chiprun_out", "benchmarks",
                                   cell.name))
    os.makedirs(run.out_dir, exist_ok=True)
    cell.kind.drive(run)
    if run.step_text is not None:
        with gzip.open(os.path.join(run.out_dir, "step.hlo.txt.gz"), "wt",
                       encoding="utf-8") as fh:
            fh.write(run.step_text)
    if run.xplane_path is not None:
        run.device_trace = xplane.load(run.xplane_path, run.trace_skip_steps)
    result.report(run)
    result.say(result.line(run))
    return 0


if __name__ == "__main__":
    sys.exit(main())
