#!/usr/bin/env python3
"""Run cells the way the driver's check does and report the spread: for each
cell, `--sets` sets of `--runs` runs, every run a new process with another
`--seed` and every set with the same seeds as the first, all in one call so
that they share the machine and the compile cache. This process never
touches JAX, so each run gets the chip.

    chiprun -- python3 benchmarks/tools/measure.py --cells a,b --sets 2 --runs 6

For every end-to-end metric it prints each set's median and spread (the
distance between the quartiles over the median) and, for a cell, the bound
the contract's rule gives: five times the wider spread, never under 1%.
Every result line is appended to `chiprun_out/benchmarks/measure.jsonl`.
`--trace` adds one traced run per cell after the sets, and `--drop-traces`
removes the profiler's raw trace after it (tens of megabytes a cell), so
that what a chip call brings back stays small.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out", "benchmarks")
RUN_TIMEOUT_S = 1500  # a cell's first run in a checkout compiles


def one_run(cell: str, seed: int, seconds: float, trace: int, log,
            extra=()):
    """One process of `run.py`; its metrics' values, or None where it
    failed (its output is shown, and the next run goes ahead)."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), *extra]
    t0 = time.perf_counter()
    try:
        done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                              timeout=RUN_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:
        print(f"  {' '.join(cmd)}: no end after {RUN_TIMEOUT_S} s\n"
              f"{(e.stdout or b'')[-2000:]}\n{(e.stderr or b'')[-2000:]}",
              flush=True)
        return None
    took = time.perf_counter() - t0
    lines = done.stdout.strip().splitlines()
    if done.returncode != 0 or not lines:
        print(f"  {' '.join(cmd)} exited {done.returncode} after "
              f"{took:.1f} s", done.stdout[-3000:], done.stderr[-5000:],
              sep="\n", flush=True)
        return None
    result = json.loads(lines[-1])
    record = {"cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
              "process_s": took, "result": result,
              "notes": [ln for ln in lines[:-1]
                        if ln.startswith(("check", "window"))]}
    log.write(json.dumps(record) + "\n")
    log.flush()
    values = {k: v["value"] for k, v in result["metrics"].items()}
    shown = {k: (round(v, 4) if isinstance(v, float) else v)
             for k, v in values.items()}
    print(f"  {cell} seed {seed} trace {trace}: correct={result['correct']} "
          f"attempted={result['attempted']} failed={result['failed']} "
          f"process {took:.1f} s  {json.dumps(shown)}", flush=True)
    if trace:
        for note in record["notes"]:
            print("    " + note, flush=True)
        print("    device " + json.dumps(result["device"]), flush=True)
        print("    breakdown " + json.dumps(result.get("breakdown")),
              flush=True)
    if not result["correct"]:
        for note in record["notes"]:
            print("    " + note, flush=True)
    return values


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    # the contract's quartiles (the default, exclusive method); numpy's
    # and `method="inclusive"` lie closer together
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seed0", type=int, default=1000)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--drop-traces", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="pass --rehearse on: tries this tool off the chip")
    args = ap.parse_args(argv)
    extra = ("--rehearse",) if args.rehearse else ()

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        doc = json.load(fh)
    seconds = args.seconds or doc["run_seconds"]
    os.makedirs(OUT, exist_ok=True)
    seed, failures = args.seed0, 0
    with open(os.path.join(OUT, "measure.jsonl"), "a", encoding="utf-8") as log:
        for cell in args.cells.split(","):
            print(f"== {cell}, {args.sets} x {args.runs} runs of {seconds} s",
                  flush=True)
            sets = []
            for _ in range(args.sets):
                runs = []
                for i in range(1, args.runs + 1):
                    values = one_run(cell, seed + i, seconds, 0, log, extra)
                    if values is None:
                        failures += 1
                    else:
                        runs.append(values)
                sets.append(runs)
            seed += args.runs
            for metric in (sets[0][0] if sets and sets[0] else {}):
                widest = 0.0
                for i, runs in enumerate(sets):
                    # setup_s: the first run of the call may compile
                    values = [r[metric] for r in runs
                              if r[metric] is not None]
                    if metric == "setup_s" and i == 0:
                        values = values[1:]
                    if not values:
                        continue
                    sp = spread(values)
                    widest = max(widest, 0.0 if sp != sp else sp)
                    print(f"  {cell} {metric} set {i + 1}: median "
                          f"{statistics.median(values):.6g}, spread "
                          f"{100 * sp:.3f}% over {len(values)} "
                          f"(min {min(values):.6g}, max {max(values):.6g})",
                          flush=True)
                print(f"  {cell} {metric}: widest spread {100 * widest:.3f}%"
                      f" -> bound {max(0.01, 5 * widest):.4f}", flush=True)
            if args.trace:
                seed += 1
                if one_run(cell, seed, seconds, 1, log, extra) is None:
                    failures += 1
                if args.drop_traces:
                    shutil.rmtree(os.path.join(OUT, cell, "profile"),
                                  ignore_errors=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
