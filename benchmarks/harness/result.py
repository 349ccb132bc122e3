"""From a finished run to the one JSON line the driver reads."""

from __future__ import annotations

import json
import sys

from benchmarks.harness import xplane

TOP = 10
TIMED = ("host_clock", "device_trace", "program_span")


def say(message: str) -> None:
    print(message, flush=True)


def metrics(run, group: str) -> dict:
    """Every metric of `group` that this cell reports and whose reader found
    something to read. In a rehearsal anything timed is `null`: a time from
    the CPU is never printed under a device metric's name."""
    out = {}
    for entry, read in run.cell.metrics(group):
        value = read(run)
        if run.rehearse and entry["source"] in TIMED:
            value = None
        elif value is None:
            continue
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def breakdown(run) -> dict:
    """Where the traced slice's time went on the first device: the kinds of
    operation that took most of it (`fusion.12` and `fusion.13` are one
    kind), and its idle time by what the host was doing meanwhile."""
    trace = run.device_trace
    ops = sorted(trace.first.ops.seconds_by_name(xplane.kind_of).items(),
                 key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((name or "no span (loop or dispatch)", s)
                   for name, s in run.idle_by_host_span.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [list(kv) for kv in ops],
            "idle_gaps": [list(kv) for kv in gaps]}


def line(run) -> str:
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed,
           "metrics": metrics(run, "per_layer" if run.trace
                              else "end_to_end"),
           "device": device}
    if run.trace and run.device_trace is not None:
        device["busy_s"] = run.device_trace.busy_s
        device["window_s"] = run.device_trace.window_s
        out["breakdown"] = breakdown(run)
    # last: every comparison behind `correct`, its numbers beside its limit
    out["checks"] = {name: {"ok": ok, "compared": detail}
                     for name, (ok, detail) in run.checks.items()}
    return json.dumps(out)


def report(run) -> None:
    """The lines before the last: what a person wants to see."""
    checks = [f"check {name}: {'ok' if ok else 'FAILED'} ({detail})"
              for name, (ok, detail) in run.checks.items()]
    for check in checks:
        say(check)
    say(f"window {run.window_s:.3f} s, {run.steps} steps, {run.items} items "
        f"({run.items / run.window_s:.1f} a second over the whole of it); "
        f"set-up {run.setup_s:.2f} s; counters "
        + json.dumps({k: v for k, v in run.counters.items()
                      if not isinstance(v, list)}))
    # the same comparisons as the last lines of standard error: a record
    # of a run that is not correct keeps the end of each stream
    for check in checks:
        print(check, file=sys.stderr)
    print(f"{run.cell.name}: the run is "
          f"{'correct' if run.correct else 'NOT correct'}", file=sys.stderr,
          flush=True)
