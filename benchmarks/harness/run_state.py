"""What one run knows: the harness fills the first part, the traffic kind
the second, and every metric reader gets the whole of it."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional


@dataclasses.dataclass
class Run:
    # ---- set by the harness before the traffic kind drives the program
    cell: Any                       # manifest.Cell: name, chips, config, traffic, family
    seed: int
    seconds: float                  # length of the measured window asked for
    trace: bool
    rehearse: bool
    devices: list
    device: dict                    # platform, kind, count as JAX reports them
    peaks: Optional[dict]           # the device's row of peaks.json; None in a rehearsal
    meter: Any                      # CompileMeter, installed before any compile
    t_start: float                  # perf_counter when the process started
    out_dir: str                    # where a traced run leaves its artefacts

    # ---- set by the traffic kind
    setup_s: Optional[float] = None     # t_start to the start of the window
    window_s: Optional[float] = None    # drained wall time of the window
    steps: int = 0                      # steps the window completed
    items: int = 0                      # tokens (or rows, requests) it completed
    step_interval_s: list = dataclasses.field(default_factory=list)  # host time from each step of the window to the next
    attempted: int = 0
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)   # name -> (ok, detail)
    counters: dict = dataclasses.field(default_factory=dict)  # exact counts and host timings
    memory_peak_bytes: Optional[int] = None
    spans: list = dataclasses.field(default_factory=list)    # (name, start, end) on perf_counter's clock, seconds
    xplane_path: Optional[str] = None   # traced run: the profiler's .xplane.pb
    clock_syncs: list = dataclasses.field(default_factory=list)  # perf_counter at each `bench.clock_sync`
    trace_skip_steps: int = 0           # leading steps of the slice that the reduction leaves out
    step_text: Optional[str] = None     # traced run: compiled HLO of the step

    # ---- set by the harness after the run, from xplane_path
    device_trace: Any = None            # xplane.DeviceTrace, or None where no device plane was found

    @functools.cached_property
    def idle_by_host_span(self) -> dict:
        """The first device's idle seconds in the traced slice, by the host
        spans open meanwhile (`DeviceTrace.idle_by_host_span`)."""
        trace = self.device_trace
        return trace.idle_by_host_span(
            self.spans, trace.clock_offset(self.clock_syncs))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(ok for ok, _ in self.checks.values())
