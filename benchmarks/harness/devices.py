"""The device a run is on, and the published peaks of the devices the
benchmark knows. A device that is not in `peaks.json` is an error, never a
default: every roofline share and every MFU divides by these numbers."""

from __future__ import annotations

import json
import os


class DeviceError(RuntimeError):
    """The devices JAX found are not the ones the cell asks for."""


def peaks_table() -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def require(chips: int, rehearse: bool):
    """`(devices, facts, peaks)` for a cell on `chips` chips. Outside a
    rehearsal the platform must be a TPU whose `device_kind` has published
    peaks, and JAX must hold exactly `chips` of them. A rehearsal takes the
    first `chips` devices of whatever backend there is and gets no peaks."""
    import jax

    found = jax.devices()
    facts = {"platform": found[0].platform, "kind": found[0].device_kind,
             "count": len(found)}
    if rehearse:
        if len(found) < chips:
            raise DeviceError(f"the cell needs {chips} devices, JAX has "
                              f"{len(found)} ({facts})")
        facts["count"] = chips
        return found[:chips], facts, None
    if facts["platform"] != "tpu":
        raise DeviceError(f"JAX found platform {facts['platform']!r}, not "
                          f"'tpu'; a run off the chip measures nothing "
                          f"(--rehearse checks the control flow)")
    if facts["count"] != chips:
        raise DeviceError(f"the cell needs {chips} chip(s), JAX holds "
                          f"{facts['count']}")
    table = peaks_table()
    if facts["kind"] not in table:
        raise DeviceError(f"device_kind {facts['kind']!r} has no entry in "
                          f"peaks.json ({sorted(table)})")
    return found, facts, table[facts["kind"]]


def allocator_peaks(devices) -> list:
    """`peak_bytes_in_use` of each device as it stands now (None where the
    backend does not say); taken before the window, for `memory_peak_bytes`."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def memory_peak_bytes(devices, before_window: list) -> int | None:
    """Peak bytes on the fullest device over the whole run, taken after the
    window and before the checks, where the backend says.

    On this TPU backend the allocator's `peak_bytes_in_use` covers
    parameters, optimizer state and batches, and leaves out what a running
    program takes for its temporaries (activations, logits), which is
    `bytes_reserved`: 3.73e9 of 9.33e9 bytes in `gpt2s-resident-t2048`
    (PERF.md, Findings PR 22). The two peaks need not fall together: on the
    2x2 the allocator peaks in set-up, while device 0 still holds the whole
    model, and the reservation in the step. So per device the peak is the
    larger of

    - the allocator's peak before the window (`before_window`), and
    - what it held inside the window (its peak, if the window raised it,
      else what it holds now: a resident batch and donated state neither
      grow nor shrink) plus the reservation's peak.
    """
    peaks = []
    for d, before in zip(devices, before_window):
        stats = d.memory_stats() or {}
        if before is None or "peak_bytes_in_use" not in stats:
            continue
        held = (stats["peak_bytes_in_use"]
                if stats["peak_bytes_in_use"] > before
                else stats["bytes_in_use"])
        peaks.append(max(before,
                         held + stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None
