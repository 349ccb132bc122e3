#!/usr/bin/env python3
"""Cut a profiler trace down to what `harness/xplane.py` reads, so that a
few steps of a real chip trace are small enough to commit as a fixture:

    python3 benchmarks/tools/cut_xplane.py <in.xplane.pb> <out.xplane.pb> --steps 3

It keeps, on the first `--devices` `/device:TPU:<n>` planes, the lines `XLA Modules` and
`XLA Ops` up to the end of the first `--steps` events of the program the
device spent most time in; on `/host:CPU` the events whose name starts with
`bench.`; and of each event its start, its duration and the first
`--name-chars` characters of its name (on a TPU an operation is named by its
whole HLO instruction, some hundreds of characters), no statistics.
Needs TensorFlow's copy of the trace's protobuf; by hand only, and no part of
the yardstick, which reads traces with `jax.profiler.ProfileData` alone.
"""

from __future__ import annotations

import argparse
import collections
import sys


def cut(space, steps: int, name_chars: int, devices: int):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        if device and int(plane.name.rsplit(":", 1)[1]) >= devices:
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()}
        until_ps = None
        if device:
            modules = next(l for l in plane.lines if l.name == "XLA Modules")
            spent = collections.Counter()
            for e in modules.events:
                spent[names[e.metadata_id]] += e.duration_ps
            step = spent.most_common(1)[0][0]
            mine = sorted((e for e in modules.events
                           if names[e.metadata_id] == step),
                          key=lambda e: e.offset_ps)[:steps]
            until_ps = (modules.timestamp_ns * 1000 + mine[-1].offset_ps
                        + mine[-1].duration_ps)
        kept = xplane_pb2.XPlane(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            new = xplane_pb2.XLine(id=line.id, name=line.name,
                                   display_name=line.display_name,
                                   timestamp_ns=line.timestamp_ns)
            for e in line.events:
                name = names[e.metadata_id]
                start_ps = line.timestamp_ns * 1000 + e.offset_ps
                if device and start_ps >= until_ps:
                    continue
                if not device and not name.startswith("bench."):
                    continue
                new.events.add(metadata_id=e.metadata_id,
                               offset_ps=e.offset_ps,
                               duration_ps=e.duration_ps)
                kept.event_metadata[e.metadata_id].id = e.metadata_id
                kept.event_metadata[e.metadata_id].name = name[:name_chars]
            if new.events:
                kept.lines.append(new)
        if kept.lines:
            out.planes.append(kept)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("source")
    ap.add_argument("target")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--name-chars", type=int, default=120)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args(argv)

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(args.source, "rb") as fh:
        space.ParseFromString(fh.read())
    small = cut(space, args.steps, args.name_chars, args.devices)
    with open(args.target, "wb") as fh:
        fh.write(small.SerializeToString())
    for plane in small.planes:
        print(plane.name, {l.name: len(l.events) for l in plane.lines})
    return 0


if __name__ == "__main__":
    sys.exit(main())
