"""`BENCHMARK.json` and the files it names.

A cell, a configuration, a traffic mix and a metric are each found by the
name `BENCHMARK.json` gives them, so adding one means adding files and
entries, never editing a file that is there:

- configuration `c`  -> the `file` of its entry (sizes) and, beside it,
                        `<family>.py`: how to build it, and its plain
                        reference
- traffic mix `t`    -> `benchmarks/traffic/<t>.json` (parameters), whose
                        `kind` names `benchmarks/traffic_kinds/<kind>.py`,
                        the generator that reads it
- metric `m`         -> `benchmarks/metrics/<m>.py`, one `read(run)`
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
GROUPS = ("end_to_end", "per_layer")


class ManifestError(ValueError):
    """`BENCHMARK.json` or a file it names is missing or inconsistent."""


def _read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise ManifestError(f"{os.path.relpath(path, ROOT)} does not exist") \
            from None


def _load_module(path: str):
    if not os.path.isfile(path):
        raise ManifestError(f"{os.path.relpath(path, ROOT)} does not exist")
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} {name!r}; there are "
                        f"{[e['name'] for e in entries]}")


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name + ".py")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", name + ".json")


def kind_path(kind: str) -> str:
    return os.path.join(BENCH_DIR, "traffic_kinds", kind + ".py")


def family_path(config_entry: dict, config: dict) -> str:
    return os.path.join(ROOT, os.path.dirname(config_entry["file"]),
                        config["family"] + ".py")


def metrics_of(doc: dict, cell: str) -> dict:
    """`{group: [entry]}`: the metrics the cell reports, which are those
    without a `workloads` list and those whose list names it."""
    return {g: [m for m in doc[g]
                if "workloads" not in m or cell in m["workloads"]]
            for g in GROUPS}


class Cell:
    """One entry of `workloads`, with everything it names loaded. With
    `rehearse`, the `rehearse` objects of the configuration and of the mix
    replace the sizes they name with tiny ones."""

    def __init__(self, doc: dict, name: str, rehearse: bool = False):
        entry = _by_name(doc["workloads"], name, "workload")
        self.name = name
        self.chips = int(entry["chips"])
        config_entry = _by_name(doc["configs"], entry["config"],
                                "configuration")
        self.config = _read_json(os.path.join(ROOT, config_entry["file"]))
        self.traffic = _read_json(traffic_path(entry["traffic"]))
        if rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.family = _load_module(family_path(config_entry, self.config))
        self.kind = _load_module(kind_path(self.traffic["kind"]))
        self._metrics = metrics_of(doc, name)

    def metrics(self, group: str) -> list:
        """`[(entry, read)]` for the metrics of `group` this cell reports."""
        return [(m, _load_module(metric_path(m["name"])).read)
                for m in self._metrics[group]]


def load() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def problems(doc: dict) -> list:
    """Everything in `doc` that names a missing file, an unknown metric or
    an ill-formed name; empty when the manifest is sound."""
    out = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME.match(n):
            out.append(f"{what} name {n!r} is not [A-Za-z0-9][A-Za-z0-9_.-]*")

    seen = set()
    for group in ("configs", "workloads") + GROUPS:
        for e in doc[group]:
            name_ok(e["name"], group)
            if e["name"] in seen:
                out.append(f"name {e['name']!r} is used twice")
            seen.add(e["name"])
    end_to_end = {m["name"] for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["per_layer"]:
        if not LAYER.match(str(m.get("layer", ""))):
            out.append(f"metric {m['name']}: layer {m.get('layer')!r} is not "
                       f"[A-Za-z0-9_][A-Za-z0-9_.-]*")
    for group in GROUPS:
        for m in doc[group]:
            if not os.path.isfile(metric_path(m["name"])):
                out.append(f"metric {m['name']}: no reader "
                           f"{os.path.relpath(metric_path(m['name']), ROOT)}")
            for w in m.get("workloads", ()):
                if w not in cells:
                    out.append(f"metric {m['name']}: unknown workload {w!r}")
    configs = {}
    for c in doc["configs"]:
        path = os.path.join(ROOT, c["file"])
        if not os.path.isfile(path):
            out.append(f"config {c['name']}: no file {c['file']}")
            continue
        configs[c["name"]] = c
        config = _read_json(path)
        if not os.path.isfile(family_path(c, config)):
            out.append(f"config {c['name']}: no family file "
                       f"{os.path.relpath(family_path(c, config), ROOT)}")
    used = set()
    pairs = set()
    for w in doc["workloads"]:
        if w["config"] not in configs:
            out.append(f"workload {w['name']}: unknown config {w['config']!r}")
        used.add(w["config"])
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: config and traffic repeat")
        pairs.add((w["config"], w["traffic"]))
        if not os.path.isfile(traffic_path(w["traffic"])):
            out.append(f"workload {w['name']}: no traffic file "
                       f"{os.path.relpath(traffic_path(w['traffic']), ROOT)}")
        else:
            kind = _read_json(traffic_path(w["traffic"])).get("kind", "")
            if not os.path.isfile(kind_path(kind)):
                out.append(f"workload {w['name']}: traffic kind {kind!r} has "
                           f"no generator")
        mine = metrics_of(doc, w["name"])
        e2e = {m["name"] for m in mine["end_to_end"]}
        if "setup_s" not in e2e or len(e2e) < 2:
            out.append(f"workload {w['name']}: needs setup_s and one more "
                       f"end-to-end metric, has {sorted(e2e)}")
        if not mine["per_layer"]:
            out.append(f"workload {w['name']}: no per-layer metric")
        for m in mine["per_layer"]:
            if m["moves"] not in end_to_end:
                out.append(f"metric {m['name']}: moves {m['moves']!r}, which "
                           f"is no end-to-end metric")
            elif m["moves"] not in e2e:
                out.append(f"metric {m['name']} is reported in "
                           f"{w['name']}, where {m['moves']} is not")
    for name in configs:
        if name not in used:
            out.append(f"config {name}: used by no workload")
    return out
