"""BENCHMARK.json against the contract it is written to, and against the
files it names. Later PRs add entries; these hold for every one of them."""

import json
import os
import re

import pytest

from benchmarks.harness import devices, manifest

REPO = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


def under_paths(doc, path):
    return any(path == p or path.startswith(p + "/") for p in doc["paths"])


def test_top_level_keys_and_limits(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["paths"]) <= 16
    assert all(PLAIN_PATH.match(p) and not p.startswith("/")
               and ".." not in p.split("/") for p in doc["paths"])
    assert 1 <= len(doc["command"]) <= 32
    for word in doc["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(REPO, word)):
            assert under_paths(doc, word), f"{word} is outside paths"
    assert 1 <= len(doc["configs"]) <= 24
    assert 2 <= len(doc["workloads"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16
    assert 1 <= len(doc["per_layer"]) <= 128


def test_every_name_file_and_reference_resolves(doc):
    assert manifest.problems(doc) == []


def test_names_are_plain_and_whys_are_short(doc):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert len(entry.get("why", "")) <= 200, entry["name"]
    for root_dir in doc["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, root_dir)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert PLAIN_PATH.match(rel), rel


def test_configs_name_their_source_and_what_they_changed(doc):
    files = [c["file"] for c in doc["configs"]]
    assert len(set(files)) == len(files)
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert under_paths(doc, c["file"])
        with open(os.path.join(REPO, c["file"]), encoding="utf-8") as fh:
            config = json.load(fh)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in config
            # a width may never be cut
            assert not re.search(r"(_dim|_rank|n_embd|n_inner|hidden|"
                                 r"intermediate|head_size|experts_per)", key)
        assert config["deployment"]["chips"] in (1, 4)


def test_cells_chips_and_traffic(doc):
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        cell = manifest.Cell(doc, w["name"])
        assert cell.config["deployment"]["chips"] == w["chips"]
        assert manifest.traffic_path(w["traffic"]).endswith(TRAFFIC_SUFFIXES)
        assert hasattr(cell.kind, "drive")
        for need in ("Model", "required_flops_per_item", "reference_loss"):
            assert hasattr(cell.family, need)


def test_metrics_have_sources_bounds_and_readers(doc):
    for m in doc["end_to_end"]:
        assert {"name", "unit", "better", "bound", "source"} <= set(m)
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
        assert 0.01 <= m["bound"] <= 0.1
    setup = [m for m in doc["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] == 0.1
    for m in doc["per_layer"]:
        assert {"name", "unit", "better", "source", "layer", "moves"} <= set(m)
        assert "bound" not in m
        assert m["source"] in SOURCES
        assert manifest.LAYER.match(m["layer"]), m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in doc["workloads"]:
        cell = manifest.Cell(doc, w["name"])
        for group in manifest.GROUPS:
            for _, read in cell.metrics(group):
                assert callable(read)


def test_peaks_table_holds_the_v5e_with_its_source():
    row = devices.peaks_table()["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["ici_bits_per_s"] == 1600e9
    assert "Google Cloud documentation" in row["source"]
