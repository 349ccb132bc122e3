"""`benchmarks/run.py --rehearse` end to end on the CPU: the same control
flow as a chip run at a tiny size, every timed metric `null`, the last line
exactly the contract's keys. And without `--rehearse`, off the chip, no
result at all."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

REPO = manifest.ROOT
TIMED = {"host_clock", "device_trace", "program_span"}


def run_cell(*args, root=REPO, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def last_line(done):
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def check_result(result, cell, group, chips):
    doc = manifest.load()
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed",
                                           "metrics", "device", "checks"}
    assert result["correct"] is True
    # last in the line: every comparison, its numbers beside its limit
    assert list(result)[-1] == "checks" and result["checks"]
    for check in result["checks"].values():
        assert check["ok"] is True and check["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": chips, "memory_peak_bytes": None}
    mine = {m["name"]: m for m in manifest.metrics_of(doc, cell)[group]}
    # every timed metric is there and null; a count is there where its
    # reader found something to count
    timed = {n for n, m in mine.items() if m["source"] in TIMED}
    assert timed <= set(result["metrics"]) <= set(mine)
    for name, got in result["metrics"].items():
        assert set(got) == {"value", "unit"}
        assert got["unit"] == mine[name]["unit"]
        if name in timed:
            assert got["value"] is None
        else:
            assert isinstance(got["value"], (int, float))
    return result["metrics"]


@pytest.mark.parametrize("cell, trace, chips", [
    ("gpt2s-resident-t2048", 0, 1),
    ("gpt2s-resident-t1024", 1, 1),
    ("gpt2l-2x2-resident-t1024", 1, 4),
])
def test_rehearsal_prints_the_contract_line(cell, trace, chips):
    done = run_cell("--workload", cell, "--seed", "7", "--seconds", "1",
                    "--trace", str(trace), "--rehearse")
    metrics = check_result(last_line(done), cell,
                           "per_layer" if trace else "end_to_end", chips)
    assert "rehearsal" in done.stdout.splitlines()[0]
    if trace:
        assert metrics["cache_misses"]["value"] == 0  # no cache on the CPU
        assert metrics["flash_kernels_in_step"]["value"] == 0
    if chips == 4:
        assert metrics["collectives_in_step"]["value"] > 0


def test_without_rehearse_the_cpu_is_refused_and_nothing_is_printed():
    done = run_cell("--workload", "gpt2s-resident-t2048", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert not done.stdout.rstrip().endswith("}")


def test_too_few_devices_is_an_error_before_any_work():
    done = run_cell("--workload", "gpt2l-2x2-resident-t1024", "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--rehearse",
                    extra_env={"XLA_FLAGS":
                               "--xla_force_host_platform_device_count=2"})
    assert done.returncode != 0
    assert "needs 4 devices" in done.stderr
    assert not done.stdout.rstrip().endswith("}")


def test_unknown_cell_is_an_error():
    done = run_cell("--workload", "no-such-cell", "--rehearse")
    assert done.returncode != 0
    assert "no workload 'no-such-cell'" in done.stderr
