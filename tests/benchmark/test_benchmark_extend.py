"""A later PR may add files and entries and may not edit a file that is
there. So a configuration, a traffic kind with its mix, a cell and a metric
of each group are added here, to a copy of the benchmark, as files and
entries only, and the copy rehearses the new cell. In the same copy, with
nothing but `BENCHMARK.json` and `paths`, the benchmark refuses to run."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from benchmarks.harness import manifest

REPO = manifest.ROOT


@pytest.fixture
def copy(tmp_path):
    doc = manifest.load()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in doc["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def run_in(copy, *args, pythonpath):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath)
    return subprocess.run(
        [sys.executable, str(copy / "benchmarks" / "run.py"), *args],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)


def test_added_files_and_entries_are_enough(copy):
    bench = copy / "benchmarks"
    # a configuration of a family that is there: one file of sizes
    config = json.loads((bench / "configs" / "gpt2-small.json").read_text())
    config["rehearse"] = dict(config["rehearse"], n_layer=1, n_head=2)
    (bench / "configs" / "gpt2-dummy.json").write_text(json.dumps(config))
    # a traffic kind, and a mix that it reads
    (bench / "traffic_kinds" / "fit_twice.py").write_text(textwrap.dedent('''
        """A kind of its own: drives the program its own way, and fills the
        same fields of the run."""
        import time

        from benchmarks.harness.planted_tokens import PlantedRule


        def drive(run):
            import jax
            cell, mix = run.cell, run.cell.traffic
            model = cell.family.Model(cell.config, run.seed, run.devices)
            ids = PlantedRule(cell.config["vocab_size"], run.seed).sequences(
                mix["batch"], mix["seq_len"], run.seed)
            batch = model.make_batch(ids)
            model.net.fit(batch)
            jax.block_until_ready(model.net.params)
            t0 = time.perf_counter()
            for _ in range(mix["steps"]):
                model.net.fit(batch)
            jax.block_until_ready(model.net.params)
            run.setup_s = t0 - run.t_start
            run.window_s = time.perf_counter() - t0
            run.steps = run.attempted = mix["steps"]
            run.items = run.steps * ids.size
            run.counters["dummy_count"] = 42
            run.checks["ran"] = (True, "it did")
    '''))
    (bench / "traffic" / "twice.json").write_text(json.dumps(
        {"kind": "fit_twice", "batch": 2, "seq_len": 16, "steps": 2}))
    # a metric of each group: one reader each
    (bench / "metrics" / "dummy_count.py").write_text(
        'def read(run):\n    return run.counters.get("dummy_count")\n')
    (bench / "metrics" / "steps_per_s.py").write_text(
        'def read(run):\n    return run.steps / run.window_s\n')
    # and the entries that name them
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "gpt2-dummy", "source": config["source"],
        "file": "benchmarks/configs/gpt2-dummy.json",
        "reduced": config["reduced"], "why": "a test"})
    doc["workloads"].append({
        "name": "dummy-cell", "config": "gpt2-dummy", "traffic": "twice",
        "chips": 1, "why": "a test"})
    doc["end_to_end"].append({
        "name": "steps_per_s", "unit": "steps/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["dummy-cell"]})
    doc["per_layer"].append({
        "name": "dummy_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "none", "moves": "steps_per_s",
        "workloads": ["dummy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(doc))

    def result(trace):
        done = run_in(copy, "--workload", "dummy-cell", "--seconds", "1",
                      "--trace", str(trace), "--rehearse", pythonpath=REPO)
        assert done.returncode == 0, done.stderr[-4000:]
        return json.loads(done.stdout.strip().splitlines()[-1])

    end_to_end = result(0)
    assert end_to_end["correct"] is True and end_to_end["attempted"] == 2
    # the cells' common metrics, and the one that only this cell has
    assert set(end_to_end["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                          "setup_s", "steps_per_s"}
    per_layer = result(1)["metrics"]
    assert per_layer["dummy_count"] == {"value": 42, "unit": "count"}
    # readers of the other cells' metrics found nothing to read here
    assert "collectives_in_step" not in per_layer

    # the cells that were there are untouched by the additions
    check = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.harness import manifest as m; "
         "import sys; sys.exit(len(m.problems(m.load())))"],
        cwd=copy, env=dict(os.environ, PYTHONPATH=str(copy)),
        capture_output=True, text=True, timeout=120)
    assert check.returncode == 0, check.stdout + check.stderr


def test_the_stream_mix_becomes_a_cell_by_entries_alone(copy):
    """`traffic/stream-b4-t2048.json` and its three readers lay in the
    benchmark without a cell until PR 32. The cell is one entry in
    `workloads` and one in `per_layer` for each reader, and nothing else:
    it is rehearsed here as it stands in `BENCHMARK.json`."""
    doc = json.loads((copy / "BENCHMARK.json").read_text())
    cell, = [w for w in doc["workloads"] if w["name"] == "gpt2s-stream-t2048"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gpt2-small", "stream-b4-t2048", 1)
    mix = json.loads((copy / "benchmarks" / "traffic"
                      / "stream-b4-t2048.json").read_text())
    assert (mix["batches"], mix["batch"], mix["seq_len"]) == ("stream", 4,
                                                              2048)
    assert mix["prefetch_depth"] is None    # fit()'s own default
    readers = {m["name"]: m for m in doc["per_layer"]
               if m["layer"] == "input_pipeline"}
    assert sorted(readers) == ["batch_build_ms", "host_wait_ms_per_step",
                               "transfer_gb_per_step"]
    for m in readers.values():
        assert (m["moves"], m["workloads"]) == ("tokens_per_s",
                                                ["gpt2s-stream-t2048"])
    done = run_in(copy, "--workload", "gpt2s-stream-t2048", "--seed", "3",
                  "--seconds", "1", "--trace", "1", "--rehearse",
                  pythonpath=REPO)
    assert done.returncode == 0, done.stderr[-4000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    # 2 * 32 int32 ids and as many int32 class ids, every step
    assert metrics["transfer_gb_per_step"]["value"] == 2 * (2 * 32 * 4) / 1e9
    assert metrics["batch_build_ms"] == {"value": None, "unit": "ms"}
    assert metrics["host_wait_ms_per_step"] == {"value": None, "unit": "ms"}
    assert "loop_gap_ms_per_step" not in metrics  # the resident cells' own
    # the kernels' readers list this cell; a CPU's step holds no kernel
    assert metrics["flash_kernels_in_step"] == {"value": 0, "unit": "count"}
    assert metrics["flash_attn_ms_per_step"]["value"] is None
    assert metrics["steps_that_compiled"]["value"] >= 1


def test_the_benchmark_alone_refuses_to_run(copy):
    done = run_in(copy, "--workload", "gpt2s-resident-t2048", "--seconds",
                  "1", "--trace", "0", "--rehearse", pythonpath="")
    assert done.returncode != 0
    assert "the program is not in this checkout" in done.stderr
    assert not done.stdout.rstrip().endswith("}")
