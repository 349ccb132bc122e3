"""Elastic training supervisor + deterministic fault injection.

The reference fixes worker membership at job start
(``SharedTrainingWrapper.java:131-156``) and delegates fault tolerance to
Spark retry; ``tests/test_multiprocess.py`` already proves kill-and-resume
*choreographed by the test*. These tests prove the LIBRARY composition
(``parallel/elastic.py`` + ``util/faultinject.py``):

- the supervisor state machine — restart budgeting under exponential
  backoff, shrink-to-surviving-slice, startup-flake forgiveness, heartbeat
  stall detection, job deadline — driven entirely by a fake launcher and a
  ``ManualTimeSource`` (injectable clock, **no real sleeps**), with
  ``elastic_restarts_total`` and the ``elastic_recovery`` spans asserted;
- generation fencing: checkpoints stamped by a fenced (superseded)
  generation are never chosen for restore, even when the zombie keeps
  writing;
- the ``FaultPlan`` schema/lint/hooks, including the corrupt-checkpoint
  fault exercising ``OrbaxCheckpointManager.restore(fallback=True)`` and
  the DCN drop/duplicate faults exercising the bridge's sequence dedup;
- the CI acceptance proof on real subprocess CPU workers: a 3-process job
  whose worker is SIGKILLed mid-training by a fault plan automatically
  shrinks to the surviving 2-process slice and converges, with final
  params EQUAL to a clean 2-process-shaped run resumed from the same
  checkpoint step.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TOOLS = os.path.join(REPO, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

from validate_fault_plan import validate_file, validate_plan  # noqa: E402

from deeplearning4j_tpu.observe import (  # noqa: E402
    MetricsRegistry,
    TraceRecorder,
    Tracer,
    disable_tracing,
    enable_tracing,
    parse_prometheus_text,
)
from deeplearning4j_tpu.parallel import elastic  # noqa: E402
from deeplearning4j_tpu.parallel.elastic import (  # noqa: E402
    BackoffPolicy,
    ElasticJobFailed,
    ElasticJobSupervisor,
    ElasticWorkerContext,
    GenerationLedger,
    StaleGenerationError,
    WorkerSpec,
    read_step_stamps,
    write_step_stamp,
)
from deeplearning4j_tpu.parallel.time_source import ManualTimeSource  # noqa: E402
from deeplearning4j_tpu.util import faultinject  # noqa: E402


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    """Every test starts and ends with fault injection inactive."""
    faultinject.set_plan(None)
    yield
    faultinject.set_plan(None)


# ---------------------------------------------------------------------------
# fake process world: supervisor unit tests with zero sleeps/subprocesses
# ---------------------------------------------------------------------------

class FakeProc:
    def __init__(self):
        self.rc = None
        self.kill_calls = 0

    def poll(self):
        return self.rc

    def kill(self):
        self.kill_calls += 1
        if self.rc is None:
            self.rc = -9

    def wait(self, timeout=None):
        return self.rc


class FakeWorld:
    """Launcher + scripted scheduler: ``sleep_fn`` advances the manual
    clock and runs the test's script — the supervisor believes time passes
    and processes live/die, but nothing real happens."""

    def __init__(self, clock, tick_seconds=1.0):
        self.clock = clock
        self.tick_seconds = tick_seconds
        self.generations = []  # one {slot: (env, FakeProc)} per generation
        self.script = lambda world: None
        self._beats = 0

    # -- launcher interface ----------------------------------------------
    def launch(self, argv, env, cwd, log_path):
        gen = int(env[elastic.ENV_GENERATION])
        while len(self.generations) < gen:
            self.generations.append({})
        p = FakeProc()
        self.generations[gen - 1][int(env[elastic.ENV_SLOT])] = (env, p)
        return p

    # -- scripting helpers ------------------------------------------------
    @property
    def current(self):
        return self.generations[-1]

    def beat(self, slot):
        env, proc = self.current[slot]
        if proc.rc is not None:
            return
        self._beats += 1
        with open(env[elastic.ENV_HEARTBEAT], "w", encoding="utf-8") as fh:
            fh.write(f"beat{self._beats}")

    def exit(self, slot, rc):
        self.current[slot][1].rc = rc

    def sleep(self, seconds):
        # the supervisor's poll/backoff sleeps all land here: advance the
        # virtual clock by the REQUESTED amount and run one script tick
        self.clock.advance(seconds=max(seconds, self.tick_seconds))
        self.script(self)


class GenTicker:
    """Per-generation tick counter for FakeWorld scripts."""

    def __init__(self):
        self.gen = 0
        self.tick = 0

    def __call__(self, world):
        if len(world.generations) != self.gen:
            self.gen = len(world.generations)
            self.tick = 0
        self.tick += 1
        return self.gen, self.tick


def make_supervisor(tmp_path, num_workers, **kw):
    clock = ManualTimeSource(start_ms=1_000)
    world = FakeWorld(clock)
    reg = MetricsRegistry()
    ports = iter(range(40000, 41000))
    sup = ElasticJobSupervisor(
        WorkerSpec(argv=["worker"], env={}), num_workers,
        ckpt_dir=str(tmp_path / "ckpt"), clock=clock,
        sleep_fn=world.sleep, launcher=world, metrics=reg,
        port_fn=lambda: next(ports), poll_interval_s=1.0, **kw)
    return sup, world, reg


class TestSupervisorStateMachine:
    def test_all_workers_exit_zero_completes(self, tmp_path):
        sup, world, reg = make_supervisor(tmp_path, 2, min_workers=1)
        ticker = GenTicker()

        def script(w):
            _, tick = ticker(w)
            if tick == 1:
                for slot in list(w.current):
                    w.beat(slot)
            elif tick == 2:
                for slot in list(w.current):
                    w.exit(slot, 0)
        world.script = script
        result = sup.run()
        assert result.status == "completed"
        assert len(result.generations) == 1
        assert result.restarts_total == 0
        assert result.final_world == [0, 1]
        # both workers launched with renumbered ids and the shared world
        envs = [world.current[s][0] for s in (0, 1)]
        assert [e[elastic.ENV_PROCESS_ID] for e in envs] == ["0", "1"]
        assert {e[elastic.ENV_NUM_PROCESSES] for e in envs} == {"2"}
        assert len({e[elastic.ENV_TOKEN] for e in envs}) == 1

    def test_crash_loop_exhausts_budget_and_fails_loudly(self, tmp_path):
        """The acceptance-criteria crash loop: a worker that dies after
        every restart burns its budget under backoff (manual clock, no
        sleeps) and the job fails with metrics + recovery spans
        recorded."""
        policy = BackoffPolicy(base_s=2.0, factor=2.0, max_s=60.0,
                               jitter=0.25, max_restarts=2)
        sup, world, reg = make_supervisor(
            tmp_path, 2, min_workers=2, backoff=policy)
        ticker = GenTicker()

        def script(w):
            _, tick = ticker(w)
            if tick == 1:
                for slot in list(w.current):
                    w.beat(slot)   # both live: deaths charge the budget
            elif tick == 2:
                w.exit(0, 1)       # slot 0 crashes, every generation
        world.script = script

        recorder = TraceRecorder()
        enable_tracing(Tracer(recorder), jax_hook=False)
        try:
            with pytest.raises(ElasticJobFailed) as ei:
                sup.run()
        finally:
            disable_tracing()
        result = ei.value.result
        assert result.status == "failed"
        assert "restart budget" in str(ei.value)
        assert "min_workers" in str(ei.value)
        # two budgeted restarts, then the failing third recovery
        assert result.restarts_total == 2
        assert [g.decision for g in result.generations] == \
            ["restart", "restart", "fail"]
        assert result.generations[-1].outcome == "failed"
        assert all(g.primary_slot == 0 for g in result.generations)
        # backoff delays are exactly the policy's deterministic schedule
        expected = [policy.delay(a, seed=f"elastic:0") for a in (1, 2)]
        assert result.backoff_delays == expected
        assert expected[0] != 2.0  # jitter applied
        # metrics: restarts by decision, deaths by reason
        series = parse_prometheus_text(reg.exposition())
        assert series["elastic_restarts_total"][
            (("decision", "restart"),)] == 2
        assert series["elastic_worker_deaths_total"][
            (("reason", "exit"),)] == 3
        # recovery spans: one per recovery round, attributed to the slot
        spans = [s for s in recorder.spans() if s.name == "elastic_recovery"]
        assert len(spans) == 3
        assert all(s.attrs["primary_slot"] == 0 for s in spans)

    def test_shrinks_to_surviving_slice_and_completes(self, tmp_path):
        sup, world, reg = make_supervisor(
            tmp_path, 3, min_workers=2,
            backoff=BackoffPolicy(max_restarts=0))
        ticker = GenTicker()

        def script(w):
            gen, tick = ticker(w)
            if tick == 1:
                for slot in list(w.current):
                    w.beat(slot)
            elif tick == 2 and gen == 1:
                w.exit(1, -9)  # SIGKILL-style death of slot 1
            elif tick == 2:
                for slot in list(w.current):
                    w.exit(slot, 0)
        world.script = script
        result = sup.run()
        assert result.status == "completed"
        assert [g.decision for g in result.generations] == ["shrink", None]
        assert result.generations[0].primary_slot == 1
        assert result.generations[1].world == [0, 2]
        assert result.final_world == [0, 2]
        # surviving slots renumbered to contiguous process ids in slot order
        envs = {s: world.current[s][0] for s in (0, 2)}
        assert envs[0][elastic.ENV_PROCESS_ID] == "0"
        assert envs[2][elastic.ENV_PROCESS_ID] == "1"
        assert envs[0][elastic.ENV_NUM_PROCESSES] == "2"
        # fresh coordinator port + new generation token after recovery
        g1 = world.generations[0][0][0]
        g2 = envs[0]
        assert g1[elastic.ENV_COORDINATOR] != g2[elastic.ENV_COORDINATOR]
        assert g1[elastic.ENV_TOKEN] != g2[elastic.ENV_TOKEN]
        series = parse_prometheus_text(reg.exposition())
        assert series["elastic_restarts_total"][
            (("decision", "shrink"),)] == 1
        assert series["elastic_world_size"][()] == 2
        assert series["elastic_generation"][()] == 2

    def test_startup_flake_retries_without_charging_budget(self, tmp_path):
        """A worker that dies before its first heartbeat is a port race /
        startup flake: relaunched free of charge, budget untouched."""
        sup, world, reg = make_supervisor(
            tmp_path, 2, min_workers=2,
            backoff=BackoffPolicy(max_restarts=0))
        ticker = GenTicker()

        def script(w):
            gen, tick = ticker(w)
            if gen == 1:
                if tick == 1:
                    w.exit(0, 1)  # dies before ever heartbeating
            else:
                if tick == 1:
                    for slot in list(w.current):
                        w.beat(slot)
                elif tick == 2:
                    for slot in list(w.current):
                        w.exit(slot, 0)
        world.script = script
        result = sup.run()
        assert result.status == "completed"
        assert result.generations[0].decision == "restart"
        assert result.backoff_delays == []  # startup retry: no backoff
        # with max_restarts=0 a BUDGET charge would have failed the job;
        # completing proves the death was treated as a startup flake

    def test_heartbeat_stall_is_killed_and_recovered(self, tmp_path):
        sup, world, reg = make_supervisor(
            tmp_path, 2, min_workers=2, heartbeat_timeout_s=5.0,
            backoff=BackoffPolicy(max_restarts=1, base_s=1.0, jitter=0.0))
        ticker = GenTicker()

        def script(w):
            gen, tick = ticker(w)
            if gen == 1:
                if tick == 1:
                    for slot in list(w.current):
                        w.beat(slot)
                else:
                    w.beat(1)  # slot 0 goes silent but stays running
            else:
                if tick == 1:
                    for slot in list(w.current):
                        w.beat(slot)
                elif tick == 2:
                    for slot in list(w.current):
                        w.exit(slot, 0)
        world.script = script
        result = sup.run()
        assert result.status == "completed"
        assert result.restarts_total == 1
        stalled = world.generations[0][0][1]
        assert stalled.kill_calls >= 1  # supervisor killed the hung proc
        series = parse_prometheus_text(reg.exposition())
        assert series["elastic_worker_deaths_total"][
            (("reason", "stall"),)] == 1

    def test_job_deadline_fails_loudly(self, tmp_path):
        sup, world, reg = make_supervisor(
            tmp_path, 1, job_deadline_s=30.0)
        world.script = lambda w: w.beat(0)  # beats forever, never exits
        with pytest.raises(ElasticJobFailed) as ei:
            sup.run()
        assert "deadline" in str(ei.value)
        assert world.current[0][1].kill_calls >= 1

    def test_constructor_validates_worker_counts(self, tmp_path):
        with pytest.raises(ValueError):
            ElasticJobSupervisor(WorkerSpec(argv=["w"]), 2, min_workers=3,
                                 ckpt_dir=str(tmp_path))
        with pytest.raises(ValueError):
            ElasticJobSupervisor(WorkerSpec(argv=["w"]), 0,
                                 ckpt_dir=str(tmp_path))


    def test_refuses_local_workers_sharing_a_tpu(self, tmp_path, monkeypatch):
        """N > 1 subprocess workers on a TPU host: all but the first die on
        libtpu's lockfile. The supervisor says so instead of retrying."""
        from deeplearning4j_tpu.parallel import elastic
        monkeypatch.setattr(elastic.glob, "glob",
                            lambda pat: ["/dev/vfio/1"] if "vfio" in pat
                            else [])
        on_tpu = WorkerSpec(argv=["w"], env={"JAX_PLATFORMS": "tpu,cpu"})
        with pytest.raises(ValueError, match="one worker per host"):
            ElasticJobSupervisor(on_tpu, 2, ckpt_dir=str(tmp_path))
        # one worker, workers held to the CPU, or an injected launcher
        # (which may start them anywhere) are all fine
        ElasticJobSupervisor(on_tpu, 1, ckpt_dir=str(tmp_path))
        ElasticJobSupervisor(WorkerSpec(argv=["w"],
                                        env={"JAX_PLATFORMS": "cpu"}),
                             2, ckpt_dir=str(tmp_path))
        ElasticJobSupervisor(on_tpu, 2, ckpt_dir=str(tmp_path),
                             launcher=object())


class TestBackoffPolicy:
    def test_deterministic_and_bounded(self):
        p = BackoffPolicy(base_s=1.0, factor=2.0, max_s=8.0, jitter=0.1,
                          max_restarts=5)
        a = [p.delay(i, seed="job:0") for i in range(1, 7)]
        b = [p.delay(i, seed="job:0") for i in range(1, 7)]
        assert a == b  # no RNG state: pure function of (seed, attempt)
        for i, d in enumerate(a, start=1):
            nominal = min(8.0, 1.0 * 2.0 ** (i - 1))
            assert abs(d - nominal) <= nominal * 0.1 + 1e-9

    def test_jitter_desynchronizes_seeds(self):
        p = BackoffPolicy(base_s=10.0, jitter=0.2)
        delays = {p.delay(1, seed=f"job:{s}") for s in range(8)}
        assert len(delays) > 1

    def test_zero_jitter_is_exact_exponential(self):
        p = BackoffPolicy(base_s=0.5, factor=3.0, max_s=100.0, jitter=0.0)
        assert [p.delay(i) for i in (1, 2, 3)] == [0.5, 1.5, 4.5]


# ---------------------------------------------------------------------------
# generation fencing
# ---------------------------------------------------------------------------

class TestGenerationFencing:
    def test_post_fence_zombie_stamp_is_not_eligible(self, tmp_path):
        d = str(tmp_path)
        ledger = GenerationLedger(d)
        ledger.open_generation(1, "t1", [0, 1])
        write_step_stamp(d, 1, "t1", 1, 2)
        write_step_stamp(d, 2, "t1", 1, 2)
        assert ledger.eligible("t1", 1) and ledger.eligible("t1", 2)
        ledger.fence("t1")
        # a zombie from generation 1 keeps writing after the fence
        write_step_stamp(d, 3, "t1", 1, 2)
        assert ledger.eligible("t1", 2)        # committed before the fence
        assert not ledger.eligible("t1", 3)    # written after it
        assert not ledger.eligible("unknown", 1)

    def test_supervisor_restore_choice_respects_fence(self, tmp_path):
        sup, world, reg = make_supervisor(tmp_path, 1)
        d = sup.ckpt_dir
        sup.ledger.open_generation(1, "t1", [0])
        write_step_stamp(d, 1, "t1", 1, 1)
        assert sup.latest_eligible_step() == 1
        sup.ledger.fence("t1")
        write_step_stamp(d, 5, "t1", 1, 1)  # zombie write: newest on disk
        assert sup.latest_eligible_step() == 1
        sup.ledger.open_generation(2, "t2", [0])
        write_step_stamp(d, 2, "t2", 2, 1)
        assert sup.latest_eligible_step() == 2

    def test_new_ledger_over_existing_dir_fences_old_lineage(self, tmp_path):
        d = str(tmp_path)
        first = GenerationLedger(d)
        first.open_generation(1, "t1", [0])
        write_step_stamp(d, 1, "t1", 1, 1)
        # supervisor crashed without fencing; a NEW supervisor loads the
        # ledger: the old generation is fenced against current stamps
        second = GenerationLedger(d)
        assert second.eligible("t1", 1)
        write_step_stamp(d, 9, "t1", 1, 1)  # zombie writes post-takeover
        assert not second.eligible("t1", 9)

    def test_torn_stamp_is_skipped(self, tmp_path):
        d = str(tmp_path)
        write_step_stamp(d, 1, "t1", 1, 2)
        with open(os.path.join(d, "elastic_step_00000002.json"), "w") as fh:
            fh.write('{"step": 2, "tok')  # torn mid-write
        stamps = read_step_stamps(d)
        assert [s["step"] for s in stamps] == [1]

    def test_worker_check_fence_raises_when_superseded(self, tmp_path):
        d = str(tmp_path)
        ctx = ElasticWorkerContext(
            coordinator="127.0.0.1:1", num_processes=2, process_id=0,
            slot=0, generation=1, token="t1", ckpt_dir=d,
            heartbeat_path=os.path.join(d, "hb"), restore_step=None)
        ctx.check_fence()  # no generation file: standalone run, fine
        elastic._atomic_write(
            os.path.join(d, elastic.GENERATION_FILE),
            json.dumps({"generation": 1, "token": "t1", "world_size": 2}))
        ctx.check_fence()  # own generation: fine
        elastic._atomic_write(
            os.path.join(d, elastic.GENERATION_FILE),
            json.dumps({"generation": 2, "token": "t2", "world_size": 1}))
        with pytest.raises(StaleGenerationError):
            ctx.check_fence()
        with pytest.raises(StaleGenerationError):
            ctx.save_checkpoint(3, model=None)  # fence precedes any write

    def test_worker_context_env_round_trip(self, tmp_path):
        env = {
            elastic.ENV_COORDINATOR: "127.0.0.1:999",
            elastic.ENV_NUM_PROCESSES: "3",
            elastic.ENV_PROCESS_ID: "1",
            elastic.ENV_SLOT: "2",
            elastic.ENV_GENERATION: "4",
            elastic.ENV_TOKEN: "g4-abc",
            elastic.ENV_CKPT_DIR: str(tmp_path),
            elastic.ENV_HEARTBEAT: str(tmp_path / "hb"),
            elastic.ENV_RESTORE_STEP: "7",
        }
        ctx = ElasticWorkerContext.from_env(env)
        assert (ctx.num_processes, ctx.process_id, ctx.slot) == (3, 1, 2)
        assert ctx.restore_step == 7
        env[elastic.ENV_RESTORE_STEP] = ""
        assert ElasticWorkerContext.from_env(env).restore_step is None
        assert ElasticWorkerContext.from_env({}) is None
        ctx.heartbeat(5)
        with open(ctx.heartbeat_path, encoding="utf-8") as fh:
            assert fh.read() == "4:5:1"
        # master-state paths are keyed by world size AND rank
        assert ctx.master_state_path(7).endswith(
            "master_state.step00000007.w3.r1.npz")
        # fence-eligible steps ride the env too (fallback allow-list)
        env[elastic.ENV_ELIGIBLE_STEPS] = "3,5,7"
        assert ElasticWorkerContext.from_env(env).eligible_steps == [3, 5, 7]
        env[elastic.ENV_ELIGIBLE_STEPS] = ""
        assert ElasticWorkerContext.from_env(env).eligible_steps == []
        del env[elastic.ENV_ELIGIBLE_STEPS]
        assert ElasticWorkerContext.from_env(env).eligible_steps is None

    def test_prune_unretained_drops_rotated_stamps_and_master_state(
            self, tmp_path):
        """Orbax rotation caps model-checkpoint disk; the stamps and the
        model-sized per-rank master-state shards for rotated-away steps
        must go with it."""
        d = str(tmp_path)
        ctx = ElasticWorkerContext(
            coordinator="", num_processes=2, process_id=0, slot=0,
            generation=1, token="t1", ckpt_dir=d,
            heartbeat_path=str(tmp_path / "hb"), restore_step=None)
        for s in (1, 2, 3):
            write_step_stamp(d, s, "t1", 1, 2)
            for r in (0, 1):
                with open(ctx.master_state_path(s, rank=r), "wb") as fh:
                    fh.write(b"x")

        class _Mgr:
            def all_steps(self):
                return [2, 3]  # step 1 rotated away

        ctx._prune_unretained(_Mgr())
        assert [s["step"] for s in read_step_stamps(d)] == [2, 3]
        assert not os.path.exists(ctx.master_state_path(1, rank=0))
        assert not os.path.exists(ctx.master_state_path(1, rank=1))
        assert os.path.exists(ctx.master_state_path(2, rank=0))
        assert os.path.exists(ctx.master_state_path(3, rank=1))


# ---------------------------------------------------------------------------
# fault plan: schema, lint, hooks
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_and_find(self):
        plan = faultinject.FaultPlan.parse({"faults": [
            {"type": "kill", "worker": 1, "step": 10},
            {"type": "drop_dcn", "worker": "*", "step": 3},
        ]})
        assert plan.find("kill", 1, 10) is not None
        assert plan.find("kill", 0, 10) is None
        assert plan.find("kill", 1, 9) is None
        assert plan.find("drop_dcn", "anything", 3) is not None

    @pytest.mark.parametrize("bad,msg", [
        ({"faults": "x"}, "list"),
        ({"faults": [{"type": "nope", "step": 1}]}, "unknown type"),
        ({"faults": [{"type": "kill", "worker": -1, "step": 1}]}, "worker"),
        ({"faults": [{"type": "kill", "step": -2}]}, "step"),
        ({"faults": [{"type": "corrupt_checkpoint", "step": 1,
                      "mode": "zap"}]}, "mode"),
        ({"faults": [{"type": "kill", "step": 1,
                      "signal": "NOSUCH"}]}, "signal"),
        ({"faults": [{"type": "kill", "step": 1, "bogus": 1}]}, "unknown"),
        ({}, "faults"),
    ])
    def test_schema_errors(self, bad, msg):
        with pytest.raises(ValueError, match=msg):
            faultinject.FaultPlan.parse(bad)

    def test_lint_duplicates_and_shadowed(self):
        plan = faultinject.FaultPlan.parse({"faults": [
            {"type": "kill", "worker": 1, "step": 5},
            {"type": "kill", "worker": 1, "step": 5},
            {"type": "stall_heartbeat", "worker": 1, "step": 9},
        ]})
        problems = plan.lint()
        assert any("duplicates" in p for p in problems)
        assert any("can never fire" in p for p in problems)
        clean = faultinject.FaultPlan.parse({"faults": [
            {"type": "kill", "worker": 1, "step": 5},
            {"type": "stall_heartbeat", "worker": 2, "step": 9},
        ]})
        assert clean.lint() == []

    def test_load_inline_json_and_file(self, tmp_path):
        spec = '{"faults": [{"type": "kill", "worker": 0, "step": 1}]}'
        assert len(faultinject.FaultPlan.load(spec).faults) == 1
        p = tmp_path / "plan.json"
        p.write_text(spec)
        assert len(faultinject.FaultPlan.load(str(p)).faults) == 1

    def test_hooks_are_noops_without_a_plan(self):
        assert faultinject.active_plan() is None
        faultinject.on_step(0, 1)
        assert faultinject.on_heartbeat(0, 1) is True
        assert faultinject.on_dcn_send(0, 1, b"x") == [b"x"]
        faultinject.on_checkpoint_saved(0, 1, "/nonexistent")

    def test_on_step_kill_fires_exactly_at_trigger(self, monkeypatch):
        killed = []
        monkeypatch.setattr(faultinject, "_kill",
                            lambda pid, sig: killed.append((pid, sig)))
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "kill", "worker": 1, "step": 10, "signal": "KILL"}]}))
        faultinject.on_step(1, 9)
        faultinject.on_step(0, 10)
        assert killed == []
        faultinject.on_step(1, 10)
        assert killed == [(os.getpid(), 9)]

    def test_on_step_stall_sleeps_for_duration(self, monkeypatch):
        slept = []
        monkeypatch.setattr(faultinject, "_sleep", slept.append)
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "stall", "worker": 0, "step": 3, "duration_s": 7.5}]}))
        faultinject.on_step(0, 3)
        assert slept == [7.5]

    def test_heartbeat_suppression_is_sticky(self):
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "stall_heartbeat", "worker": 2, "step": 5}]}))
        assert faultinject.on_heartbeat(2, 4) is True
        assert faultinject.on_heartbeat(2, 5) is False
        assert faultinject.on_heartbeat(2, 50) is False  # never resumes
        assert faultinject.on_heartbeat(1, 50) is True

    def test_dcn_drop_and_duplicate(self):
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "drop_dcn", "worker": "A", "step": 1},
            {"type": "duplicate_dcn", "worker": "A", "step": 2}]}))
        assert faultinject.on_dcn_send("A", 0, b"f") == [b"f"]
        assert faultinject.on_dcn_send("A", 1, b"f") == []
        assert faultinject.on_dcn_send("A", 2, b"f") == [b"f", b"f"]

    def test_corrupt_checkpoint_modes(self, tmp_path):
        f = tmp_path / "ckpt.bin"
        f.write_bytes(b"x" * 100)
        faultinject.corrupt_checkpoint(str(f), mode="truncate")
        assert f.stat().st_size == 50
        f.write_bytes(b"x" * 100)
        faultinject.corrupt_checkpoint(str(f), mode="garbage")
        assert b"\xff" in f.read_bytes()
        d = tmp_path / "stepdir" / "inner"
        d.mkdir(parents=True)
        (d / "a.bin").write_bytes(b"y" * 10)
        touched = faultinject.corrupt_checkpoint(
            str(tmp_path / "stepdir"), mode="delete")
        assert len(touched) == 1 and not (d / "a.bin").exists()
        with pytest.raises(FileNotFoundError):
            faultinject.corrupt_checkpoint(str(tmp_path / "nope"))
        with pytest.raises(ValueError):
            faultinject.corrupt_checkpoint(str(f), mode="zap")


class TestFaultPlanValidator:
    def test_shipped_example_plan_is_clean(self):
        path = os.path.join(REPO, "examples", "fault_plan.json")
        assert validate_file(path) == []
        assert validate_file(path, num_workers=3) == []

    def test_schema_and_lint_problems_reported(self, tmp_path):
        assert validate_plan({"faults": []}) == ["schema: no faults defined"]
        problems = validate_plan({"faults": [
            {"type": "kill", "worker": 0, "step": 1},
            {"type": "kill", "worker": 0, "step": 1}]})
        assert any(p.startswith("lint:") for p in problems)
        assert validate_plan({"faults": [{"type": "wat", "step": 1}]})[0] \
            .startswith("schema:")
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert "unreadable" in validate_file(str(bad))[0]

    def test_workers_bound_check(self):
        problems = validate_plan(
            {"faults": [{"type": "kill", "worker": 5, "step": 1}]},
            num_workers=3)
        assert any("5" in p and "3 workers" in p for p in problems)


# ---------------------------------------------------------------------------
# DCN sequence dedup (satellite of the duplicate_dcn fault)
# ---------------------------------------------------------------------------

class _FrameQueue:
    def __init__(self):
        self.frames = []

    def publish(self, frame):
        self.frames.append(frame)

    def poll(self, timeout=0.0):
        return self.frames.pop(0) if self.frames else None


class TestDcnSequenceDedup:
    def _bridge_pair(self):
        from deeplearning4j_tpu.parallel.dcn import CrossSliceGradientBridge
        a_out, b_out = _FrameQueue(), _FrameQueue()
        a = CrossSliceGradientBridge(a_out, b_out, threshold=1e-3,
                                     slice_id="A")
        b = CrossSliceGradientBridge(b_out, a_out, threshold=1e-3,
                                     slice_id="B")
        return a, b, a_out

    def test_replayed_frame_applied_once(self):
        a, b, a_out = self._bridge_pair()
        params_a = [{"w": np.zeros(32, np.float32)}]
        a.publish_update(params_a)  # first call: baseline, no frame
        params_a = [{"w": np.full(32, 0.5, np.float32)}]
        assert a.publish_update(params_a) > 0
        frame = a_out.frames[-1]
        a_out.frames.append(frame)  # broker re-delivery: same frame twice
        params_b = [{"w": np.zeros(32, np.float32)}]
        params_b, applied = b.poll_and_apply(params_b)
        assert applied == 1  # duplicate dropped, update applied ONCE
        np.testing.assert_allclose(np.asarray(params_b[0]["w"]), 0.5,
                                   atol=2e-3)

    def test_duplicate_dcn_fault_sends_twice_receiver_dedups(self):
        a, b, a_out = self._bridge_pair()
        params_a = [{"w": np.zeros(16, np.float32)}]
        a.publish_update(params_a)  # all-zero baseline: no frame, seq unused
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "duplicate_dcn", "worker": "A", "step": 0}]}))
        params_a = [{"w": np.ones(16, np.float32)}]
        a.publish_update(params_a)
        assert len(a_out.frames) == 2  # the fault duplicated seq 0
        params_b = [{"w": np.zeros(16, np.float32)}]
        params_b, applied = b.poll_and_apply(params_b)
        assert applied == 1
        # sparse frames carry ±threshold quanta: ONE application leaves
        # exactly one quantum — a double-apply would show 2e-3
        np.testing.assert_allclose(np.asarray(params_b[0]["w"]), 1e-3,
                                   rtol=1e-5)

    def test_drop_dcn_fault_loses_frame_in_transit(self):
        a, b, a_out = self._bridge_pair()
        params_a = [{"w": np.zeros(16, np.float32)}]
        a.publish_update(params_a)  # all-zero baseline: no frame, seq unused
        faultinject.set_plan(faultinject.FaultPlan.parse({"faults": [
            {"type": "drop_dcn", "worker": "A", "step": 0}]}))
        a.publish_update([{"w": np.ones(16, np.float32)}])
        assert a_out.frames == []  # dropped in transit
        faultinject.set_plan(None)
        # the NEXT exchange still carries fresh movement (seq advanced)
        assert a.publish_update([{"w": np.full(16, 2.0, np.float32)}]) > 0
        meta_len = int.from_bytes(a_out.frames[-1][:4], "big")
        meta = json.loads(a_out.frames[-1][4:4 + meta_len])
        assert meta["seq"] == 1

    def test_restarted_sender_is_not_mistaken_for_a_replay(self):
        """Elastic recovery rebuilds the bridge with its seq back at 0;
        the fresh incarnation token must keep the peer from discarding
        every post-restart frame as a duplicate."""
        from deeplearning4j_tpu.parallel.dcn import CrossSliceGradientBridge
        a, b, a_out = self._bridge_pair()
        a.publish_update([{"w": np.zeros(16, np.float32)}])
        assert a.publish_update([{"w": np.ones(16, np.float32)}]) > 0
        old_frame = a_out.frames[-1]
        params_b = [{"w": np.zeros(16, np.float32)}]
        params_b, applied = b.poll_and_apply(params_b)
        assert applied == 1  # peer's high-water mark for A is now seq 0
        # A's process restarts: new bridge object, same slice id, seq=0
        a2 = CrossSliceGradientBridge(a_out, _FrameQueue(), threshold=1e-3,
                                      slice_id="A")
        a2.publish_update([{"w": np.zeros(16, np.float32)}])
        assert a2.publish_update([{"w": np.ones(16, np.float32)}]) > 0
        params_b, applied = b.poll_and_apply(params_b)
        assert applied == 1  # new incarnation accepted, not dropped
        # ...while a broker redelivering a frame from A's PREVIOUS life
        # is still recognized as already applied
        a_out.frames.append(old_frame)
        params_b, applied = b.poll_and_apply(params_b)
        assert applied == 0


# ---------------------------------------------------------------------------
# checkpoint integrity satellites
# ---------------------------------------------------------------------------

def _tiny_net(seed=1):
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.core import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).updater("adam")
            .list()
            .layer(DenseLayer(n_in=3, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=2))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 3).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)]
    return net, x, y


class TestOrbaxIntegrityFallback:
    def test_corrupt_latest_falls_back_to_previous_retained(self, tmp_path):
        from deeplearning4j_tpu.util.orbax_checkpoint import (
            OrbaxCheckpointManager)
        net, x, y = _tiny_net()
        d = str(tmp_path / "rot")
        with OrbaxCheckpointManager(d, max_to_keep=3) as mgr:
            for step in (1, 2):
                net.fit(x, y)
                mgr.save(step, net)
                mgr.wait_until_finished()
            good = [np.asarray(v) for layer in net.params
                    for v in layer.values()]
            del good  # params at step 2; step-1 params are older
            # the fault injector's torn checkpoint: damage EVERY file of
            # the newest step so no quiet partial restore is possible
            faultinject.corrupt_checkpoint(os.path.join(d, "2"),
                                           mode="truncate")
            with pytest.raises(ValueError,
                               match="unrestorable|truncated or corrupt"):
                mgr.restore(2)
            restored = mgr.restore(2, fallback=True)
            assert mgr.restored_step == 1
            assert restored.iteration > 0
        with OrbaxCheckpointManager(d, max_to_keep=3) as mgr2:
            again = mgr2.restore(fallback=True)  # latest → walks back
            assert mgr2.restored_step == 1
            np.testing.assert_allclose(np.asarray(again.output(x)),
                                       np.asarray(restored.output(x)),
                                       rtol=1e-6)

    def test_every_step_corrupt_raises_with_all_errors(self, tmp_path):
        from deeplearning4j_tpu.util.orbax_checkpoint import (
            OrbaxCheckpointManager)
        net, x, y = _tiny_net()
        d = str(tmp_path / "allbad")
        with OrbaxCheckpointManager(d, max_to_keep=2) as mgr:
            mgr.save(1, net)
            mgr.wait_until_finished()
            faultinject.corrupt_checkpoint(os.path.join(d, "1"),
                                           mode="delete")
            with pytest.raises(ValueError, match="no restorable checkpoint"):
                mgr.restore(1, fallback=True)

    def test_overwrite_existing_rewrites_a_corrupt_finalized_step(
            self, tmp_path):
        """Re-training a step whose finalized-but-corrupt dir survived a
        fallback restore: a plain orbax save silently declines (returns
        False, writes nothing); overwrite_existing clears the stale dir
        so the step is actually rewritten — the elastic commit path
        refuses to stamp otherwise."""
        from deeplearning4j_tpu.util.orbax_checkpoint import (
            OrbaxCheckpointManager)
        net, x, y = _tiny_net()
        d = str(tmp_path / "rewrite")
        with OrbaxCheckpointManager(d) as mgr:
            assert mgr.save(1, net)
            mgr.wait_until_finished()
            faultinject.corrupt_checkpoint(os.path.join(d, "1"),
                                           mode="truncate")
        with OrbaxCheckpointManager(d) as mgr2:
            net.fit(x, y)
            assert mgr2.save(1, net) is False      # orbax declines
            assert mgr2.save(1, net, overwrite_existing=True)
            mgr2.wait_until_finished()
            restored = mgr2.restore(1)
            assert restored.iteration == net.iteration


class TestModelZipIntegrity:
    def test_truncated_zip_fails_fast_with_clear_error(self, tmp_path):
        from deeplearning4j_tpu.util import model_serializer
        net, _, _ = _tiny_net()
        p = str(tmp_path / "m.zip")
        model_serializer.write_model(net, p)
        assert model_serializer.validate_model_zip(p) == []
        data = open(p, "rb").read()
        open(p, "wb").write(data[:len(data) // 2])
        problems = model_serializer.validate_model_zip(p)
        assert problems, "truncated zip must fail validation"
        with pytest.raises(ValueError, match="integrity"):
            model_serializer.restore_model(p)

    def test_crc_damage_detected(self, tmp_path):
        from deeplearning4j_tpu.util import model_serializer
        net, _, _ = _tiny_net()
        p = str(tmp_path / "m.zip")
        model_serializer.write_model(net, p)
        data = bytearray(open(p, "rb").read())
        # flip payload bytes early in the archive (member data, not the
        # central directory at the tail) — CRC catches it
        for i in range(64, 96):
            data[i] ^= 0xFF
        open(p, "wb").write(bytes(data))
        problems = model_serializer.validate_model_zip(p)
        assert problems
        with pytest.raises(ValueError, match="integrity"):
            model_serializer.restore_model(p)


class TestPreemptionArmOffMainThread:
    def test_arm_off_main_thread_raises_clear_error(self):
        from deeplearning4j_tpu.util.preemption import PreemptionHandler
        caught = []

        def worker():
            try:
                PreemptionHandler(None, "/tmp/never-written.zip").arm()
            except Exception as e:  # noqa: BLE001
                caught.append(e)
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert len(caught) == 1
        assert isinstance(caught[0], RuntimeError)
        assert "main thread" in str(caught[0])
        assert "ElasticJobSupervisor" in str(caught[0])


class TestShardingFinalizeGuard:
    def test_unfinalized_conf_raises_loudly(self):
        import types

        from deeplearning4j_tpu.parallel.sharding import tp_param_specs
        fake = types.SimpleNamespace(
            conf=types.SimpleNamespace(_finalized=False))
        with pytest.raises(RuntimeError, match="init\\(\\)"):
            tp_param_specs(fake)

    def test_initialized_net_passes_guard(self):
        from deeplearning4j_tpu.parallel.sharding import tp_param_specs
        net, _, _ = _tiny_net()
        specs = tp_param_specs(net)  # finalized conf: no raise
        assert len(specs) == len(net.params)


# ---------------------------------------------------------------------------
# master compression-state round trip across a mesh reshape (elastic shrink)
# ---------------------------------------------------------------------------

class TestMasterStateAcrossReshape:
    def _master_with_residual(self, workers, batch):
        import jax

        from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                         ListDataSetIterator)
        from deeplearning4j_tpu.parallel import (DistributedMultiLayerNetwork,
                                                 SharedTrainingMaster)
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        net, _, _ = _tiny_net(seed=3)
        rng = np.random.RandomState(1)
        x = rng.randn(4 * batch, 3).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 4 * batch)]
        mesh = make_mesh({"data": workers},
                         devices=jax.devices()[:workers])
        master = SharedTrainingMaster(batch_size_per_worker=batch // workers,
                                      threshold=1e-3, mesh=mesh)
        DistributedMultiLayerNetwork(net, master).fit(
            ListDataSetIterator(DataSet(x, y), batch), epochs=2)
        return master, x, y

    def test_residual_mass_and_threshold_survive_3_to_2(self, tmp_path):
        import jax

        master3, x, y = self._master_with_residual(3, 24)
        path = str(tmp_path / "state.npz")
        master3.save_state(path)
        saved = np.load(path)
        res_keys = sorted((k for k in saved.files if k.startswith("res")),
                          key=lambda k: int(k[3:]))
        assert res_keys, "training must have accumulated a residual"
        assert saved[res_keys[0]].shape[0] == 3  # stacked per-worker

        from deeplearning4j_tpu.parallel import SharedTrainingMaster
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
        master2 = SharedTrainingMaster(batch_size_per_worker=12,
                                       threshold=1e-3, mesh=mesh2)
        master2.load_state(path)
        assert master2.threshold == master3.threshold  # adapted value kept
        assert master2._steps_done == master3._steps_done
        # place the restored 3-worker stack onto the 2-worker mesh shape:
        # un-transmitted mass is conserved (summed then spread evenly)
        zeros = [np.zeros((2,) + tuple(saved[k].shape[1:]), np.float32)
                 for k in res_keys]
        placed = master2._place_restored_residual(zeros, mp=False,
                                                  shard_spec=None)
        for k, arr in zip(res_keys, placed):
            np.testing.assert_allclose(
                np.asarray(arr).sum(axis=0),
                np.asarray(saved[k], np.float64).sum(axis=0),
                rtol=1e-5, atol=1e-7,
                err_msg=f"{k}: residual mass lost across the reshape")

    def test_resumed_training_runs_after_reshape(self, tmp_path):
        import jax

        from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                         ListDataSetIterator)
        from deeplearning4j_tpu.parallel import (DistributedMultiLayerNetwork,
                                                 SharedTrainingMaster)
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        master3, x, y = self._master_with_residual(3, 24)
        path = str(tmp_path / "state.npz")
        master3.save_state(path)
        net2, _, _ = _tiny_net(seed=3)
        mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
        master2 = SharedTrainingMaster(batch_size_per_worker=12,
                                       threshold=1e-3, mesh=mesh2)
        master2.load_state(path)  # deferred placement: applied on next fit
        front = DistributedMultiLayerNetwork(net2, master2)
        front.fit(ListDataSetIterator(DataSet(x, y), 24), epochs=1)
        assert np.isfinite(float(net2.score_))

    def test_architecture_mismatch_still_fails_loudly(self, tmp_path):
        import jax

        from deeplearning4j_tpu.parallel import SharedTrainingMaster
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        master3, _, _ = self._master_with_residual(3, 24)
        path = str(tmp_path / "state.npz")
        master3.save_state(path)
        mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
        master2 = SharedTrainingMaster(mesh=mesh2)
        master2.load_state(path)
        saved = np.load(path)
        res_keys = sorted((k for k in saved.files if k.startswith("res")),
                          key=lambda k: int(k[3:]))
        # same leaf count, but per-parameter shapes from a DIFFERENT model
        zeros = [np.zeros((2, 5, 7), np.float32) for _ in res_keys]
        with pytest.raises(ValueError, match="different architecture"):
            master2._place_restored_residual(zeros, mp=False,
                                             shard_spec=None)


# ---------------------------------------------------------------------------
# real subprocess supervision
# ---------------------------------------------------------------------------

def _sub_env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


@pytest.mark.multiprocess
class TestRealSubprocesses:
    def test_trivial_workers_complete(self, tmp_path):
        script = ("import os\n"
                  "open(os.environ['DL4J_TPU_ELASTIC_HEARTBEAT_FILE'],"
                  "'w').write('up')\n")
        sup = ElasticJobSupervisor(
            WorkerSpec(argv=[sys.executable, "-c", script],
                       env=_sub_env()),
            2, ckpt_dir=str(tmp_path / "ckpt"), metrics=MetricsRegistry(),
            poll_interval_s=0.05, job_deadline_s=120)
        result = sup.run()
        assert result.status == "completed"
        assert len(result.generations) == 1
        logs = os.listdir(os.path.join(sup.ckpt_dir, "logs"))
        assert sorted(logs) == ["gen001_slot0.log", "gen001_slot1.log"]

    def test_crash_looping_worker_fails_after_budget(self, tmp_path):
        sup = ElasticJobSupervisor(
            WorkerSpec(argv=[sys.executable, "-c",
                             "import sys; print('boom'); sys.exit(3)"],
                       env=_sub_env()),
            1, ckpt_dir=str(tmp_path / "ckpt"), metrics=MetricsRegistry(),
            backoff=BackoffPolicy(max_restarts=1, base_s=0.01, max_s=0.02),
            startup_retries=1, poll_interval_s=0.05, job_deadline_s=120)
        with pytest.raises(ElasticJobFailed) as ei:
            sup.run()
        assert "restart budget" in str(ei.value)
        # captured worker output is reachable for postmortem
        assert "boom" in sup.tail_log(0, 1)


# ---------------------------------------------------------------------------
# the CI acceptance proof: SIGKILL mid-training → shrink 3→2 → converge,
# equal to a clean 2-worker-shaped resume from the same checkpoint
# ---------------------------------------------------------------------------

SAMPLES, FEATURES, CLASSES = 240, 6, 3
BATCH = 24          # divisible by 3 AND 2: survives the shrink
EPOCHS = 3          # 10 iterations/epoch
KILL_STEP = 14      # mid-epoch-2: checkpoint step 1 committed, step 2 not


def _make_job_inputs(tmp_path):
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.util import model_serializer
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(0.01))
            .list()
            .layer(DenseLayer(n_out=12, activation="tanh"))
            .layer(OutputLayer(n_out=CLASSES))
            .set_input_type(InputType.feed_forward(FEATURES)).build())
    net = MultiLayerNetwork(conf).init()
    model_path = str(tmp_path / "model.zip")
    model_serializer.write_model(net, model_path)
    rng = np.random.default_rng(0)
    yc = rng.integers(0, CLASSES, SAMPLES)
    x = rng.normal(size=(SAMPLES, FEATURES)).astype(np.float32)
    x[np.arange(SAMPLES), yc] += 2.5
    y = np.eye(CLASSES, dtype=np.float32)[yc]
    data_path = str(tmp_path / "data.npz")
    np.savez(data_path, features=x, labels=y)
    return model_path, data_path, x, y


@pytest.mark.multiprocess
def test_elastic_shrink_to_surviving_slice_converges_and_matches(tmp_path):
    """ISSUE 7 acceptance: a 3-process job whose worker 1 is SIGKILLed at
    iteration 14 by the fault plan automatically shrinks to the surviving
    2-process slice [0, 2] and completes; the final params EQUAL a clean
    2-worker-shaped run resumed from the same checkpoint step."""
    model_path, data_path, x, y = _make_job_inputs(tmp_path)
    out_path = str(tmp_path / "final.zip")
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w", encoding="utf-8") as fh:
        json.dump({"faults": [{"type": "kill", "worker": 1,
                               "step": KILL_STEP, "signal": "KILL"}]}, fh)
    assert validate_file(plan_path, num_workers=3) == []

    spec = WorkerSpec(
        argv=[sys.executable, "-m",
              "deeplearning4j_tpu.parallel.elastic_worker",
              "--modelPath", model_path, "--dataPath", data_path,
              "--out", out_path, "--batchSize", str(BATCH),
              "--epochs", str(EPOCHS), "--threshold", "1e-3"],
        env=_sub_env({"DL4J_TPU_FAULT_PLAN": plan_path}))
    reg = MetricsRegistry()
    sup = ElasticJobSupervisor(
        spec, 3, min_workers=2, ckpt_dir=str(tmp_path / "ckpt"),
        backoff=BackoffPolicy(max_restarts=0),
        metrics=reg, poll_interval_s=0.2,
        job_deadline_s=540)  # hard bound: the job can never hang CI
    result = sup.run()

    def _debug():
        out = []
        for g in result.generations:
            for slot in g.world:
                out.append(f"--- gen {g.generation} slot {slot} ---\n"
                           + sup.tail_log(slot, g.generation, 2000))
        return "\n".join(out)

    assert result.status == "completed", _debug()
    assert len(result.generations) == 2, _debug()
    g1, g2 = result.generations
    assert g1.decision == "shrink"
    assert g1.primary_slot == 1
    assert g2.world == [0, 2]
    # the shrunk generation resumed from the only committed step
    assert g2.restore_step == 1, _debug()
    series = parse_prometheus_text(reg.exposition())
    assert series["elastic_restarts_total"][(("decision", "shrink"),)] == 1
    assert series["elastic_world_size"][()] == 2

    # ---- comparator: clean 2-worker-shaped resume from the SAME step ----
    import jax

    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.parallel import (DistributedMultiLayerNetwork,
                                             SharedTrainingMaster)
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.util import model_serializer
    from deeplearning4j_tpu.util.orbax_checkpoint import (
        OrbaxCheckpointManager)

    with OrbaxCheckpointManager(sup.ckpt_dir, active_processes={0},
                                barrier_sync_key_prefix="cmp") as mgr:
        net_b = mgr.restore(1)
    assert int(net_b.epoch) == 1
    mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
    master = SharedTrainingMaster(batch_size_per_worker=BATCH,
                                  threshold=1e-3, mesh=mesh2)
    front = DistributedMultiLayerNetwork(net_b, master)
    for _ in range(int(net_b.epoch), EPOCHS):
        front.fit(ListDataSetIterator(DataSet(x, y), BATCH), epochs=1)

    elastic_net = model_serializer.restore_model(out_path)
    assert int(elastic_net.epoch) == EPOCHS
    for i, (a, b) in enumerate(zip(elastic_net.params, net_b.params)):
        for k in a:
            np.testing.assert_allclose(
                np.asarray(a[k]), np.asarray(b[k]), rtol=2e-5, atol=2e-6,
                err_msg=f"layer {i} param {k}: elastic shrink diverged "
                        "from the clean 2-worker resume")
