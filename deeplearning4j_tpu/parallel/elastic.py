"""Elastic training supervisor: automatic failure recovery + shrink.

The reference fixes worker membership at job start
(``SharedTrainingWrapper.java:131-156``) and delegates fault tolerance to
Spark task retry — losing a worker permanently ends the job. Every
ingredient for doing better already exists in this repo (kill-and-resume
choreography in ``tests/test_multiprocess.py``, ``util/preemption.py``,
``util/orbax_checkpoint.py`` rotation, ``SharedTrainingMaster.save_state``)
but lived in test code. This module is the library composition:

``ElasticJobSupervisor`` launches N worker processes from a
:class:`WorkerSpec`, tracks liveness via per-worker heartbeat files on an
injectable clock, and on worker death (SIGKILL-style, no grace) runs the
full recovery loop automatically:

1. first observed death is the *primary* victim; the surviving peers are
   killed too (their collectives can never complete) and treated as
   collateral — restarted free of charge;
2. decide **restart-in-place** (the victim still has restart budget:
   exponential backoff + deterministic jitter, so a crash-looping worker
   cannot storm) vs **shrink to the surviving slice** (budget exhausted,
   and the remaining slots still satisfy ``min_workers``) vs **fail
   loudly** (cannot shrink further);
3. re-form the world: fresh coordinator port, process ids renumbered
   0..M-1 over the surviving slots, a new generation token;
4. workers restore the latest *eligible* orbax rotation checkpoint and
   resume ``SharedTrainingMaster`` training.

**Generation fencing** makes checkpoints written by stale workers from a
previous world un-restorable: every generation gets a token; workers
stamp each committed checkpoint step with their token, and re-read the
supervisor's ``elastic_generation.json`` before each save (a stale token
aborts the save). When a generation ends, the supervisor *fences* its
token in a persistent ledger together with a snapshot of the steps it had
committed — a stamp carrying a fenced token that is NOT in the snapshot
(i.e. written after the fence by a zombie) is never restored. The ledger
survives supervisor restarts, so a brand-new supervisor over an existing
checkpoint directory resumes from the previous lineage's snapshot.

At pod scale (round 12) the substrate grows three capabilities:

- **Host failure domains** (``num_hosts``/``min_hosts``): workers are
  grouped into host groups and the whole decision ladder operates on
  hosts — any worker death victimizes its host group, budgets charge
  the host (one lost machine = one fault), shrink removes whole hosts
  so per-host slice shapes stay valid. The coordinator bind/advertise
  address is configurable (``WorkerSpec.bind_host``/``advertise_host``,
  ``DL4J_TPU_ELASTIC_BIND_HOST``/``_ADVERTISE_HOST``) instead of
  hardcoded loopback.
- **Async sharded checkpointing** (:class:`AsyncCheckpointSession`,
  ``run_elastic_worker(save_mode="async")``): every rank snapshots its
  shard on the training thread and a bounded background pipeline does
  the writes; the stamp commits only after ALL ranks' finalize landed,
  so a crash at any phase of an overlapped save leaves a torn step that
  is never restorable, and a slow filesystem backpressures through the
  in-flight window instead of accumulating.
- **Partition tolerance** (``progress_timeout_s``): a step-progress
  watchdog distinguishes a partition (heartbeats alive — workers beat
  from a background thread when armed — but no step progress anywhere)
  from a slow worker, and resolves it as death of the least-progressed
  side.

Failure paths are CI-provable on subprocess CPU workers via the
deterministic fault harness (``util/faultinject.py``,
``DL4J_TPU_FAULT_PLAN`` — incl. host-scoped ``kill_host``/``partition``/
``slow_save`` and commit-phase kills). Everything reports through the
existing observability stack: ``elastic_restarts_total`` /
``elastic_world_size`` / ``elastic_hosts`` / ``elastic_partitions_total``
metrics, ``elastic_recovery``/``elastic_async_save`` spans, structured
logs, and the shipped restart-storm alert rule
(``examples/elastic_alert_rules.json``).
"""

from __future__ import annotations

import dataclasses
import glob
import hashlib
import json
import os
import socket
import subprocess
import sys
import threading
import uuid
from typing import Dict, List, Optional, Sequence

from deeplearning4j_tpu.util.fsio import atomic_write_text as _atomic_write

# Environment seam between supervisor and workers. Everything a worker
# needs to join its generation arrives through these variables.
ENV_COORDINATOR = "DL4J_TPU_ELASTIC_COORDINATOR"
ENV_NUM_PROCESSES = "DL4J_TPU_ELASTIC_NUM_PROCESSES"
ENV_PROCESS_ID = "DL4J_TPU_ELASTIC_PROCESS_ID"
ENV_SLOT = "DL4J_TPU_ELASTIC_SLOT"
ENV_HOST = "DL4J_TPU_ELASTIC_HOST"
ENV_NUM_HOSTS = "DL4J_TPU_ELASTIC_NUM_HOSTS"
ENV_GENERATION = "DL4J_TPU_ELASTIC_GENERATION"
ENV_TOKEN = "DL4J_TPU_ELASTIC_TOKEN"
ENV_CKPT_DIR = "DL4J_TPU_ELASTIC_CKPT_DIR"
ENV_HEARTBEAT = "DL4J_TPU_ELASTIC_HEARTBEAT_FILE"
ENV_RESTORE_STEP = "DL4J_TPU_ELASTIC_RESTORE_STEP"
ENV_ELIGIBLE_STEPS = "DL4J_TPU_ELASTIC_ELIGIBLE_STEPS"
# pod mesh over the elastic env: the per-host mesh slice shape
# (``parse_mesh_axes`` grammar, e.g. "model=2" — the data axis is always
# the generation's process count) and an optional sharding-rules JSON
# path workers place params with (absent → DEFAULT_2D_RULES)
ENV_MESH = "DL4J_TPU_ELASTIC_MESH"
ENV_SHARDING_RULES = "DL4J_TPU_ELASTIC_SHARDING_RULES"
ENV_PROGRESS_BEAT = "DL4J_TPU_ELASTIC_PROGRESS_BEAT_S"
# operator-level coordinator addressing (read by WorkerSpec, overridable
# per-spec): where process 0 binds its coordination service and the
# address peers dial — the pod-scale replacement for hardcoded loopback
ENV_BIND_HOST = "DL4J_TPU_ELASTIC_BIND_HOST"
ENV_ADVERTISE_HOST = "DL4J_TPU_ELASTIC_ADVERTISE_HOST"
# fleet observability seam: the supervisor's per-generation elastic_job
# span context (W3C traceparent — worker spans parent into the job
# trace), the directory workers stream their spans into (crash-durable
# JSONL, merged by observe.export.merge_chrome_traces), and the file a
# worker writes its Prometheus exposition snapshots to (scraped by the
# supervisor's FleetRegistry). All three absent → every hook is a no-op.
ENV_TRACEPARENT = "DL4J_TPU_ELASTIC_TRACEPARENT"
ENV_TRACE_DIR = "DL4J_TPU_ELASTIC_TRACE_DIR"
ENV_METRICS_FILE = "DL4J_TPU_ELASTIC_METRICS_FILE"

GENERATION_FILE = "elastic_generation.json"
LEDGER_FILE = "elastic_ledger.json"
_STAMP_PREFIX = "elastic_step_"


def _free_port(bind_host: str = "127.0.0.1") -> int:
    family = socket.AF_INET6 if ":" in bind_host else socket.AF_INET
    s = socket.socket(family)
    s.bind((bind_host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _join_host_port(host: str, port) -> str:
    """``host:port`` with IPv6 literals bracketed — ``fd00::1`` must
    become ``[fd00::1]:4711`` or the joined address is unparseable."""
    if ":" in host and not host.startswith("["):
        return f"[{host}]:{port}"
    return f"{host}:{port}"


def _stamp_path(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"{_STAMP_PREFIX}{int(step):08d}.json")


def write_step_stamp(ckpt_dir: str, step: int, token: str, generation: int,
                     world_size: int) -> None:
    """Commit marker for a checkpoint step: written only after the orbax
    save finalized AND every rank's master state landed. Carries the
    generation token — the fencing unit."""
    _atomic_write(_stamp_path(ckpt_dir, step), json.dumps(
        {"step": int(step), "token": token, "generation": int(generation),
         "world_size": int(world_size)}))


def read_step_stamps(ckpt_dir: str) -> List[dict]:
    """All committed step stamps, oldest first. Unreadable/partial stamps
    are skipped (a torn stamp simply means that step never committed)."""
    out = []
    try:
        names = sorted(os.listdir(ckpt_dir))
    except OSError:
        return []
    for name in names:
        if not (name.startswith(_STAMP_PREFIX) and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(ckpt_dir, name), encoding="utf-8") as fh:
                s = json.load(fh)
            out.append({"step": int(s["step"]), "token": str(s["token"]),
                        "generation": int(s.get("generation", 0)),
                        "world_size": int(s.get("world_size", 0))})
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return out


class GenerationLedger:
    """Persistent record of every generation this job lineage formed.

    Eligibility rule for restoring a stamped checkpoint step:

    - its token belongs to a generation this ledger knows, AND
    - that generation is still open, OR the step is in the snapshot taken
      when the generation was fenced.

    A zombie worker from a fenced generation can still *write* files, but
    nothing it writes after the fence can ever be chosen for restore.
    Loading an existing ledger fences every recorded generation against
    the stamps currently on disk — a new supervisor inherits the old
    lineage's committed steps and nothing more.
    """

    def __init__(self, ckpt_dir: str):
        self.ckpt_dir = ckpt_dir
        self.path = os.path.join(ckpt_dir, LEDGER_FILE)
        self.generations: List[dict] = []
        if os.path.exists(self.path):
            with open(self.path, encoding="utf-8") as fh:
                self.generations = json.load(fh)["generations"]
            known = read_step_stamps(ckpt_dir)
            for g in self.generations:
                if not g.get("fenced"):
                    g["fenced"] = True
                    g["known_steps"] = sorted(
                        s["step"] for s in known if s["token"] == g["token"])
            self._persist()

    def _persist(self) -> None:
        os.makedirs(self.ckpt_dir, exist_ok=True)
        _atomic_write(self.path,
                      json.dumps({"generations": self.generations}, indent=1))

    def open_generation(self, generation: int, token: str,
                        world: Sequence[int]) -> None:
        self.generations.append({"generation": int(generation),
                                 "token": token, "world": list(world),
                                 "fenced": False, "known_steps": []})
        self._persist()

    def fence(self, token: str) -> None:
        """Close a generation: snapshot the steps it committed so far;
        later writes under its token become un-restorable."""
        known = [s["step"] for s in read_step_stamps(self.ckpt_dir)
                 if s["token"] == token]
        for g in self.generations:
            if g["token"] == token:
                g["fenced"] = True
                g["known_steps"] = sorted(known)
        self._persist()

    def eligible(self, token: str, step: int) -> bool:
        for g in self.generations:
            if g["token"] != token:
                continue
            return (not g["fenced"]) or int(step) in g["known_steps"]
        return False


# -- supervisor --------------------------------------------------------------

@dataclasses.dataclass
class WorkerSpec:
    """How to launch one worker process. The elastic context (coordinator,
    world size, renumbered process id, generation token, checkpoint dir,
    heartbeat path, restore step) is injected through the environment —
    ``argv`` stays the user's command line."""

    argv: List[str]
    env: Optional[Dict[str, str]] = None  # base env; default os.environ
    cwd: Optional[str] = None
    # each worker must own exactly ONE local device; a host-device
    # multiplier inherited from a test/bench parent would make every
    # worker claim the whole virtual mesh
    single_device: bool = True
    # where process 0's jax.distributed coordinator listens and the
    # address the generation's workers dial. None → the
    # DL4J_TPU_ELASTIC_BIND_HOST / DL4J_TPU_ELASTIC_ADVERTISE_HOST env
    # vars, then loopback — the pre-pod behavior stays the default
    bind_host: Optional[str] = None
    advertise_host: Optional[str] = None
    # pod mesh: each worker owns a mesh SLICE of this shape (ICI inside
    # the host); the data axis always spans the generation's processes
    # (DCN across hosts) and must be -1/absent here. E.g.
    # ``{"model": 2}`` → every worker gets 2 local devices sharded over
    # the model axis while training stays data-parallel across workers.
    mesh_axes: Optional[Dict[str, int]] = None
    # sharding-rules JSON path forwarded to workers (None → the shipped
    # DEFAULT_2D_RULES)
    sharding_rules: Optional[str] = None

    def local_mesh_devices(self) -> int:
        """Devices each worker's mesh slice needs (the product of the
        non-data axes; 1 = classic one-device-per-worker)."""
        n = 1
        for name, size in (self.mesh_axes or {}).items():
            if name == "data":
                continue
            n *= max(1, int(size))
        return n

    def resolved_bind_host(self) -> str:
        if self.bind_host:
            return self.bind_host
        return os.environ.get(ENV_BIND_HOST) or "127.0.0.1"

    def resolved_advertise_host(self) -> str:
        """The address workers dial; defaults to the bind host — except
        a wildcard bind (0.0.0.0 / ::), which is not dialable and must
        be advertised as something routable."""
        if self.advertise_host:
            return self.advertise_host
        adv = os.environ.get(ENV_ADVERTISE_HOST)
        if adv:
            return adv
        bind = self.resolved_bind_host()
        if bind in ("0.0.0.0", "::"):
            return socket.gethostname()
        return bind

    def environment(self) -> Dict[str, str]:
        env = dict(os.environ if self.env is None else self.env)
        if self.single_device and "XLA_FLAGS" in env:
            # strip ONLY the host-device multiplier; the operator's other
            # XLA flags (dump dirs, tuning) must reach the workers
            kept = [t for t in env["XLA_FLAGS"].split()
                    if not t.startswith(
                        "--xla_force_host_platform_device_count")]
            if kept:
                env["XLA_FLAGS"] = " ".join(kept)
            else:
                del env["XLA_FLAGS"]
        n_local = self.local_mesh_devices()
        if n_local > 1:
            # the worker owns a multi-device mesh slice: on the CPU
            # (host) platform that slice must be forced into existence;
            # on real accelerators the flag is inert and the host's
            # locally-attached chips form the slice
            kept = [t for t in env.get("XLA_FLAGS", "").split()
                    if t and not t.startswith(
                        "--xla_force_host_platform_device_count")]
            kept.append(f"--xla_force_host_platform_device_count={n_local}")
            env["XLA_FLAGS"] = " ".join(kept)
        return env


@dataclasses.dataclass
class BackoffPolicy:
    """Restart budgeting: exponential backoff with deterministic jitter.

    ``max_restarts`` is the per-slot budget of post-liveness restarts; a
    slot that exhausts it is shrunk away (or, at ``min_workers``, fails
    the job). Jitter is hashed from ``(seed, attempt)`` — reproducible,
    no RNG state, but still de-synchronizes a fleet of supervisors."""

    base_s: float = 1.0
    factor: float = 2.0
    max_s: float = 60.0
    jitter: float = 0.1
    max_restarts: int = 2

    def delay(self, attempt: int, seed: str = "") -> float:
        d = min(self.max_s, self.base_s * self.factor ** max(0, attempt - 1))
        if self.jitter:
            h = int(hashlib.sha256(f"{seed}:{attempt}".encode())
                    .hexdigest()[:8], 16)
            d *= 1.0 + self.jitter * (2.0 * (h / 0xffffffff) - 1.0)
        return d


def refuse_shared_tpu(env: Dict[str, str], num_workers: int) -> None:
    """Raise when ``num_workers`` local processes would share this host's
    TPU. :class:`SubprocessLauncher` starts every worker here with one
    environment, so on a TPU host each would bring up the TPU runtime and
    ask for every local chip. The first gets them; the others die at
    start-up (seen on a v5e, libtpu 0.0.34: ``Unable to initialize backend
    'tpu': ABORTED: Internal error when accessing libtpu multi-process
    lockfile``) and the supervisor would spend its retries on them. A chip
    belongs to one process, and nothing here pins one chip per worker."""
    platforms = [p for p in env.get("JAX_PLATFORMS", "").split(",") if p]
    if platforms and "tpu" not in platforms:
        return  # the workers are held off the TPU
    if not (glob.glob("/dev/vfio/[0-9]*") or glob.glob("/dev/accel*")):
        return  # no TPU device nodes on this host
    raise ValueError(
        f"{num_workers} elastic workers on one TPU host would each claim "
        f"every local chip and all but the first would fail at start-up. "
        f"Run one worker per host (--elastic 1 here; a mesh over the "
        f"host's chips belongs inside that worker: --mesh model=N), or "
        f"hold the workers to the CPU with JAX_PLATFORMS=cpu")


class SubprocessLauncher:
    """Default process backend (injectable: unit tests drive the
    supervisor with fake handles and a manual clock)."""

    def launch(self, argv: List[str], env: Dict[str, str],
               cwd: Optional[str], log_path: str):
        fh = open(log_path, "wb")
        proc = subprocess.Popen(argv, env=env, cwd=cwd, stdout=fh,
                                stderr=subprocess.STDOUT)
        proc._elastic_log = fh  # closed on reap
        return proc


@dataclasses.dataclass
class _Slot:
    """Supervisor-internal per-slot state (survives generations; restart
    budgets live on the slot's failure domain — :class:`_Domain`)."""

    slot_id: int
    # per-generation fields:
    proc: object = None
    log_path: str = ""
    hb_path: str = ""
    last_beat: Optional[str] = None
    last_beat_at_ms: int = 0
    live: bool = False        # has this incarnation ever heartbeat?
    done: bool = False
    exit_code: Optional[int] = None
    death_reason: Optional[str] = None
    # step-progress tracking (partition watchdog): the newest training
    # step parsed out of the heartbeat payload, when it changed, and
    # whether it ever ADVANCED past the first reported value this
    # generation (a generation that never progressed is starting up —
    # first-step compile — not partitioned)
    last_step: Optional[int] = None
    last_step_at_ms: int = 0
    progressed: bool = False


@dataclasses.dataclass
class _Domain:
    """Restart budget for one failure domain — a host group when the job
    has host grouping, a single slot otherwise. Charging the domain (not
    the slot) is what makes a lost HOST one fault instead of
    workers-per-host simultaneous budget exhaustions."""

    domain_id: object
    restarts_used: int = 0
    startup_retries_used: int = 0


@dataclasses.dataclass
class GenerationRecord:
    generation: int
    token: str
    world: List[int]
    restore_step: Optional[int]
    outcome: str = "running"          # completed | recovered | failed
    dead_slots: List[int] = dataclasses.field(default_factory=list)
    primary_slot: Optional[int] = None
    decision: Optional[str] = None    # restart | shrink | fail
    primary_host: Optional[int] = None  # victim host group (host mode)


@dataclasses.dataclass
class ElasticJobResult:
    status: str                       # completed | failed
    reason: Optional[str] = None
    generations: List[GenerationRecord] = dataclasses.field(
        default_factory=list)
    restarts_total: int = 0
    backoff_delays: List[float] = dataclasses.field(default_factory=list)

    @property
    def final_world(self) -> List[int]:
        return self.generations[-1].world if self.generations else []


class ElasticJobFailed(RuntimeError):
    """The job could not be kept alive (restart budget exhausted and the
    world cannot shrink below ``min_workers``, or the job deadline
    passed). Carries the full :class:`ElasticJobResult`."""

    def __init__(self, message: str, result: ElasticJobResult):
        super().__init__(message)
        self.result = result


class ElasticJobSupervisor:
    """Launch, watch and heal an elastic data-parallel training job.

    Every time-dependent decision runs on an injectable
    :class:`~deeplearning4j_tpu.parallel.time_source.TimeSource` +
    ``sleep_fn`` pair, and process management goes through an injectable
    launcher — the whole state machine is unit-testable with a manual
    clock and fake processes, no real sleeps or subprocesses.
    """

    def __init__(self, spec: WorkerSpec, num_workers: int, *,
                 min_workers: int = 1, ckpt_dir: str,
                 num_hosts: Optional[int] = None, min_hosts: int = 1,
                 backoff: Optional[BackoffPolicy] = None,
                 heartbeat_timeout_s: float = 120.0,
                 startup_timeout_s: float = 300.0,
                 startup_retries: int = 3,
                 poll_interval_s: float = 0.25,
                 job_deadline_s: Optional[float] = None,
                 progress_timeout_s: Optional[float] = None,
                 clock=None, sleep_fn=None, launcher=None,
                 metrics=None, port_fn=_free_port,
                 job_id: str = "elastic",
                 fleet=None, metrics_port: Optional[int] = None,
                 incidents: bool = True,
                 incident_dir: Optional[str] = None):
        if num_workers < 1 or min_workers < 1 or min_workers > num_workers:
            raise ValueError(
                f"need 1 <= min_workers <= num_workers, got "
                f"{min_workers}/{num_workers}")
        if num_hosts is not None:
            if num_hosts < 1 or num_workers % num_hosts != 0:
                raise ValueError(
                    f"num_hosts must divide num_workers evenly (per-host "
                    f"slice shapes), got {num_hosts}/{num_workers}")
            if min_hosts < 1 or min_hosts > num_hosts:
                raise ValueError(
                    f"need 1 <= min_hosts <= num_hosts, got "
                    f"{min_hosts}/{num_hosts}")
        self.spec = spec
        self.num_workers = num_workers
        self.min_workers = min_workers
        #: None → each worker is its own failure domain (the pre-pod
        #: behavior); N → workers are grouped into N host groups of
        #: num_workers/N slots and EVERY recovery decision operates on
        #: whole hosts (a worker death marks its host the victim,
        #: shrink removes the host, budgets charge the host)
        self.num_hosts = num_hosts
        self.min_hosts = min_hosts
        self.progress_timeout_s = progress_timeout_s
        self.ckpt_dir = os.path.abspath(ckpt_dir)
        self.backoff = backoff if backoff is not None else BackoffPolicy()
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.startup_timeout_s = startup_timeout_s
        self.startup_retries = startup_retries
        self.poll_interval_s = poll_interval_s
        self.job_deadline_s = job_deadline_s
        if clock is None:
            from deeplearning4j_tpu.parallel.time_source import (
                get_time_source)
            clock = get_time_source()
        self.clock = clock
        import time as _time
        self.sleep_fn = sleep_fn if sleep_fn is not None else _time.sleep
        if launcher is None:
            if num_workers > 1:
                refuse_shared_tpu(spec.environment(), num_workers)
            launcher = SubprocessLauncher()
        self.launcher = launcher
        if metrics is None:
            from deeplearning4j_tpu.observe import default_registry
            metrics = default_registry()
        self.metrics = metrics
        self.port_fn = port_fn
        self.job_id = job_id
        os.makedirs(self.ckpt_dir, exist_ok=True)
        self.ledger = GenerationLedger(self.ckpt_dir)
        from deeplearning4j_tpu.observe import get_logger
        self._log = get_logger("elastic")
        self._restarts = metrics.counter(
            "elastic_restarts_total",
            "Elastic recovery events by decision", ("decision",))
        self._deaths = metrics.counter(
            "elastic_worker_deaths_total",
            "Worker deaths observed by the supervisor", ("reason",))
        self._world_gauge = metrics.gauge(
            "elastic_world_size", "Current elastic world size")
        self._gen_gauge = metrics.gauge(
            "elastic_generation", "Current elastic generation number")
        self._hosts_gauge = metrics.gauge(
            "elastic_hosts", "Current number of live host groups")
        self._partitions = metrics.counter(
            "elastic_partitions_total",
            "Network partitions resolved by the step-progress watchdog")
        self._domains: Dict[object, _Domain] = {}
        # -- fleet observability (each piece a no-op when absent) ---------
        #: FleetRegistry serving the job-wide metrics union; created
        #: automatically when --metrics-port asks for the scrape endpoint
        self.fleet = fleet
        if self.fleet is None and metrics_port is not None:
            from deeplearning4j_tpu.observe.fleet import FleetRegistry
            self.fleet = FleetRegistry(local=metrics)
        self.metrics_port = metrics_port
        self.metrics_server = None
        #: optional AlertManager surfaced at the metrics server's
        #: /alerts endpoint (the CLI attaches its --alerts manager here
        #: before run())
        self.alerts = None
        #: optional SLOSet surfaced at the metrics server's /slo
        #: endpoint (the CLI attaches its --slo set here before run())
        self.slo = None
        #: where workers stream crash-durable span files (set per
        #: generation only while a tracer is active in THIS process)
        self.trace_dir = os.path.join(self.ckpt_dir, "trace")
        self.incidents = None
        if incidents:
            from deeplearning4j_tpu.observe.incident import IncidentRecorder
            self.incidents = IncidentRecorder(
                incident_dir if incident_dir is not None
                else os.path.join(self.ckpt_dir, "incidents"))

    # -- failure domains ---------------------------------------------------
    def host_of(self, slot_id: int) -> Optional[int]:
        """Host group of a slot (stable across generations: assignment is
        by the ORIGINAL world, so renumbering never moves a worker
        between failure domains). None without host grouping."""
        if self.num_hosts is None:
            return None
        return slot_id // (self.num_workers // self.num_hosts)

    def _domain_of(self, slot_id: int) -> _Domain:
        did = ("host", self.host_of(slot_id)) if self.num_hosts is not None \
            else ("slot", slot_id)
        if did not in self._domains:
            self._domains[did] = _Domain(domain_id=did)
        return self._domains[did]

    def _domain_slots(self, slot_id: int, world: List[int]) -> List[int]:
        """Every slot of ``slot_id``'s failure domain still in the
        world — the unit the decision ladder kills/shrinks together."""
        if self.num_hosts is None:
            return [slot_id]
        h = self.host_of(slot_id)
        return [s for s in world if self.host_of(s) == h]

    def _live_hosts(self, world: List[int]) -> int:
        if self.num_hosts is None:
            return len(world)
        return len({self.host_of(s) for s in world})

    # -- checkpoint eligibility ------------------------------------------
    def eligible_steps(self) -> List[int]:
        """Every committed checkpoint step whose generation stamp passes
        the fence, ascending — the ONLY steps a worker may restore
        (including its corrupt-step fallback walk: a zombie's unfenced
        write must not become restorable just because the newest eligible
        step is torn)."""
        return sorted({s["step"] for s in read_step_stamps(self.ckpt_dir)
                       if self.ledger.eligible(s["token"], s["step"])})

    def latest_eligible_step(self) -> Optional[int]:
        """Newest committed checkpoint step whose generation stamp passes
        the fence — what the next generation restores."""
        steps = self.eligible_steps()
        return steps[-1] if steps else None

    # -- main loop --------------------------------------------------------
    def run(self, *, raise_on_failure: bool = True) -> ElasticJobResult:
        if self.metrics_port is not None and self.metrics_server is None:
            from deeplearning4j_tpu.observe.fleet import FleetMetricsServer
            self.metrics_server = FleetMetricsServer(
                self.fleet, port=self.metrics_port, alerts=self.alerts,
                slo=getattr(self, "slo", None))
            self.metrics_server.start()
            self._log.info("fleet metrics server up",
                           url=self.metrics_server.url())
        try:
            return self._run(raise_on_failure=raise_on_failure)
        finally:
            if self.metrics_server is not None:
                self.metrics_server.stop()
                self.metrics_server = None

    def _gen_span_start(self, generation, token, world, restore_step):
        """Per-generation ``elastic_job`` root span (None while tracing
        is off). Its context ships to workers as a W3C traceparent, so
        every worker's train/recovery/checkpoint spans parent into one
        job trace per generation."""
        from deeplearning4j_tpu.observe import get_active_tracer
        tr = get_active_tracer()
        if tr is None:
            return None
        return tr.start_span(
            "elastic_job", category="elastic",
            attrs={"job_id": self.job_id, "generation": generation,
                   "token": token, "world": str(world),
                   "restore_step": restore_step})

    def _gen_span_end(self, gen_span, outcome: str) -> None:
        if gen_span is None:
            return
        from deeplearning4j_tpu.observe import get_active_tracer
        tr = get_active_tracer()
        gen_span.set_attribute("outcome", outcome)
        if tr is not None:
            tr.end_span(gen_span)

    def _record_decision(self, gen_span, generation, decision, primary,
                         reason) -> None:
        """The supervisor's restart/shrink/fail call as a point-in-time
        span (category ``decision`` — merge_chrome_traces renders it as
        an instant event on the supervisor row)."""
        from deeplearning4j_tpu.observe import get_active_tracer
        tr = get_active_tracer()
        if tr is None:
            return
        import time as _time
        now = _time.perf_counter_ns()
        tr.record(f"elastic_{decision}", now, now, category="decision",
                  parent=None if gen_span is None else gen_span.context,
                  attrs={"generation": generation, "decision": decision,
                         "primary_slot": primary.slot_id,
                         "reason": reason or ""})

    def _run(self, *, raise_on_failure: bool) -> ElasticJobResult:
        # the trace dir holds THIS run's span streams: a previous run on
        # the same ckpt_dir reuses generation numbering, and its stale
        # files would contaminate write_fleet_trace's merge (hours-old
        # anchors stretch the timeline) and incident bundles (old
        # evidence presented as current)
        try:
            for name in os.listdir(self.trace_dir):
                if name.endswith(".jsonl"):
                    os.unlink(os.path.join(self.trace_dir, name))
        except OSError:
            pass
        result = ElasticJobResult(status="failed")
        world = list(range(self.num_workers))
        generation = 0
        deadline_ms = None
        if self.job_deadline_s is not None:
            deadline_ms = self.clock.current_time_millis() \
                + int(self.job_deadline_s * 1000)
        slots = {i: _Slot(slot_id=i) for i in world}
        while True:
            generation += 1
            token = f"g{generation}-{uuid.uuid4().hex[:12]}"
            eligible = self.eligible_steps()
            restore_step = eligible[-1] if eligible else None
            record = GenerationRecord(generation=generation, token=token,
                                      world=list(world),
                                      restore_step=restore_step)
            result.generations.append(record)
            self.ledger.open_generation(generation, token, world)
            _atomic_write(os.path.join(self.ckpt_dir, GENERATION_FILE),
                          json.dumps({"generation": generation,
                                      "token": token,
                                      "world_size": len(world)}))
            gen_span = self._gen_span_start(generation, token, world,
                                            restore_step)
            self._launch_generation(generation, token, world, slots,
                                    restore_step, eligible,
                                    gen_span=gen_span)
            self._world_gauge.set(len(world))
            self._gen_gauge.set(generation)
            self._hosts_gauge.set(self._live_hosts(world))
            self._log.info("generation started", generation=generation,
                           token=token, world=world,
                           restore_step=restore_step)
            outcome, dead = self._watch(
                [slots[s] for s in world], deadline_ms)
            self.ledger.fence(token)
            if outcome == "completed":
                record.outcome = "completed"
                result.status = "completed"
                self._gen_span_end(gen_span, "completed")
                self._log.info("job completed", generation=generation,
                               world=world)
                return result
            if outcome == "deadline":
                record.outcome = "failed"
                self._kill_world([slots[s] for s in world])
                self._gen_span_end(gen_span, "deadline")
                result.reason = (f"job deadline "
                                 f"({self.job_deadline_s}s) exceeded")
                return self._fail(result, raise_on_failure)

            # ---- recovery -------------------------------------------------
            from deeplearning4j_tpu.observe import span
            primary = dead[0]
            record.outcome = "recovered"
            record.dead_slots = [d.slot_id for d in dead]
            record.primary_slot = primary.slot_id
            record.primary_host = self.host_of(primary.slot_id)
            with span("elastic_recovery", category="elastic",
                      parent=None if gen_span is None else gen_span.context,
                      attrs={"generation": generation,
                             "primary_slot": primary.slot_id,
                             "primary_host": record.primary_host,
                             "dead_slots": record.dead_slots,
                             "reason": primary.death_reason}):
                self._kill_world([slots[s] for s in world])
                for d in dead:
                    self._deaths.inc(reason=d.death_reason or "exit")
                decision, delay, new_world, ladder = self._decide(
                    primary, world, result)
                record.decision = decision
                if decision == "fail":
                    record.outcome = "failed"
                    domain = (f"host {record.primary_host}"
                              if record.primary_host is not None
                              else f"slot {primary.slot_id}")
                    result.reason = (
                        f"{domain} exhausted its restart "
                        f"budget ({self.backoff.max_restarts}) and the "
                        f"world cannot shrink below min_workers="
                        f"{self.min_workers}"
                        + (f" / min_hosts={self.min_hosts}"
                           if self.num_hosts is not None else ""))
                    self._record_decision(gen_span, generation, decision,
                                          primary, result.reason)
                    self._write_incident(generation, decision,
                                         result.reason, 0.0, ladder,
                                         primary, dead, world, world,
                                         slots, restore_step)
                    self._gen_span_end(gen_span, "failed")
                    self._log.error("job failed",
                                    generation=generation,
                                    slot=primary.slot_id,
                                    reason=result.reason)
                    return self._fail(result, raise_on_failure)
                self._restarts.inc(decision=decision)
                result.restarts_total += 1
                reason = (f"{primary.death_reason or 'exit'} on slot "
                          f"{primary.slot_id}")
                self._record_decision(gen_span, generation, decision,
                                      primary, reason)
                self._write_incident(generation, decision, reason, delay,
                                     ladder, primary, dead, world,
                                     new_world, slots, restore_step)
                self._log.warning(
                    "recovering", generation=generation,
                    decision=decision, primary_slot=primary.slot_id,
                    death_reason=primary.death_reason,
                    backoff_s=round(delay, 3), next_world=new_world)
            self._gen_span_end(gen_span, "recovered")
            if delay > 0:
                result.backoff_delays.append(delay)
                self.sleep_fn(delay)
            world = new_world

    def _fail(self, result: ElasticJobResult,
              raise_on_failure: bool) -> ElasticJobResult:
        result.status = "failed"
        if raise_on_failure:
            raise ElasticJobFailed(result.reason or "elastic job failed",
                                   result)
        return result

    # -- recovery decision -------------------------------------------------
    def _decide(self, primary: _Slot, world: List[int],
                result: ElasticJobResult):
        """(decision, backoff_delay, new_world, ladder) for one recovery
        round; ``ladder`` is the per-rung reasoning the incident bundle
        records (which rungs were considered, which one was taken, why).

        Only the PRIMARY victim's failure DOMAIN is charged: peers die
        as collateral when the world breaks (their collectives can never
        complete) and a budget charge for each would turn one fault into
        a cascade of budget exhaustion. With host grouping the domain is
        the whole host — shrink removes every slot of the victim host,
        keeping per-host slice shapes intact down to ``min_hosts``."""
        ladder: List[dict] = []
        domain = self._domain_of(primary.slot_id)
        startup_eligible = not primary.live \
            and domain.startup_retries_used < self.startup_retries
        ladder.append({
            "rung": "startup_retry", "taken": startup_eligible,
            "detail": (f"never live, retries used "
                       f"{domain.startup_retries_used}/"
                       f"{self.startup_retries}" if not primary.live
                       else "worker was live: not a startup flake")})
        if startup_eligible:
            # never became live: a port race / startup flake, not a
            # training fault — retry in place without touching the budget
            domain.startup_retries_used += 1
            return "restart", 0.0, list(world), ladder
        budget_left = domain.restarts_used < self.backoff.max_restarts
        ladder.append({
            "rung": "restart", "taken": budget_left,
            "detail": (f"domain budget {domain.restarts_used}/"
                       f"{self.backoff.max_restarts} used")})
        if budget_left:
            domain.restarts_used += 1
            host = self.host_of(primary.slot_id)
            seed = f"{self.job_id}:h{host}" if host is not None \
                else f"{self.job_id}:{primary.slot_id}"
            delay = self.backoff.delay(domain.restarts_used, seed=seed)
            return "restart", delay, list(world), ladder
        victims = set(self._domain_slots(primary.slot_id, world))
        survivors = [s for s in world if s not in victims]
        can_shrink = len(survivors) >= self.min_workers \
            and self._live_hosts(survivors) >= self.min_hosts
        ladder.append({
            "rung": "shrink", "taken": can_shrink,
            "detail": (f"survivors {survivors} vs floors min_workers="
                       f"{self.min_workers}, min_hosts={self.min_hosts}")})
        if can_shrink:
            return "shrink", 0.0, survivors, ladder
        ladder.append({"rung": "fail", "taken": True,
                       "detail": "cannot restart or shrink further"})
        return "fail", 0.0, list(world), ladder

    # -- incident flight recorder ------------------------------------------
    def _write_incident(self, generation: int, decision: str, reason: str,
                        delay: float, ladder: List[dict], primary: _Slot,
                        dead: List[_Slot], world_before: List[int],
                        world_after: List[int], slots: Dict[int, _Slot],
                        restore_step: Optional[int]) -> None:
        """Assemble the bounded incident bundle for one recovery
        decision. Best-effort by design: a broken flight recorder is a
        log line, never a second incident."""
        if self.incidents is None:
            return
        try:
            dead_ids = {d.slot_id for d in dead}
            workers = []
            for slot_id in world_before:
                s = slots[slot_id]
                workers.append({
                    "slot": slot_id, "host": self.host_of(slot_id),
                    "last_step": s.last_step, "live": s.live,
                    "death_reason": s.death_reason,
                    "exit_code": s.exit_code})
            log_tails = {d.slot_id: self.tail_log(d.slot_id, generation)
                         for d in dead}
            span_files = []
            try:
                # only the dying generation's streams: a long job writes
                # one file per generation per worker, and copying them
                # ALL into every bundle would grow incident disk with
                # job age (the flight recorder must stay bounded)
                tag = f".gen{generation:03d}."
                span_files = sorted(
                    os.path.join(self.trace_dir, n)
                    for n in os.listdir(self.trace_dir)
                    if n.endswith(".jsonl") and tag in n)
            except OSError:
                pass
            live_spans = None
            from deeplearning4j_tpu.observe import get_active_tracer
            tr = get_active_tracer()
            if tr is not None:
                live_spans = ("supervisor", tr.recorder.spans())
            metrics_text = (self.fleet.exposition() if self.fleet is not None
                            else self.metrics.exposition())
            env = self.spec.environment()
            path = self.incidents.record(
                job_id=self.job_id, generation=generation,
                ts_ms=self.clock.current_time_millis(),
                decision=decision, reason=reason, backoff_s=delay,
                ladder=ladder,
                victim={"slot": primary.slot_id,
                        "host": self.host_of(primary.slot_id),
                        "death_reason": primary.death_reason},
                dead_slots=sorted(dead_ids),
                world_before=world_before, world_after=world_after,
                workers=workers,
                checkpoint={"restore_step": restore_step,
                            # the generation is already fenced at
                            # decision time: this is the exact step the
                            # recovered world will resume from
                            "next_restore_step": self.latest_eligible_step(),
                            "eligible_steps": self.eligible_steps()},
                fault_plan_env=env.get("DL4J_TPU_FAULT_PLAN"),
                metrics_text=metrics_text,
                span_files=span_files, live_spans=live_spans,
                log_tails=log_tails)
            self._log.info("incident bundle written", path=path,
                           decision=decision, generation=generation)
        except Exception as e:  # noqa: BLE001 - never fail recovery
            self._log.error("incident bundle failed", error=str(e))

    # -- process management ------------------------------------------------
    def _launch_generation(self, generation: int, token: str,
                           world: List[int], slots: Dict[int, _Slot],
                           restore_step: Optional[int],
                           eligible: Optional[Sequence[int]] = None,
                           gen_span=None) -> None:
        if eligible is None:
            eligible = self.eligible_steps()
        eligible_env = ",".join(str(s) for s in eligible)
        bind = self.spec.resolved_bind_host()
        port = _free_port(bind) if self.port_fn is _free_port \
            else self.port_fn()
        coordinator = _join_host_port(
            self.spec.resolved_advertise_host(), port)
        log_dir = os.path.join(self.ckpt_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        if gen_span is not None:
            os.makedirs(self.trace_dir, exist_ok=True)
        if self.fleet is not None:
            # the federation follows the CURRENT world: sources of shrunk
            # slots drop out (their series go absent, which the absence
            # rules can alert on) and survivors re-register under the new
            # generation label
            self.fleet.clear_sources()
        now = self.clock.current_time_millis()
        for pid, slot_id in enumerate(sorted(world)):
            s = slots[slot_id]
            s.hb_path = os.path.join(
                self.ckpt_dir, f"heartbeat.slot{slot_id}")
            try:
                # a stale beat from the previous generation would mark the
                # relaunched worker live before it ever runs — turning a
                # startup flake into a budget charge
                os.unlink(s.hb_path)
            except OSError:
                pass
            s.log_path = os.path.join(
                log_dir, f"gen{generation:03d}_slot{slot_id}.log")
            s.last_beat = None
            s.last_beat_at_ms = now
            s.live = False
            s.done = False
            s.exit_code = None
            s.death_reason = None
            s.last_step = None
            s.last_step_at_ms = now
            s.progressed = False
            env = self.spec.environment()
            env.update({
                ENV_COORDINATOR: coordinator,
                ENV_NUM_PROCESSES: str(len(world)),
                ENV_PROCESS_ID: str(pid),
                ENV_SLOT: str(slot_id),
                ENV_GENERATION: str(generation),
                ENV_TOKEN: token,
                ENV_CKPT_DIR: self.ckpt_dir,
                ENV_HEARTBEAT: s.hb_path,
                ENV_RESTORE_STEP: "" if restore_step is None
                else str(restore_step),
                ENV_ELIGIBLE_STEPS: eligible_env,
            })
            if self.spec.mesh_axes:
                from deeplearning4j_tpu.parallel.mesh import format_mesh_axes
                env[ENV_MESH] = format_mesh_axes(self.spec.mesh_axes)
            if self.spec.sharding_rules:
                env[ENV_SHARDING_RULES] = self.spec.sharding_rules
            host = self.host_of(slot_id)
            if host is not None:
                env[ENV_HOST] = str(host)
                env[ENV_NUM_HOSTS] = str(self.num_hosts)
            if gen_span is not None:
                # worker spans parent into this generation's job trace
                # and stream crash-durably into the supervisor's trace dir
                env[ENV_TRACEPARENT] = gen_span.context.traceparent()
                env[ENV_TRACE_DIR] = self.trace_dir
            if self.fleet is not None:
                metrics_path = os.path.join(
                    self.ckpt_dir, f"metrics.slot{slot_id}.prom")
                try:
                    # a stale snapshot from the previous generation must
                    # not masquerade as this incarnation's series
                    os.unlink(metrics_path)
                except OSError:
                    pass
                env[ENV_METRICS_FILE] = metrics_path
                labels = {"slot": slot_id, "generation": generation}
                if host is not None:
                    labels["host"] = host
                self.fleet.set_source(slot_id, metrics_path, labels)
            if bind != "127.0.0.1":
                # process 0 must LISTEN on the bind interface while peers
                # dial the advertised one (ctx.init_distributed forwards
                # this as jax's coordinator_bind_address)
                env[ENV_BIND_HOST] = bind
            if self.progress_timeout_s is not None:
                # the partition signature is liveness WITHOUT progress:
                # workers must keep beating from a background thread
                # while a step blocks, at a cadence the watchdog can see
                env[ENV_PROGRESS_BEAT] = str(
                    max(0.05, min(1.0, self.progress_timeout_s / 5.0)))
            s.proc = self.launcher.launch(self.spec.argv, env,
                                          self.spec.cwd, s.log_path)

    def _watch(self, live_slots: List[_Slot], deadline_ms: Optional[int]):
        """Poll until every worker exits 0 ("completed") or a death/stall
        is observed (returns the dead slots, primary first)."""
        while True:
            now = self.clock.current_time_millis()
            if deadline_ms is not None and now > deadline_ms:
                return "deadline", []
            dead: List[_Slot] = []
            all_done = True
            for s in live_slots:
                if s.done:
                    continue
                rc = s.proc.poll()
                if rc is not None:
                    self._reap(s)
                    if rc == 0:
                        s.done = True
                        continue
                    s.exit_code = rc
                    s.death_reason = "signal" if rc < 0 else "exit"
                    dead.append(s)
                    continue
                all_done = False
                beat = self._read_heartbeat(s)
                if beat is not None and beat != s.last_beat:
                    s.last_beat = beat
                    s.last_beat_at_ms = now
                    s.live = True
                    step = self._parse_heartbeat_step(beat)
                    if step is not None and step != s.last_step:
                        if s.last_step is not None:
                            s.progressed = True
                        s.last_step = step
                        s.last_step_at_ms = now
                    elif beat.rstrip().endswith(":save"):
                        # a declared in-progress checkpoint holds the
                        # partition watchdog: a save stall (slow
                        # filesystem, backpressured async window) is not
                        # a partition — the job deadline still backstops
                        # a save that never ends
                        s.last_step_at_ms = now
                else:
                    timeout = (self.heartbeat_timeout_s if s.live
                               else self.startup_timeout_s)
                    if now - s.last_beat_at_ms > timeout * 1000:
                        s.proc.kill()
                        self._reap(s)
                        s.death_reason = "stall"
                        dead.append(s)
            if not dead:
                dead = self._check_progress(live_slots, now)
            if dead:
                # signal-killed victims ahead of error exits: when a kill
                # and its collateral land in one poll round, the victim is
                # the primary
                dead.sort(key=lambda d: (0 if d.death_reason == "signal"
                                         else 1 if d.death_reason == "stall"
                                         else 2, d.slot_id))
                return "dead", dead
            if all_done:
                return "completed", []
            self.sleep_fn(self.poll_interval_s)

    @staticmethod
    def _parse_heartbeat_step(beat: str) -> Optional[int]:
        """Training step out of a ``generation:step:beats`` heartbeat
        payload; None for any other format (legacy workers — progress
        tracking simply stays inactive for them)."""
        parts = beat.split(":")
        if len(parts) >= 2:
            try:
                return int(parts[1])
            except ValueError:
                return None
        return None

    def _check_progress(self, live_slots: List[_Slot], now: int):
        """The partition watchdog: every live worker still heartbeating
        (alive) but NO worker advancing its training step for
        ``progress_timeout_s`` is the signature of a network partition —
        a collective across the cut can never complete, so both sides
        stall mid-step while staying perfectly healthy. A mere slow
        worker never trips this: as long as steps complete anywhere,
        progress timestamps keep moving. Neither does a generation that
        has not completed a single step yet — a long first-step compile
        stalls everyone globally and is startup, not a partition (the
        startup/heartbeat timeouts own that window).

        Resolution: the side that stopped progressing FIRST (lowest
        heartbeat step) is the partitioned minority — it is killed and
        charged like a death, and the decision ladder restarts or
        shrinks it away. Ties resolve against the smaller host group,
        then the higher host id (deterministic; with a symmetric cut
        someone must die, and the survivors keep the job)."""
        if self.progress_timeout_s is None:
            return []
        candidates = [s for s in live_slots if not s.done and s.live]
        if not candidates:
            return []
        if any(s.last_step is None for s in candidates):
            return []  # someone never reported a step — not a partition
        # a generation where nobody ever advanced is usually starting up
        # (first-step compile) — give it the STARTUP window instead of
        # the step window, but not forever: a generation relaunched into
        # a still-active cut also never completes a step, and with
        # background beats alive nothing else would ever resolve it
        window = self.progress_timeout_s
        if not any(s.progressed for s in candidates):
            window = max(window, self.startup_timeout_s)
        if any(now - s.last_step_at_ms <= window * 1000
               for s in candidates):
            return []
        # group by failure domain; victim = least-progressed group
        groups: Dict[object, List[_Slot]] = {}
        for s in candidates:
            key = self.host_of(s.slot_id)
            key = s.slot_id if key is None else key
            groups.setdefault(key, []).append(s)
        if len(groups) < 2:
            return []  # one domain left: nothing to resolve a cut against
        victim_key = min(
            groups,
            key=lambda k: (max(s.last_step for s in groups[k]),
                           len(groups[k]), -(k if isinstance(k, int) else 0)))
        victims = sorted(groups[victim_key], key=lambda s: s.slot_id)
        for v in victims:
            v.proc.kill()
            self._reap(v)
            v.death_reason = "partition"
        self._partitions.inc()
        self._log.warning(
            "partition resolved", victim_domain=victim_key,
            victim_slots=[v.slot_id for v in victims],
            progress_timeout_s=self.progress_timeout_s)
        return victims

    def _read_heartbeat(self, s: _Slot) -> Optional[str]:
        try:
            with open(s.hb_path, encoding="utf-8") as fh:
                return fh.read()
        except OSError:
            return None

    def _kill_world(self, live_slots: List[_Slot]) -> None:
        for s in live_slots:
            if s.done or s.proc is None:
                continue
            if s.proc.poll() is None:
                s.proc.kill()
            self._reap(s)

    @staticmethod
    def _reap(s: _Slot) -> None:
        try:
            s.proc.wait(timeout=30)
        except Exception:  # noqa: BLE001 - last resort; do not hang recovery
            pass
        fh = getattr(s.proc, "_elastic_log", None)
        if fh is not None:
            fh.close()
            s.proc._elastic_log = None

    #: hard cap on one tail_log read — the ring-buffer discipline: a
    #: multi-GB worker log must never be slurped whole into the
    #: supervisor (or an incident bundle) because a caller asked big
    TAIL_LOG_CAP = 1 << 20

    def tail_log(self, slot_id: int, generation: int,
                 n_bytes: int = 4000) -> str:
        """Last bytes of one worker incarnation's captured output.
        Tolerates the worker truncating/rotating its own log mid-read
        (the computed tail offset may no longer exist — re-read from the
        top instead of returning garbage or raising) and caps the read
        at :data:`TAIL_LOG_CAP` regardless of ``n_bytes``."""
        n_bytes = max(0, min(int(n_bytes), self.TAIL_LOG_CAP))
        path = os.path.join(self.ckpt_dir, "logs",
                            f"gen{generation:03d}_slot{slot_id}.log")
        try:
            with open(path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                size = fh.tell()
                fh.seek(max(0, size - n_bytes))
                data = fh.read(n_bytes)
                if not data and size > 0:
                    # truncated/rotated between tell() and read(): the
                    # offset we computed is past the new EOF
                    fh.seek(0)
                    data = fh.read(n_bytes)
                return data.decode(errors="replace")
        except (OSError, ValueError):
            return ""

    def write_fleet_trace(self, path: str) -> int:
        """Stitch every worker span stream under ``trace_dir`` plus the
        supervisor's own recorded spans into ONE Perfetto-loadable
        timeline at ``path`` (``observe.export.merge_chrome_traces``);
        returns the event count (0 = tracing never ran)."""
        from deeplearning4j_tpu.observe import get_active_tracer
        from deeplearning4j_tpu.observe.export import merge_chrome_traces
        from deeplearning4j_tpu.observe.trace import EPOCH_ANCHOR
        sources: List[object] = []
        try:
            sources.extend(sorted(
                os.path.join(self.trace_dir, n)
                for n in os.listdir(self.trace_dir)
                if n.endswith(".jsonl")))
        except OSError:
            pass
        tr = get_active_tracer()
        if tr is not None and len(tr.recorder):
            sources.append({"label": "supervisor",
                            "spans": tr.recorder.spans(),
                            "anchor": EPOCH_ANCHOR})
        obj = merge_chrome_traces(sources, out=path)
        return len(obj["traceEvents"])


# -- worker side -------------------------------------------------------------

class StaleGenerationError(RuntimeError):
    """This worker's generation token no longer matches the supervisor's
    current generation — the world moved on; nothing this process writes
    may be trusted."""


def _parse_env_mesh(spec: Optional[str]) -> Optional[Dict[str, int]]:
    if not spec:
        return None
    from deeplearning4j_tpu.parallel.mesh import parse_mesh_axes
    return parse_mesh_axes(spec)


@dataclasses.dataclass
class ElasticWorkerContext:
    """A worker's view of its elastic world, decoded from the supervisor's
    environment variables."""

    coordinator: str
    num_processes: int
    process_id: int
    slot: int
    generation: int
    token: str
    ckpt_dir: str
    heartbeat_path: str
    restore_step: Optional[int]
    #: fence-eligible steps as computed by the supervisor at launch; the
    #: corrupt-step fallback walk is restricted to these (None = launched
    #: outside a supervisor, no fence to honor)
    eligible_steps: Optional[List[int]] = None
    #: host failure domain (None = no host grouping)
    host: Optional[int] = None
    num_hosts: Optional[int] = None
    #: per-host mesh slice shape from the supervisor (non-data axes of
    #: the pod mesh; None = classic one-device-per-worker data
    #: parallelism) and the sharding-rules JSON path to place params with
    mesh_axes: Optional[Dict[str, int]] = None
    sharding_rules_path: Optional[str] = None
    #: background-heartbeat cadence; set by the supervisor when its
    #: step-progress (partition) watchdog is armed
    progress_beat_s: Optional[float] = None
    #: interface process 0's coordinator must LISTEN on when it differs
    #: from the advertised address (None → jax binds the advertised one)
    bind_host: Optional[str] = None
    #: fleet observability seam (all None outside a fleet-observing
    #: supervisor — every dependent hook is then a no-op): the
    #: supervisor's per-generation elastic_job span context, the
    #: directory this worker streams its spans into, and the file it
    #: writes Prometheus exposition snapshots to
    traceparent: Optional[str] = None
    trace_dir: Optional[str] = None
    metrics_file: Optional[str] = None
    _beats: int = 0
    _last_step: int = 0
    _beat_thread: object = None
    _beat_stop: object = None
    # one lock guards the heartbeat write AND the saving counter: the
    # training, beat and async-saver threads all pass through here
    _beat_lock: object = dataclasses.field(default_factory=threading.Lock)
    # >0 while a checkpoint is in flight anywhere (blocking save, async
    # submit, background write); heartbeats then declare the save so the
    # supervisor's partition watchdog holds fire — a save stall is not a
    # partition
    _saving: int = 0

    @classmethod
    def from_env(cls, environ=None) -> Optional["ElasticWorkerContext"]:
        env = os.environ if environ is None else environ
        if ENV_TOKEN not in env:
            return None
        restore = env.get(ENV_RESTORE_STEP, "")
        eligible = env.get(ENV_ELIGIBLE_STEPS)
        host = env.get(ENV_HOST)
        ctx = cls(
            coordinator=env[ENV_COORDINATOR],
            num_processes=int(env[ENV_NUM_PROCESSES]),
            process_id=int(env[ENV_PROCESS_ID]),
            slot=int(env[ENV_SLOT]),
            generation=int(env[ENV_GENERATION]),
            token=env[ENV_TOKEN],
            ckpt_dir=env[ENV_CKPT_DIR],
            heartbeat_path=env[ENV_HEARTBEAT],
            restore_step=int(restore) if restore else None,
            eligible_steps=None if eligible is None
            else [int(s) for s in eligible.split(",") if s],
            host=int(host) if host is not None else None,
            num_hosts=int(env[ENV_NUM_HOSTS])
            if ENV_NUM_HOSTS in env else None,
            mesh_axes=_parse_env_mesh(env.get(ENV_MESH)),
            sharding_rules_path=env.get(ENV_SHARDING_RULES) or None,
            progress_beat_s=float(env[ENV_PROGRESS_BEAT])
            if env.get(ENV_PROGRESS_BEAT) else None,
            bind_host=env.get(ENV_BIND_HOST) or None,
            traceparent=env.get(ENV_TRACEPARENT) or None,
            trace_dir=env.get(ENV_TRACE_DIR) or None,
            metrics_file=env.get(ENV_METRICS_FILE) or None)
        if ctx.host is not None:
            from deeplearning4j_tpu.util import faultinject
            faultinject.set_host(ctx.host)  # host-scoped faults key on it
        return ctx

    # -- liveness ---------------------------------------------------------
    def heartbeat(self, step: int) -> None:
        from deeplearning4j_tpu.util import faultinject
        self._last_step = int(step)
        if not faultinject.on_heartbeat(self.slot, step):
            return
        # serialized against the background beat thread: the atomic-write
        # tmp name is keyed by PID only, so two same-process writers
        # would race on one tmp file (os.replace stealing it mid-write)
        with self._beat_lock:
            self._beats += 1
            busy = ":save" if self._saving > 0 else ""
            _atomic_write(self.heartbeat_path,
                          f"{self.generation}:{step}:{self._beats}{busy}")

    def _mark_saving(self, delta: int) -> None:
        """Adjust the in-progress-checkpoint count (lock-guarded: the
        training thread and the async saver thread both touch it)."""
        with self._beat_lock:
            self._saving += delta

    def start_heartbeat_thread(self) -> None:
        """Keep beating from a daemon thread at ``progress_beat_s`` while
        the main thread is inside a step — liveness and step progress
        become independently observable, which is exactly what lets the
        supervisor tell a partition (alive, stuck) from a dead worker.
        The beat repeats the LAST step the main thread reported; only
        the main thread ever advances it."""
        if self._beat_thread is not None or not self.progress_beat_s:
            return
        self._beat_stop = threading.Event()

        def _loop():
            while not self._beat_stop.wait(self.progress_beat_s):
                self.heartbeat(self._last_step)

        self._beat_thread = threading.Thread(
            target=_loop, name=f"elastic-beat-slot{self.slot}", daemon=True)
        self._beat_thread.start()

    def stop_heartbeat_thread(self) -> None:
        if self._beat_thread is not None:
            self._beat_stop.set()
            self._beat_thread.join(timeout=5)
            self._beat_thread = None

    # -- world formation --------------------------------------------------
    def init_distributed(self) -> None:
        from deeplearning4j_tpu.parallel.master import init_distributed
        bind_address = None
        if self.process_id == 0 and self.bind_host:
            # listen on the bind interface, advertise the dialable one —
            # same port (the supervisor probed it on the BIND interface;
            # rsplit keeps a bracketed IPv6 advertise address intact)
            port = self.coordinator.rsplit(":", 1)[-1]
            bind_address = _join_host_port(self.bind_host, port)
        init_distributed(coordinator_address=self.coordinator,
                         num_processes=self.num_processes,
                         process_id=self.process_id,
                         coordinator_bind_address=bind_address)

    # -- fenced checkpointing ---------------------------------------------
    def check_fence(self) -> None:
        """Abort (loudly) when the supervisor has moved to a newer
        generation: a stale worker must not write checkpoints."""
        try:
            with open(os.path.join(self.ckpt_dir, GENERATION_FILE),
                      encoding="utf-8") as fh:
                current = json.load(fh)
        except (OSError, ValueError):
            return  # no generation file yet — standalone run
        if current.get("token") != self.token:
            raise StaleGenerationError(
                f"generation {self.generation} ({self.token}) has been "
                f"superseded by {current.get('generation')} "
                f"({current.get('token')}); refusing to checkpoint")

    def master_state_path(self, step: int, rank: Optional[int] = None,
                          world: Optional[int] = None) -> str:
        """Rank-local compression state for one committed step. Keyed by
        world size: residual shards only make sense on the world shape
        that wrote them — a shrunk world skips them and re-accumulates."""
        rank = self.process_id if rank is None else rank
        world = self.num_processes if world is None else world
        return os.path.join(
            self.ckpt_dir,
            f"master_state.step{int(step):08d}.w{world}.r{rank}.npz")

    def pod_mesh_axes(self) -> Dict[str, int]:
        """The generation's pod mesh shape: ``data`` spans the CURRENT
        processes (DCN across hosts), any supervisor-forwarded extra
        axes live inside each host's slice (ICI). Shrinks change only
        the data extent — the model sharding survives a generation."""
        axes = {"data": self.num_processes}
        for name, size in (self.mesh_axes or {}).items():
            if name != "data":
                axes[name] = int(size)
        return axes

    def save_checkpoint_sharded(self, step: int, model, manager,
                                peer_wait_s: float = 120.0) -> None:
        """Pod-mesh commit: EVERY rank participates in one collective
        orbax save — each process writes exactly the model shards its
        devices own (genuinely sharded bytes, not a replicated copy from
        rank 0) — then rank 0 alone runs the fencing commit (stamp,
        prune). No master residual shards on this path: GSPMD owns the
        gradient exchange, so the stamp waits on no peer files."""
        from deeplearning4j_tpu.util import faultinject
        self.check_fence()
        self._mark_saving(+1)
        try:
            faultinject.on_save_phase(self.slot, step, "pre_write",
                                      host=self.host)
            ok = manager.save(step, model,
                              overwrite_existing=(self.process_id == 0))
            faultinject.on_save_phase(self.slot, step, "mid_shard",
                                      host=self.host)
            if self.process_id == 0:
                self._commit_step(step, manager, save_model_fn=lambda: ok,
                                  expect_shards=False,
                                  peer_wait_s=peer_wait_s)
        finally:
            self._mark_saving(-1)

    def save_checkpoint(self, step: int, model, master=None, manager=None,
                        peer_wait_s: float = 120.0) -> None:
        """One committed checkpoint step: every rank saves its own master
        compression state; rank 0 writes the orbax model checkpoint, waits
        for every peer's state file, applies any planned
        ``corrupt_checkpoint`` fault, then writes the step stamp (the
        commit marker the supervisor's restore choice reads). The
        ``on_save_phase`` fault hooks fire at the same protocol points as
        on the async path — a phase-scoped fault plan behaves identically
        under both save modes."""
        from deeplearning4j_tpu.util import faultinject
        self.check_fence()
        self._mark_saving(+1)
        try:
            faultinject.on_save_phase(self.slot, step, "pre_write",
                                      host=self.host)
            if master is not None:
                master.save_state(self.master_state_path(step))
            faultinject.on_save_phase(self.slot, step, "mid_shard",
                                      host=self.host)
            if manager is not None:  # rank 0 owns the model checkpoint
                self._commit_step(
                    step, manager,
                    # overwrite_existing: a finalized-but-corrupt dir for
                    # this step (fenced-lineage leftover the fallback
                    # restore walked past) makes a plain orbax save
                    # silently decline — stamping then would re-advertise
                    # the corrupt bytes under OUR token
                    save_model_fn=lambda: manager.save(
                        step, model, overwrite_existing=True),
                    expect_shards=master is not None,
                    peer_wait_s=peer_wait_s)
        finally:
            self._mark_saving(-1)

    def _commit_step(self, step: int, manager, *, save_model_fn,
                     expect_shards: bool, peer_wait_s: float) -> None:
        """The committing rank's barrier — ONE implementation for the
        sync and async paths (the fencing protocol must never diverge
        between them): orbax write + finalize, every rank's shard file
        landed, the planned ``corrupt_checkpoint`` fault, the pre_stamp
        hook, a fence re-check, the step stamp, retention pruning."""
        import time as _time
        from deeplearning4j_tpu.util import faultinject
        if not save_model_fn():
            raise RuntimeError(
                f"orbax declined to save checkpoint step {step}; "
                f"refusing to stamp a step that was not written")
        manager.wait_until_finished()
        if expect_shards:
            deadline = _time.time() + peer_wait_s
            for r in range(self.num_processes):
                path = self.master_state_path(step, rank=r)
                while not os.path.exists(path):
                    if _time.time() > deadline:
                        raise RuntimeError(
                            f"rank {r} shard for step {step} never "
                            f"appeared at {path}; leaving the step "
                            f"torn (unstamped)")
                    _time.sleep(0.1)
        step_dir = os.path.join(self.ckpt_dir, str(int(step)))
        if os.path.isdir(step_dir):
            faultinject.on_checkpoint_saved(self.slot, step, step_dir)
        faultinject.on_save_phase(self.slot, step, "pre_stamp",
                                  host=self.host)
        self.check_fence()
        write_step_stamp(self.ckpt_dir, step, self.token,
                         self.generation, self.num_processes)
        self._prune_unretained(manager)

    def _prune_unretained(self, manager) -> None:
        """Drop step stamps and master-state shards whose model
        checkpoint fell out of the orbax retention window: nothing can
        restore them, and the per-rank residual shards are model-sized —
        ``max_to_keep`` caps orbax disk, this caps the rest (otherwise a
        long job fills the checkpoint volume the supervisor depends on)."""
        try:
            retained = set(manager.all_steps())
        except Exception:  # noqa: BLE001 - pruning must never fail a save
            return
        for name in os.listdir(self.ckpt_dir):
            step = None
            if name.startswith(_STAMP_PREFIX) and name.endswith(".json"):
                step = name[len(_STAMP_PREFIX):-len(".json")]
            elif name.startswith("master_state.step"):
                step = name[len("master_state.step"):][:8]
            if step is None:
                continue
            try:
                step = int(step)
            except ValueError:
                continue
            if step not in retained:
                try:
                    os.unlink(os.path.join(self.ckpt_dir, name))
                except OSError:
                    pass


class AsyncCheckpointSession:
    """Asynchronous sharded checkpointing as the elastic recovery
    substrate: every rank hands its shard (the rank-local master
    compression state, snapshotted on the training thread) plus — on the
    manager-owning rank — a host-numpy snapshot of the model state to a
    single background saver thread, and trains on while the bytes hit
    disk. The generation-fencing commit protocol is unchanged, just
    moved off the step path: the step stamp is written only after the
    orbax save finalized AND every rank's shard landed, so a crash at
    ANY phase of an overlapped save leaves a torn step that is never
    restorable (the fallback walk only sees stamped steps).

    In-flight saves are bounded by ``max_in_flight``: once the window is
    full, :meth:`submit` blocks until the oldest save completes — a slow
    filesystem backpressures training instead of accumulating unbounded
    snapshots (the time spent blocked is accounted in
    ``submit_stall_s``). All checkpoint-manager calls happen on the
    saver thread; do not use the manager from other threads while a
    session is open."""

    def __init__(self, ctx: "ElasticWorkerContext", *, manager=None,
                 master=None, max_in_flight: int = 2,
                 peer_wait_s: float = 120.0):
        import queue
        import threading
        if max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self.ctx = ctx
        self.manager = manager
        self.master = master
        self.peer_wait_s = peer_wait_s
        self._sem = threading.Semaphore(max_in_flight)
        self._q: "queue.Queue" = queue.Queue()
        self._pending: List[object] = []
        self.errors: List[str] = []
        self.committed: List[int] = []
        self.submitted = 0
        #: seconds the TRAINING thread spent blocked on the in-flight
        #: window — the measured save stall of the async path
        self.submit_stall_s = 0.0
        self._thread = threading.Thread(
            target=self._run, name=f"elastic-ckpt-slot{ctx.slot}",
            daemon=True)
        self._thread.start()

    # -- training-thread side --------------------------------------------
    def submit(self, step: int, model) -> None:
        """Snapshot and enqueue one checkpoint step. Blocks only when
        ``max_in_flight`` saves are already in the pipe (backpressure);
        otherwise returns as soon as the device arrays are copied to
        host — the save overlaps the next training step."""
        import threading
        import time as _time
        # heartbeats declare the save from here until the SAVER thread
        # finishes the item (released in _run) — the whole in-flight
        # window, including the final flush, holds the supervisor's
        # partition watchdog, not just the submit/backpressure slice
        self.ctx._mark_saving(+1)
        try:
            t0 = _time.perf_counter()
            self._sem.acquire()
            self.submit_stall_s += _time.perf_counter() - t0
            try:
                self.ctx.check_fence()  # fail fast on the training thread
                master_snap = None if self.master is None \
                    else self.master.state_snapshot()
                state = None
                if self.manager is not None:
                    from deeplearning4j_tpu.util.orbax_checkpoint import (
                        snapshot_state)
                    state = snapshot_state(model)
            except BaseException:
                self._sem.release()
                raise
        except BaseException:
            self.ctx._mark_saving(-1)  # nothing was enqueued
            raise
        done = threading.Event()
        item = {"step": int(step), "model": model, "state": state,
                "master_snap": master_snap, "done": done}
        # keep only in-flight events: a long per-step-checkpoint run must
        # not grow this list (and every flush walk) without bound
        self._pending = [ev for ev in self._pending if not ev.is_set()]
        self._pending.append(done)
        self.submitted += 1
        self._q.put(item)

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait for every submitted save to finish (committed or failed);
        True when all landed within ``timeout`` seconds."""
        import time as _time
        deadline = None if timeout is None else _time.time() + timeout
        for ev in list(self._pending):
            remaining = None if deadline is None \
                else max(0.0, deadline - _time.time())
            if not ev.wait(remaining):
                return False
        return True

    def close(self, timeout: Optional[float] = None) -> bool:
        """Flush, then stop the saver thread. Returns the flush result."""
        ok = self.flush(timeout)
        self._q.put(None)
        self._thread.join(timeout=5)
        return ok

    # -- saver-thread side ------------------------------------------------
    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._do_save(item)
            except BaseException as e:  # noqa: BLE001 - a failed save is
                # a torn step (no stamp), NOT a dead worker: record it
                # and keep training; restore falls back to the previous
                # committed step
                self.errors.append(
                    f"step {item['step']}: {type(e).__name__}: {e}")
            finally:
                item["done"].set()
                self._sem.release()
                self.ctx._mark_saving(-1)  # paired with submit's +1

    def _do_save(self, item: dict) -> None:
        from deeplearning4j_tpu.observe import span
        from deeplearning4j_tpu.util import faultinject
        ctx, step = self.ctx, item["step"]
        with span("elastic_async_save", category="elastic",
                  attrs={"step": step, "slot": ctx.slot,
                         "rank": ctx.process_id}):
            faultinject.on_save_phase(ctx.slot, step, "pre_write",
                                      host=ctx.host)
            if item["master_snap"] is not None:
                # the rank-local shard; its (atomic) existence is this
                # rank's "finalize landed" signal to the committing rank
                self.master.write_state_snapshot(
                    item["master_snap"], ctx.master_state_path(step))
            faultinject.on_save_phase(ctx.slot, step, "mid_shard",
                                      host=ctx.host)
            if self.manager is None:
                return
            # the committing rank: the SAME barrier the sync path runs
            # (orbax finalize → all shards → pre_stamp → fence → stamp),
            # just fed from the snapshot instead of the live model
            ctx._commit_step(
                step, self.manager,
                save_model_fn=lambda: self.manager.save(
                    step, item["model"], overwrite_existing=True,
                    state=item["state"]),
                expect_shards=item["master_snap"] is not None,
                peer_wait_s=self.peer_wait_s)
            self.committed.append(step)


def run_elastic_worker(build_model, build_iterator, *, epochs: int,
                       master_kwargs: Optional[dict] = None,
                       checkpoint_every: int = 1,
                       max_to_keep: Optional[int] = None,
                       save_mode: str = "sync",
                       max_in_flight: int = 2,
                       flush_timeout_s: float = 300.0,
                       on_done=None, ctx: Optional[ElasticWorkerContext]
                       = None):
    """Generic elastic worker runloop — the library composition the
    recovery tests used to hand-roll (``tests/failover_worker.py``):

    join the generation's ``jax.distributed`` world → restore the
    supervisor-chosen checkpoint step (with corrupt-step fallback) →
    rebuild the mesh at the CURRENT world size → resume
    ``SharedTrainingMaster`` training with per-iteration heartbeats +
    fault hooks → write fenced rotation checkpoints every
    ``checkpoint_every`` epochs.

    ``save_mode="async"`` routes checkpoints through an
    :class:`AsyncCheckpointSession`: saves overlap the next training
    steps, bounded at ``max_in_flight`` in the pipe, and the final flush
    (capped at ``flush_timeout_s``) happens before the manager closes. A
    save that fails asynchronously is a torn (never-restorable) step,
    not a worker death — it is logged and the job trains on.

    ``build_model()`` must be deterministic (fresh start only);
    ``build_iterator()`` is called once per epoch. ``on_done(net, ctx)``
    runs after the final epoch (e.g. rank 0 dumps params).
    Returns the trained network.
    """
    if save_mode not in ("sync", "async"):
        raise ValueError(f"save_mode must be sync|async, got {save_mode!r}")
    if ctx is None:
        ctx = ElasticWorkerContext.from_env()
    if ctx is None:
        raise RuntimeError(
            "run_elastic_worker needs the supervisor environment "
            f"({ENV_TOKEN} etc.) — launch through ElasticJobSupervisor")
    # fleet observability: both hooks ride the supervisor env and are
    # no-ops without it (standalone workers pay one None check)
    tracer = None
    exporter = None
    obs_registry = None
    if ctx.metrics_file is not None:
        from deeplearning4j_tpu.observe import default_registry
        from deeplearning4j_tpu.observe.fleet import MetricsFileExporter
        obs_registry = default_registry()
        exporter = MetricsFileExporter(obs_registry, ctx.metrics_file)
    if ctx.trace_dir is not None:
        os.makedirs(ctx.trace_dir, exist_ok=True)
        from deeplearning4j_tpu.observe import Tracer, enable_tracing
        from deeplearning4j_tpu.observe.fleet import SpanFileWriter
        span_writer = SpanFileWriter(
            os.path.join(
                ctx.trace_dir,
                f"spans.gen{ctx.generation:03d}.slot{ctx.slot}.jsonl"),
            label=f"slot {ctx.slot} gen {ctx.generation}",
            extra_meta={"slot": ctx.slot, "generation": ctx.generation,
                        "host": ctx.host, "rank": ctx.process_id})
        tracer = enable_tracing(Tracer(span_writer),
                                metrics=obs_registry)
    ctx.init_distributed()
    from deeplearning4j_tpu.parallel.master import (
        DistributedMultiLayerNetwork, SharedTrainingMaster)
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.util import faultinject
    from deeplearning4j_tpu.util.orbax_checkpoint import (
        OrbaxCheckpointManager)

    pod_axes = ctx.pod_mesh_axes()
    model_parallel = any(k != "data" and int(v) > 1
                         for k, v in pod_axes.items())
    pod_mesh = make_mesh(pod_axes) if model_parallel else None
    rules = None
    if ctx.sharding_rules_path:
        from deeplearning4j_tpu.parallel.sharding import load_sharding_rules
        rules = load_sharding_rules(ctx.sharding_rules_path)
    if model_parallel and save_mode == "async":
        # the overlapped session snapshots to host numpy, which would
        # gather the model shards; pod-mesh saves go through orbax's own
        # collective sharded writer instead
        print(f"[slot {ctx.slot}] pod mesh active: async save_mode "
              f"falls back to sync collective saves", flush=True)
        save_mode = "sync"

    if ctx.restore_step is not None:
        # every process restores independently (active_processes={pid}:
        # read-only restores need no cross-process barrier); fallback
        # walks to an older retained step when the chosen one is corrupt.
        # On a pod mesh the restore reshards STRAIGHT INTO this
        # generation's mesh — a 2×4 checkpoint restores onto a 1×4
        # world after a host-failure shrink (the data extent changed,
        # the rules re-place every param on the surviving slice)
        with OrbaxCheckpointManager(
                ctx.ckpt_dir, active_processes={ctx.process_id},
                barrier_sync_key_prefix=(
                    f"restore_g{ctx.generation}_p{ctx.process_id}")) as mgr:
            net = mgr.restore(ctx.restore_step, fallback=True,
                              fallback_steps=ctx.eligible_steps,
                              mesh=pod_mesh, sharding_rules=rules)
            restored_step = mgr.restored_step
    else:
        net = build_model()
        restored_step = None
        if pod_mesh is not None:
            from deeplearning4j_tpu.parallel.sharding import (
                shard_model_with_rules)
            shard_model_with_rules(net, pod_mesh, rules)

    if pod_mesh is not None:
        # DP×MP via GSPMD: the jitted train step IS the distributed
        # program (batch over data, params over model — gradient
        # exchange compiled in); no deterministic-broadcast master
        from deeplearning4j_tpu.parallel.mesh import format_mesh_axes
        print(f"[slot {ctx.slot}] pod mesh "
              f"{format_mesh_axes(pod_axes)} (GSPMD 2-D)", flush=True)
        mesh = pod_mesh
        master = None
        front = net
    else:
        mesh = make_mesh({"data": ctx.num_processes})
        master = SharedTrainingMaster(mesh=mesh, **(master_kwargs or {}))
        if restored_step is not None:
            state_path = ctx.master_state_path(restored_step)
            if os.path.exists(state_path):
                # same world size as the writer → exact resume including
                # residuals; after a shrink the file (keyed by world
                # size) does not exist and residuals re-accumulate
                master.load_state(state_path)
        front = DistributedMultiLayerNetwork(net, master)

    if tracer is not None or exporter is not None:
        # per-iteration train_iteration spans (parented into the job
        # trace via the root span below) + training_* series for the
        # supervisor's federation — appended BEFORE _Beat so the span
        # for step S is crash-durably written before a planned kill at
        # S fires in the heartbeat listener
        from deeplearning4j_tpu.observe import TraceListener
        net.listeners.append(TraceListener(
            tracer=tracer, metrics=obs_registry, model_name="elastic"))

    class _Beat:
        def iteration_done(self, model, iteration, epoch):
            # the fault hook runs BEFORE the heartbeat: a worker blocked
            # by a partition fault at step S never advertises S — its
            # heartbeat step freezes at S-1, which is exactly the
            # lowest-progress signature the supervisor's watchdog keys
            # its victim choice on
            faultinject.on_step(ctx.slot, iteration, host=ctx.host)
            ctx.heartbeat(iteration)
            if exporter is not None:
                exporter.export()

    net.listeners.append(_Beat())

    manager = None
    if pod_mesh is not None and ctx.num_processes > 1:
        # params are sharded ACROSS processes: every rank owns shards
        # only it can write, so every rank joins the collective save
        manager = OrbaxCheckpointManager(
            ctx.ckpt_dir, max_to_keep=max_to_keep,
            barrier_sync_key_prefix=f"save_g{ctx.generation}")
    elif ctx.process_id == 0:
        manager = OrbaxCheckpointManager(
            ctx.ckpt_dir, max_to_keep=max_to_keep,
            active_processes={0},
            barrier_sync_key_prefix=f"save_g{ctx.generation}")
    ctx.heartbeat(0)  # first beat: the world formed, jax is up
    if exporter is not None:
        exporter.export()  # series visible to the fleet before step 1
    ctx.start_heartbeat_thread()  # no-op unless the supervisor armed it
    session = None
    if save_mode == "async":
        session = AsyncCheckpointSession(ctx, manager=manager,
                                         master=master,
                                         max_in_flight=max_in_flight)
    start_epoch = int(net.epoch)
    flushed = True
    import contextlib
    root_cm = contextlib.nullcontext()
    if tracer is not None:
        # the ambient context for everything this worker records:
        # parented to the supervisor's per-generation elastic_job span,
        # so train_iteration / checkpoint / DCN spans join the job trace
        from deeplearning4j_tpu.observe import parse_traceparent
        root_cm = tracer.span(
            "elastic_worker", parent=parse_traceparent(ctx.traceparent),
            category="elastic",
            attrs={"slot": ctx.slot, "rank": ctx.process_id,
                   "generation": ctx.generation,
                   "restored_step": restored_step})
    try:
        with root_cm:
            for epoch in range(start_epoch, epochs):
                front.fit(build_iterator(), epochs=1)
                step = epoch + 1
                ctx.heartbeat(net.iteration)
                if step % max(1, checkpoint_every) == 0 or step == epochs:
                    if session is not None:
                        session.submit(step, net)
                    elif pod_mesh is not None:
                        ctx.save_checkpoint_sharded(step, net, manager)
                    else:
                        ctx.save_checkpoint(step, net, master, manager)
    finally:
        if session is not None:
            flushed = session.close(timeout=flush_timeout_s)
            if not flushed:
                print(f"[slot {ctx.slot}] async checkpoint flush timed "
                      f"out after {flush_timeout_s}s", flush=True)
            for err in session.errors:
                print(f"[slot {ctx.slot}] async checkpoint torn: {err}",
                      flush=True)
        ctx.stop_heartbeat_thread()
        if exporter is not None:
            exporter.export()  # final snapshot: the last committed step
        # a timed-out flush means the saver thread may still be INSIDE a
        # manager call — closing the manager under it would crash the
        # worker; the in-flight step stays torn (unstamped) and the
        # process exit reclaims everything
        if manager is not None and flushed:
            manager.close()
    if on_done is not None:
        on_done(net, ctx)
    return net
