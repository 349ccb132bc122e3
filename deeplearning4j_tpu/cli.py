"""Command-line entry points.

The reference exposes exactly two ``main()``s (SURVEY.md §1): training via
``ParallelWrapperMain`` (`deeplearning4j-scaleout/.../parallelism/main/ParallelWrapperMain.java`,
JCommander flags: modelPath, workers, averagingFrequency, prefetchSize,
modelOutputPath, uiUrl) and serving via ``NearestNeighborsServer``
(`NearestNeighborsServer.java:3-10`). This module provides both:

- ``python -m deeplearning4j_tpu.cli train ...`` — load a serialized model,
  train it data-parallel over the mesh, save the result.
- ``python -m deeplearning4j_tpu.cli nn-server ...`` — serve k-NN queries
  (delegates to :meth:`NearestNeighborsServer.main`).
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

import numpy as np


def parallel_wrapper_main(argv: Optional[List[str]] = None):
    """ParallelWrapperMain parity: train a saved model over the mesh."""
    ap = argparse.ArgumentParser("parallel-wrapper-train")
    ap.add_argument("--modelPath", required=True,
                    help="model zip written by ModelSerializer")
    ap.add_argument("--dataPath", required=True,
                    help=".npz with 'features' and 'labels' arrays")
    ap.add_argument("--modelOutputPath", required=True)
    ap.add_argument("--workers", type=int, default=None,
                    help="mesh data-axis size (default: all devices)")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="2-D GSPMD mesh, e.g. data=4,model=2 (-1 infers "
                         "one axis from the device count): params are "
                         "placed by --sharding-rules over the model "
                         "axis, batches shard over data. With --elastic "
                         "the data size MUST be -1 or absent — the world "
                         "is dynamic (each generation's process count IS "
                         "the data axis); model axes are per-host slices")
    ap.add_argument("--sharding-rules", default=None, dest="sharding_rules",
                    metavar="RULES.json",
                    help="partition rule file (regex over param path -> "
                         "PartitionSpec; lint with "
                         "tools/validate_sharding_rules.py); default: "
                         "the built-in Megatron 2-D rule set")
    ap.add_argument("--mode", choices=("shared_gradients", "averaging"),
                    default="shared_gradients")
    ap.add_argument("--averagingFrequency", type=int, default=5)
    ap.add_argument("--batchSize", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--prefetchSize", type=int, default=2,
                    help="async prefetch buffer (AsyncDataSetIterator)")
    ap.add_argument("--uiUrl", default=None,
                    help="remote UI /remote endpoint to report stats to")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the run with the observe tracer and write "
                         "a Chrome trace (chrome://tracing / Perfetto) here")
    ap.add_argument("--log-json", default=None, metavar="OUT.jsonl",
                    dest="log_json",
                    help="structured JSON-lines logging with trace "
                         "correlation to this file ('-' for stderr)")
    ap.add_argument("--watchdog", choices=("off", "log", "raise"),
                    default="off",
                    help="training health watchdog (NaN loss/params, "
                         "gradient explosion, divergence, stalls) with "
                         "this action policy")
    ap.add_argument("--alerts", default=None, metavar="RULES.json",
                    help="evaluate these alert rules against the metrics "
                         "registry in the background during training")
    ap.add_argument("--slo", default=None, metavar="SLO.json",
                    help="load SLO definitions (observe/slo.py schema) and "
                         "evaluate their burn-rate rules alongside --alerts; "
                         "under --elastic the set is surfaced at /slo on "
                         "the --metrics-port server")
    ap.add_argument("--elastic", type=int, default=None, metavar="N",
                    help="run as an elastic multi-process job: N worker "
                         "processes supervised with automatic failure "
                         "recovery and shrink-to-surviving-slice "
                         "(parallel/elastic.py)")
    ap.add_argument("--min-workers", type=int, default=1,
                    dest="min_workers",
                    help="smallest world --elastic may shrink to before "
                         "the job fails loudly")
    ap.add_argument("--ckpt-dir", default=None, dest="ckpt_dir",
                    help="checkpoint/recovery directory (required with "
                         "--elastic): orbax rotation checkpoints, "
                         "generation ledger, heartbeats")
    ap.add_argument("--max-restarts", type=int, default=2,
                    dest="max_restarts",
                    help="per-worker restart budget before the supervisor "
                         "shrinks the world (exponential backoff between "
                         "restarts)")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0,
                    dest="heartbeat_timeout",
                    help="seconds without a worker heartbeat before the "
                         "supervisor declares it hung and recovers")
    ap.add_argument("--hosts", type=int, default=None,
                    help="group the --elastic workers into this many host "
                         "failure domains: a worker death marks its WHOLE "
                         "host the victim, restart budgets charge the "
                         "host, shrink removes the host (per-host slice "
                         "shapes stay valid). Coordinator bind/advertise "
                         "addresses come from DL4J_TPU_ELASTIC_BIND_HOST/"
                         "DL4J_TPU_ELASTIC_ADVERTISE_HOST (default "
                         "loopback)")
    ap.add_argument("--min-hosts", type=int, default=1, dest="min_hosts",
                    help="smallest number of host groups --elastic may "
                         "shrink to before the job fails loudly")
    ap.add_argument("--save-mode", choices=("sync", "async"),
                    default="sync", dest="save_mode",
                    help="worker checkpoint path: async overlaps orbax "
                         "saves with training (bounded in-flight, "
                         "all-ranks commit protocol); sync blocks the "
                         "step until the save lands")
    ap.add_argument("--progress-timeout", type=float, default=None,
                    dest="progress_timeout",
                    help="arm the partition watchdog: seconds without "
                         "step progress anywhere (while heartbeats stay "
                         "alive) before the supervisor resolves a "
                         "network partition by killing the "
                         "least-progressed side")
    ap.add_argument("--metrics-port", type=int, default=None,
                    dest="metrics_port",
                    help="with --elastic: serve the job-wide metrics "
                         "union (workers re-labeled {slot,host,"
                         "generation} + supervisor series) at this "
                         "port's /metrics (0 = ephemeral)")
    args = ap.parse_args(argv)

    mesh_axes = None
    if args.mesh:
        from deeplearning4j_tpu.parallel.mesh import parse_mesh_axes
        try:
            mesh_axes = parse_mesh_axes(args.mesh)
        except ValueError as e:
            ap.error(f"--mesh: {e}")
        if args.workers is not None:
            ap.error("--workers and --mesh both size the data axis; "
                     "use --mesh data=N[,model=M] alone")
    if args.sharding_rules and not args.mesh:
        ap.error("--sharding-rules needs --mesh (the rules place params "
                 "over the mesh's model axes)")
    if args.sharding_rules:
        # an unreadable/invalid rule file fails BEFORE training (and
        # before worker processes are launched under --elastic)
        from deeplearning4j_tpu.parallel.sharding import load_sharding_rules
        try:
            load_sharding_rules(args.sharding_rules)
        except (OSError, ValueError) as e:
            ap.error(f"--sharding-rules: {e}")

    if args.elastic is not None:
        if not args.ckpt_dir:
            ap.error("--elastic requires --ckpt-dir (the recovery "
                     "substrate: rotation checkpoints + generation ledger)")
        # flags that act INSIDE the training process are not plumbed into
        # the supervised workers — reject rather than silently ignore
        # (--trace IS supported: workers stream spans back and the
        # supervisor writes ONE merged fleet trace)
        unsupported = [flag for flag, hit in (
            ("--workers", args.workers is not None),
            ("--mode averaging", args.mode != "shared_gradients"),
            ("--averagingFrequency", args.averagingFrequency != 5),
            ("--prefetchSize", args.prefetchSize != 2),
            ("--uiUrl", args.uiUrl is not None),
            ("--watchdog", args.watchdog != "off"),
        ) if hit]
        if unsupported:
            ap.error(
                f"{', '.join(unsupported)} affect(s) in-process training "
                "and is not forwarded to --elastic workers (they train "
                "shared_gradients at the elastic world size); drop it, or "
                "run without --elastic. --log-json, --alerts, --slo, "
                "--trace and --metrics-port ARE supported (they observe "
                "the fleet)")
        if mesh_axes is not None and mesh_axes.get("data", -1) != -1:
            # the elastic world is dynamic: each generation's process
            # count IS the data extent, so a pinned size is a lie the
            # first shrink would expose
            ap.error(f"--mesh data={mesh_axes['data']} cannot be pinned "
                     "under --elastic (the supervisor sizes the data axis "
                     "to the live world); use data=-1 or omit it, e.g. "
                     "--mesh model=2")
        return _elastic_train(args, mesh_axes=mesh_axes)
    if args.metrics_port is not None:
        ap.error("--metrics-port only applies to --elastic jobs (the "
                 "in-process serve command exposes /metrics itself)")

    from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.util import model_serializer
    from deeplearning4j_tpu.util.compile_cache import (
        enable_persistent_compile_cache)

    enable_persistent_compile_cache()
    net = model_serializer.restore_model(args.modelPath)
    z = np.load(args.dataPath)
    ds = DataSet(z["features"], z["labels"])
    it = ListDataSetIterator(ds, args.batchSize, shuffle=True)
    if args.uiUrl:
        from deeplearning4j_tpu.ui import StatsListener
        from deeplearning4j_tpu.ui.remote import RemoteUIStatsStorageRouter
        net.listeners.append(
            StatsListener(RemoteUIStatsStorageRouter(args.uiUrl)))
    tracer = None
    if args.log_json:
        from deeplearning4j_tpu.observe import enable_structured_logging
        if args.log_json == "-":
            enable_structured_logging(stream=sys.stderr)
        else:
            enable_structured_logging(path=args.log_json)
    if args.trace:
        from deeplearning4j_tpu.observe import default_registry, enable_tracing
        tracer = enable_tracing(metrics=default_registry())
    if args.trace or args.watchdog != "off" or args.alerts or args.slo:
        # one attachment path for TraceListener AND the watchdog. With
        # --alerts/--slo the TraceListener is attached even without
        # --trace: it is what exports the training_* series into the
        # registry the rules evaluate (spans stay off while tracing is
        # not enabled)
        from deeplearning4j_tpu.observe import (attach_observability,
                                                default_registry)
        attach_observability(
            net, tracer=tracer, metrics=default_registry(),
            trace=bool(args.trace) or bool(args.alerts) or bool(args.slo),
            watchdog=(None if args.watchdog == "off"
                      else {"action": args.watchdog}))
    alert_mgr = None
    if args.alerts or args.slo:
        from deeplearning4j_tpu.observe import (AlertManager, LogSink,
                                                default_registry, load_rules,
                                                load_slos)
        rules = list(load_rules(args.alerts)) if args.alerts else []
        if args.slo:
            rules += load_slos(args.slo).rules()
        alert_mgr = AlertManager(default_registry(),
                                 rules, [LogSink()],
                                 interval_s=5.0).start()
    mesh = None
    gspmd = mesh_axes is not None and any(
        k != "data" and int(v) > 1 for k, v in mesh_axes.items())
    if gspmd:
        # DP×MP: the jitted train step IS the distributed program — the
        # replica-averaging knobs have nothing to act on
        unsupported = [flag for flag, hit in (
            ("--mode averaging", args.mode != "shared_gradients"),
            ("--averagingFrequency", args.averagingFrequency != 5),
        ) if hit]
        if unsupported:
            ap.error(f"{', '.join(unsupported)} drive(s) the replica-"
                     "averaging ParallelWrapper and do(es) not apply to a "
                     "--mesh with model axes (GSPMD shards ONE program)")
        from deeplearning4j_tpu.parallel.sharding import (
            load_sharding_rules, shard_model_with_rules)
        mesh = make_mesh(mesh_axes)
        rules = (load_sharding_rules(args.sharding_rules)
                 if args.sharding_rules else None)
        shard_model_with_rules(net, mesh, rules)
        print(f"GSPMD mesh {args.mesh}: params placed by "
              f"{args.sharding_rules or 'the default 2-D rule set'}")
        pw = None
    else:
        if mesh_axes is not None:  # data-only --mesh ≡ --workers
            mesh = make_mesh(mesh_axes)
        elif args.workers:
            mesh = make_mesh({"data": args.workers})
        pw = ParallelWrapper(net, mesh, mode=args.mode,
                             averaging_frequency=args.averagingFrequency,
                             metrics=(None if tracer is None
                                      else tracer.metrics))
    try:
        if pw is not None:
            pw.fit(it, epochs=args.epochs, prefetch_depth=args.prefetchSize)
        else:
            net.fit(it, epochs=args.epochs)
    finally:
        if alert_mgr is not None:
            alert_mgr.evaluate_once()  # final round so late series count
            alert_mgr.stop()
            firing = alert_mgr.firing()
            print(f"alerts firing at exit: {firing if firing else 'none'}")
        if tracer is not None:
            from deeplearning4j_tpu.observe import disable_tracing
            n = tracer.flush(args.trace)
            print(f"wrote Chrome trace ({n} spans) to {args.trace}")
            print(tracer.timeline(limit=40))
            disable_tracing()
        if args.log_json:
            from deeplearning4j_tpu.observe import disable_structured_logging
            disable_structured_logging()
    model_serializer.write_model(net, args.modelOutputPath)
    return net


def _elastic_train(args, mesh_axes=None):
    """``train --elastic N``: supervise N elastic worker processes
    (``python -m deeplearning4j_tpu.parallel.elastic_worker``) over the
    model/data from --modelPath/--dataPath. Worker death triggers
    automatic recovery — restart-in-place under a backoff budget, then
    shrink to the surviving slice down to --min-workers. Rank 0 of the
    finishing generation writes --modelOutputPath. ``--log-json``
    observes the supervisor; ``--alerts`` evaluates against the FLEET
    union (worker ``training_*`` series re-labeled
    ``{slot,host,generation}`` plus the supervisor's ``elastic_*``
    series — a FleetRegistry is created for the rules even without
    ``--metrics-port``); ``--trace`` writes ONE merged fleet timeline."""
    from deeplearning4j_tpu.parallel.elastic import (BackoffPolicy,
                                                     ElasticJobSupervisor,
                                                     WorkerSpec)

    if args.log_json:
        from deeplearning4j_tpu.observe import enable_structured_logging
        if args.log_json == "-":
            enable_structured_logging(stream=sys.stderr)
        else:
            enable_structured_logging(path=args.log_json)
    tracer = None
    if args.trace:
        # fleet tracing: the supervisor's generation/decision spans land
        # in its own ring; workers stream theirs back through the
        # ckpt-dir trace files; ONE merged timeline is written at exit
        from deeplearning4j_tpu.observe import default_registry, enable_tracing
        tracer = enable_tracing(metrics=default_registry())

    worker_mesh = None
    if mesh_axes:
        # the data axis is the live world size, owned by the supervisor;
        # only the per-host model axes ride the WorkerSpec
        worker_mesh = {k: v for k, v in mesh_axes.items() if k != "data"}
    spec = WorkerSpec(argv=[
        sys.executable, "-m", "deeplearning4j_tpu.parallel.elastic_worker",
        "--modelPath", args.modelPath,
        "--dataPath", args.dataPath,
        "--out", args.modelOutputPath,
        "--batchSize", str(args.batchSize),
        "--epochs", str(args.epochs),
        "--save-mode", args.save_mode,
    ], mesh_axes=worker_mesh or None,
        sharding_rules=args.sharding_rules)
    fleet = None
    if (args.alerts or args.slo) and args.metrics_port is None:
        # --alerts/--slo observe the FLEET: the rules must see the
        # job-wide union ({slot,host,generation}-labeled worker series),
        # so a FleetRegistry exists whenever rules do, scrape port or not
        from deeplearning4j_tpu.observe import FleetRegistry, default_registry
        fleet = FleetRegistry(local=default_registry())
    supervisor = ElasticJobSupervisor(
        spec, num_workers=args.elastic, min_workers=args.min_workers,
        num_hosts=args.hosts, min_hosts=args.min_hosts,
        ckpt_dir=args.ckpt_dir,
        backoff=BackoffPolicy(max_restarts=args.max_restarts),
        heartbeat_timeout_s=args.heartbeat_timeout,
        progress_timeout_s=args.progress_timeout,
        metrics_port=args.metrics_port, fleet=fleet)
    alert_mgr = None
    if args.alerts or args.slo:
        from deeplearning4j_tpu.observe import (AlertManager, LogSink,
                                                load_rules, load_slos)
        rules = list(load_rules(args.alerts)) if args.alerts else []
        if args.slo:
            slo_set = load_slos(args.slo)
            rules += slo_set.rules()
            supervisor.slo = slo_set  # surfaced at /slo on the
            # --metrics-port server
        alert_mgr = AlertManager(
            supervisor.fleet, rules, [LogSink()],
            interval_s=5.0).start()
        supervisor.alerts = alert_mgr  # surfaced at /alerts on the
        # --metrics-port server
    try:
        result = supervisor.run()
    finally:
        if alert_mgr is not None:
            alert_mgr.evaluate_once()
            alert_mgr.stop()
            firing = alert_mgr.firing()
            print(f"alerts firing at exit: {firing if firing else 'none'}")
        if tracer is not None:
            from deeplearning4j_tpu.observe import disable_tracing
            n = supervisor.write_fleet_trace(args.trace)
            print(f"wrote merged fleet trace ({n} events) to {args.trace}")
            disable_tracing()
        if args.log_json:
            from deeplearning4j_tpu.observe import (
                disable_structured_logging)
            disable_structured_logging()
    last = result.generations[-1]
    print(f"elastic job {result.status}: {len(result.generations)} "
          f"generation(s), {result.restarts_total} recovery event(s), "
          f"final world {last.world} "
          f"(min_workers={args.min_workers})")
    print(f"wrote {args.modelOutputPath}")
    return result


def cluster_setup_main(argv: Optional[List[str]] = None, runner=None):
    """``ClusterSetup`` parity (``aws/ec2/provision/ClusterSetup.java``
    JCommander flags → argparse): bring up N TPU VMs, wait until READY,
    provision each with the worker script. ``runner`` is injectable for
    tests/dry runs; ``--dry-run`` prints the gcloud commands instead."""
    ap = argparse.ArgumentParser("cloud-setup")
    ap.add_argument("-w", "--workers", type=int, default=1,
                    help="number of TPU VMs (ClusterSetup -w)")
    ap.add_argument("--project", required=True)
    ap.add_argument("--zone", required=True,
                    help="GCP zone (the -region flag's role)")
    ap.add_argument("--accelerator-type", default="v5p-8",
                    help="TPU slice type (the -s instance-size flag's role)")
    ap.add_argument("--version", default="tpu-ubuntu2204-base",
                    help="TPU VM image (the -ami flag's role)")
    ap.add_argument("--name-prefix", default="dl4j-tpu")
    ap.add_argument("--wscript", default=None,
                    help="worker setup script to upload and run on every VM")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the gcloud commands; execute nothing")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.cloud import ClusterProvisioner, TpuProvisioner

    if args.dry_run:
        import shlex
        runner = lambda cmd: (print(shlex.join(cmd)), "READY")[-1]
    prov = TpuProvisioner(args.project, args.zone, runner=runner)
    cluster = ClusterProvisioner(prov, num_workers=args.workers,
                                 accelerator_type=args.accelerator_type,
                                 version=args.version,
                                 name_prefix=args.name_prefix)
    cluster.create()
    cluster.block_till_all_running(poll_seconds=0.0 if args.dry_run else 10.0)
    if args.wscript:
        cluster.provision_workers(args.wscript)
    return cluster


def _lower_step_hlo(net, ds) -> str:
    """Compiled HLO text of the net's jitted train step."""
    import jax.numpy as jnp
    it = jnp.asarray(net.iteration, jnp.float32)
    ep = jnp.asarray(net.epoch, jnp.float32)
    lowered = net._get_train_step().lower(
        net.params, net.states, net.updater_states, it, ep,
        *net._to_batch(ds), net._next_rng())
    return lowered.compile().as_text()


def profile_main(argv: Optional[List[str]] = None):
    """Profile a saved model's jitted train step on the current backend:
    a trace window via ProfilerListener, bucketed per-op device time via
    the HLO-mapped xplane analysis (the tools/tpu_perf_session.py
    machinery, exposed as a framework command)."""
    import json as _json
    import os as _os

    ap = argparse.ArgumentParser("profile")
    ap.add_argument("--modelPath", required=True,
                    help="model zip written by ModelSerializer")
    ap.add_argument("--dataPath", required=True,
                    help=".npz with 'features' and 'labels' arrays")
    ap.add_argument("--batchSize", type=int, default=32)
    ap.add_argument("--logDir", default="/tmp/dl4j_tpu_profile")
    ap.add_argument("--out", default=None, help="write the report JSON here")
    args = ap.parse_args(argv)

    _os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                           "python")
    tools = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from hlo_map import HloModule
    from tpu_perf_session import profile_step

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util import model_serializer

    net = model_serializer.restore_model(args.modelPath)
    z = np.load(args.dataPath)
    ds = DataSet(z["features"][:args.batchSize],
                 z["labels"][:args.batchSize])
    mod = HloModule(_lower_step_hlo(net, ds))
    times = profile_step(net, ds, args.logDir)
    total = sum(t for t, _ in times.values())
    buckets = {}
    batch = int(np.asarray(ds.features).shape[0])
    for nm, (t, c) in times.items():
        key = nm.split(" = ")[0].strip().lstrip("%")
        cat, flops = mod.classify(key, batch)
        b = buckets.setdefault(cat, {"time": 0.0, "flops": 0})
        b["time"] += t
        b["flops"] += flops * c
    report = {
        "device_ms_per_step": round(total / 4 * 1e3, 3),
        "buckets": {k: {"share_pct": round(v["time"] / total * 100, 1),
                        "ms_per_step": round(v["time"] / 4 * 1e3, 3),
                        "tflops": (round(v["flops"] / v["time"] / 1e12, 1)
                                   if v["flops"] else None)}
                    for k, v in sorted(buckets.items(),
                                       key=lambda kv: -kv[1]["time"])},
    }
    print(_json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as fh:
            _json.dump(report, fh, indent=1)


def evaluate_main(argv: Optional[List[str]] = None):
    """``evaluate`` subcommand: load any supported model artifact
    (ModelGuesser chain) and print classification metrics over a CSV
    dataset — the ``MultiLayerNetwork.evaluate`` flow from the shell."""
    p = argparse.ArgumentParser(prog="deeplearning4j_tpu evaluate")
    p.add_argument("--model", required=True,
                   help="model artifact (own/DL4J zip or Keras h5)")
    p.add_argument("--csv", required=True, help="delimited dataset file")
    p.add_argument("--label-index", type=int, default=-1,
                   help="label column (default: last column)")
    p.add_argument("--classes", type=int, required=True,
                   help="number of classes")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--delimiter", default=",")
    p.add_argument("--skip-lines", type=int, default=0)
    p.add_argument("--top-n", type=int, default=1)
    args = p.parse_args(argv)

    from deeplearning4j_tpu.datasets.records import (
        CSVRecordReader,
        RecordReaderDataSetIterator,
    )
    from deeplearning4j_tpu.util.model_guesser import load_model_guess

    model = load_model_guess(args.model)
    reader = CSVRecordReader(args.csv, skip_lines=args.skip_lines,
                             delimiter=args.delimiter)
    label_index = args.label_index
    if label_index < 0:
        first = reader.next_record()
        reader.reset()
        label_index = len(first) - 1  # a Record is a list of values
    it = RecordReaderDataSetIterator(reader, args.batch,
                                     label_index=label_index,
                                     num_possible_labels=args.classes)
    e = model.evaluate(it, top_n=args.top_n)
    print(e.stats())
    return e


def serve_main(argv: Optional[List[str]] = None, block: bool = True):
    """``serve`` subcommand: stand up the production serving tier from the
    shell — register one or more model artifacts (ModelGuesser chain:
    own/DL4J zips, Keras h5) under names and serve them over HTTP with
    admission control and ``/metrics``."""
    p = argparse.ArgumentParser(prog="deeplearning4j_tpu serve")
    p.add_argument("--model", action="append", required=True,
                   metavar="NAME=PATH",
                   help="model to register (repeatable); NAME=PATH, or a "
                        "bare PATH served under its file stem")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8500,
                   help="listen port (0 → ephemeral)")
    p.add_argument("--max-batch-size", type=int, default=32)
    p.add_argument("--wait-ms", type=float, default=2.0,
                   help="batching window measured from the oldest request")
    p.add_argument("--mesh", default=None, metavar="AXES",
                   help="serve every --model GSPMD-sharded over this 2-D "
                        "mesh, e.g. data=4,model=2 (-1 infers one axis): "
                        "params placed by --sharding-rules, request "
                        "batches sharded over data, buckets rounded to "
                        "the data-axis size")
    p.add_argument("--sharding-rules", default=None, dest="sharding_rules",
                   metavar="RULES.json",
                   help="partition rule file for --mesh (default: the "
                        "built-in Megatron 2-D rule set); lint with "
                        "tools/validate_sharding_rules.py")
    p.add_argument("--buckets", default=None, metavar="N,N,...",
                   help="declared batch buckets (default: powers of two up "
                        "to --max-batch-size); these are pre-compiled at "
                        "registration and the dispatcher pads to them")
    p.add_argument("--warmup", choices=("sync", "async", "off"),
                   default="sync",
                   help="AOT bucket warmup at registration: sync blocks "
                        "until every bucket is compiled, async warms in "
                        "the background (/readyz lists cold buckets), off "
                        "restores lazy first-request compilation")
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="persistent XLA compilation cache: restarts and "
                        "rollbacks re-warm from disk instead of compiling "
                        "(default: $JAX_COMPILATION_CACHE_DIR, else "
                        "<checkout>/.jax_cache on an accelerator and none "
                        "on CPU)")
    p.add_argument("--dtype-policy", action="append", default=[],
                   metavar="NAME=POLICY",
                   help="serve NAME quantized: POLICY is int8, bf16 or "
                        "float32 (repeatable)")
    p.add_argument("--input-shape", action="append", default=[],
                   metavar="NAME=DIMS",
                   help="per-row input shape for warmup when the model "
                        "conf does not declare one, e.g. lenet=28x28x1 "
                        "(repeatable)")
    p.add_argument("--max-inflight", type=int, default=64,
                   help="admission limit before requests shed as 429")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request deadline (504 past expiry)")
    p.add_argument("--max-dispatcher-restarts", type=int, default=2,
                   help="in-place restarts of a crashed batching "
                        "dispatcher before the crash is terminal "
                        "(exponential backoff between restarts; 0 "
                        "restores the old die-forever behavior)")
    p.add_argument("--breaker-threshold", type=int, default=3,
                   help="forward crashes within --breaker-window that "
                        "quarantine a model version (per-version circuit "
                        "breaker; 0 disables breakers)")
    p.add_argument("--breaker-window", type=float, default=30.0,
                   help="rolling window (seconds) the crash threshold "
                        "counts over")
    p.add_argument("--breaker-cooldown", type=float, default=5.0,
                   help="seconds an open breaker waits before letting a "
                        "half-open probe through")
    p.add_argument("--breaker-probes", type=int, default=1,
                   help="consecutive probe successes that close a "
                        "half-open breaker")
    p.add_argument("--fallback", action="append", default=[],
                   metavar="NAME=VERSION",
                   help="failover chain for NAME while its live version "
                        "is quarantined/crashed: a version number, "
                        "'previous', or a comma list (repeatable)")
    p.add_argument("--brownout", action="store_true",
                   help="enable brownout degradation: under sustained "
                        "admission saturation, shed X-Priority<=0 "
                        "traffic with 429 and route un-pinned predicts "
                        "to the --fallback chain until pressure clears")
    p.add_argument("--brownout-saturation", type=float, default=0.9,
                   help="fraction of --max-inflight that counts as "
                        "saturation pressure")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="trace requests (spans across HTTP, dispatcher and "
                        "device) and write a Chrome trace here on shutdown")
    p.add_argument("--log-json", default=None, metavar="OUT.jsonl",
                   dest="log_json",
                   help="structured JSON-lines logging with trace "
                        "correlation to this file ('-' for stderr)")
    p.add_argument("--alerts", default=None, metavar="RULES.json",
                   help="alert rules evaluated against /metrics in the "
                        "background; state served at /alerts")
    p.add_argument("--alert-interval", type=float, default=15.0,
                   help="seconds between alert evaluation rounds")
    p.add_argument("--slo", default=None, metavar="SLO.json",
                   help="load SLO definitions (observe/slo.py schema): "
                        "their burn-rate rules join --alerts evaluation "
                        "and compliance is served at /slo")
    args = p.parse_args(argv)

    import os

    from deeplearning4j_tpu.serving import (DTYPE_POLICIES,
                                            ModelRegistry, ModelServer,
                                            default_registry)

    tracer = None
    if args.trace:
        from deeplearning4j_tpu.observe import enable_tracing
        tracer = enable_tracing(metrics=default_registry())
    if args.log_json:
        from deeplearning4j_tpu.observe import enable_structured_logging
        if args.log_json == "-":
            enable_structured_logging(stream=sys.stderr)
        else:
            enable_structured_logging(path=args.log_json)
    slo_set = None
    if args.slo:
        from deeplearning4j_tpu.observe import load_slos
        try:
            slo_set = load_slos(args.slo)
        except (ValueError, OSError) as e:
            p.error(f"--slo: {e}")
        print(f"serving {len(slo_set.slos)} SLO(s) from {args.slo} "
              "(compliance at /slo)")
    alert_mgr = None
    if args.alerts or slo_set is not None:
        from deeplearning4j_tpu.observe import (AlertManager, LogSink,
                                                load_rules)
        rules = list(load_rules(args.alerts)) if args.alerts else []
        if slo_set is not None:
            rules += slo_set.rules()
        alert_mgr = AlertManager(default_registry(),
                                 rules, [LogSink()],
                                 interval_s=args.alert_interval).start()
        print(f"alerting on {len(alert_mgr.rules)} rule(s) from "
              f"{args.alerts or args.slo} (state at /alerts)")

    serve_mesh = None
    serve_rules = None
    if args.sharding_rules and not args.mesh:
        p.error("--sharding-rules needs --mesh (the rules place params "
                "over the mesh's model axes)")
    if args.mesh:
        from deeplearning4j_tpu.parallel.mesh import (make_mesh,
                                                      parse_mesh_axes)
        try:
            serve_mesh = make_mesh(parse_mesh_axes(args.mesh))
        except ValueError as e:
            p.error(f"--mesh: {e}")
        if args.sharding_rules:
            from deeplearning4j_tpu.parallel.sharding import (
                load_sharding_rules)
            try:
                serve_rules = load_sharding_rules(args.sharding_rules)
            except (OSError, ValueError) as e:
                p.error(f"--sharding-rules: {e}")
        if args.dtype_policy:
            p.error("--dtype-policy cannot combine with --mesh (GSPMD-"
                    "sharded serving is float32-only)")

    buckets = None
    if args.buckets:
        try:
            buckets = [int(b) for b in args.buckets.split(",") if b.strip()]
        except ValueError:
            p.error(f"--buckets needs comma-separated batch sizes, "
                    f"got {args.buckets!r}")
        if not buckets or min(buckets) < 1:
            p.error(f"--buckets needs positive batch sizes, "
                    f"got {args.buckets!r}")
    policies = {}
    for spec in args.dtype_policy:
        name, sep, policy = spec.partition("=")
        if not sep:
            p.error(f"--dtype-policy needs NAME=POLICY, got {spec!r}")
        if policy not in DTYPE_POLICIES:
            p.error(f"--dtype-policy {name}={policy!r}: unknown policy "
                    f"(one of {', '.join(DTYPE_POLICIES)})")
        policies[name] = policy
    shapes = {}
    for spec in args.input_shape:
        name, sep, dims = spec.partition("=")
        if not sep:
            p.error(f"--input-shape needs NAME=DIMS, got {spec!r}")
        try:
            shapes[name] = tuple(int(d) for d in dims.lower().split("x"))
        except ValueError:
            p.error(f"--input-shape needs DIMS like 28x28x1, got {dims!r}")
        if not shapes[name] or min(shapes[name]) < 1:
            p.error(f"--input-shape needs positive DIMS, got {dims!r}")
    fallbacks = {}
    for spec in args.fallback:
        name, sep, chain = spec.partition("=")
        if not sep or not chain:
            p.error(f"--fallback needs NAME=VERSION, got {spec!r}")
        parsed_chain = []
        for entry in chain.split(","):
            entry = entry.strip()
            if entry == "previous":
                parsed_chain.append("previous")
                continue
            try:
                parsed_chain.append(int(entry))
            except ValueError:
                p.error(f"--fallback {spec!r}: entries are version "
                        f"numbers or 'previous', got {entry!r}")
        fallbacks[name] = parsed_chain
    if args.max_dispatcher_restarts < 0:
        p.error("--max-dispatcher-restarts must be >= 0")
    if args.breaker_threshold < 0:
        p.error("--breaker-threshold must be >= 0 (0 disables)")
    breaker = None
    if args.breaker_threshold > 0:
        breaker = dict(failure_threshold=args.breaker_threshold,
                       window_s=args.breaker_window,
                       cooldown_s=args.breaker_cooldown,
                       half_open_probes=args.breaker_probes)
    from deeplearning4j_tpu.util.compile_cache import (
        enable_persistent_compile_cache)
    try:
        enable_persistent_compile_cache(args.compile_cache_dir)
    except ValueError as e:
        p.error(f"--compile-cache-dir: {e}")
    registry = ModelRegistry(metrics=default_registry(),
                             max_batch_size=args.max_batch_size,
                             wait_ms=args.wait_ms, buckets=buckets,
                             warmup=args.warmup,
                             max_dispatcher_restarts=(
                                 args.max_dispatcher_restarts),
                             breaker=breaker)
    models = []
    for spec in args.model:
        name, sep, path = spec.partition("=")
        if not sep:
            name, path = os.path.splitext(os.path.basename(spec))[0], spec
        models.append((name, path))
    model_names = [n for n, _ in models]
    # a typo'd NAME in a per-model flag must not silently serve the model
    # unquantized / unwarmed
    for flag, mapping in (("--dtype-policy", policies),
                          ("--input-shape", shapes),
                          ("--fallback", fallbacks)):
        unknown = sorted(set(mapping) - set(model_names))
        if unknown:
            p.error(f"{flag} names no registered --model: "
                    f"{', '.join(unknown)} (models: "
                    f"{', '.join(model_names)})")
    for name, path in models:
        version = registry.register(
            name, path=path, dtype_policy=policies.get(name, "float32"),
            input_shape=shapes.get(name),
            mesh=serve_mesh, sharding_rules=serve_rules)
        state = registry.warmup_state(name, version)
        mesh_tag = "" if serve_mesh is None else f" [mesh {args.mesh}]"
        extra = ""
        if state["status"] == "warm":
            extra = (f" (warmed {len(state['warm'])} bucket(s) in "
                     f"{state['seconds']:.2f}s)")
        elif state["status"] in ("pending", "warming"):
            extra = " (warming in background)"
        elif state["status"] == "skipped":
            extra = f" (warmup skipped: {state['reason']})"
        elif state["status"] == "error":
            extra = f" (warmup FAILED: {state['reason']})"
        print(f"registered {name!r} v{version} from {path}{mesh_tag}{extra}")
    for name, chain in fallbacks.items():
        try:
            registry.set_fallback(name, chain)
        except (KeyError, ValueError) as e:
            p.error(f"--fallback {name}: {e}")
        print(f"fallback chain for {name!r}: {chain}")
    brownout = None
    if args.brownout:
        brownout = dict(saturation=args.brownout_saturation)
    server = ModelServer(
        registry, host=args.host, port=args.port, metrics=default_registry(),
        max_inflight=args.max_inflight,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms is not None else None),
        alerts=alert_mgr, brownout=brownout, slo=slo_set)
    port = server.start()
    print(f"model server listening on {server.url} "
          f"(models: {', '.join(registry.names())}); port {port}")
    if tracer is not None:
        # the trace flushes when the server stops, however it is stopped —
        # the blocking KeyboardInterrupt path AND block=False callers
        server.tracer = tracer
        orig_stop = server.stop

        def _stop_and_flush(*a, **kw):
            from deeplearning4j_tpu.observe import disable_tracing
            try:
                return orig_stop(*a, **kw)
            finally:
                n = tracer.flush(args.trace)
                print(f"wrote Chrome trace ({n} spans) to {args.trace}")
                disable_tracing()

        server.stop = _stop_and_flush
    if block:
        try:
            server._thread.join()
        except KeyboardInterrupt:
            server.stop(drain=True, shutdown_registry=True)
    return server


def pipeline_main(argv: Optional[List[str]] = None):
    """``pipeline`` subcommand: the continuous-training loop
    (``deeplearning4j_tpu/pipeline/``) as a self-contained product —
    register the saved model as the serving baseline, stream the dataset
    through mini-epoch retraining, gate the candidate on a held-out
    split, canary it at ramped traffic fractions (self-driven synthetic
    traffic from the eval split; a production deployment attaches a
    ModelServer and real traffic), and auto-promote or roll back.  The
    journal under ``--state-dir`` makes the run crash-safe: re-running
    the same command after a kill resumes at the crashed stage
    (``DL4J_TPU_FAULT_PLAN`` with worker ``"pipeline"`` injects such
    kills deterministically).  SIGTERM drains cleanly: the open run is
    decided as a journaled rollback instead of dying mid-stage."""
    import signal

    p = argparse.ArgumentParser(prog="deeplearning4j_tpu pipeline")
    p.add_argument("--modelPath", required=True,
                   help="serving baseline (model zip / DL4J / Keras h5)")
    p.add_argument("--dataPath", required=True,
                   help=".npz with 'features' and 'labels': the stream "
                        "source and (split off) the held-out eval set")
    p.add_argument("--config", required=True, metavar="PIPELINE.json",
                   help="pipeline config (schema: pipeline.PipelineConfig; "
                        "lint with tools/validate_pipeline_config.py)")
    p.add_argument("--state-dir", required=True, dest="state_dir",
                   help="journal + candidate-checkpoint directory (the "
                        "crash-recovery substrate; reuse it to resume)")
    p.add_argument("--eval-fraction", type=float, default=0.2,
                   dest="eval_fraction",
                   help="tail fraction of the dataset held out for the "
                        "eval gate (never streamed)")
    p.add_argument("--cycles", type=int, default=None,
                   help="pipeline runs to execute (default: config)")
    p.add_argument("--modelOutputPath", default=None,
                   help="write the final serving model here on exit")
    p.add_argument("--log-json", default=None, metavar="OUT.jsonl",
                   dest="log_json",
                   help="structured JSON-lines logging with trace "
                        "correlation to this file ('-' for stderr)")
    p.add_argument("--alerts", default=None, metavar="RULES.json",
                   help="alert rules evaluated against the pipeline's "
                        "metrics registry; firing rules roll a canary "
                        "back (config canary.abort_on_alerts)")
    p.add_argument("--alert-interval", type=float, default=5.0,
                   help="seconds between alert evaluation rounds")
    # in-process-only flags are rejected, not silently ignored — the
    # same contract train --elastic applies to its worker processes
    p.add_argument("--trace", default=None, help=argparse.SUPPRESS)
    p.add_argument("--watchdog", default=None, help=argparse.SUPPRESS)
    p.add_argument("--uiUrl", default=None, help=argparse.SUPPRESS)
    p.add_argument("--workers", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    unsupported = [flag for flag, hit in (
        ("--trace", args.trace is not None),
        ("--watchdog", args.watchdog is not None),
        ("--uiUrl", args.uiUrl is not None),
        ("--workers", args.workers is not None),
    ) if hit]
    if unsupported:
        p.error(f"{', '.join(unsupported)} affect(s) in-process training "
                "and is not a pipeline flag: the watchdog is configured "
                "in the pipeline config (train.watchdog) and tracing/UI "
                "belong to the train subcommand. --log-json and --alerts "
                "ARE supported (they observe the pipeline)")
    if not 0.0 < args.eval_fraction < 1.0:
        p.error(f"--eval-fraction must be in (0, 1), "
                f"got {args.eval_fraction}")

    import time

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.observe.metrics import default_registry
    from deeplearning4j_tpu.pipeline import (ContinuousPipeline,
                                             PipelineConfig, StreamBuffer)
    from deeplearning4j_tpu.serving import ModelRegistry
    from deeplearning4j_tpu.streaming import Route
    from deeplearning4j_tpu.util import model_serializer

    config = PipelineConfig.parse(args.config)
    if args.log_json:
        from deeplearning4j_tpu.observe import enable_structured_logging
        if args.log_json == "-":
            enable_structured_logging(stream=sys.stderr)
        else:
            enable_structured_logging(path=args.log_json)
    metrics = default_registry()
    alert_mgr = None
    if args.alerts:
        from deeplearning4j_tpu.observe import (AlertManager, LogSink,
                                                load_rules)
        alert_mgr = AlertManager(metrics, load_rules(args.alerts),
                                 [LogSink()],
                                 interval_s=args.alert_interval).start()

    z = np.load(args.dataPath)
    features = np.asarray(z["features"], np.float32)
    labels = np.asarray(z["labels"], np.float32)
    n_eval = max(1, int(len(features) * args.eval_fraction))
    eval_set = DataSet(features[-n_eval:], labels[-n_eval:])
    stream_x, stream_y = features[:-n_eval], labels[:-n_eval]

    registry = ModelRegistry(metrics=metrics, wait_ms=1.0)
    registry.register(config.name, path=args.modelPath,
                      sample_input=features[:1])

    bs = config.train["batch_size"]
    batches = [DataSet(stream_x[i:i + bs], stream_y[i:i + bs])
               for i in range(0, len(stream_x), bs)]
    cycles = args.cycles if args.cycles is not None else config.cycles
    # hold every cycle's pass outright (buffer stores references to the
    # already-materialized batch list): a cycle that drains less than a
    # full pass must not leave a later cycle's route blocked in put()
    buffer = StreamBuffer(
        capacity=max(1024, (cycles + 1) * max(1, len(batches))))

    def canary_traffic(poll_s):
        # self-driven canary traffic so weighted routing and shadow
        # diffs observe real forwards between ticks
        for i in range(4):
            registry.predict(config.name,
                             eval_set.features[i % n_eval:][:2])
        time.sleep(poll_s)

    pipe = ContinuousPipeline(
        registry, config.name, args.state_dir, config=config,
        buffer=buffer, eval_set=eval_set, metrics=metrics,
        alerts=alert_mgr, sample_input=features[:1],
        canary_wait=canary_traffic)
    signal.signal(signal.SIGTERM, lambda *a: pipe.request_stop())
    # a restarted process registers the ORIGINAL artifact as baseline;
    # if the journal already committed a promotion, re-apply it so the
    # resumed pipeline (and --modelOutputPath) serve the promoted weights
    restored = pipe.restore_promoted()
    if restored is not None:
        print(f"restored journaled promotion as v{restored}")

    try:
        # ONE stream pass per cycle (a real deployment points the route
        # at a broker): replaying all passes up front would let the
        # trainer's greedy drain starve later cycles into aborted runs
        summaries = []
        for _ in range(cycles):
            route = (Route().from_source(list(batches))
                     .to_callable(buffer.put).start())
            pipe.route = route
            summaries.append(pipe.run_cycle())
            route.join(timeout=60)
            if pipe.stopped:
                break
    finally:
        if alert_mgr is not None:
            alert_mgr.evaluate_once()
            alert_mgr.stop()
            firing = alert_mgr.firing()
            print(f"alerts firing at exit: {firing if firing else 'none'}")
        registry.shutdown()
        if args.log_json:
            from deeplearning4j_tpu.observe import (
                disable_structured_logging)
            disable_structured_logging()
    for s in summaries:
        print(f"run {s['run']}: {s['outcome']} "
              f"(live version {s['live_version']})")
    if args.modelOutputPath:
        served = registry.get(config.name)
        model_serializer.write_model(
            served.versions[served.current_version].model,
            args.modelOutputPath)
        print(f"wrote {args.modelOutputPath}")
    return summaries


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m deeplearning4j_tpu.cli "
              "{train,evaluate,serve,pipeline,nn-server,cloud-setup,"
              "profile} ...")
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "serve":
        serve_main(rest)
        return 0
    if cmd == "pipeline":
        pipeline_main(rest)
        return 0
    if cmd == "train":
        parallel_wrapper_main(rest)
        return 0
    if cmd == "evaluate":
        evaluate_main(rest)
        return 0
    if cmd == "profile":
        profile_main(rest)
        return 0
    if cmd == "nn-server":
        from deeplearning4j_tpu.clustering.server import NearestNeighborsServer
        server = NearestNeighborsServer.main(rest)
        print(f"nearest-neighbors server listening on port {server.port}")
        try:
            server._thread.join()
        except KeyboardInterrupt:
            server.stop()
        return 0
    if cmd == "cloud-setup":
        cluster_setup_main(rest)
        return 0
    print(f"unknown command {cmd!r}; expected 'train', 'evaluate', "
          "'serve', 'pipeline', 'nn-server', 'cloud-setup', or 'profile'")
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
