"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline: **ResNet50 ImageNet-shape training throughput (images/sec) on one
chip** — the tracked metric in BASELINE.json ("zoo ResNet50 images/sec/chip").
Training step = full forward/backward/update on 224x224x3 synthetic batches
via the zoo ResNet50 graph, mixed precision (f32 master weights, bfloat16
compute — the TPU-idiomatic configuration; the reference has no published
number to compare against, BASELINE.md "published: {}").

``vs_baseline`` is the ratio against the first value this framework recorded
on the target hardware (below), or 1.0 until one exists.

The single JSON line also carries a ``suite`` object covering the other four
BASELINE.json configs (round-5: per-round regression coverage of the whole
headline suite, VERDICT r4 Weak #1), each with wall AND profiled device time
(``tools/tpu_perf_session.py`` methodology):

- ``lenet_mnist``          — configs[0], zoo LeNet, B=512 f32
- ``graveslstm_char_rnn``  — configs[3], 2x512 GravesLSTM, B=64 T=128 bf16
                             (re-measured with device time in round 5)
- ``bert_base_import``     — configs[2], genuine Keras BERT-base through the
                             import path; a fixture that cannot be built or
                             imported is this entry's ``error``, never a
                             different model under this name
- ``vgg16``                — configs[4]'s single-chip half, zoo VGG16 B=64
                             bf16 (the ICI-scaling half is exercised by
                             ``__graft_entry__.dryrun_multichip``)

Each suite entry is individually guarded: a failure records ``error`` for
that entry and never blocks the headline line, but the run then exits
non-zero. On a TPU a failed device profile is such a failure.

``--trace DIR`` (or ``DL4J_TPU_BENCH_TRACE_DIR``) records each config —
headline included — with the observe tracer and writes one Chrome-trace
JSON per config into DIR (``<name>.trace.json``): per-step spans with the
XLA compile spans attributed to the steps that paid for them.

``--pod-scaling [OUT.json]`` runs the pod-scale elastic series instead of
the headline (MULTICHIP_r06: step time vs world size on the mesh, and
the per-step checkpoint save stall sync vs async — the async overlapped
path must beat the blocking one). ``--save-mode sync|async`` restricts
the save-stall half to one mode.

``--train-pipeline [OUT.json]`` runs the training input-pipeline + fused
updater series (BENCH_TRAIN_r01): step time over an ETL-bound iterator
with prefetch off vs on (the ``fit(prefetch_depth=...)`` async wrap must
hide the host work), host_wait per step, transfer bytes, steady-state
compile counts, and the fused Pallas optimizer step vs the stock
per-param chain (timing + numerical agreement + kernel-launch count).
``--train-pipeline --check COMMITTED.json`` validates a committed record
(prefetch-on faster, zero steady-state compiles) plus LIVE oracles on
this machine: fused-vs-stock agreement ≤2e-5, exactly one pallas_call
per fusable tensor in the train-step jaxpr, none with the seam clear,
zero steady-state compiles — exits non-zero on any violation.

``--sharding-2d [OUT.json]`` runs the GSPMD 2-D parallelism series
(MULTICHIP_r07) on the virtual 8-device CPU mesh: DP-only vs DP×MP
(Megatron rule-based placement) step time plus per-config collective
counts from the compiled train-step and forward HLO. The record fails
outright if a 2-D forward contains an all-gather — the zero-all-gather
vocab path (row-sharded embedding take, column-sharded logits + LSE
loss) is the series' invariant. ``--sharding-2d --check COMMITTED.json``
validates a committed record and re-proves the invariant live, before
and after a train step (placement pinning regression).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# First recorded value on the round-1 bench hardware (TPU v5e lite, batch 256,
# mixed bf16/f32; matches BASELINE.md). Update when the framework improves.
BASELINE_IMAGES_PER_SEC = 2035.4

BERT_H5 = "/tmp/bert_base_import.h5"


def _trace_dir():
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return os.environ.get("DL4J_TPU_BENCH_TRACE_DIR") or None


def _with_trace(name, fn):
    """Run one bench config, optionally recording it as its own trace."""
    out_dir = _trace_dir()
    if not out_dir:
        return fn()
    from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                            enable_tracing)
    os.makedirs(out_dir, exist_ok=True)
    tracer = enable_tracing(Tracer())  # fresh recorder per config
    try:
        with tracer.span(f"bench:{name}", category="bench"):
            return fn()
    finally:
        disable_tracing()
        path = os.path.join(out_dir, f"{name}.trace.json")
        print(f"bench trace: {path} ({tracer.flush(path)} spans)",
              file=sys.stderr)


def _profiled_device_ms(net, ds):
    """Profiled on-device ms/step on a TPU, where a profile that fails or
    finds no device events is an error; None on any other platform, which
    has no TPU plane to read."""
    import jax
    if jax.devices()[0].platform != "tpu":
        return None
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from tpu_perf_session import profile_step
    times = profile_step(net, ds, "/tmp/bench_prof")
    dev = sum(t for t, _ in times.values()) / 4
    if dev <= 0:
        raise RuntimeError("device profile holds no time on the TPU plane")
    return dev * 1e3


def _measure(net, ds, items_per_batch, steps=8, warmup=3):
    """Wall + device per-step timings for one config; items/s from both."""
    from deeplearning4j_tpu.observe import trace as _trace
    with _trace.span("warmup", attrs={"steps": warmup}):
        for _ in range(warmup):
            net._fit_batch(ds)
        float(net.score_)  # materialize: a data read is the only reliable sync
    t0 = time.perf_counter()
    with _trace.span("measure", attrs={"steps": steps}):
        for _ in range(steps):
            net._fit_batch(ds)
        float(net.score_)  # drain the whole queue before stopping the clock
    wall_ms = (time.perf_counter() - t0) / steps * 1e3
    rec = {"wall_ms_per_step": round(wall_ms, 2),
           "wall_items_per_sec": round(items_per_batch / wall_ms * 1e3, 1)}
    dev_ms = _profiled_device_ms(net, ds)
    if dev_ms is not None:
        rec["device_ms_per_step"] = round(dev_ms, 2)
        rec["device_items_per_sec"] = round(items_per_batch / dev_ms * 1e3, 1)
    return rec


def _resnet50_headline():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.models import ResNet50

    batch = 256
    steps = 10
    warmup = 3

    conf = ResNet50(num_labels=1000, seed=1).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    net = ComputationGraph(conf)
    net.init()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3)).astype(np.float32))
    y = jnp.asarray(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, size=batch)])
    ds = DataSet(x, y)  # resident on device for the whole run

    from deeplearning4j_tpu.observe import trace as _trace
    with _trace.span("warmup", attrs={"steps": warmup}):
        for _ in range(warmup):
            net._fit_batch(ds)
        float(net.score_)

    t0 = time.perf_counter()
    with _trace.span("measure", attrs={"steps": steps}):
        for _ in range(steps):
            net._fit_batch(ds)
        float(net.score_)
    dt = time.perf_counter() - t0

    ips = batch * steps / dt
    vs = ips / BASELINE_IMAGES_PER_SEC if BASELINE_IMAGES_PER_SEC else 1.0
    record = {
        "metric": "resnet50_train_throughput_per_chip",
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(vs, 4),
    }
    dev_ms = _profiled_device_ms(net, ds)
    if dev_ms is not None:
        record["device_ms_per_step"] = round(dev_ms, 2)
        record["device_time_images_per_sec"] = round(batch / dev_ms * 1e3, 1)
        record["dispatch_overhead_ms_per_step"] = round(
            dt / steps * 1e3 - dev_ms, 2)
    return record


def _bench_lenet():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.models import LeNet

    batch = 512
    net = MultiLayerNetwork(LeNet(num_labels=10, seed=1).conf())
    net.init()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(batch, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)])
    rec = _measure(net, DataSet(x, y), batch)
    rec["config"] = "zoo LeNet, B=512, f32"
    return rec


def _bench_graveslstm():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import GravesLSTMLayer, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    batch, t, vocab, width = 64, 128, 77, 512
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
            .list()
            .layer(GravesLSTMLayer(n_in=vocab, n_out=width,
                                   activation="tanh"))
            .layer(GravesLSTMLayer(n_in=width, n_out=width,
                                   activation="tanh"))
            .layer(RnnOutputLayer(n_in=width, n_out=vocab,
                                  activation="softmax",
                                  loss="negativeloglikelihood"))
            .set_input_type(InputType.recurrent(vocab, t))
            .build())
    conf.global_conf.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(2)
    ids = rng.integers(0, vocab, size=(batch, t))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        np.roll(ids, -1, axis=1)])
    rec = _measure(net, DataSet(x, y), batch * t)  # items = characters
    rec["config"] = "2x512 GravesLSTM char-RNN, B=64 T=128 V=77, bf16"
    return rec


def _bench_bert_import():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.modelimport.keras.importer import (
        KerasModelImport)

    batch, t = 32, 128
    rng = np.random.default_rng(3)

    if not os.path.exists(BERT_H5):
        # the make stage needs keras, which must not share the TPU process;
        # the child is held to the CPU and never asks for the chip.
        # A timed-out/killed make must not leave a truncated h5 that
        # poisons every later run: build to a temp name, rename on success.
        tmp_h5 = BERT_H5 + ".part"
        try:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       DL4J_TPU_BERT_H5=tmp_h5)
            subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "tools", "r4_bert_import_bench.py"), "make"],
                env=env, timeout=900, check=True, stdout=subprocess.DEVNULL)
            os.replace(tmp_h5, BERT_H5)
        finally:
            if os.path.exists(tmp_h5):
                os.remove(tmp_h5)

    net = KerasModelImport.import_keras_model_and_weights(BERT_H5)
    net.conf.global_conf.compute_dtype = "bfloat16"
    tok = rng.integers(0, 30522, size=(batch, t)).astype(np.float32)
    pos = np.tile(np.arange(t, dtype=np.float32), (batch, 1))
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=batch)]
    ds = MultiDataSet([jnp.asarray(tok), jnp.asarray(pos)],
                      [jnp.asarray(y)])

    rec = _measure(net, ds, batch * t)  # items = tokens
    rec["path"] = "import"
    rec["config"] = "BERT-base shape 12L/768/12H/3072, B=32 T=128, bf16"
    return rec


def _bench_vgg16():
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.models import VGG16

    batch = 64
    conf = VGG16(num_labels=1000, seed=1).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3)).astype(np.float32))
    y = jnp.asarray(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, size=batch)])
    rec = _measure(net, DataSet(x, y), batch, steps=6)
    rec["config"] = "zoo VGG16, B=64, 224x224x3, bf16, single chip"
    return rec


SUITE = {
    "lenet_mnist": _bench_lenet,
    "graveslstm_char_rnn": _bench_graveslstm,
    "bert_base_import": _bench_bert_import,
    "vgg16": _bench_vgg16,
}


# -- pod-scale elastic series (MULTICHIP_r06) --------------------------------

def _scaling_net(seed=1, width=512):
    """A model big enough that its checkpoint write is measurable (~1M
    params ≈ 4 MB of f32 + updater state) but cheap to step on CPU."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=width, activation="relu"))
            .layer(DenseLayer(n_out=width, activation="relu"))
            .layer(OutputLayer(n_out=10))
            .set_input_type(InputType.feed_forward(width)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(seed)
    batch = 128
    x = jnp.asarray(rng.normal(size=(batch, width)).astype(np.float32))
    y = jnp.asarray(
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)])
    return net, DataSet(x, y), batch


def _pod_scaling_worlds(steps=8, warmup=3):
    """Step time vs data-parallel world size on the local mesh — the
    scaling half of the curve."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.parallel import (DistributedMultiLayerNetwork,
                                             SharedTrainingMaster)
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    worlds = []
    for w in (1, 2, 4, 8):
        if w > len(devices):
            break
        net, ds, batch = _scaling_net()
        mesh = make_mesh({"data": w}, devices=devices[:w])
        master = SharedTrainingMaster(batch_size_per_worker=batch // w,
                                      threshold=1e-3, mesh=mesh)
        front = DistributedMultiLayerNetwork(net, master)
        x = np.asarray(ds.features)
        y = np.asarray(ds.labels)
        it = lambda: ListDataSetIterator(DataSet(x, y), batch)  # noqa: E731
        front.fit(it(), epochs=warmup)  # compile + warm
        t0 = time.perf_counter()
        front.fit(it(), epochs=steps)
        wall_ms = (time.perf_counter() - t0) / steps * 1e3
        worlds.append({"world": w,
                       "wall_ms_per_step": round(wall_ms, 2),
                       "items_per_sec": round(batch / wall_ms * 1e3, 1)})
    return worlds


def _pod_save_stall(mode, tmp_dir, steps=6):
    """Per-step checkpoint stall for one save mode: the wall time the
    TRAINING thread loses to each per-step checkpoint. sync = full
    orbax save + finalize on the step path; async = snapshot + bounded
    submit (AsyncCheckpointSession), commit runs behind the next
    steps."""
    import shutil

    from deeplearning4j_tpu.parallel.elastic import (AsyncCheckpointSession,
                                                     ElasticWorkerContext)
    from deeplearning4j_tpu.util.orbax_checkpoint import (
        OrbaxCheckpointManager)

    net, ds, batch = _scaling_net()
    d = os.path.join(tmp_dir, f"save_{mode}")
    shutil.rmtree(d, ignore_errors=True)
    for _ in range(3):
        net._fit_batch(ds)
    float(net.score_)
    stalls = []
    mgr = OrbaxCheckpointManager(d, max_to_keep=2)
    session = None
    committed = 0
    if mode == "async":
        ctx = ElasticWorkerContext(
            coordinator="", num_processes=1, process_id=0, slot=0,
            generation=1, token="bench", ckpt_dir=d,
            heartbeat_path=os.path.join(d, "hb"), restore_step=None)
        session = AsyncCheckpointSession(ctx, manager=mgr,
                                         max_in_flight=2)
    t_train0 = time.perf_counter()
    for step in range(1, steps + 1):
        net._fit_batch(ds)
        float(net.score_)
        t0 = time.perf_counter()
        if session is not None:
            session.submit(step, net)
        else:
            if mgr.save(step, net, overwrite_existing=True):
                committed += 1
            mgr.wait_until_finished()
        stalls.append(time.perf_counter() - t0)
    total_wall = time.perf_counter() - t_train0
    if session is not None:
        flushed = session.close(timeout=300)
        committed = len(session.committed)
    else:
        flushed = True
    # a timed-out flush means the saver thread may still be inside a
    # manager call — do NOT close the manager under it (same rule as
    # run_elastic_worker); process exit reclaims it, and the record
    # reports flushed=false
    if flushed:
        mgr.close()
    return {"mode": mode,
            "save_stall_ms_per_step": round(
                sum(stalls) / len(stalls) * 1e3, 2),
            "save_stall_ms_max": round(max(stalls) * 1e3, 2),
            "wall_ms_per_step_with_saves": round(
                total_wall / steps * 1e3, 2),
            "steps": steps, "flushed": flushed,
            "committed_steps": committed}


def _pod_scaling_main(out_path, save_mode):
    import tempfile

    import jax
    record = {
        "metric": "pod_scale_elastic",
        "series": "MULTICHIP_r06",
        "config": "3-layer 512-wide MLP (~790k params, Adam), B=128 f32, "
                  "per-step orbax checkpoint rotation",
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "note": "worlds = step time vs data-axis size on the local "
                "device mesh (on the virtual CPU mesh collective overhead "
                "dominates at this model size, so the curve RISES — the "
                "series exists to track the shape run-over-run and on "
                "real ICI); save = per-step checkpoint stall on the "
                "training thread, sync vs async commit path",
        "worlds": _pod_scaling_worlds(),
        "save": {},
    }
    modes = ("sync", "async") if save_mode is None else (save_mode,)
    with tempfile.TemporaryDirectory(prefix="pod_bench_") as td:
        for mode in modes:
            record["save"][mode] = _pod_save_stall(mode, td)
    if {"sync", "async"} <= set(record["save"]):
        sync_ms = record["save"]["sync"]["save_stall_ms_per_step"]
        async_ms = record["save"]["async"]["save_stall_ms_per_step"]
        record["async_stall_vs_sync"] = round(async_ms / sync_ms, 4) \
            if sync_ms > 0 else None
    line = json.dumps(record, indent=2)
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
        print(f"wrote {out_path}", file=sys.stderr)
    print(line)


# -- GSPMD 2-D parallelism series (MULTICHIP_r07) ----------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all")


def _force_cpu_mesh(n=8):
    """This series is DEFINED on the virtual 8-device CPU mesh (same
    substrate as the test tier) — must run before the first jax import."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _collective_counts(hlo_text):
    import re as _re
    return {c.replace("-", "_"):
            len(_re.findall(r"\b%s\b" % c, hlo_text))
            for c in _COLLECTIVES}


def _lm_2d_net(mesh=None, rules=None, vocab=512, d_model=64, n_heads=4,
               n_layers=2, d_ff=128, t=16, seed=7):
    """Tiny-but-real TransformerLM + LM batch; sharded when mesh given.
    n_heads must be divisible by the model-axis size (head-major QKV
    reshape propagation keeps the layout; a non-dividing head count
    forces GSPMD to re-gather activations)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel.sharding import shard_model_with_rules
    from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels

    net = TransformerLM(vocab_size=vocab, d_model=d_model, n_heads=n_heads,
                        n_layers=n_layers, d_ff=d_ff, max_length=t,
                        seed=seed).init()
    if mesh is not None:
        shard_model_with_rules(net, mesh, rules)
    rng = np.random.default_rng(seed)
    batch = 32
    toks = rng.integers(0, vocab, size=(batch, t))
    x = toks.astype(np.float32)
    y = np.asarray(lm_labels(jnp.asarray(toks), vocab))
    return net, DataSet(x, y), batch


def _lm_step_hlo(net, ds, mesh):
    """Compiled HLO of the graph train step on mesh-placed args."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.sharding import place_batch

    step = net._get_train_step()
    it, ep, rng_k = net._device_tick()
    xj = place_batch(jnp.asarray(np.asarray(ds.features)), mesh) \
        if mesh is not None else jnp.asarray(np.asarray(ds.features))
    yj = place_batch(jnp.asarray(np.asarray(ds.labels)), mesh) \
        if mesh is not None else jnp.asarray(np.asarray(ds.labels))
    return step.lower(net.params, net.states, net.updater_states, it, ep,
                      {"tokens": xj}, [yj], None, None,
                      rng_k).compile().as_text()


def _lm_forward_hlo(net, ds, mesh):
    """Compiled HLO of the forward (the vocab-path oracle surface:
    row-sharded embedding take in, column-sharded logits out)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.sharding import place_batch

    ofn = net._output_fn()
    xj = place_batch(jnp.asarray(np.asarray(ds.features)), mesh) \
        if mesh is not None else jnp.asarray(np.asarray(ds.features))
    return ofn.lower(net.params, net.states,
                     {"tokens": xj}, None).compile().as_text()


def _sharding_2d_config(name, axes, steps=8, warmup=3):
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dict(axes)) if axes else None
    net, ds, batch = _lm_2d_net(mesh=mesh)
    for _ in range(warmup):
        net.fit(ds)
    float(net.score_)
    t0 = time.perf_counter()
    for _ in range(steps):
        net.fit(ds)
    float(net.score_)
    wall_ms = (time.perf_counter() - t0) / steps * 1e3
    return {"mesh": dict(axes) if axes else {"data": 1},
            "wall_ms_per_step": round(wall_ms, 2),
            "items_per_sec": round(batch / wall_ms * 1e3, 1),
            "train_step": _collective_counts(_lm_step_hlo(net, ds, mesh)),
            # forward AFTER training: placement pinning must have kept
            # the params where the rules put them (sharding drift would
            # show up here as all-gathers)
            "forward": _collective_counts(_lm_forward_hlo(net, ds, mesh))}


def _sharding_2d_main(out_path):
    import jax

    configs = {
        "dp8": {"data": 8},
        "dp4_mp2": {"data": 4, "model": 2},
        "dp2_mp4": {"data": 2, "model": 4},
    }
    record = {
        "metric": "sharding_2d",
        "series": "MULTICHIP_r07",
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "config": "TransformerLM 2L/64d/4h/512V T=16 B=32 f32 Adam, "
                  "rule-based GSPMD placement (DEFAULT_2D_RULES)",
        "note": "dp8 = data-parallel only; dp4_mp2/dp2_mp4 = Megatron "
                "2-D over the same 8 virtual CPU devices (collective "
                "overhead dominates at this size on CPU — the series "
                "tracks the collective COUNTS and the shape run-over-"
                "run; on real ICI the model axis buys memory headroom). "
                "forward.all_gather == 0 is the zero-all-gather vocab-"
                "path invariant: row-sharded embedding take + column-"
                "sharded logits with LSE cross-entropy never "
                "re-assemble the vocab dimension",
        "configs": {name: _sharding_2d_config(name, axes)
                    for name, axes in configs.items()},
    }
    for name in ("dp4_mp2", "dp2_mp4"):
        ag = record["configs"][name]["forward"]["all_gather"]
        if ag != 0:
            print(f"sharding-2d: {name} forward has {ag} all-gather(s) — "
                  f"the vocab-path invariant is BROKEN", file=sys.stderr)
            raise SystemExit(1)
    line = json.dumps(record, indent=2)
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
        print(f"wrote {out_path}", file=sys.stderr)
    print(line)


def _sharding_2d_check(path):
    """Validate a committed MULTICHIP_r07 record + live vocab-path
    oracle. Timing is checked against the committed record only (live
    timing on CI is noise); the zero-all-gather invariant is re-proven
    live on this machine, before AND after a train step."""
    errors = []

    def expect(cond, msg):
        if not cond:
            errors.append(msg)

    with open(path, encoding="utf-8") as fh:
        rec = json.load(fh)
    expect(rec.get("metric") == "sharding_2d", "metric != sharding_2d")
    cfgs = rec.get("configs") or {}
    for name in ("dp8", "dp4_mp2", "dp2_mp4"):
        expect(name in cfgs, f"configs.{name} missing")
    for name in ("dp4_mp2", "dp2_mp4"):
        if name in cfgs:
            expect(cfgs[name]["forward"].get("all_gather") == 0,
                   f"committed record: {name} forward all-gathers != 0 "
                   f"(vocab path re-assembles the vocab dim)")
            expect(cfgs[name]["train_step"].get("all_reduce", 0) > 0,
                   f"committed record: {name} train step has no "
                   f"all-reduce (gradient exchange missing?)")
        if name in cfgs and "dp8" in cfgs:
            expect(cfgs[name].get("wall_ms_per_step", 0) > 0
                   and cfgs["dp8"].get("wall_ms_per_step", 0) > 0,
                   f"committed record: {name}/dp8 timing missing")

    # live oracle — the invariant, re-proven on this machine every run
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 4, "model": 2})
    net, ds, _ = _lm_2d_net(mesh=mesh)
    ag0 = _collective_counts(_lm_forward_hlo(net, ds, mesh))["all_gather"]
    expect(ag0 == 0, f"live: fresh placement forward has {ag0} "
                     f"all-gather(s)")
    net.fit(ds)  # one optimizer step: updated params must stay pinned
    float(net.score_)
    ag1 = _collective_counts(_lm_forward_hlo(net, ds, mesh))["all_gather"]
    expect(ag1 == 0, f"live: post-step forward has {ag1} all-gather(s) — "
                     f"train-step output shardings drifted off the rules")

    if errors:
        for e in errors:
            print(f"sharding-2d check FAILED: {e}", file=sys.stderr)
        raise SystemExit(1)
    print(f"sharding-2d check OK: {path} (committed collective counts "
          f"consistent; zero-all-gather vocab path holds live, before "
          f"and after a train step)")


# -- training input pipeline + fused updater series (BENCH_TRAIN_r01) --------

class _OneHotETLIterator:
    """Transfer-bound input source: every batch costs an ingest latency
    (``io_ms`` of GIL-released wait — the remote-storage read profile) plus
    real numpy decode work (one-hot encode), the stall the async prefetch
    wrap exists to hide behind the running step. Yields fresh numpy-backed
    DataSets, so it is safe to device_put/mutate downstream."""

    def __init__(self, n_batches, batch, t, vocab, n_labels=10, seed=0,
                 io_ms=15.0):
        self.n_batches = int(n_batches)
        self.batch, self.t, self.vocab = int(batch), int(t), int(vocab)
        self.n_labels = int(n_labels)
        self.seed = int(seed)
        self.io_ms = float(io_ms)

    def reset(self):
        pass

    def __iter__(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        rng = np.random.default_rng(self.seed)
        eye = np.eye(self.vocab, dtype=np.float32)
        for _ in range(self.n_batches):
            time.sleep(self.io_ms / 1e3)  # the read we are hiding
            ids = rng.integers(0, self.vocab, size=(self.batch, self.t))
            x = eye[ids].reshape(self.batch, self.t * self.vocab)
            y = np.eye(self.n_labels, dtype=np.float32)[
                rng.integers(0, self.n_labels, size=self.batch)]
            yield DataSet(x, y)


def _pipeline_net(n_in, width=128, n_labels=10, seed=1):
    """Small dense model over wide one-hot input: the step is cheap enough
    that an unhidden ETL stage dominates the loop."""
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_out=width, activation="relu"))
            .layer(OutputLayer(n_out=n_labels))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _prefetch_run(net, depth, n_batches, batch, t, vocab, seed):
    """One epoch over the ETL-bound iterator at one prefetch depth, with the
    production observability attached — the TraceListener reads the score
    every step, the per-iteration sync every monitored training run pays.
    Returns wall/step, host_wait/step (from the fit loop's trace spans),
    transfer MB (from the exported counter) and compiles on this thread."""
    from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                            enable_tracing)
    from deeplearning4j_tpu.observe.listener import TraceListener
    from deeplearning4j_tpu.observe.metrics import MetricsRegistry

    it = _OneHotETLIterator(n_batches, batch, t, vocab, seed=seed)
    metrics = MetricsRegistry()
    tracer = enable_tracing(Tracer(metrics=metrics))
    listener = TraceListener(tracer, metrics, model_name="bench")
    net.listeners.append(listener)
    try:
        t0 = time.perf_counter()
        net.fit(it, epochs=1, prefetch_depth=depth)
        float(net.score_)  # drain the dispatch queue before stopping the clock
        dt = time.perf_counter() - t0
        compiles = tracer.thread_compile_count()
    finally:
        net.listeners.remove(listener)
        disable_tracing()
    host_wait_ms = sum(s.end_ns - s.start_ns
                       for s in tracer.recorder.spans()
                       if s.name == "host_wait" and s.end_ns) / 1e6
    xfer = metrics.get("training_transfer_bytes_total")
    return {
        "prefetch_depth": depth,
        "wall_ms_per_step": round(dt / n_batches * 1e3, 2),
        "host_wait_ms_per_step": round(host_wait_ms / n_batches, 2),
        "transfer_mb_total": round(
            (xfer.value(model="bench") if xfer is not None else 0) / 2**20, 2),
        "steady_state_compiles": int(compiles),
    }


def _max_param_diff(a, b):
    """max |Δ| over every parameter tensor of two same-structure nets."""
    import jax
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree_util.tree_leaves(a.params),
                               jax.tree_util.tree_leaves(b.params)))


def _count_pallas_eqns(jaxpr):
    """pallas_call equations in a jaxpr, recursing into sub-jaxprs (pjit
    bodies, scan/cond branches)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for u in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(u, "jaxpr", u)
                if hasattr(inner, "eqns"):
                    n += _count_pallas_eqns(inner)
    return n


def _updater_helper():
    """The fused updater as this backend can run it: compiled on a TPU,
    the Pallas interpreter anywhere else (the record's ``backend`` and
    ``note`` fields say which)."""
    import jax

    from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
    return PallasUpdaterHelper(interpret=jax.default_backend() != "tpu")


def _pallas_call_counts(net, ds):
    """(pallas_call eqns in the traced train step, fusable param tensors).
    With the fused updater registered the two must be EQUAL — one kernel
    launch per parameter's read-modify-write, no per-param op chain."""
    import jax
    import jax.numpy as jnp

    fn = net._get_train_step(False)
    closed = jax.make_jaxpr(fn)(
        net.params, net.states, net.updater_states,
        jnp.float32(0.0), jnp.float32(0.0),
        jnp.asarray(np.asarray(ds.features), jnp.float32),
        jnp.asarray(np.asarray(ds.labels), jnp.float32),
        None, None, jax.random.PRNGKey(0), None)
    probe = _updater_helper()
    fusable = sum(1 for i, layer_params in enumerate(net.params)
                  for n, p in layer_params.items()
                  if probe.supports(net._updaters[i][n], p, p))
    return _count_pallas_eqns(closed.jaxpr), fusable


def _fused_updater_bench():
    """Fused Pallas optimizer step vs the stock per-param chain on twin
    nets (same seed, same data): wall time each way, post-run numerical
    agreement, and the kernel-launch oracle."""
    import jax

    from deeplearning4j_tpu.nn import helpers as _helpers

    net_a, ds, batch = _scaling_net(seed=7)
    net_b, _, _ = _scaling_net(seed=7)
    _helpers.clear_helper("updater")
    try:
        rec = {"config": "3-layer 512-wide MLP (~790k params, Adam), "
                         "B=128 f32 (the pod-scaling net)"}
        # per-update agreement contract first: fresh twins, 3 identical
        # steps each way — the tolerance is per update, not compounded
        # over a long chaotic trajectory
        tw_a, tw_ds, _ = _scaling_net(seed=11, width=64)
        tw_b, _, _ = _scaling_net(seed=11, width=64)
        for _ in range(3):
            tw_a._fit_batch(tw_ds)
        _helpers.set_helper("updater", _updater_helper())
        for _ in range(3):
            tw_b._fit_batch(tw_ds)
        rec["max_abs_param_diff"] = float(_max_param_diff(tw_a, tw_b))
        rec["agreement_steps"] = 3
        _helpers.clear_helper("updater")
        rec["stock"] = _measure(net_a, ds, batch)
        _helpers.set_helper("updater", _updater_helper())
        rec["fused"] = _measure(net_b, ds, batch)
        stock_ms = rec["stock"]["wall_ms_per_step"]
        fused_ms = rec["fused"]["wall_ms_per_step"]
        rec["fused_vs_stock"] = round(fused_ms / stock_ms, 4) \
            if stock_ms > 0 else None
        pallas, fusable = _pallas_call_counts(net_b, ds)
        rec["pallas_calls_in_train_step"] = pallas
        rec["fusable_tensors"] = fusable
        if jax.default_backend() != "tpu":
            rec["note"] = ("interpret-mode Pallas off-TPU: the fused timing "
                           "measures the seam, not the kernel — the "
                           "correctness/launch-count oracles are the "
                           "backend-portable signal")
        return rec
    finally:
        _helpers.clear_helper("updater")


def _train_pipeline_main(out_path):
    import jax

    vocab, t, batch, n_batches = 256, 32, 64, 24
    net = _pipeline_net(t * vocab)
    # compile outside the measured windows (identical shapes throughout)
    net.fit(_OneHotETLIterator(2, batch, t, vocab, seed=99), epochs=1,
            prefetch_depth=0)
    float(net.score_)

    prefetch = {
        "off": _prefetch_run(net, 0, n_batches, batch, t, vocab, seed=5),
        "on": _prefetch_run(net, 2, n_batches, batch, t, vocab, seed=6),
    }
    on_ms = prefetch["on"]["wall_ms_per_step"]
    record = {
        "metric": "train_pipeline",
        "series": "BENCH_TRAIN_r01",
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "config": f"dense-128 over {t * vocab}-wide one-hot, B={batch}, "
                  f"{n_batches} batches/epoch, 15ms ingest latency + numpy "
                  "decode per batch, Adam, f32, TraceListener attached "
                  "(per-step score sync)",
        "note": "prefetch off = the fit thread pays ingest + decode + "
                "transfer between steps; on = AsyncDataSetIterator producer "
                "+ device_put stage hides them behind the running step, so "
                "host_wait collapses",
        "prefetch": prefetch,
        "prefetch_speedup": round(
            prefetch["off"]["wall_ms_per_step"] / on_ms, 4) if on_ms else None,
        "fused_updater": _fused_updater_bench(),
    }
    line = json.dumps(record, indent=2)
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
        print(f"wrote {out_path}", file=sys.stderr)
    print(line)


def _train_check(path):
    """Validate a committed BENCH_TRAIN record + live functional oracles.
    Timing claims are checked against the COMMITTED record (live timing on
    an arbitrary CI box is noise); correctness claims are re-proven live."""
    errors = []

    def expect(cond, msg):
        if not cond:
            errors.append(msg)

    with open(path, encoding="utf-8") as fh:
        rec = json.load(fh)
    expect(rec.get("metric") == "train_pipeline", "metric != train_pipeline")
    pre = rec.get("prefetch") or {}
    expect("off" in pre and "on" in pre, "prefetch.off/on missing")
    if "off" in pre and "on" in pre:
        expect(pre["on"]["wall_ms_per_step"] < pre["off"]["wall_ms_per_step"],
               "committed record: prefetch-on not faster than prefetch-off")
        expect(pre["on"]["host_wait_ms_per_step"]
               <= pre["off"]["host_wait_ms_per_step"],
               "committed record: prefetch did not reduce host_wait")
        for k in ("off", "on"):
            expect(pre[k].get("steady_state_compiles") == 0,
                   f"committed record: prefetch.{k} recompiled in steady "
                   f"state")
            expect(pre[k].get("transfer_mb_total", 0) > 0,
                   f"committed record: prefetch.{k} transfer counter empty")
    fu = rec.get("fused_updater") or {}
    expect(fu.get("max_abs_param_diff", 1.0) <= 2e-5,
           "committed record: fused/stock divergence > 2e-5")
    expect(fu.get("fusable_tensors", 0) > 0
           and fu.get("pallas_calls_in_train_step")
           == fu.get("fusable_tensors"),
           "committed record: kernel launches != fusable tensors")

    # live oracles — re-proven on this machine, every run
    from deeplearning4j_tpu.nn import helpers as _helpers
    from deeplearning4j_tpu.observe import (Tracer, disable_tracing,
                                            enable_tracing)

    net_a, ds, _ = _scaling_net(seed=3, width=64)
    net_b, _, _ = _scaling_net(seed=3, width=64)
    _helpers.clear_helper("updater")
    try:
        for _ in range(3):
            net_a._fit_batch(ds)
        pallas0, _ = _pallas_call_counts(net_a, ds)
        expect(pallas0 == 0,
               f"live: {pallas0} pallas_call(s) with the updater seam clear")
        _helpers.set_helper("updater", _updater_helper())
        for _ in range(3):
            net_b._fit_batch(ds)
        diff = _max_param_diff(net_a, net_b)
        expect(diff <= 2e-5,
               f"live: fused diverged from stock by {diff:.2e} > 2e-5")
        pallas, fusable = _pallas_call_counts(net_b, ds)
        expect(fusable > 0 and pallas == fusable,
               f"live: {pallas} pallas_call(s) for {fusable} fusable tensors")
        tracer = enable_tracing(Tracer())
        try:
            for _ in range(3):
                net_b._fit_batch(ds)
            float(net_b.score_)
            live_compiles = tracer.thread_compile_count()
            expect(live_compiles == 0,
                   f"live: {live_compiles} steady-state compile(s) on the "
                   f"fused path")
        finally:
            disable_tracing()
    finally:
        _helpers.clear_helper("updater")

    if errors:
        for e in errors:
            print(f"train-pipeline check FAILED: {e}", file=sys.stderr)
        raise SystemExit(1)
    print(f"train-pipeline check OK: {path} (prefetch speedup "
          f"{rec.get('prefetch_speedup')}x committed; fused updater agrees "
          f"live, one kernel per tensor, zero steady-state compiles)")


def main():
    record = _with_trace("resnet50_headline", _resnet50_headline)
    if os.environ.get("DL4J_TPU_BENCH_HEADLINE_ONLY") != "1":
        suite = {}
        for name, fn in SUITE.items():
            try:
                suite[name] = _with_trace(name, fn)
            except Exception as e:  # noqa: BLE001 - isolate per-config failures
                suite[name] = {"error": f"{type(e).__name__}: {e}"}
        record["suite"] = suite
    print(json.dumps(record))
    failed = [n for n, r in record.get("suite", {}).items() if "error" in r]
    if failed:
        print(f"bench: suite entries failed: {', '.join(failed)}",
              file=sys.stderr)
        raise SystemExit(1)


def _parse_train_args():
    """(--train-pipeline present, out path or None, --check path or None);
    (False, None, None) when the flag is absent. Unknown flags pass
    through, mirroring _parse_pod_args."""
    if "--train-pipeline" not in sys.argv[1:]:
        return False, None, None
    import argparse
    ap = argparse.ArgumentParser("bench --train-pipeline", add_help=False)
    ap.add_argument("--train-pipeline", nargs="?", default=None,
                    metavar="OUT.json", dest="out")
    ap.add_argument("--check", default=None, metavar="COMMITTED.json")
    args, _unknown = ap.parse_known_args(sys.argv[1:])
    return True, args.out, args.check


def _parse_sharding_args():
    """(--sharding-2d present, out path or None, --check path or None);
    (False, None, None) when the flag is absent."""
    if "--sharding-2d" not in sys.argv[1:]:
        return False, None, None
    import argparse
    ap = argparse.ArgumentParser("bench --sharding-2d", add_help=False)
    ap.add_argument("--sharding-2d", nargs="?", default=None,
                    metavar="OUT.json", dest="out")
    ap.add_argument("--check", default=None, metavar="COMMITTED.json")
    args, _unknown = ap.parse_known_args(sys.argv[1:])
    return True, args.out, args.check


def _parse_pod_args():
    """(--pod-scaling out_path_or_None, --save-mode or None); returns
    (False, None, None) when --pod-scaling is absent. Unknown flags
    (--trace etc.) belong to the headline path and pass through."""
    if "--pod-scaling" not in sys.argv[1:]:
        return False, None, None
    import argparse
    ap = argparse.ArgumentParser("bench --pod-scaling", add_help=False)
    ap.add_argument("--pod-scaling", nargs="?", default=None,
                    metavar="OUT.json", dest="out")
    ap.add_argument("--save-mode", choices=("sync", "async"),
                    default=None, dest="mode")
    args, _unknown = ap.parse_known_args(sys.argv[1:])
    return True, args.out, args.mode


def _enable_compile_cache():
    # imported here, not at the top: --sharding-2d must set its platform
    # before anything imports jax
    from deeplearning4j_tpu.util.compile_cache import (
        enable_persistent_compile_cache)
    enable_persistent_compile_cache()


if __name__ == "__main__":
    train, _train_out, _train_check_path = _parse_train_args()
    if train:
        _enable_compile_cache()
        if _train_check_path:
            _train_check(_train_check_path)
        else:
            _train_pipeline_main(_train_out)
        raise SystemExit(0)
    pod, _pod_out, _pod_mode = _parse_pod_args()
    if pod:
        _enable_compile_cache()
        _pod_scaling_main(_pod_out, _pod_mode)
        raise SystemExit(0)
    sh2d, _sh_out, _sh_check = _parse_sharding_args()
    if sh2d:
        _force_cpu_mesh()  # BEFORE the first jax import
        _enable_compile_cache()
        if _sh_check:
            _sharding_2d_check(_sh_check)
        else:
            _sharding_2d_main(_sh_out)
        raise SystemExit(0)
    _enable_compile_cache()
    main()
