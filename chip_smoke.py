#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path runs on the TPU.

One process drives, through the entry points a user calls and at the full
width of models the zoo ships:

- **kernels**  every Pallas kernel the program can reach, compiled by
               Mosaic (never the interpreter), against the stock XLA path
               computed on the same chip;
- **train**    zoo ``TransformerLM`` at its defaults (GPT-2-small, 12L / 768
               / 12H / 3072, vocabulary 50,257) at T=2048 in bf16 through
               ``ComputationGraph.fit()`` over an iterator, so the prefetch
               thread and the default-on causal flash gate are on the chip;
- **profile**  ``ProfilerListener`` over three of those steps, and the
               ``.xplane.pb`` read back with ``jax.profiler.ProfileData``;
- **serve**    zoo ``ResNet50`` behind ``ModelRegistry`` / ``ModelServer``
               on a loopback port, queried with ``ModelServingClient``;
- **fourchip** the same LM under 2x2 GSPMD rules and under 4-way
               ``ParallelWrapper``; ``not run: 1 device`` on one chip.

It fails loudly. The platform must be ``tpu`` (JAX falls back to the CPU
by itself when no chip answers; this script does not). No phase is wrapped
in a handler that records an error and goes on: the first failed check
raises, the exit code is non-zero and no result line is printed. On
success the last two lines of stdout are ``report `` followed by one JSON
object with the versions, every phase's result and the compile-cache
counts (the same object is written, with the profile trace, under
``chiprun_out/chip_smoke/``), and then the result line, which holds
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with the
device as JAX reports it.

Depth is the zoo default and weights are random from a seed. Times printed
here are set-up facts (how long a cold and a warm start take), not
benchmark results.

    python chip_smoke.py                  # everything
    python chip_smoke.py --phases train   # a subset, while debugging
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

PHASES = ("kernels", "train", "profile", "serve", "fourchip")

LM_T = 2048
# the forward kernel of PallasFlashAttentionHelper as a differentiated step
# names it (tests/test_chip_smoke.py holds the helper to this name)
FLASH_KERNEL_IN_STEP = "splash_mha_fwd_residuals"
# each batch carries a float32 one-hot label tensor [B, 2048, 50257], 1.65 GB
# at B=4, and fit() keeps up to four of them on the device (one in the step,
# two queued, one waiting to be queued)
LM_BATCH = 4
LM_STEPS = 6          # the first LM_WARMUP may compile, the rest may not
LM_WARMUP = 2
FOURCHIP_BATCH = 4    # must divide over data=2 and over data=4


class SmokeFailure(RuntimeError):
    """A check of this script did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# ----------------------------------------------------------------- device
def device_facts() -> dict:
    """Print what JAX found and refuse anything that is not a TPU."""
    import jax

    versions = {name: importlib.metadata.version(name)
                for name in ("jax", "jaxlib", "libtpu")}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("  ".join(f"{k} {v}" for k, v in versions.items()))
    say(f"platform={device['platform']}  device_kind={device['kind']!r}  "
        f"device_count={device['count']}")
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not 'tpu'; "
              f"this script proves nothing off the chip and stops here",
              file=sys.stderr, flush=True)
        raise SystemExit(3)
    return {"device": device, "versions": versions}


def result_line(device: dict) -> str:
    """The last line of stdout: these keys and no others."""
    return json.dumps({"ok": True,
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


class CompileMeter:
    """Persistent-cache hits and backend compile seconds, from
    ``jax.monitoring`` (a cache hit still reports a short compile event)."""

    def __init__(self):
        self.hits = self.misses = 0
        self.seconds = 0.0

    def install(self) -> "CompileMeter":
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# ---------------------------------------------------------------- kernels
def _close(name, got, want, rtol, atol) -> dict:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want)))
    ok = bool(np.allclose(got, want, rtol=rtol, atol=atol))
    say(f"  {name:<44} max|err| {err:.3e}  (rtol {rtol:g} atol {atol:g})  "
        f"{'ok' if ok else 'MISMATCH'}")
    return {"name": name, "max_abs_err": err, "ok": ok}


def _lstm_rows() -> list:
    """Fused LSTM at its gate shapes vs the lax.scan path."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.layers import LSTMLayer
    from deeplearning4j_tpu.nn.pallas_kernels import PallasLSTMHelper

    helper = PallasLSTMHelper()
    check(helper.interpret is False, "PallasLSTMHelper would interpret")
    rows = []
    n, t, c = 16, 256, 64
    rng = np.random.default_rng(0)
    # the stock path must not be served by the kernel it is the reference
    # for: on a TPU these shapes are inside the auto gate
    helpers.set_auto_fused_lstm(False)
    try:
        for h in (128, 256):
            layer = LSTMLayer(n_in=c, n_out=h)
            params32 = layer.init_params(jax.random.PRNGKey(h))
            x32 = jnp.asarray(rng.normal(size=(n, t, c)).astype(np.float32))
            w32 = jnp.asarray(rng.normal(size=(n, t, h)).astype(np.float32))
            # (dtype, matmul precision, rtol, atol): the f32 pair is held
            # to the CPU twin test's tolerance with real f32 matmuls on
            # both sides; at the chip's default precision and in bf16 the
            # two paths round differently at every one of the 256 steps
            for dt, precision, rtol, atol in (
                    (jnp.float32, "highest", 1e-4, 1e-5),
                    (jnp.float32, None, 2e-2, 2e-2),
                    (jnp.bfloat16, None, 5e-2, 5e-2)):
                params = jax.tree_util.tree_map(lambda a: a.astype(dt),
                                                params32)
                x, w = x32.astype(dt), w32.astype(dt)

                def fused(p, xx):
                    return helper.forward_seq(layer, p, xx, None)

                def stock(p, xx):
                    return layer.forward_seq(p, xx)

                def loss(fn):
                    return lambda p, xx: jnp.sum(
                        (fn(p, xx)[0] * w).astype(jnp.float32))

                with jax.default_matmul_precision(precision or "default"):
                    y_f, (hn_f, cn_f) = jax.jit(fused)(params, x)
                    y_s, (hn_s, cn_s) = jax.jit(stock)(params, x)
                    g_f = jax.jit(jax.grad(loss(fused)))(params, x)
                    g_s = jax.jit(jax.grad(loss(stock)))(params, x)
                tag = (f"lstm H={h} T={t} {jnp.dtype(dt).name}"
                       f"{' highest' if precision else ''}")
                rows.append(_close(f"{tag} ys", y_f, y_s, rtol, atol))
                rows.append(_close(f"{tag} c_T", cn_f, cn_s, rtol, atol))
                scale = float(np.max(np.abs(np.asarray(g_s["RW"],
                                                       np.float32))))
                rows.append(_close(f"{tag} dRW", g_f["RW"], g_s["RW"],
                                   rtol, atol * max(1.0, scale)))
    finally:
        helpers.set_auto_fused_lstm(True)
    return rows


def _lstm_default_gate() -> dict:
    """The case that failed at trace time before: a standard LSTM net at
    n_out=256, T=256 under bf16 compute, nothing registered — the auto gate
    must pick the kernel and the step must run."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import LSTMLayer, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(3).list()
            .layer(LSTMLayer(n_out=256))
            .layer(RnnOutputLayer(n_out=8))
            .set_input_type(InputType.recurrent(32, 256)).build())
    conf.global_conf.compute_dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(16, 256, 32)).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.integers(0, 8, size=(16, 256))]
    lowered = net._output_fn().lower(net.params, net.states,
                                     jnp.asarray(x), None).as_text()
    check("_lstm_kernel" in lowered,
          "auto gate did not select the fused LSTM kernel for "
          "LSTMLayer(n_out=256) at T=256 under bf16")
    net.fit(DataSet(x, y))
    loss = float(net.score_)
    check(np.isfinite(loss), f"bf16 LSTM fit step gave loss {loss}")
    say(f"  LSTMLayer(256) T=256 bf16 through fit(): kernel selected, "
        f"loss {loss:.4f}")
    return {"name": "lstm auto gate bf16 fit", "loss": loss, "ok": True}


def _updater_rows() -> list:
    """Fused Adam / Nadam / AMSGrad vs the stock per-op chain."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.pallas_kernels import PallasUpdaterHelper
    from deeplearning4j_tpu.nn.updaters import Adam, AMSGrad, Nadam

    helper = PallasUpdaterHelper()
    check(helper.interpret is False, "PallasUpdaterHelper would interpret")
    rows = []
    rng = np.random.default_rng(2)
    for shape in ((768, 3072), (130, 257)):
        p = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        g = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        for cls in (Adam, Nadam, AMSGrad):
            u = cls(1e-3)
            check(helper.supports(u, p, g),
                  f"updater helper declines {cls.__name__} {shape}")
            state = {"m": jnp.asarray(
                         rng.normal(scale=0.1, size=shape).astype(np.float32)),
                     "v": jnp.asarray(np.abs(
                         rng.normal(scale=1e-2, size=shape)
                     ).astype(np.float32))}
            if cls is AMSGrad:
                state["v_hat"] = state["v"] * 1.5

            def stock(p, g, state):
                upd, s = u.update(g, state, 1e-3, 3.0)
                return p - upd, s

            def fused(p, g, state):
                return helper.apply(u, p, g, state, 1e-3, 3.0)

            p_s, s_s = jax.jit(stock)(p, g, state)
            p_f, s_f = jax.jit(fused)(p, g, state)
            tag = f"updater {cls.__name__} {shape}"
            rows.append(_close(f"{tag} param", p_f, p_s, 2e-5, 2e-6))
            for k in s_s:
                rows.append(_close(f"{tag} {k}", s_f[k], s_s[k],
                                   2e-5, 2e-6))
    return rows


def _flash_rows() -> list:
    """Flash attention (the splash kernel bundled with jax, our block
    sizes) vs the einsum path, forward and grad. The helper's interpreter
    mode is an argument that only CPU tests pass, and ``supports()``
    refuses anything that is not a TPU: what runs here Mosaic compiled."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import helpers
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.nn.pallas_kernels import (
        PallasFlashAttentionHelper)

    rows = []
    shape = (2, 12, LM_T, 64)
    rng = np.random.default_rng(4)
    q32, k32, v32, w32 = (jnp.asarray(rng.normal(size=shape)
                                      .astype(np.float32)) for _ in range(4))
    # same reason as the LSTM: causal T=2048 is inside the auto-flash gate
    helpers.set_auto_flash_attention(False)
    try:
        for causal in (True, False):
            helper = PallasFlashAttentionHelper(causal=causal)
            check(helper.interpret is False,
                  "PallasFlashAttentionHelper would interpret")
            # f32 is held tight with real f32 matmuls on both sides. At the
            # chip's default precision an f32 matmul is a bf16 pass, and two
            # roundings of the scores differ by up to ~1e-2 in a causal row
            # that attends over a handful of keys (seen: 6e-3 f32, 1.6e-2
            # bf16), so those rows get the bf16 tolerance
            for dt, precision, rtol, atol in (
                    (jnp.float32, "highest", 1e-3, 1e-4),
                    (jnp.float32, None, 5e-2, 3e-2),
                    (jnp.bfloat16, None, 5e-2, 3e-2)):
                q, k, v, w = (a.astype(dt) for a in (q32, k32, v32, w32))
                check(helper.supports(None, q.shape, None, False,
                                      causal=causal),
                      f"flash helper declines {shape} causal={causal}")

                def stock(q, k, v):
                    return dot_product_attention(q, k, v, causal=causal)

                def loss(fn):
                    return lambda q, k, v: jnp.sum(
                        (fn(q, k, v) * w).astype(jnp.float32))

                with jax.default_matmul_precision(precision or "default"):
                    o_f = jax.jit(helper.attend)(q, k, v)
                    o_s = jax.jit(stock)(q, k, v)
                    g_f = jax.jit(jax.grad(loss(helper.attend),
                                           argnums=(0, 1, 2)))(q, k, v)
                    g_s = jax.jit(jax.grad(loss(stock),
                                           argnums=(0, 1, 2)))(q, k, v)
                tag = (f"flash causal={causal} T={LM_T} dh=64 "
                       f"{jnp.dtype(dt).name}"
                       f"{' highest' if precision else ''}")
                rows.append(_close(f"{tag} out", o_f, o_s, rtol, atol))
                for name, a, b in zip(("dq", "dk", "dv"), g_f, g_s):
                    scale = float(np.max(np.abs(np.asarray(b, np.float32))))
                    rows.append(_close(f"{tag} {name}", a, b, rtol,
                                       atol * max(1.0, scale)))
    finally:
        helpers.set_auto_flash_attention(True)
    return rows


def phase_kernels(ctx) -> dict:
    rows = _lstm_rows() + [_lstm_default_gate()] + _updater_rows() \
        + _flash_rows()
    bad = [r["name"] for r in rows if not r["ok"]]
    check(not bad, f"kernels disagree with the XLA path: {bad}")
    return {"compared": len(rows),
            "worst": max(rows, key=lambda r: r.get("max_abs_err", 0.0))}


# ------------------------------------------------------------------ train
class PlantedLMBatches:
    """Seeded token batches with a planted next-token rule: 64 ids spread
    over the whole vocabulary (among them 30521 and 50256, which bfloat16
    cannot hold), each followed always by the same other one. Ids are
    int32 — a float id would be cast to the compute dtype on the way in."""

    def __init__(self, vocab: int, batch: int, steps: int, seed: int = 11):
        self.vocab, self.batch, self.steps, self.seed = (vocab, batch,
                                                         steps, seed)
        rng = np.random.default_rng(seed)
        self.alphabet = np.unique(np.concatenate(
            [[30521, vocab - 1], rng.choice(vocab, size=62, replace=False)]))
        self.successor = rng.permutation(len(self.alphabet))

    def reset(self) -> None:
        pass

    def __iter__(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.zoo.models import lm_labels

        rng = np.random.default_rng(self.seed + 1)
        for _ in range(self.steps):
            idx = np.empty((self.batch, LM_T), np.int64)
            idx[:, 0] = rng.integers(0, len(self.alphabet), size=self.batch)
            for t in range(1, LM_T):
                idx[:, t] = self.successor[idx[:, t - 1]]
            tokens = self.alphabet[idx].astype(np.int32)
            yield DataSet(tokens, lm_labels(tokens, self.vocab))


def _build_lm():
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.models import TransformerLM

    model = TransformerLM(max_length=LM_T, seed=7)
    conf = model.conf()
    conf.global_conf.compute_dtype = "bfloat16"
    return ComputationGraph(conf).init(), model.vocab_size


class StepProbe:
    """TrainingListener: per step, the loss, the seconds since the last
    step with the device drained, and the tracer's compile count."""

    def __init__(self, tracer):
        self.tracer = tracer
        self.rows = []
        self._t = time.perf_counter()

    def iteration_done(self, model, iteration, epoch) -> None:
        import jax
        jax.block_until_ready(model.params)
        now = time.perf_counter()
        self.rows.append({"iteration": iteration,
                          "loss": float(model.score_),
                          "seconds": now - self._t,
                          "compiles": self.tracer.compile_count})
        self._t = now


def _flash_in_train_step(net, batch: int) -> bool:
    """Whether the lowered train step holds the causal flash kernel (the
    layers share one definition of it, so there is nothing to count)."""
    import jax
    import jax.numpy as jnp

    step = net._get_train_step()
    it, ep, rng = net._device_tick()
    tokens = jax.ShapeDtypeStruct((batch, LM_T), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, LM_T), jnp.int32)
    text = step.lower(net.params, net.states, net.updater_states, it, ep,
                      {"tokens": tokens}, [labels], None, None, rng).as_text()
    check("tpu_custom_call" in text, "train step holds no tpu_custom_call")
    return f'kernel_name = "{FLASH_KERNEL_IN_STEP}"' in text


def phase_train(ctx) -> dict:
    import jax

    net, vocab = _build_lm()
    ctx["lm"] = (net, vocab)
    probe = StepProbe(ctx["tracer"])
    net.listeners.append(probe)
    t0 = time.perf_counter()
    net.fit(PlantedLMBatches(vocab, LM_BATCH, LM_STEPS), epochs=1)
    net.listeners.remove(probe)
    for r in probe.rows:
        say(f"  step {r['iteration']}: loss {r['loss']:.4f}  "
            f"{r['seconds']:.2f} s  compiles so far {r['compiles']}")
    losses = [r["loss"] for r in probe.rows]
    check(len(losses) == LM_STEPS, f"fit() ran {len(losses)} steps, "
                                   f"expected {LM_STEPS}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    steady = probe.rows[LM_WARMUP - 1]["compiles"]
    check(probe.rows[-1]["compiles"] == steady,
          f"steps after warm-up compiled: {[r['compiles'] for r in probe.rows]}")
    check(_flash_in_train_step(net, LM_BATCH),
          "the causal flash gate did not open at T=2048: no "
          f"{FLASH_KERNEL_IN_STEP} in the lowered train step")
    stats = jax.devices()[0].memory_stats() or {}
    return {"model": "TransformerLM 12L/768/12H/3072 V=50257",
            "batch": LM_BATCH, "T": LM_T, "compute_dtype": "bfloat16",
            "losses": [round(v, 4) for v in losses],
            "flash_kernel_in_train_step": True,
            "compiles_in_warmup": steady,
            "info_first_step_seconds": round(probe.rows[0]["seconds"], 2),
            "info_steady_step_seconds": [round(r["seconds"], 3)
                                         for r in probe.rows[LM_WARMUP:]],
            "info_fit_seconds": round(time.perf_counter() - t0, 2),
            "info_peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---------------------------------------------------------------- profile
def phase_profile(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.optimize.listeners import ProfilerListener

    if "lm" not in ctx:
        ctx["lm"] = _build_lm()
    net, vocab = ctx["lm"]
    log_dir = os.path.join(OUT_DIR, "profile")
    prof = ProfilerListener(log_dir, start_iteration=net.iteration + 1,
                            n_iterations=3)
    net.listeners.append(prof)
    net.fit(PlantedLMBatches(vocab, LM_BATCH, 5, seed=23), epochs=1)
    prof.close()
    net.listeners.remove(prof)
    check(prof.last_error is None,
          f"ProfilerListener swallowed: {prof.last_error}")
    traces = [os.path.join(d, f) for d, _, files in os.walk(log_dir)
              for f in files if f.endswith(".xplane.pb")]
    check(traces, f"no .xplane.pb under {log_dir}")
    newest = max(traces, key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(newest)
    seen = {}
    for plane in data.planes:
        lines = {line.name: sum(1 for _ in line.events)
                 for line in plane.lines}
        seen[plane.name] = lines
        say(f"  plane {plane.name!r}: "
            + ", ".join(f"{n} ({c})" for n, c in lines.items()))
    device_events = sum(sum(lines.values()) for name, lines in seen.items()
                        if name.startswith("/device:TPU"))
    check(device_events > 0, f"no TPU device plane with events in "
                             f"{newest}; planes: {list(seen)}")
    return {"trace": os.path.relpath(newest, ROOT),
            "planes": {name: list(lines) for name, lines in seen.items()},
            "tpu_device_events": device_events}


# ------------------------------------------------------------------ serve
def phase_serve(ctx) -> dict:
    from deeplearning4j_tpu.serving import (MetricsRegistry, ModelRegistry,
                                            ModelServer, ModelServingClient)
    from deeplearning4j_tpu.zoo.models import ResNet50

    tracer = ctx["tracer"]
    net = ResNet50(num_labels=1000, seed=1).init()
    rng = np.random.default_rng(5)
    batches = {n: rng.normal(size=(n, 224, 224, 3)).astype(np.float32)
               for n in (1, 3, 8)}

    metrics = MetricsRegistry()
    registry = ModelRegistry(metrics=metrics, buckets=(1, 8), warmup="sync")
    t0 = time.perf_counter()
    registry.register("resnet50", net, input_shape=(224, 224, 3))
    warm_s = time.perf_counter() - t0
    server = ModelServer(registry, metrics=metrics)  # loopback, port 0
    port = server.start()
    client = ModelServingClient(server.url)
    try:
        compiles0 = tracer.compile_count
        answers = [(n, binary,
                    client.predict("resnet50", batches[n], binary=binary))
                   for n, binary in ((1, False), (3, False), (1, True),
                                     (3, True), (8, True), (8, True))]
        leaked = tracer.compile_count - compiles0
        check(leaked == 0, f"{leaked} compile(s) after warm-up")
        cold = sum(client.metrics().get(
            "inference_cold_dispatches_total", {}).values())
        check(cold == 0, f"inference_cold_dispatches_total = {cold}")
        # the reference comes after the window: net.output() shares the
        # jitted forward and would have warmed the buckets for the registry.
        # A random-weight ResNet50 answers near 1/1000 everywhere and rows
        # differ by ~2e-5, so the tolerance is relative only
        want = {n: np.asarray(net.output(x)) for n, x in batches.items()}
        for n, binary, got in answers:
            row = _close(f"serve {n} row(s) {'binary' if binary else 'json'}",
                         got, want[n], 1e-3, 1e-7)
            check(row["ok"], f"served output differs from net.output: {row}")
    finally:
        stopper = threading.Thread(
            target=lambda: server.stop(drain=True, shutdown_registry=True),
            daemon=True)
        stopper.start()
        stopper.join(timeout=60)
    check(not stopper.is_alive(), "server.stop(drain=True) did not return")
    return {"model": "ResNet50 224x224x3 -> 1000", "buckets": [1, 8],
            "port": port, "requests_answered": len(answers),
            "cold_dispatches": int(cold), "compiles_after_warmup": leaked,
            "info_warmup_seconds": round(warm_s, 2)}


# --------------------------------------------------------------- fourchip
def _forward_collectives(net, mesh, tokens) -> dict:
    """Collective counts in the compiled, partitioned forward, and how many
    Mosaic kernels it holds (the attention kernels, one a layer, each under
    the seam's ``shard_map``: nn/helpers.py ``kernel_shards``)."""
    import re

    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.sharding import place_batch
    x = place_batch(jnp.asarray(tokens), mesh)
    text = net._output_fn().lower(
        net.params, net.states, {"tokens": x}, None).compile().as_text()
    counts = {c: len(re.findall(r"\b%s\b" % c, text))
              for c in ("all-gather", "all-reduce", "reduce-scatter",
                        "collective-permute", "all-to-all")}
    counts["tpu_custom_call"] = text.count("tpu_custom_call")
    return counts


def _shard_devices(net) -> set:
    import jax
    return {d.id for leaf in jax.tree_util.tree_leaves(net.params)
            for d in leaf.sharding.device_set}


def phase_fourchip(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.parallel.sharding import shard_model_with_rules

    tracer = ctx["tracer"]
    ref_net, vocab = _build_lm()
    first = next(iter(PlantedLMBatches(vocab, FOURCHIP_BATCH, 1)))
    ref_loss = float(ref_net.score(first))  # one chip, initial weights
    del ref_net
    say(f"  one-chip loss at the initial weights: {ref_loss:.4f}")

    def run(net, fit):
        probe = StepProbe(tracer)
        net.listeners.append(probe)
        # two steps only: each prefetched batch parks 1.65 GB of one-hot
        # labels on device 0 before it is spread over the mesh
        fit(PlantedLMBatches(vocab, FOURCHIP_BATCH, 2))
        net.listeners.remove(probe)
        losses = [r["loss"] for r in probe.rows]
        check(len(losses) == 2 and all(np.isfinite(losses)),
              f"losses {losses}")
        check(abs(losses[0] - ref_loss) <= 2e-2 * abs(ref_loss),
              f"first-step loss {losses[0]} vs one chip {ref_loss}")
        return [round(v, 4) for v in losses]

    # -- 2x2 GSPMD, rule-placed
    mesh = make_mesh({"data": 2, "model": 2})
    layout = [[(d.id, getattr(d, "coords", None)) for d in row]
              for row in mesh.devices]
    say(f"  make_mesh(data=2, model=2) device (id, coords): {layout}")
    net, _ = _build_lm()
    shard_model_with_rules(net, mesh)
    placed = _shard_devices(net)
    check(len(placed) == 4, f"parameter shards sit on devices {placed}, "
                            f"not on four")
    ff1 = net.params["block0-ff1"]["W"]
    check(ff1.addressable_shards[0].data.shape[1] * 2 == ff1.shape[1],
          "block0-ff1/W is not column-sharded over the model axis")
    # information for the next PR: 50,257 is odd, so the rules cannot split
    # the embedding or the output weight over model=2 and replicate them
    vocab_specs = {n: str(net.params[n]["W"].sharding.spec)
                   for n in ("embed", "out")}
    say(f"  vocab-path placement at V={vocab}: {vocab_specs}")
    before = _forward_collectives(net, mesh, first.features)
    check(before["all-gather"] == 0 and before["all-reduce"] > 0,
          f"2x2 forward collectives before fit: {before}")
    gspmd_losses = run(net, lambda it: net.fit(it, epochs=1))
    after = _forward_collectives(net, mesh, first.features)
    check(after["all-gather"] == 0,
          f"2x2 forward collectives after fit: {after}")
    del net

    # -- 4-way data parallel
    net, _ = _build_lm()
    wrapper = ParallelWrapper(net, make_mesh({"data": 4}))
    check(len(_shard_devices(net)) == 4,
          "ParallelWrapper left the parameters off some device")
    dp_losses = run(net, lambda it: wrapper.fit(it, epochs=1))
    return {"one_chip_loss": round(ref_loss, 4),
            "mesh_2x2_devices": layout,
            "gspmd_2x2": {"losses": gspmd_losses,
                          "forward_collectives": after,
                          "vocab_path_specs": vocab_specs},
            "parallel_wrapper_dp4": {"losses": dp_losses},
            "devices_holding_shards": sorted(placed)}


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {','.join(PHASES)} "
                         f"(default: all)")
    args = ap.parse_args(argv)
    selected = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = [p for p in selected if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; choose from {PHASES}")

    t_start = time.perf_counter()
    facts = device_facts()
    import jax

    from deeplearning4j_tpu.native import native_available
    from deeplearning4j_tpu.observe import Tracer, enable_tracing
    from deeplearning4j_tpu.util.compile_cache import (
        ENV_VAR, enable_persistent_compile_cache)

    meter = CompileMeter().install()
    cache_dir = enable_persistent_compile_cache()
    say(f"compile cache: {cache_dir} "
        f"({ENV_VAR + ' set' if os.environ.get(ENV_VAR) else 'default path'}"
        f", {len(os.listdir(cache_dir))} entries at start)")
    say(f"native_available() = {native_available()}")
    os.makedirs(OUT_DIR, exist_ok=True)
    ctx = {"tracer": enable_tracing(Tracer())}

    runners = {"kernels": phase_kernels, "train": phase_train,
               "profile": phase_profile, "serve": phase_serve,
               "fourchip": phase_fourchip}
    phases = {}
    for name in PHASES:
        if name not in selected:
            phases[name] = {"result": "not run: not selected"}
            continue
        if name == "fourchip" and jax.device_count() < 4:
            phases[name] = {"result": f"not run: {jax.device_count()} device"}
            say(f"== {name}: not run: {jax.device_count()} device")
            continue
        say(f"== {name}")
        t0 = time.perf_counter()
        hits0, secs0 = meter.hits, meter.seconds
        detail = runners[name](ctx)
        phases[name] = {"result": "pass", **detail,
                        "info_seconds": round(time.perf_counter() - t0, 2),
                        "info_compile_seconds": round(meter.seconds - secs0, 2),
                        "cache_hits": meter.hits - hits0}
        say(f"== {name}: pass in {phases[name]['info_seconds']} s "
            f"(compile {phases[name]['info_compile_seconds']} s, "
            f"{phases[name]['cache_hits']} cache hits)")

    report = {
        "ok": True,
        **facts,
        "phases": phases,
        "compile": {"cache_dir": cache_dir, "cache_hits": meter.hits,
                    "cache_misses": meter.misses,
                    "info_compile_seconds": round(meter.seconds, 2)},
        "native_available": native_available(),
        "info_total_seconds": round(time.perf_counter() - t_start, 2),
    }
    say(f"compile cache hits {meter.hits}, misses {meter.misses}, "
        f"compile seconds {meter.seconds:.1f}")
    line = json.dumps(report)
    with open(os.path.join(OUT_DIR, "result.json"), "w",
              encoding="utf-8") as fh:
        fh.write(line + "\n")
    say("report " + line)
    say(result_line(facts["device"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
