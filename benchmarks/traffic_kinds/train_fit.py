"""Training through `net.fit(iterator, epochs=1)`, measured from outside.

`fit()` owns the loop, so the window is taken from the two places where it
calls out: the iterator it pulls batches from, and a listener it calls after
every step.

1. The iterator yields batches: the warm-up's, then more until a deadline.
2. The listener keeps each step's loss as a device scalar. At the last
   warm-up step it drains the device, takes `t0` and sets the deadline.
3. When `fit()` returns, the device is drained again and `t1` is taken.
   The window's steps are those dispatched after `t0`, and all of them have
   completed by `t1`.
4. Losses are read, and every check is made, after `t1`.

Inside the window the listener never drains the device. It does wait for the
loss of the step `IN_FLIGHT` steps back, which only bounds how far the host
may dispatch ahead (and with it how long the drain at the end can take); a
device with `IN_FLIGHT - 1` steps queued is never idle for it. It also notes
the time of every call: with the host held to `IN_FLIGHT` steps ahead, or
waiting for batches, the calls come at the pace at which steps complete
(`stall_share` reads them).

A traced run measures in the same way up to `trace_seconds` before the end
of its window, drains the device, and starts the profiler from another
thread, so that the loop (and the prefetch queue behind it) goes on as
before; the steps dispatched once the profiler is on are the traced slice.
At its end the device is drained, the profiler stopped, and only then the
iterator: no step of the slice ran off a queue that a pause had filled.

A mix's parameters (`benchmarks/traffic/<mix>.json`):

- `batches`: `resident` (one batch placed on the device in set-up and
  yielded again each step) or `stream` (every step a fresh batch built on
  the host by the family's `make_batch` from ids made in set-up);
- `batch`, `seq_len`: the step's shape; `pool_batches`: how many distinct
  batches of ids a stream has before it starts over;
- `prefetch_depth`: passed to `fit()` as it is (`null` is the default);
- `warmup_steps`: steps before `t0`; the first compiles;
- `trace_seconds`, `trace_steps`, `trace_skip_steps`: a traced run profiles
  the end of its window for at most so many seconds and steps, and its
  reduction leaves the first `trace_skip_steps` step events out (the
  profiler may come on in the middle of one);
- `check_sequences`: how many sequences the reference check uses.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import shutil
import threading
import time

import numpy as np

from benchmarks.harness import devices as devices_mod
from benchmarks.harness.planted_tokens import PlantedRule

IN_FLIGHT = 8


class _Window:
    """Shared by the iterator (which, behind a prefetch thread, runs ahead
    of the loop) and the listener (which runs in the loop)."""

    def __init__(self, run, mix: dict):
        self.run = run
        self.warmup_steps = int(mix["warmup_steps"])
        self.traced = run.trace
        slice_s = min(float(mix["trace_seconds"]), run.seconds / 2)
        self.slice_seconds = slice_s if run.trace else 0.0
        self.slice_steps = int(mix["trace_steps"]) + int(mix["trace_skip_steps"])
        self.measure_seconds = run.seconds - self.slice_seconds
        self.losses = []            # device scalars, warm-up included
        self.step_times = []        # t0, then each later call of the listener
        self.t0 = self.deadline = None
        self.t_first_step = None    # the first step is dispatched: it compiled
        self.at_t0 = {}             # counters as they stood at t0
        self.stop = False
        # traced runs only
        self.t_slice = None         # end of the untraced part, device drained
        self.steps_before_slice = 0
        self.slice_deadline = None
        self.steps_in_slice = 0
        self.spans = []

    def wants_batch(self) -> bool:
        if self.stop:
            return False
        if self.deadline is None or self.traced:
            return True             # warming up, or the listener says when
        return time.perf_counter() < self.deadline

    @contextlib.contextmanager
    def span(self, name: str):
        """A span of the benchmark's own: into the profiler's trace and
        onto `spans`, in a traced run; nothing otherwise."""
        if not self.traced:
            yield
            return
        import jax
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans.append((name, start, time.perf_counter()))


class _Listener:
    def __init__(self, window: _Window, profile: "_Profile"):
        self.w = window
        self.profile = profile

    def iteration_done(self, model, iteration, epoch) -> None:
        import jax
        w = self.w
        with w.span("iteration_done"):
            w.losses.append(model._score_arr)
            n = len(w.losses)
            if n == 1:
                w.t_first_step = time.perf_counter()
            if n == w.warmup_steps:
                jax.block_until_ready(model.params)
                meter = w.run.meter
                w.at_t0 = {"transfer_bytes": model.transfer_bytes,
                           "compile_s": meter.seconds(),
                           "cache_misses": meter.misses,
                           "cache_hits": meter.hits}
                w.t0 = time.perf_counter()
                w.step_times.append(w.t0)
                w.deadline = w.t0 + w.measure_seconds
                return
            if n < w.warmup_steps:
                return
            if n > IN_FLIGHT:
                w.losses[-IN_FLIGHT].block_until_ready()
            now = time.perf_counter()
            if w.t_slice is None:
                w.step_times.append(now)
            if not w.traced:
                return
            if w.t_slice is None:
                if now >= w.deadline:
                    jax.block_until_ready(model.params)
                    w.t_slice = time.perf_counter()
                    w.steps_before_slice = n - w.warmup_steps
                    self.profile.start_in_background()
                return
            if not self.profile.is_on():
                return              # still starting: this step is not traced
            if w.slice_deadline is None:
                w.slice_deadline = now + w.slice_seconds
            w.steps_in_slice += 1
            if w.steps_in_slice >= w.slice_steps or now >= w.slice_deadline:
                jax.block_until_ready(model.params)
                self.profile.stop()
                w.stop = True


class _Batches:
    """What `fit()` iterates: `next_batch()` for as long as the window
    wants one."""

    def __init__(self, window: _Window, next_batch):
        self.w = window
        self.next_batch = next_batch

    def reset(self) -> None:
        pass

    def __iter__(self):
        while self.w.wants_batch():
            yield self.next_batch()


class _Profile:
    """The profiler over the traced slice, with `bench.clock_sync` marks
    that tie the trace's clock to `perf_counter`."""

    def __init__(self, run):
        self.run = run
        self.dir = os.path.join(run.out_dir, "profile")
        self.thread = None
        self.on = False
        self.error = None

    def _sync(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation("bench.clock_sync"):
            self.run.clock_syncs.append(time.perf_counter())

    def _start(self) -> None:
        import jax
        try:
            shutil.rmtree(self.dir, ignore_errors=True)  # never a stale trace
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._sync()
            self.on = True
        except BaseException as e:  # raised again where the loop looks
            self.error = e

    def start_in_background(self) -> None:
        self.thread = threading.Thread(target=self._start, daemon=True)
        self.thread.start()

    def is_on(self) -> bool:
        if self.error is not None:
            raise self.error
        return self.on

    def stop(self) -> None:
        import jax
        if self.thread is None:
            return
        self.thread.join()
        self.thread = None
        if not self.is_on():
            return
        self._sync()
        jax.profiler.stop_trace()
        self.on = False
        found = [os.path.join(d, f) for d, _, files in os.walk(self.dir)
                 for f in files if f.endswith(".xplane.pb")]
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {self.dir}, "
                               f"found {found}")
        self.run.xplane_path = found[0]


def _batch_source(model, rule, mix: dict, seed: int, window: _Window):
    """`(next_batch, placed_example, build_s)`: what the iterator calls for
    each batch, a batch on the device(s) as `fit()` feeds the step (for
    lowering it), and where a stream notes `(start, seconds)` of each
    build."""
    batch, seq_len = int(mix["batch"]), int(mix["seq_len"])
    build_s = []
    if mix["batches"] == "resident":
        placed = model.resident(
            model.make_batch(rule.sequences(batch, seq_len, seed + 1)))
        return (lambda: placed), (lambda: placed), build_s
    if mix["batches"] != "stream":
        raise ValueError(f"batches: {mix['batches']!r} is neither "
                         f"'resident' nor 'stream'")
    pool = rule.sequences(int(mix["pool_batches"]) * batch, seq_len,
                          seed + 1).reshape(-1, batch, seq_len)
    turn = itertools.count()

    def next_batch():
        ids = pool[next(turn) % len(pool)]
        start = time.perf_counter()
        with window.span("make_batch"):
            ds = model.make_batch(ids)
        build_s.append((start, time.perf_counter() - start))
        return ds

    return (next_batch,
            lambda: model.resident(model.make_batch(pool[0])), build_s)


def _check(run, model, rule, losses: np.ndarray, t0: float, t1: float):
    """The run's correctness, all of it outside the window."""
    config, mix = run.cell.config, run.cell.traffic
    k = max(1, len(losses) // 10)
    first, last = float(np.mean(losses[:k])), float(np.mean(losses[-k:]))
    in_window = run.meter.between(t0, t1)
    held = model.devices_holding_params()
    run.checks["every_loss_finite"] = (run.failed == 0,
                                       f"{run.failed} of {len(losses)}")
    run.checks["loss_falls"] = (last < first, f"mean of the first tenth "
                                f"{first:.4f}, of the last {last:.4f}")
    run.checks["no_compile_in_window"] = (in_window == 0, f"{in_window}")
    run.checks["params_on_every_chip"] = (held == run.cell.chips,
                                          f"{held} of {run.cell.chips}")
    sample = rule.sequences(int(mix["check_sequences"]), int(mix["seq_len"]),
                            run.seed + 2)
    got, want = model.score(sample), model.reference(sample)
    tol = config["reference_tolerance"]
    run.checks["score_matches_reference"] = (
        bool(abs(got - want) <= tol["rtol"] * abs(want) + tol["atol"]),
        f"net.score {got:.6f}, float32 reference {want:.6f} on "
        f"{len(sample)} held-out sequences at the trained weights "
        f"(rtol {tol['rtol']}, atol {tol['atol']})")


def drive(run) -> None:
    import jax

    cell, mix = run.cell, run.cell.traffic
    tracer = None
    if run.trace:
        from deeplearning4j_tpu import observe
        tracer = observe.enable_tracing()

    t_drive = time.perf_counter()
    model = cell.family.Model(cell.config, run.seed, run.devices)
    t_model = time.perf_counter()
    net = model.net
    rule = PlantedRule(cell.config["vocab_size"], run.seed)
    window = _Window(run, mix)
    next_batch, placed_example, build_s = _batch_source(
        model, rule, mix, run.seed, window)
    profile = _Profile(run)
    net.listeners.append(_Listener(window, profile))
    allocator_peaks = devices_mod.allocator_peaks(run.devices)
    t_fit = time.perf_counter()
    try:
        net.fit(_Batches(window, next_batch), epochs=1,
                prefetch_depth=mix["prefetch_depth"])
        jax.block_until_ready(net.params)
        t1 = time.perf_counter()
    finally:
        profile.stop()
    if window.t0 is None:
        raise RuntimeError("fit() returned before the warm-up was over")
    if run.trace and run.xplane_path is None:
        raise RuntimeError("fit() returned before the traced slice was over")

    # ---- everything below is outside the window
    run.memory_peak_bytes = devices_mod.memory_peak_bytes(run.devices,
                                                          allocator_peaks)
    run.setup_s = window.t0 - run.t_start
    run.attempted = len(window.losses) - window.warmup_steps
    if run.trace:
        run.steps = window.steps_before_slice
        run.window_s = window.t_slice - window.t0
        run.trace_skip_steps = int(mix["trace_skip_steps"])
    else:
        run.steps = run.attempted
        run.window_s = t1 - window.t0
    run.items = run.steps * int(mix["batch"]) * int(mix["seq_len"])
    run.step_interval_s = np.diff(window.step_times).tolist()
    if run.steps < 2:
        raise RuntimeError(f"the window completed {run.steps} step(s); it "
                           f"needs some tens to say anything")
    losses = np.asarray(jax.device_get(window.losses),
                        np.float64)[window.warmup_steps:]
    run.failed = int(np.sum(~np.isfinite(losses)))
    _check(run, model, rule, losses, window.t0, t1)

    run.counters.update(
        setup_phases_s={
            "imports_and_devices": t_drive - run.t_start,
            "build_model": t_model - t_drive,
            "ids_and_resident_batch": t_fit - t_model,
            "first_step": window.t_first_step - t_fit,
            "rest_of_warmup": window.t0 - window.t_first_step},
        compile_s=window.at_t0["compile_s"],
        cache_misses=window.at_t0["cache_misses"],
        cache_hits=window.at_t0["cache_hits"],
        transfer_bytes_per_step=(
            (net.transfer_bytes - window.at_t0["transfer_bytes"])
            / run.attempted),
        batch_build_s=[d for start, d in build_s if start >= window.t0],
        first_loss=float(losses[0]), last_loss=float(losses[-1]),
        memory_stats=run.devices[0].memory_stats())
    if run.trace:
        run.spans = window.spans + [
            (s.name, s.start_ns / 1e9, s.end_ns / 1e9)
            for s in tracer.recorder.spans() if s.start_ns / 1e9 >= window.t0]
        run.step_text = model.compiled_step_text(placed_example())
