"""`train_fit` with ids drawn from the whole vocabulary, for a model whose
router must see many distinct inputs.

Everything but the ids is `train_fit`'s and is imported from it: the window,
the listener, the profile, the batch source and the checks; a mix's
parameters are those it documents, and one more, `restart_every`. `drive`
is its own because `train_fit.drive` is the one place that names the
planted rule, and differs from it in three things: the rule, the family's
`counters()` added to `run.counters` where the family has them, and the
check `no_token_dropped`.

**Why other ids.** `harness/planted_tokens.py` draws from 64 ids, each
always followed by the same one. Before the first attention layer a token's
hidden state is then a function of its id alone, so a router sees some tens
of distinct inputs and the rows that land on the experts held here swing by
a seventh from seed to seed. `WideIds` keeps the interface
(`sequences(n, length, seed)`) and the learnable rule, over every id: a
seeded permutation of the vocabulary is the successor, and a sequence
starts again at a uniform id every `restart_every` positions, so one
sequence of 8,192 walks 128 stretches of the permutation.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import devices as devices_mod
from benchmarks.traffic_kinds import train_fit


class WideIds:
    def __init__(self, vocab: int, seed: int, restart_every: int):
        self.vocab, self.restart_every = vocab, int(restart_every)
        self.successor = np.random.default_rng(seed).permutation(vocab)

    def sequences(self, n: int, length: int, seed: int) -> np.ndarray:
        """`[n, length]` int32 ids; every id is followed by its successor,
        but where a stretch of `restart_every` ends."""
        rng = np.random.default_rng(seed)
        every = self.restart_every
        starts = rng.integers(0, self.vocab, size=(n, -(-length // every)))
        ids = np.empty((n, length), np.int64)
        for t in range(length):
            ids[:, t] = (starts[:, t // every] if t % every == 0
                         else self.successor[ids[:, t - 1]])
        return ids.astype(np.int32)

    def follows_rule(self, tokens: np.ndarray) -> bool:
        inside = np.arange(1, tokens.shape[1]) % self.restart_every != 0
        return bool(np.array_equal(
            self.successor[tokens[:, :-1]][:, inside], tokens[:, 1:][:, inside]))


def check_no_token_dropped(run, counters: dict) -> None:
    """Every (token, expert) pair of the last step was either computed by a
    held expert or belongs to one that is not held, in every expert layer."""
    mix, config = run.cell.traffic, run.cell.config
    pairs = (int(mix["batch"]) * int(mix["seq_len"])
             * int(config["num_experts_per_tok"]))
    got = {name: sum(rows) + counters["rows_elsewhere"][name]
           for name, rows in counters["expert_rows"].items()}
    run.checks["no_token_dropped"] = (
        bool(got) and all(n == pairs for n in got.values()),
        f"pairs computed + pairs elsewhere by expert layer {got}, "
        f"tokens x experts per token {pairs}")


def check_step_matches_reference(run, model, placed) -> None:
    """A mean loss over thousands of tokens hardly moves with the precision
    of the step (PERF.md §6, PR 27), so the step itself is compared: what
    one more step of `fit()`, on the batch `placed`, changes the first
    moments of the small parameters by (the step's own gradient, nothing
    else) against what the float32 reference's step changes them by."""
    limit = run.cell.config["reference_tolerance"]["step_change"]
    reading = model.step_change_error(placed)
    run.checks["step_matches_reference"] = (
        bool(reading <= limit),
        f"the change of the small parameters' first moments in one more "
        f"step differs from the float32 reference's by {reading:.5f} of its "
        f"norm (limit {limit}; a state left unchanged reads 1)")
    run.counters["step_change_error"] = reading


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
    rule = WideIds(cell.config["vocab_size"], run.seed, mix["restart_every"])
    window = train_fit._Window(run, mix)
    next_batch, placed_example, build_s = train_fit._batch_source(
        model, rule, mix, run.seed, window)
    profile = train_fit._Profile(run)
    net.listeners.append(train_fit._Listener(window, profile))
    allocator_peaks = devices_mod.allocator_peaks(run.devices)
    t_fit = time.perf_counter()
    try:
        net.fit(train_fit._Batches(window, next_batch), epochs=1,
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
    # the last step's counts, before a check runs the model again
    counters = model.counters() if hasattr(model, "counters") else None
    train_fit._check(run, model, rule, losses, window.t0, t1)
    if counters is not None:
        check_no_token_dropped(run, counters)
        run.counters.update(counters)
    if hasattr(model, "step_change_error"):
        # on ids the model has not seen: the resident batch is learnt by
        # heart by now, and a vanishing gradient is mostly its own rounding
        fresh = rule.sequences(int(mix["batch"]), int(mix["seq_len"]),
                               run.seed + 3)
        check_step_matches_reference(
            run, model, model.resident(model.make_batch(fresh)))

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
