"""TransformerLM (causal decoder) — causality, KV-cache decoding, training,
generation.

Reference seam: the zoo's text-generation model
(``deeplearning4j-zoo/.../zoo/model/TextGenerationLSTM.java``) and stateful
inference (``MultiLayerNetwork.rnnTimeStep:2800``); the attention-era decoder
has no reference counterpart (the snapshot predates attention, SURVEY.md §5).
The KV-cache path must match the full quadratic forward exactly — the same
"same-math equivalence" bar the reference applies to its cuDNN helpers
(``deeplearning4j-cuda/src/test/.../ValidateCudnnLSTM.java``).
"""

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.zoo.models import TransformerLM, generate, lm_labels

VOCAB = 11


def tiny_lm(**kw):
    args = dict(vocab_size=VOCAB, max_length=16, n_layers=2, d_model=32,
                n_heads=4, d_ff=64, seed=7)
    args.update(kw)
    net = ComputationGraph(TransformerLM(**args).conf())
    net.init()
    return net


def cycle_batch(rng, n, t, step=3):
    """Sequences following a fixed successor rule: x[t+1] = (x[t]+step) % V —
    a next-token task a 2-layer decoder learns quickly."""
    start = rng.integers(0, VOCAB, size=(n, 1))
    seq = (start + step * np.arange(t)[None, :]) % VOCAB
    return seq.astype(np.float32)


class TestCausality:
    def test_future_tokens_do_not_change_past_outputs(self):
        net = tiny_lm()
        ids = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.float32)
        full = np.asarray(net.output(ids))
        ids2 = ids.copy()
        ids2[0, -1] = 9
        full2 = np.asarray(net.output(ids2))
        np.testing.assert_allclose(full[:, :-1], full2[:, :-1], atol=1e-6)
        assert np.abs(full[:, -1] - full2[:, -1]).max() > 1e-6

    def test_padding_mask_matches_short_batch(self):
        net = tiny_lm()
        rng = np.random.default_rng(0)
        short = rng.integers(0, VOCAB, size=(3, 5)).astype(np.float32)
        pad = np.zeros((3, 8), np.float32)
        pad[:, :5] = short
        mask = np.zeros((3, 8), np.float32)
        mask[:, :5] = 1.0
        out_short = np.asarray(net.output(short))
        out_pad = np.asarray(net.output(pad, masks=[mask]))
        np.testing.assert_allclose(out_pad[:, :5], out_short, atol=1e-5)


class TestKVCache:
    def test_single_token_steps_equal_full_forward(self):
        net = tiny_lm()
        ids = np.array([[1, 2, 3, 4, 5, 6, 7, 8],
                        [8, 7, 6, 5, 4, 3, 2, 1]], np.float32)
        full = np.asarray(net.output(ids))
        net.rnn_clear_previous_state()
        steps = [np.asarray(net.rnn_time_step(ids[:, t:t + 1, None]))[:, 0]
                 for t in range(ids.shape[1])]
        np.testing.assert_allclose(np.stack(steps, 1), full, atol=1e-5)

    def test_prompt_chunk_then_single_steps(self):
        net = tiny_lm()
        ids = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.float32)
        full = np.asarray(net.output(ids))
        net.rnn_clear_previous_state()
        chunk = np.asarray(net.rnn_time_step(ids[:, :5, None]))
        np.testing.assert_allclose(chunk, full[:, :5], atol=1e-5)
        for t in range(5, 8):
            o = np.asarray(net.rnn_time_step(ids[:, t:t + 1, None]))
            np.testing.assert_allclose(o[:, 0], full[:, t], atol=1e-5)

    def test_clear_state_resets_positions(self):
        net = tiny_lm()
        ids = np.array([[1, 2, 3]], np.float32)
        net.rnn_clear_previous_state()
        a = np.asarray(net.rnn_time_step(ids[:, :, None]))
        net.rnn_clear_previous_state()
        b = np.asarray(net.rnn_time_step(ids[:, :, None]))
        np.testing.assert_allclose(a, b, atol=1e-6)


class TestTraining:
    def test_learns_successor_rule_and_generates_it(self):
        net = tiny_lm(seed=3)
        rng = np.random.default_rng(0)
        x = cycle_batch(rng, 64, 16)
        y = lm_labels(x, VOCAB)
        lmask = np.ones(x.shape[:2], np.float32)
        lmask[:, -1] = 0.0  # last step has no next token
        ds = DataSet(x, y, labels_mask=lmask)
        s0 = net.score(ds)
        for _ in range(150):
            net.fit(ds)
        assert net.score_ < s0 * 0.2, (s0, net.score_)
        # greedy generation continues the +3 cycle
        prompt = cycle_batch(np.random.default_rng(1), 2, 6)
        gen = generate(net, prompt, 6)
        want = (prompt[:, -1:] + 3 * np.arange(1, 7)[None, :]) % VOCAB
        assert (gen == want).mean() > 0.9, (gen, want)

    def test_lm_labels_shift(self):
        ids = np.array([[0, 1, 2, 3]])
        lab = lm_labels(ids, 5)
        assert lab.shape == (1, 4) and lab.dtype == np.int32
        assert lab[0, 0] == 1 and lab[0, 2] == 3
        assert lab[0, 3] == 3  # final step repeats last id

    def test_class_id_labels_score_like_one_hot_rows(self):
        net = tiny_lm()
        x = cycle_batch(np.random.default_rng(2), 4, 16)
        ids = lm_labels(x, VOCAB)
        rows = np.eye(VOCAB, dtype=np.float32)[ids]
        lmask = np.ones(x.shape[:2], np.float32)
        lmask[:, -1] = 0.0
        np.testing.assert_allclose(
            net.score(DataSet(x, ids, labels_mask=lmask)),
            net.score(DataSet(x, rows, labels_mask=lmask)), rtol=1e-6)
        np.testing.assert_allclose(net.score_examples(DataSet(x, ids)),
                                   net.score_examples(DataSet(x, rows)),
                                   rtol=1e-5)

    def test_evaluate_counts_next_token_hits_from_ids(self):
        net = tiny_lm()
        x = cycle_batch(np.random.default_rng(3), 4, 16)
        lmask = np.ones(x.shape[:2], np.float32)
        lmask[:, -1] = 0.0
        ev = net.evaluate([DataSet(x, lm_labels(x, VOCAB),
                                   labels_mask=lmask)])
        assert ev.confusion.sum() == 4 * 15
        hits = (np.asarray(net.output(x)).argmax(-1)[:, :-1]
                == x[:, 1:]).sum()
        assert np.trace(ev.confusion) == hits


class TestGuards:
    def test_kv_cache_overflow_raises(self):
        net = tiny_lm()  # max_length 16
        net.rnn_clear_previous_state()
        ids = np.ones((1, 10, 1), np.float32)
        net.rnn_time_step(ids)
        with np.testing.assert_raises(ValueError):
            net.rnn_time_step(ids)  # 10 + 10 > 16

    def test_generate_capacity_check(self):
        net = tiny_lm()
        with np.testing.assert_raises(ValueError):
            generate(net, np.ones((1, 10)), 10)  # needs 19 > 16 slots
        # exactly at capacity is fine: 10 + 7 - 1 == 16
        generate(net, np.ones((1, 10)), 7)

    def test_num_labels_is_vocab_size(self):
        from deeplearning4j_tpu.zoo.zoo_model import ModelSelector
        m = ModelSelector.select("transformerlm", num_labels=40)
        assert m.vocab_size == 40 and m.num_labels == 40

    def test_causal_helper_flag_respected(self):
        # a causal=True seq-parallel helper must refuse non-causal requests
        # and take causal ones (and vice versa) — outputs never change
        import jax
        from deeplearning4j_tpu.parallel.ring import (
            SequenceParallelAttentionHelper)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
        h = SequenceParallelAttentionHelper(mesh, axis_name="sp", causal=True)
        q_shape = (2, 4, 8, 16)
        assert h.supports(None, q_shape, None, False, causal=True)
        assert not h.supports(None, q_shape, None, False, causal=False)
        h2 = SequenceParallelAttentionHelper(mesh, axis_name="sp")
        assert h2.supports(None, q_shape, None, False)
        assert not h2.supports(None, q_shape, None, False, causal=True)


class TestGenerate:
    def test_temperature_sampling_in_vocab(self):
        net = tiny_lm()
        gen = generate(net, np.array([[1, 2, 3]]), 4, temperature=1.0, seed=5)
        assert gen.shape == (1, 4)
        assert ((gen >= 0) & (gen < VOCAB)).all()

    def test_device_loop_matches_host_greedy(self):
        # the single-dispatch lax.scan decode must equal the host loop
        # token for token under greedy sampling
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
        host = generate(net, prompt, 8)
        dev = generate_on_device(net, prompt, 8)
        assert (host == dev).all()

    def test_device_loop_sampling_and_edges(self):
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[1, 2, 3]])
        s = generate_on_device(net, prompt, 5, temperature=1.0, seed=3)
        assert s.shape == (1, 5) and ((s >= 0) & (s < VOCAB)).all()
        assert generate_on_device(net, prompt, 0).shape == (1, 0)
        with np.testing.assert_raises(ValueError):
            generate_on_device(net, np.ones((1, 10)), 10)  # > capacity

    def test_selector_has_transformer_lm(self):
        from deeplearning4j_tpu.zoo.zoo_model import ModelSelector
        assert "transformerlm" in ModelSelector.available()


class TestTBPTTCapacity:
    def test_tbptt_overflow_rejected_before_jit(self):
        # jitted TBPTT steps cannot raise on KV-cache overflow; the host
        # loop must reject overlong sequences upfront
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers import (
            CausalSelfAttentionLayer, RnnOutputLayer)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(CausalSelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                                max_cache=8))
                .layer(RnnOutputLayer(n_out=4, loss="mcxent",
                                      activation="softmax"))
                .backprop_type("tbptt").t_bptt_length(4)
                .set_input_type(InputType.recurrent(8, 16))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.default_rng(0).normal(size=(2, 16, 8)).astype(np.float32)
        y = np.zeros((2, 16, 4), np.float32)
        y[..., 0] = 1
        with np.testing.assert_raises(ValueError):
            net.fit(x, y)

    def test_device_loop_temperature_not_cached_across_values(self):
        # each temperature must compile its own sampler (the value is baked
        # into the closure, so it must be part of the cache key)
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[1, 2, 3]])
        generate_on_device(net, prompt, 4, temperature=0.5, seed=1)
        generate_on_device(net, prompt, 4, temperature=2.0, seed=1)
        keys = [k for k in net._jit_cache if k and k[0] == "generate"]
        assert len(set(keys)) == 2


class TestBeamSearch:
    """Device-side beam search: beams ride the batch axis, carries are
    re-indexed per step; one compiled dispatch for the whole search."""

    def _trained(self):
        net = tiny_lm(seed=3)
        rng = np.random.default_rng(0)
        x = cycle_batch(rng, 64, 16)
        y = lm_labels(x, VOCAB)
        lmask = np.ones(x.shape[:2], np.float32)
        lmask[:, -1] = 0.0
        ds = DataSet(x, y, labels_mask=lmask)
        for _ in range(150):
            net.fit(ds)
        return net

    def test_beam_one_equals_greedy(self):
        from deeplearning4j_tpu.zoo.models import (beam_search,
                                                   generate_on_device)
        net = tiny_lm()
        prompt = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
        greedy = generate_on_device(net, prompt, 6)
        toks, scores = beam_search(net, prompt, 6, beam_size=1)
        assert (toks == greedy).all()
        assert scores.shape == (2,) and np.isfinite(scores).all()

    def test_beam_finds_the_learned_sequence(self):
        from deeplearning4j_tpu.zoo.models import beam_search
        net = self._trained()
        prompt = cycle_batch(np.random.default_rng(1), 2, 6)
        toks, scores = beam_search(net, prompt, 6, beam_size=4)
        want = (prompt[:, -1:] + 3 * np.arange(1, 7)[None, :]) % VOCAB
        assert (toks == want).all(), (toks, want)
        # wider beam can only match or improve the greedy path's score
        t1, s1 = beam_search(net, prompt, 6, beam_size=1)
        assert (scores >= s1 - 1e-5).all()

    def test_eos_freezes_finished_beams(self):
        from deeplearning4j_tpu.zoo.models import beam_search
        net = self._trained()
        prompt = cycle_batch(np.random.default_rng(1), 1, 6)
        want = (prompt[:, -1:] + 3 * np.arange(1, 7)[None, :]) % VOCAB
        eos = int(want[0, 1])                 # hit at step 1
        toks, _ = beam_search(net, prompt, 6, beam_size=3, eos_id=eos)
        assert toks[0, 1] == eos
        assert (toks[0, 2:] == eos).all()     # frozen: eos repeats at 0 cost

    def test_capacity_and_empty(self):
        from deeplearning4j_tpu.zoo.models import beam_search
        net = tiny_lm()
        toks, scores = beam_search(net, np.array([[1, 2]]), 0)
        assert toks.shape == (1, 0)
        with np.testing.assert_raises(ValueError):
            beam_search(net, np.ones((1, 10)), 10)
        with np.testing.assert_raises_regex(ValueError, "length_penalty"):
            beam_search(net, np.array([[1, 2]]), 3, length_penalty=-0.5)

    def test_length_penalty_normalizes_scores(self):
        # with no EOS every beam has full length L, so alpha=1.0 must
        # return exactly rawscore/L for the same winning beam
        from deeplearning4j_tpu.zoo.models import beam_search
        net = self._trained()
        prompt = cycle_batch(np.random.default_rng(1), 2, 6)
        toks_raw, s_raw = beam_search(net, prompt, 6, beam_size=3)
        toks_n, s_n = beam_search(net, prompt, 6, beam_size=3,
                                  length_penalty=1.0)
        assert (toks_raw == toks_n).all()
        np.testing.assert_allclose(s_n, s_raw / 6.0, rtol=1e-5)

    def test_length_penalty_counts_tokens_to_eos(self):
        # an early-EOS beam's frozen raw sum is divided by its true short
        # length, not the full horizon
        from deeplearning4j_tpu.zoo.models import beam_search
        net = self._trained()
        prompt = cycle_batch(np.random.default_rng(1), 1, 6)
        want = (prompt[:, -1:] + 3 * np.arange(1, 7)[None, :]) % VOCAB
        eos = int(want[0, 1])                 # hit at step 1 → length 2
        toks, s_n = beam_search(net, prompt, 6, beam_size=1, eos_id=eos,
                                length_penalty=1.0)
        _, s_raw = beam_search(net, prompt, 6, beam_size=1, eos_id=eos)
        assert toks[0, 1] == eos
        np.testing.assert_allclose(s_n, s_raw / 2.0, rtol=1e-5)

    def test_graph_only_paths_reject_mln_clearly(self):
        from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.zoo.models import (beam_search,
                                                   generate_on_device)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = (NeuralNetConfiguration.builder().seed(0).list()
                .layer(DenseLayer(n_in=4, n_out=8))
                .layer(OutputLayer(n_in=8, n_out=4, activation="softmax",
                                   loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        with np.testing.assert_raises_regex(TypeError, "ComputationGraph"):
            generate_on_device(net, np.array([[1, 2]]), 3)
        with np.testing.assert_raises_regex(TypeError, "ComputationGraph"):
            beam_search(net, np.array([[1, 2]]), 3)


class TestTopKTopP:
    def test_top_k_one_is_greedy(self):
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[1, 2, 3]])
        greedy = generate_on_device(net, prompt, 6)
        k1 = generate_on_device(net, prompt, 6, temperature=1.0, top_k=1,
                                seed=9)
        assert (k1 == greedy).all()

    def test_top_k_restricts_support(self):
        # with top_k=2, every sampled token must be one of the 2 most
        # likely continuations of its actual prefix — verify step by step
        # against the stateful model
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[1, 2, 3]])
        toks = generate_on_device(net, prompt, 5, temperature=1.0, top_k=2,
                                  seed=4)[0]
        net.rnn_clear_previous_state()
        probs = np.asarray(net.rnn_time_step(prompt[:, :, None].astype(np.float32)))
        for t in range(5):
            top2 = np.argsort(probs[0, -1])[-2:]
            assert toks[t] in top2, (t, toks[t], top2)
            probs = np.asarray(net.rnn_time_step(
                np.array([[toks[t]]])[:, :, None].astype(np.float32)))

    def test_top_p_tiny_nucleus_is_greedy(self):
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        prompt = np.array([[4, 5, 6]])
        greedy = generate_on_device(net, prompt, 6)
        p_small = generate_on_device(net, prompt, 6, temperature=1.0,
                                     top_p=1e-6, seed=11)
        assert (p_small == greedy).all()  # nucleus always keeps >= 1 token

    def test_top_p_samples_in_vocab(self):
        from deeplearning4j_tpu.zoo.models import generate_on_device
        net = tiny_lm()
        s = generate_on_device(net, np.array([[1, 2]]), 5, temperature=1.2,
                               top_p=0.9, top_k=5, seed=3)
        assert ((s >= 0) & (s < VOCAB)).all()
