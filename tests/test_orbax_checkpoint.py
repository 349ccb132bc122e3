"""Orbax-backed checkpointing: save/restore round trips, async writes,
step-managed rotation.

The zip path (util/model_serializer.py) is the DL4J interchange; this is
the TPU-idiomatic path (sharding-aware orbax writes + CheckpointManager
retention, the CheckpointListener keepLast role at pod scale).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.util.orbax_checkpoint import (
    OrbaxCheckpointManager,
    restore_model,
    save_model,
)


def trained_net(steps=5, seed=1):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater("adam").list()
            .layer(DenseLayer(n_in=3, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=2))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 3).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)]
    for _ in range(steps):
        net.fit(x, y)
    return net, x, y


class TestSaveRestore:
    def test_round_trip_outputs_and_counters(self, tmp_path):
        net, x, _ = trained_net()
        d = str(tmp_path / "ckpt")
        save_model(net, d)
        again = restore_model(d)
        np.testing.assert_allclose(np.asarray(again.output(x)),
                                   np.asarray(net.output(x)), rtol=1e-6)
        assert again.iteration == net.iteration
        assert again.epoch == net.epoch

    def test_updater_state_resume_equality(self, tmp_path):
        """Training after restore == training without the save/restore."""
        net, x, y = trained_net()
        d = str(tmp_path / "ckpt")
        save_model(net, d)
        for _ in range(3):
            net.fit(x, y)
        resumed = restore_model(d)
        for _ in range(3):
            resumed.fit(x, y)
        for a, b in zip(net.params, resumed.params):
            for k in a:
                np.testing.assert_allclose(np.asarray(a[k]),
                                           np.asarray(b[k]), rtol=2e-5,
                                           atol=1e-6)

    def test_async_write(self, tmp_path):
        net, x, _ = trained_net()
        d = str(tmp_path / "async")
        handle = save_model(net, d, async_write=True)
        assert handle is not None
        handle.wait_until_finished()
        again = restore_model(d)
        np.testing.assert_allclose(np.asarray(again.output(x)),
                                   np.asarray(net.output(x)), rtol=1e-6)

    def test_updater_flag_mismatch_both_directions(self, tmp_path):
        """Checkpoint without updater restores with default flags and
        vice versa (template matches what is actually on disk)."""
        net, x, _ = trained_net()
        d1 = str(tmp_path / "no_updater")
        save_model(net, d1, save_updater=False)
        again = restore_model(d1)  # load_updater=True against a bare ckpt
        np.testing.assert_allclose(np.asarray(again.output(x)),
                                   np.asarray(net.output(x)), rtol=1e-6)
        d2 = str(tmp_path / "with_updater")
        save_model(net, d2, save_updater=True)
        bare = restore_model(d2, load_updater=False)
        np.testing.assert_allclose(np.asarray(bare.output(x)),
                                   np.asarray(net.output(x)), rtol=1e-6)

    def test_manager_updater_flag_mismatch(self, tmp_path):
        net, x, y = trained_net(steps=1)
        with OrbaxCheckpointManager(str(tmp_path / "m")) as mgr:
            assert mgr.save(0, net, save_updater=False)
            mgr.wait_until_finished()
            restored = mgr.restore()
            np.testing.assert_allclose(np.asarray(restored.output(x)),
                                       np.asarray(net.output(x)), rtol=1e-6)

    def test_graph_round_trip(self, tmp_path):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = (NeuralNetConfiguration.builder().seed(2).updater("sgd")
                .graph_builder().add_inputs("in")
                .add_layer("d", DenseLayer(n_in=3, n_out=4), "in")
                .add_layer("out", OutputLayer(n_in=4, n_out=2), "d")
                .set_outputs("out").build())
        g = ComputationGraph(conf).init()
        d = str(tmp_path / "g")
        save_model(g, d)
        again = restore_model(d)
        x = np.ones((2, 3), np.float32)
        np.testing.assert_allclose(np.asarray(again.output_single(x)),
                                   np.asarray(g.output_single(x)), rtol=1e-6)


    def test_padded_vocabulary_round_trip(self, tmp_path):
        """A net whose odd vocabulary the mesh padded is saved at the
        published shapes; restored without a mesh it has them, restored
        onto a mesh it is padded again, and both compute the same."""
        import jax
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sharding import (
            shard_model_with_rules)
        from deeplearning4j_tpu.zoo.models import TransformerLM, lm_labels
        vocab = 63
        lm = TransformerLM(vocab_size=vocab, max_length=8, n_layers=1,
                           d_model=16, n_heads=4, d_ff=32, seed=3)
        mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
        net = lm.init()
        shard_model_with_rules(net, mesh)
        toks = np.random.default_rng(0).integers(0, vocab, (4, 8))
        ds = DataSet(toks.astype(np.float32),
                     np.asarray(lm_labels(toks, vocab)))
        net.fit(ds)
        assert net.params["out"]["W"].shape == (16, 64)
        d = str(tmp_path / "lm")
        save_model(net, d)

        plain = restore_model(d)
        again = restore_model(d, mesh=mesh)
        for k, n in (("embed", "W"), ("out", "W"), ("out", "b")):
            want = lm.init().params[k][n].shape
            assert plain.params[k][n].shape == want
            assert all(s.shape == want
                       for s in plain.updater_states[k][n].values())
            padded = again.params[k][n].shape
            assert padded != want and 64 in padded
            assert all(s.shape == padded
                       for s in again.updater_states[k][n].values())
        want = np.asarray(net.output(ds.features))
        assert want.shape == (4, 8, vocab)
        for back in (plain, again):
            np.testing.assert_allclose(np.asarray(back.output(ds.features)),
                                       want, atol=1e-6)
        plain.fit(ds)
        again.fit(ds)
        assert float(again.score_) == pytest.approx(float(plain.score_),
                                                    abs=1e-5)


class TestManagerRotation:
    def test_keep_last_and_latest_restore(self, tmp_path):
        net, x, y = trained_net(steps=1)
        d = str(tmp_path / "mgr")
        with OrbaxCheckpointManager(d, max_to_keep=2) as mgr:
            for step in range(5):
                net.fit(x, y)
                assert mgr.save(step, net)
            mgr.wait_until_finished()
            assert mgr.latest_step() == 4
            assert len(mgr.all_steps()) == 2  # rotation kept last 2
            restored = mgr.restore()
            np.testing.assert_allclose(np.asarray(restored.output(x)),
                                       np.asarray(net.output(x)), rtol=1e-6)

    def test_save_interval(self, tmp_path):
        net, x, y = trained_net(steps=1)
        d = str(tmp_path / "mgr2")
        with OrbaxCheckpointManager(d, max_to_keep=None,
                                    save_interval_steps=2) as mgr:
            saved = [mgr.save(s, net) for s in range(4)]
            mgr.wait_until_finished()
            assert saved == [True, False, True, False]

    def test_restore_empty_raises(self, tmp_path):
        with OrbaxCheckpointManager(str(tmp_path / "empty")) as mgr:
            with pytest.raises(ValueError):
                mgr.restore()


class TestPreemptionOrbaxBackend:
    def test_orbax_backend_save_resume(self, tmp_path):
        from deeplearning4j_tpu.util.preemption import PreemptionHandler
        net, x, y = trained_net()
        d = str(tmp_path / "preempt_ckpt")
        handler = PreemptionHandler(net, d, backend="orbax")
        handler.save()
        model, state = PreemptionHandler.resume(d)
        assert state["iteration"] == net.iteration
        np.testing.assert_allclose(np.asarray(model.output(x)),
                                   np.asarray(net.output(x)), rtol=1e-6)

    def test_orbax_backend_second_save_keeps_previous(self, tmp_path):
        """Rotation means the earlier checkpoint is still on disk while
        (and after) the new one commits — the grace-window durability the
        zip path gets from tmp+os.replace."""
        import os
        from deeplearning4j_tpu.util.preemption import PreemptionHandler
        net, x, y = trained_net()
        d = str(tmp_path / "preempt2")
        handler = PreemptionHandler(net, d, backend="orbax")
        handler.save()
        net.fit(x, y)
        handler.save()
        steps = sorted(int(p) for p in os.listdir(d) if p.isdigit())
        assert len(steps) == 2  # both checkpoints retained (max_to_keep=2)
        model, state = PreemptionHandler.resume(d)
        assert state["iteration"] == net.iteration  # latest wins

    def test_bad_backend_rejected(self, tmp_path):
        from deeplearning4j_tpu.util.preemption import PreemptionHandler
        net, _, _ = trained_net(steps=1)
        with pytest.raises(ValueError):
            PreemptionHandler(net, str(tmp_path / "x"), backend="tape")


class TestSchemePaths:
    def test_url_paths_not_mangled(self):
        """gs:// checkpoint dirs must not be abspath'd into <cwd>/gs:/…
        (they route through etils epath instead)."""
        from deeplearning4j_tpu.util.orbax_checkpoint import _canonical_dir
        assert _canonical_dir("gs://bucket/ckpt") == "gs://bucket/ckpt"
        assert _canonical_dir("s3://bucket/ckpt") == "s3://bucket/ckpt"

    def test_local_paths_absolutized(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.util.orbax_checkpoint import _canonical_dir
        monkeypatch.chdir(tmp_path)
        assert _canonical_dir("ckpt") == str(tmp_path / "ckpt")
