"""Zoo model construction + forward/fit smoke tests.

Mirrors the reference's ``deeplearning4j-zoo/src/test/.../TestInstantiation.java``
(build every zoo model, forward a batch, fit a batch) at CPU-friendly sizes.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.zoo import (
    AlexNet, Darknet19, FaceNetNN4Small2, GoogLeNet, InceptionResNetV1,
    LeNet, ModelSelector, ResNet50, SimpleCNN, TextGenerationLSTM, TinyYOLO,
    VGG16, VGG19, YOLO2,
)


def _nhwc(shape_chw, batch=2):
    c, h, w = shape_chw
    return np.random.RandomState(0).rand(batch, h, w, c).astype(np.float32)


def _onehot(n, k, rng=0):
    r = np.random.RandomState(rng)
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), r.randint(0, k, n)] = 1
    return y


def _fit_and_forward(model, n_labels, batch=2):
    net = model.init()
    x = _nhwc(model.input_shape, batch)
    y = _onehot(batch, n_labels)
    out = net.output(x)
    out = out[0] if isinstance(out, list) else out
    assert out.shape == (batch, n_labels)
    assert np.allclose(np.asarray(out).sum(axis=-1), 1.0, atol=1e-4)
    net.fit(x, y, epochs=1)
    return net


class TestZooInstantiation:
    def test_lenet(self):
        _fit_and_forward(LeNet(num_labels=10, input_shape=(1, 28, 28)), 10)

    def test_simplecnn(self):
        _fit_and_forward(SimpleCNN(num_labels=5, input_shape=(3, 48, 48)), 5)

    def test_alexnet(self):
        _fit_and_forward(AlexNet(num_labels=7, input_shape=(3, 112, 112)), 7)

    def test_vgg16_small(self):
        _fit_and_forward(VGG16(num_labels=4, input_shape=(3, 64, 64)), 4)

    def test_vgg19_builds(self):
        conf = VGG19(num_labels=4, input_shape=(3, 64, 64)).conf()
        assert conf.num_params() > 0

    def test_darknet19(self):
        _fit_and_forward(Darknet19(num_labels=6, input_shape=(3, 64, 64)), 6)

    def test_resnet50(self):
        net = ResNet50(num_labels=4, input_shape=(3, 64, 64)).init()
        x = _nhwc((3, 64, 64))
        out = net.output(x)
        out = out[0] if isinstance(out, list) else out
        assert out.shape == (2, 4)
        net.fit(x, _onehot(2, 4), epochs=1)

    def test_googlenet(self):
        net = GoogLeNet(num_labels=4, input_shape=(3, 64, 64)).init()
        out = net.output(_nhwc((3, 64, 64)))
        out = out[0] if isinstance(out, list) else out
        assert out.shape == (2, 4)

    def test_inception_resnet_v1_builds(self):
        conf = InceptionResNetV1(num_labels=8, input_shape=(3, 96, 96)).conf()
        assert conf.num_params() > 1_000_000

    def test_facenet(self):
        net = FaceNetNN4Small2(num_labels=4, input_shape=(3, 64, 64)).init()
        x = _nhwc((3, 64, 64))
        out = net.output(x)
        out = out[0] if isinstance(out, list) else out
        assert out.shape == (2, 4)
        net.fit(x, _onehot(2, 4), epochs=1)

    def test_tiny_yolo(self):
        m = TinyYOLO(num_labels=3, input_shape=(3, 64, 64))
        net = m.init()
        x = _nhwc((3, 64, 64))
        out = net.output(x)
        out = out[0] if isinstance(out, list) else out
        # 64/32 = 2x2 grid, 5 anchors * (5+3) channels
        assert out.shape[1:3] == (2, 2)

    def test_yolo2_builds(self):
        conf = YOLO2(num_labels=3, input_shape=(3, 64, 64)).conf()
        assert conf.num_params() > 1_000_000

    def test_text_generation_lstm(self):
        m = TextGenerationLSTM(num_labels=12, max_length=10)
        net = m.init()
        x = np.random.RandomState(0).rand(2, 10, 12).astype(np.float32)
        y = np.zeros((2, 10, 12), np.float32)
        y[..., 0] = 1
        out = net.output(x)
        assert out.shape == (2, 10, 12)
        net.fit(x, y, epochs=1)

    def test_model_selector(self):
        names = ModelSelector.available()
        # the reference's 13 architectures (ZooModel.java inventory) ...
        reference_13 = {
            "alexnet", "darknet19", "facenetnn4small2", "googlenet",
            "inceptionresnetv1", "lenet", "resnet50", "simplecnn",
            "textgenerationlstm", "tinyyolo", "vgg16", "vgg19", "yolo2"}
        assert reference_13 <= set(names)
        # ... plus the attention-era additions with no reference counterpart
        assert set(names) - reference_13 == {"gatedwindowmoelm",
                                             "hybridconvmoelm",
                                             "transformerencoder",
                                             "transformerlm",
                                             "visiontransformer"}
        m = ModelSelector.select("lenet", num_labels=10)
        assert isinstance(m, LeNet)
        with pytest.raises(KeyError):
            ModelSelector.select("nope")

    def test_meta_data(self):
        md = ResNet50(num_labels=1000).meta_data()
        assert md.input_shape == ((3, 224, 224),)
        assert not md.use_mds


class TestTransformerEncoder:
    def test_small_encoder_trains(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.zoo.models import TransformerEncoder

        m = TransformerEncoder(num_labels=2, n_layers=2, d_model=16,
                               n_heads=2, d_ff=32, vocab_size=50,
                               max_length=12, seed=7)
        net = ComputationGraph(m.conf()).init()
        rng = np.random.default_rng(0)
        # learnable toy task: class = does token 7 appear in the sequence
        x = rng.integers(0, 50, size=(96, 12)).astype(np.float32)
        cls = (x == 7).any(axis=1).astype(int)
        y = np.eye(2, dtype=np.float32)[cls]
        from deeplearning4j_tpu.datasets.dataset import DataSet
        s0 = net.score(DataSet(x, y))
        for _ in range(60):
            net.fit(x, y)
        assert net.score_ < s0

    def test_vit_patchifies_and_learns(self):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.zoo.models import VisionTransformer

        m = VisionTransformer(num_labels=2, image_size=16, patch_size=4,
                              n_layers=2, d_model=32, n_heads=4, d_ff=64,
                              seed=7)
        assert m.num_patches == 16
        net = ComputationGraph(m.conf()).init()
        rng = np.random.default_rng(0)
        # learnable toy task: class = bright top-left patch
        x = rng.normal(0, 0.3, size=(64, 16, 16, 3)).astype(np.float32)
        cls = rng.integers(0, 2, 64)
        x[cls == 1, :4, :4, :] += 2.0
        y = np.eye(2, dtype=np.float32)[cls]
        from deeplearning4j_tpu.datasets.dataset import DataSet
        s0 = net.score(DataSet(x, y))
        for _ in range(40):
            net.fit(x, y)
        assert net.score_ < s0
        pred = np.asarray(net.output_single(x)).argmax(1)
        assert (pred == cls).mean() > 0.9

    def test_vit_rejects_indivisible_patch(self):
        from deeplearning4j_tpu.zoo.models import VisionTransformer
        with pytest.raises(ValueError):
            VisionTransformer(image_size=30, patch_size=4)

    def test_selector_has_transformer(self):
        from deeplearning4j_tpu.zoo.zoo_model import ModelSelector
        assert "transformerencoder" in ModelSelector.available()

    def test_encoder_variable_length_masking(self):
        # padded batch + mask must equal the unpadded prefix batch
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.zoo.models import TransformerEncoder

        m = TransformerEncoder(num_labels=2, n_layers=2, d_model=16,
                               n_heads=2, d_ff=32, vocab_size=50,
                               max_length=12, seed=7)
        net = ComputationGraph(m.conf()).init()
        rng = np.random.default_rng(0)
        x_short = rng.integers(1, 50, size=(3, 8)).astype(np.float32)
        x_pad = np.zeros((3, 12), np.float32)
        x_pad[:, :8] = x_short
        mask = np.zeros((3, 12), np.float32)
        mask[:, :8] = 1.0
        out_short = np.asarray(net.output(x_short))
        out_pad = np.asarray(net.output(x_pad, masks=[mask]))
        np.testing.assert_allclose(out_pad, out_short, atol=1e-5)


class TestInitPretrained:
    """ZooModel.java:51-93 — cache lookup, Adler32 verification, full
    restore through the real checkpoint readers (own zip AND reference
    DL4J ModelSerializer zip)."""

    def _stage(self, tmp_path, monkeypatch, src, name):
        import shutil
        zoo_dir = tmp_path / "zoo"
        zoo_dir.mkdir(exist_ok=True)
        monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(zoo_dir))
        dst = zoo_dir / name
        shutil.copyfile(src, dst)
        return str(dst)

    def test_dl4j_zip_restores_through_zoo_path(self, tmp_path, monkeypatch):
        import os
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import LeNet
        fix = os.path.join(os.path.dirname(__file__), "fixtures",
                           "dl4j_checkpoint_convnet.zip")
        self._stage(tmp_path, monkeypatch, fix, "lenet_mnist.zip")
        net = LeNet(num_labels=3).init_pretrained(PretrainedType.MNIST)
        exp = np.load(os.path.join(os.path.dirname(__file__), "fixtures",
                                   "dl4j_checkpoint_convnet_expected.npz"))
        out = np.asarray(net.output(exp["x"]))
        np.testing.assert_allclose(out, exp["out"], rtol=1e-5, atol=1e-6)

    def test_checksum_pass_and_mismatch(self, tmp_path, monkeypatch):
        import os
        import zlib
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import LeNet
        fix = os.path.join(os.path.dirname(__file__), "fixtures",
                           "dl4j_checkpoint_convnet.zip")
        staged = self._stage(tmp_path, monkeypatch, fix, "lenet_mnist.zip")
        with open(staged, "rb") as fh:
            good = zlib.adler32(fh.read())
        net = LeNet(num_labels=3).init_pretrained(
            PretrainedType.MNIST, expected_checksum=good)
        assert net.params is not None
        with pytest.raises(ValueError, match="failed checksum"):
            LeNet(num_labels=3).init_pretrained(
                PretrainedType.MNIST, expected_checksum=good + 1)
        assert os.path.exists(staged)  # user files are never deleted
        # registered class-level checksum is honored too
        monkeypatch.setattr(LeNet, "PRETRAINED_CHECKSUMS",
                            {PretrainedType.MNIST: good}, raising=False)
        assert LeNet(num_labels=3).init_pretrained(
            PretrainedType.MNIST).params is not None

    def test_own_format_zip_loads(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.util.model_serializer import write_model
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        m = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)).init()
        src = tmp_path / "own.zip"
        write_model(m, str(src))
        self._stage(tmp_path, monkeypatch, str(src), "simplecnn_cifar10.zip")
        net = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(net.output(x)),
                                   np.asarray(m.output(x)), rtol=1e-5)

    def test_missing_raises(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import LeNet
        monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(tmp_path / "empty"))
        with pytest.raises(FileNotFoundError, match="No pretrained weights"):
            LeNet().init_pretrained(PretrainedType.VGGFACE)


class TestPretrainedTransport:
    """ZooModel.java:51-81 — the FULL transport round trip: registered URL
    → fetch → Adler32 verify → cache → restore; corrupt downloads deleted
    so a retry re-fetches; cache hits skip the transport entirely.
    file:// URLs drive the identical urllib path as http(s)."""

    def _serve(self, tmp_path, monkeypatch):
        """Stage a weight blob at a file:// 'origin' + point the cache at
        an empty dir. Returns (model_cls, origin_path, checksum, cache_dir,
        reference_net)."""
        import os
        import zlib
        from deeplearning4j_tpu.util.model_serializer import write_model
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        origin = tmp_path / "origin"
        origin.mkdir()
        m = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)).init()
        blob = origin / "weights.zip"
        write_model(m, str(blob))
        with open(blob, "rb") as fh:
            good = zlib.adler32(fh.read())
        cache = tmp_path / "cache"
        monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(cache))
        return blob, good, cache, m

    def test_fetch_checksum_cache_restore(self, tmp_path, monkeypatch):
        import os
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        blob, good, cache, ref = self._serve(tmp_path, monkeypatch)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_URLS",
            {PretrainedType.CIFAR10: blob.as_uri()}, raising=False)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_CHECKSUMS",
            {PretrainedType.CIFAR10: good}, raising=False)
        net = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        # the artifact landed in the cache slot (and no .part residue)
        cached = cache / "simplecnn_cifar10.zip"
        assert cached.exists()
        assert not (cache / "simplecnn_cifar10.zip.part").exists()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(net.output(x)),
                                   np.asarray(ref.output(x)), rtol=1e-5)
        # cache HIT: origin removed, second init must not touch transport
        os.remove(blob)
        net2 = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        np.testing.assert_allclose(np.asarray(net2.output(x)),
                                   np.asarray(ref.output(x)), rtol=1e-5)

    def test_corrupt_download_deleted_then_refetch_succeeds(
            self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        blob, good, cache, _ = self._serve(tmp_path, monkeypatch)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_URLS",
            {PretrainedType.CIFAR10: blob.as_uri()}, raising=False)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_CHECKSUMS",
            {PretrainedType.CIFAR10: good + 1}, raising=False)
        with pytest.raises(ValueError, match="corrupt download was deleted"):
            SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
                .init_pretrained(PretrainedType.CIFAR10)
        # the reference deletes bad downloads (ZooModel.java:75-81): the
        # cache slot must be empty so the next attempt re-fetches
        assert not (cache / "simplecnn_cifar10.zip").exists()
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_CHECKSUMS",
            {PretrainedType.CIFAR10: good}, raising=False)
        net = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        assert net.params is not None

    def test_sha256_verified_when_registered(self, tmp_path, monkeypatch):
        """ADVICE r4: Adler32 over plain http is corruption detection only;
        a registered SHA-256 adds tamper-evident verification with the
        same download-deletion semantics."""
        import hashlib
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        blob, good, cache, ref = self._serve(tmp_path, monkeypatch)
        with open(blob, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_URLS",
            {PretrainedType.CIFAR10: blob.as_uri()}, raising=False)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_CHECKSUMS",
            {PretrainedType.CIFAR10: good}, raising=False)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_SHA256",
            {PretrainedType.CIFAR10: digest.upper()},  # case-insensitive
            raising=False)
        net = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        assert net.params is not None

        # wrong digest: the forged blob passes Adler32 registration (an
        # attacker can match Adler32) but fails SHA-256 — download deleted
        import shutil
        shutil.rmtree(cache)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_SHA256",
            {PretrainedType.CIFAR10: "0" * 64}, raising=False)
        with pytest.raises(ValueError, match="SHA-256"):
            SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
                .init_pretrained(PretrainedType.CIFAR10)
        assert not (cache / "simplecnn_cifar10.zip").exists()

    def test_fetched_cache_reverified_user_files_trusted(
            self, tmp_path, monkeypatch):
        """A fetched artifact re-verifies against the registry checksum on
        every load (corruption in the cache is caught and evicted); a
        user-placed file is their own weights — registry checksums don't
        apply, only an explicit expected_checksum does."""
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        blob, good, cache, _ = self._serve(tmp_path, monkeypatch)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_URLS",
            {PretrainedType.CIFAR10: blob.as_uri()}, raising=False)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_CHECKSUMS",
            {PretrainedType.CIFAR10: good}, raising=False)
        SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        slot = cache / "simplecnn_cifar10.zip"
        marker = cache / "simplecnn_cifar10.zip.src"
        assert marker.exists()
        # corrupt the fetched cache: the next load must catch it, but never
        # delete a file it didn't just download (the slot could equally be
        # the user's own replacement)
        slot.write_bytes(slot.read_bytes() + b"bitrot")
        with pytest.raises(ValueError, match="delete the file"):
            SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
                .init_pretrained(PretrainedType.CIFAR10)
        assert slot.exists()
        slot.unlink()
        marker.unlink()
        # user-placed file in the slot (their own fine-tune, a DIFFERENT
        # byte stream than the registry artifact): registry checksum does
        # NOT apply — it loads
        import zlib
        from deeplearning4j_tpu.util.model_serializer import write_model
        own = SimpleCNN(num_labels=4, input_shape=(3, 32, 32), seed=777).init()
        write_model(own, str(slot))
        with open(slot, "rb") as fh:
            assert zlib.adler32(fh.read()) != good
        net = SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
            .init_pretrained(PretrainedType.CIFAR10)
        assert net.params is not None

    def test_interrupted_fetch_leaves_no_artifact(self, tmp_path, monkeypatch):
        """A transport failure mid-stream must not leave a half-written
        file posing as a finished artifact in the cache slot."""
        from deeplearning4j_tpu.zoo.zoo_model import PretrainedType, ZooModel
        from deeplearning4j_tpu.zoo.models import SimpleCNN
        blob, good, cache, _ = self._serve(tmp_path, monkeypatch)
        monkeypatch.setattr(
            SimpleCNN, "PRETRAINED_URLS",
            {PretrainedType.CIFAR10: blob.as_uri()}, raising=False)

        import shutil
        def explode(src, dst):
            dst.write(b"partial")
            raise OSError("link dropped")
        monkeypatch.setattr(shutil, "copyfileobj", explode)
        with pytest.raises(OSError, match="link dropped"):
            SimpleCNN(num_labels=4, input_shape=(3, 32, 32)) \
                .init_pretrained(PretrainedType.CIFAR10)
        assert not (cache / "simplecnn_cifar10.zip").exists()
        assert not (cache / "simplecnn_cifar10.zip.part").exists()


class TestLabels:
    """zoo/util label helpers (Labels SPI, decodePredictions,
    VOC/COCO/ImageNet tables)."""

    def test_voc_and_coco_tables(self):
        from deeplearning4j_tpu.zoo.labels import COCOLabels, VOCLabels
        voc, coco = VOCLabels(), COCOLabels()
        assert len(voc) == 20 and len(coco) == 80
        assert voc.get_label(14) == "person"
        assert coco.get_label(0) == "person"
        assert coco.get_label(79) == "toothbrush"

    def test_decode_predictions_top5(self):
        from deeplearning4j_tpu.zoo.labels import VOCLabels
        rng = np.random.default_rng(0)
        probs = rng.dirichlet(np.ones(20), size=3)
        probs[1, 7] = 5.0  # cat dominates example 1
        probs = probs / probs.sum(1, keepdims=True)
        decoded = VOCLabels().decode_predictions(probs, top=5)
        assert len(decoded) == 3 and len(decoded[0]) == 5
        assert decoded[1][0].label == "cat"
        assert decoded[1][0].probability > 0.5
        # descending probability within each example
        ps = [c.probability for c in decoded[0]]
        assert ps == sorted(ps, reverse=True)

    def test_class_count_mismatch_raises(self):
        from deeplearning4j_tpu.zoo.labels import VOCLabels
        with pytest.raises(ValueError, match="label"):
            VOCLabels().decode_predictions(np.ones((2, 80)) / 80)

    def test_imagenet_loads_keras_index_format(self, tmp_path, monkeypatch):
        import json
        from deeplearning4j_tpu.zoo.labels import ImageNetLabels
        idx = {str(i): [f"n{i:08d}", f"class_{i}"] for i in range(1000)}
        idx["0"] = ["n01440764", "tench"]
        p = tmp_path / "imagenet_class_index.json"
        p.write_text(json.dumps(idx))
        labels = ImageNetLabels(str(p))
        assert len(labels) == 1000
        assert labels.get_label(0) == "tench"
        # env-dir resolution
        monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(tmp_path))
        assert ImageNetLabels().get_label(0) == "tench"
        monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(tmp_path / "none"))
        with pytest.raises(FileNotFoundError, match="label table"):
            ImageNetLabels()


def test_darknet19_resolution_specific_cache_slots(monkeypatch, tmp_path):
    """224 and 448 Darknet19 weights are different artifacts (different
    URLs/checksums) — they must occupy different cache slots."""
    from deeplearning4j_tpu.zoo.models import Darknet19
    monkeypatch.setenv("DL4J_TPU_ZOO_DIR", str(tmp_path))
    p224 = Darknet19(input_shape=(3, 224, 224))._cache_path("imagenet")
    p448 = Darknet19(input_shape=(3, 448, 448))._cache_path("imagenet")
    assert p224 != p448


def test_fetch_failure_leaves_no_orphan_src_marker(monkeypatch, tmp_path):
    """A crash mid-fetch must not leave a .src marker without an artifact
    in a way that later misattributes a user-placed file to the fetcher."""
    from deeplearning4j_tpu.zoo.zoo_model import ZooModel
    dest = tmp_path / "slot.zip"
    import shutil

    def explode(src, dst):
        raise OSError("mid-stream failure")
    monkeypatch.setattr(shutil, "copyfileobj", explode)
    blob = tmp_path / "origin.zip"
    blob.write_bytes(b"payload")
    with pytest.raises(OSError):
        ZooModel._fetch(blob.as_uri(), str(dest))
    assert not dest.exists()
    assert not (tmp_path / "slot.zip.part").exists()
    assert not (tmp_path / "slot.zip.src").exists()
