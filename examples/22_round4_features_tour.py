"""Example 22 — round-4 feature tour: exact distributed resume, dropout
schedules, pretrained transport, scatter ops.

Four additions in one runnable script:

1. EXACT preemption resume of threshold-compressed distributed training —
   model checkpoint (orbax) + the master's compression state
   (``save_state``/``load_state``: adaptive threshold + residual shards);
   resumed params equal the uninterrupted run bit-for-bit.
2. Dropout schedules (``Dropout.java:45`` pSchedule): the retain
   probability follows the device tick inside the compiled step.
3. Zoo pretrained transport over file:// — registered URL, fetch,
   Adler32 verify, cache.
4. SameDiff scatter/segment ops in a trained graph.

Run: JAX_PLATFORMS=cpu python examples/22_round4_features_tour.py   (small demo)
"""

import os
import tempfile
import zlib

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.dropout import Dropout
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Adam, MapSchedule
from deeplearning4j_tpu.parallel import (
    DistributedMultiLayerNetwork,
    SharedTrainingMaster,
)
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.util.orbax_checkpoint import OrbaxCheckpointManager

# --- 1. exact resume of compressed distributed training --------------------
print("== 1. exact distributed resume (model + compression state)")


def build_net(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


rng = np.random.default_rng(0)
x = rng.normal(size=(128, 6)).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 128)]
ds = DataSet(x, y)
mesh = make_mesh()  # all local devices on the data axis

net_a = build_net()
m_a = SharedTrainingMaster(batch_size_per_worker=16, threshold=1e-3, mesh=mesh)
front_a = DistributedMultiLayerNetwork(net_a, m_a)
for _ in range(6):
    front_a.fit([ds])

net_b = build_net()
m_b = SharedTrainingMaster(batch_size_per_worker=16, threshold=1e-3, mesh=mesh)
front_b = DistributedMultiLayerNetwork(net_b, m_b)
for _ in range(3):
    front_b.fit([ds])
with tempfile.TemporaryDirectory() as td:
    with OrbaxCheckpointManager(os.path.join(td, "ckpt")) as mgr:
        mgr.save(3, net_b)
        mgr.wait_until_finished()
    m_b.save_state(os.path.join(td, "master.npz"))
    # ---- "the job is preempted here; a new process restarts" ----
    with OrbaxCheckpointManager(os.path.join(td, "ckpt")) as mgr:
        resumed = mgr.restore()
    m_c = SharedTrainingMaster(batch_size_per_worker=16, threshold=1e-3,
                               mesh=mesh)
    m_c.load_state(os.path.join(td, "master.npz"))
    front_c = DistributedMultiLayerNetwork(resumed, m_c)
    for _ in range(3):
        front_c.fit([ds])
drift = max(float(np.abs(np.asarray(pa[k]) - np.asarray(pc[k])).max())
            for pa, pc in zip(net_a.params, resumed.params) for k in pa)
print(f"   resumed-vs-uninterrupted max param drift: {drift:.2e}")
assert drift < 1e-5

# --- 2. dropout schedules ---------------------------------------------------
print("== 2. dropout pSchedule follows the device tick")
sched = MapSchedule(values=((0, 0.95), (10, 0.6)))
conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_in=6, n_out=16, activation="relu",
                          dropout=Dropout(sched)))
        .layer(OutputLayer(n_in=16, n_out=3))
        .build())
snet = MultiLayerNetwork(conf).init()
for i in range(15):
    snet.fit(x, y)
print(f"   trained 15 steps across the schedule breakpoint; "
      f"score={float(snet.score_):.4f}")

# --- 3. pretrained transport over file:// ----------------------------------
print("== 3. zoo pretrained transport (fetch -> checksum -> cache)")
from deeplearning4j_tpu.util.model_serializer import write_model
from deeplearning4j_tpu.zoo.models import SimpleCNN
from deeplearning4j_tpu.zoo.zoo_model import PretrainedType

with tempfile.TemporaryDirectory() as td:
    src = SimpleCNN(num_labels=3, input_shape=(3, 32, 32)).init()
    blob = os.path.join(td, "weights.zip")
    write_model(src, blob)
    with open(blob, "rb") as fh:
        checksum = zlib.adler32(fh.read())
    os.environ["DL4J_TPU_ZOO_DIR"] = os.path.join(td, "cache")
    SimpleCNN.PRETRAINED_URLS = {PretrainedType.CIFAR10: "file://" + blob}
    SimpleCNN.PRETRAINED_CHECKSUMS = {PretrainedType.CIFAR10: checksum}
    fetched = SimpleCNN(num_labels=3, input_shape=(3, 32, 32)) \
        .init_pretrained(PretrainedType.CIFAR10)
    xi = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    same = np.allclose(np.asarray(fetched.output(xi)),
                       np.asarray(src.output(xi)), rtol=1e-5)
    print(f"   fetched+verified weights reproduce source outputs: {same}")
    assert same

# --- 4. SameDiff scatter/segment ops ---------------------------------------
print("== 4. SameDiff scatter_add + segment_sum in a trained graph")
from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig

sd = SameDiff.create()
xin = sd.place_holder("input", shape=(None, 6))
lab = sd.place_holder("label", shape=(None, 2))
w = sd.var("w", value=(rng.normal(size=(6, 2)) * 0.1))
base = sd.constant("base", np.zeros((4, 2), np.float32))
idx = sd.constant("idx", np.array([1, 3], np.int32))
upd = sd.var("upd", value=np.zeros((2, 2)))
sd.math.scatter_add(base, idx, upd, name="table")  # trainable lookup rows
logits = xin.mmul(w, name="logits")
sd.loss.softmax_cross_entropy(lab, logits, name="loss")
sd.set_loss_variables("loss")
sd.set_training_config(TrainingConfig(
    updater=Adam(0.05), data_set_feature_mapping=["input"],
    data_set_label_mapping=["label"]))
cls2 = (x[:, 0] > 0).astype(int)
loss = sd.fit(DataSet(x, np.eye(2, dtype=np.float32)[cls2]), epochs=60)
print(f"   samediff graph trained to loss {float(loss):.4f}")
assert float(loss) < 0.4

print("example 22 complete")
