"""Distributed training over a device mesh.

Mirrors the reference's scale-out stack (ParallelWrapper, Spark training
masters): the same model trained three ways — per-step synchronous data
parallelism, periodic parameter averaging, and threshold-compressed gradient
sharing — on a virtual 8-device CPU mesh (exactly how multi-chip sharding is
validated without hardware; on a real pod the same code rides ICI).

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
     python examples/05_distributed_training.py
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.parallel import (
    DistributedMultiLayerNetwork,
    ParallelWrapper,
    ParameterAveragingTrainingMaster,
    SharedTrainingMaster,
)
from deeplearning4j_tpu.parallel.mesh import make_mesh


def make_net():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


def main():
    rng = np.random.default_rng(0)
    y = rng.integers(0, 3, 512)
    x = rng.normal(size=(512, 6)).astype(np.float32)
    x[np.arange(512), y] += 2.5
    ds = DataSet(x, np.eye(3, dtype=np.float32)[y])
    mesh = make_mesh({"data": 8})
    print("mesh:", dict(mesh.shape))

    # 1. per-step sync DP: batch sharded, params replicated, XLA emits the
    #    gradient all-reduce
    net = make_net()
    ParallelWrapper(net, mesh, mode="shared_gradients").fit(
        ListDataSetIterator(ds, 128, shuffle=True), epochs=10)
    print("shared_gradients accuracy:",
          net.evaluate(ListDataSetIterator(ds, 256)).accuracy())

    # 2. parameter averaging every 4 local steps (Spark TrainingMaster role)
    net = make_net()
    master = ParameterAveragingTrainingMaster(batch_size_per_worker=16,
                                              averaging_frequency=4, mesh=mesh)
    DistributedMultiLayerNetwork(net, master).fit([ds], epochs=10)
    print("parameter averaging accuracy:",
          net.evaluate(ListDataSetIterator(ds, 256)).accuracy(),
          "| phase stats:", master.get_training_stats().as_dict())

    # 3. threshold-compressed gradient sharing (Aeron/Strom design, on-mesh)
    net = make_net()
    master = SharedTrainingMaster(batch_size_per_worker=16, threshold=1e-3,
                                  mesh=mesh)
    front = DistributedMultiLayerNetwork(net, master)
    front.fit(ListDataSetIterator(ds, 128, shuffle=True), epochs=15)
    print("shared (compressed) accuracy:",
          net.evaluate(ListDataSetIterator(ds, 256)).accuracy(),
          f"| final threshold {master.threshold:.2e}")


if __name__ == "__main__":
    main()
