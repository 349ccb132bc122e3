"""Example 21 — VisionTransformer + the orbax checkpoint path.

The two TPU-native additions from round 3's late session: a ViT zoo model
(patch-embed conv -> shared transformer encoder blocks) trained on a toy
image task, checkpointed through the orbax path with step rotation, then
preemption-resumed.

Run: JAX_PLATFORMS=cpu python examples/21_vision_transformer_and_orbax.py   (small demo)
"""

import tempfile

import numpy as np

from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.util.orbax_checkpoint import OrbaxCheckpointManager
from deeplearning4j_tpu.util.preemption import PreemptionHandler
from deeplearning4j_tpu.zoo import VisionTransformer

# --- 1. a small ViT ---------------------------------------------------------
vit = VisionTransformer(num_labels=2, image_size=16, patch_size=4,
                        n_layers=2, d_model=32, n_heads=4, d_ff=64, seed=7)
print(f"ViT: {vit.num_patches} patches per image")
net = ComputationGraph(vit.conf())
net.init()

# toy task: is the top-left patch bright?
rng = np.random.default_rng(0)
x = rng.normal(0, 0.3, size=(64, 16, 16, 3)).astype(np.float32)
cls = rng.integers(0, 2, 64)
x[cls == 1, :4, :4, :] += 2.0
y = np.eye(2, dtype=np.float32)[cls]

# --- 2. train with rotating orbax checkpoints ------------------------------
with tempfile.TemporaryDirectory() as ckpt_dir:
    with OrbaxCheckpointManager(ckpt_dir, max_to_keep=2,
                                save_interval_steps=10) as mgr:
        for step in range(40):
            net.fit(x, y)
            mgr.save(step, net)
        mgr.wait_until_finished()
        print(f"checkpoints kept: steps {mgr.all_steps()}")
        acc = (np.asarray(net.output_single(x)).argmax(1) == cls).mean()
        print(f"train accuracy: {acc:.2f}")

        # --- 3. "preemption": restore the latest step and keep going -------
        resumed = mgr.restore()
        print(f"restored at iteration {resumed.iteration}")
        resumed.fit(x, y)

    # --- 4. the SIGTERM-armed handler uses the same machinery --------------
    handler = PreemptionHandler(net, ckpt_dir + "/preempt", backend="orbax")
    handler.save()  # what the SIGTERM hook runs in the grace window
    model, state = PreemptionHandler.resume(ckpt_dir + "/preempt")
    print(f"preemption round trip at iteration {state['iteration']}: "
          f"outputs equal = "
          f"{np.allclose(np.asarray(model.output_single(x)), np.asarray(net.output_single(x)), rtol=1e-6)}")
