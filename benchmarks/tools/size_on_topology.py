#!/usr/bin/env python3
"""Rehearsal 3 of the `on-chip-measurement` guide, by hand: compile a cell's
real train step for a TPU that is described and not attached, and print the
compiler's memory analysis and the collectives it put in.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/size_on_topology.py <cell> [--batch N]

Nothing runs, so this says nothing about times or results; it says whether
the step compiles and fits, which costs no chip time to learn. It is a tool
and no part of the yardstick: it reaches into the program (`net._mesh`,
`_get_train_step`) to hand it described devices and shapes in place of
arrays, which `shard_model_with_rules` cannot take. The flash gate asks
`jax.default_backend()`, which says `cpu` here; it is answered `tpu` while
the step is lowered, so that the step is the one the chip compiles.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--batch", type=int, help="instead of the mix's batch")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.harness import manifest
    from benchmarks.harness.hlo_text import collective_counts
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.sharding import (
        DEFAULT_2D_RULES, _leaf_sharding_ok, match_partition_rules)

    cell = manifest.Cell(manifest.load(), args.cell)
    config, mix = cell.config, cell.traffic
    batch = args.batch or mix["batch"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    axes = config["deployment"].get("mesh") or {"data": 1}
    devices = np.asarray(topo.devices[:cell.chips]).reshape(
        tuple(axes.values()))
    mesh = Mesh(devices, tuple(axes))

    net = ComputationGraph(cell.family.network_conf(config, seed=0))

    def init():
        net.init()
        return net.params, net.states, net.updater_states

    params, states, upd = jax.eval_shape(init)
    specs = match_partition_rules(DEFAULT_2D_RULES, params)

    def named(leaf, spec):
        ok = cell.chips > 1 and _leaf_sharding_ok(leaf.shape, spec, mesh)
        return NamedSharding(mesh, spec if ok else P())

    p_sh = jax.tree_util.tree_map(named, params, specs)
    u_sh = {v: {n: {slot: p_sh[v][n] for slot in upd[v][n]}
                for n in upd[v]} for v in upd}
    repl = NamedSharding(mesh, P())

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    net.params = shaped(params, p_sh)
    net.updater_states = shaped(upd, u_sh)
    net.states = shaped(states, jax.tree_util.tree_map(lambda _: repl, states))
    if cell.chips > 1:
        net._mesh, net._param_shardings, net._upd_shardings = mesh, p_sh, u_sh

    data = NamedSharding(mesh, P("data"))
    tokens = jax.ShapeDtypeStruct((batch, mix["seq_len"]), jnp.int32,
                                  sharding=data)
    # class ids, as `lm_labels` makes them: the path the cells run
    labels = jax.ShapeDtypeStruct((batch, mix["seq_len"]), jnp.int32,
                                  sharding=data)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        t0 = time.perf_counter()
        lowered = net._get_train_step().lower(
            net.params, net.states, net.updater_states, scalar, scalar,
            {"tokens": tokens}, [labels], None, None, key)
    finally:
        jax.default_backend = real_backend
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    print(f"{cell.name}: batch {batch}, T {mix['seq_len']}, "
          f"{n_params / 1e6:.1f}M parameters, compiled for {args.topology} "
          f"({cell.chips} chip(s)) in {seconds:.0f} s")
    print(f"  per chip: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, program "
          f"{mem.generated_code_size_in_bytes / 1e6:.0f} MB")
    print(f"  per chip, arguments + outputs + temporaries - aliased: "
          f"{per_chip / 1e9:.2f} GB")
    print(f"  collectives: {collective_counts(text)}; tpu_custom_call: "
          f"{text.count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
