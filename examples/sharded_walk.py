"""The production distribution mode: Pixie on a graph too big for one chip.

Spawns 8 fake devices, shards the graph over a 4-way 'model' axis, and runs
the pod-sharded batched fused walk engine (core/distributed.py) — the same
program the multi-pod dry-run lowers at 3B-node scale.  Must be a fresh
process (device count locks at first jax init), hence the XLA_FLAGS lines
first.

  PYTHONPATH=src python examples/sharded_walk.py
"""

import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as D
from repro.core import walk as W
from repro.graphs.synthetic import SyntheticGraphConfig, generate
from repro.launch.mesh import make_mesh

def main(
    n_pins: int = 8_000,
    n_boards: int = 800,
    n_shards: int = 4,
    mesh_shape: tuple = (2, 4),
    n_supersteps: int = 48,
    walkers_per_shard: int = 256,
    top_k: int = 15,
    slack: float = 8.0,
):
    """Run the sharded walk demo; parameters shrink it to a smoke test
    (tests/test_examples.py runs a 1-shard single-device configuration
    through this same path).  Returns (overlap, dropped)."""
    sg = generate(SyntheticGraphConfig(n_pins=n_pins, n_boards=n_boards,
                                       seed=3))
    mesh = make_mesh(mesh_shape, ("data", "model")[-len(mesh_shape):])
    shg = D.shard_graph(sg.graph, n_shards, mesh)
    print(f"graph sharded {n_shards} ways: {shg.pins_per_shard} pins/shard, "
          f"{shg.boards_per_shard} boards/shard")

    degs = np.asarray(sg.graph.p2b.degrees())
    qs = np.argsort(-degs)[:3]
    qp = jnp.asarray([int(qs[0]), int(qs[1]), int(qs[2]), -1], jnp.int32)
    qw = jnp.asarray([1.0, 0.8, 0.5, 0.0], jnp.float32)

    cfg = D.ShardedWalkConfig(
        n_supersteps=n_supersteps, walkers_per_shard=walkers_per_shard,
        top_k=top_k, slack=slack,
    )
    with jax.set_mesh(mesh):
        res = D.pixie_walk_sharded(shg, qp, qw, jax.random.key(0), cfg, mesh)
    print(f"walkers dropped by routing capacity: {int(res.dropped)}")
    print("top pins (pod-sharded batched fused walk):")
    for s, p in zip(np.asarray(res.top_scores), np.asarray(res.top_pins)):
        if s > 0:
            print(f"  pin {p:6d}  score {s:8.1f}")

    # cross-check against the single-machine walk (the paper's deployment)
    w_total = n_shards * walkers_per_shard
    wcfg = W.WalkConfig(n_steps=n_supersteps * w_total, n_walkers=w_total,
                        bias_beta=0.0, top_k=top_k, n_p=10**9, n_v=10**9)
    scores, ids = W.recommend(
        sg.graph, qp, qw, jnp.asarray(0, jnp.int32), jax.random.key(1), wcfg
    )
    overlap = len(
        set(np.asarray(res.top_pins).tolist())
        & set(np.asarray(ids).tolist())
    )
    print(f"top-{top_k} overlap with replicated walk: {overlap}/{top_k}")
    return overlap, int(res.dropped)

if __name__ == "__main__":
    main()
