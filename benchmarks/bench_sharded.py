"""Pod-sharded batched fused walk engine sweep: per-shard supersteps on
the bounded ``_route`` fabric vs the unsharded batched engine.

Quantifies the sharding tentpole (``core/distributed.py``): the graph CSR
node-range-sharded over a 'model' mesh axis, each per-shard superstep
running the fused hop kernels (or their XLA oracle twins) on shard-local
slices, ONE bounded-capacity all_to_all route per hop for the whole query
batch — swept over n_shards {1, 2, 4, 8} x engine {xla, fused} x batch
{1, 8} on 8 forced host devices.

Recorded per cell: walk ms and per-superstep ms, routed-walker occupancy
vs route capacity (``max_occupancy`` telemetry from ``_route``), and
dropped-walker counts.  A deliberately starved-slack row shows drops are
counted, never silent.

The agreement verdict is the regression signal: ``sharded_engine_agrees``
asserts fused sharded == xla sharded == unsharded batched bit-identically
(counts, board counts, steps_taken, n_high) for every swept cell, with
zero drops at parity slack.  On CPU hosts the kernels run in interpret
mode and the 8 "devices" share one machine — ms columns measure plumbing,
not ICI; regress on ``sharded_engine_agrees``, not the CPU ratios.

Needs 8 devices (``common.run_multi_device``): it runs in this process
where JAX already sees 8; a CPU host re-executes this module in a child
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; an
accelerator host with fewer chips refuses (one process per chip).

Results land in ``results/bench.json`` AND merge into
``BENCH_serving.json`` as the ``sharded`` section.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict

N_DEVICES = 8
SHARDS = (1, 2, 4, 8)
BATCHES = (1, 8)
WALKERS_PER_QUERY = 32
N_SLOTS = 4


def _child_sweep(seed: int) -> Dict:
    """The suite body, on 8 devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import counter as counter_lib
    from repro.core import distributed as dist_lib
    from repro.core import walk as walk_lib
    from repro.graphs.synthetic import small_test_graph, top_degree_pins
    from repro.launch.mesh import make_mesh

    sg = small_test_graph(seed)
    g = sg.graph
    qs = top_degree_pins(sg, 16)
    base = walk_lib.WalkConfig(
        n_steps=2_048, n_walkers=WALKERS_PER_QUERY, chunk_steps=4,
        n_p=30, n_v=3, bias_beta=0.0, count_boards=True,
    )

    def queries(batch):
        pins = np.full((batch, N_SLOTS), -1, np.int32)
        weights = np.zeros((batch, N_SLOTS), np.float32)
        for b in range(batch):
            pins[b, :3] = qs[(3 * b) % 12:(3 * b) % 12 + 3]
            weights[b, :3] = (1.0, 0.7, 0.4)
        return jnp.asarray(pins), jnp.asarray(weights)

    def timed(fn, arg, iters=2):
        out = jax.block_until_ready(fn(arg))  # compile + warm
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(arg))
            times.append(time.perf_counter() - t0)
        return out, 1e3 * float(np.mean(times))

    refs = {}  # unsharded batched oracle per batch size
    for batch in BATCHES:
        pins, weights = queries(batch)
        keys = jax.random.split(jax.random.key(seed), batch)
        r = walk_lib.pixie_random_walk_batched(
            g, pins, weights, jnp.zeros((batch,), jnp.int32), keys, base
        )
        refs[batch] = tuple(
            np.asarray(x) for x in (r.counts, r.board_counts,
                                    r.steps_taken, r.n_high)
        )

    sweep = []
    agree_all = True
    supersteps = base.max_chunks() * base.chunk_steps
    for n_shards in SHARDS:
        mesh = make_mesh((n_shards,), ("model",))
        shg = dist_lib.shard_graph(g, n_shards, mesh)
        for batch in BATCHES:
            pins, weights = queries(batch)
            keys = jax.random.split(jax.random.key(seed), batch)
            w_total = batch * WALKERS_PER_QUERY
            # parity slack: capacity >= the whole walker pool, so routing
            # can never drop (occupancy telemetry still shows real skew)
            slack = float(n_shards * n_shards)
            cap = dist_lib.route_capacity(n_shards, w_total, slack)
            row: Dict = {"n_shards": n_shards, "batch": batch,
                         "route_capacity": cap, "engines": {}}
            engines = [("xla", "scalar"), ("fused_scalar", "scalar")]
            if n_shards in (2, 4):
                engines.append(("fused_dma", "dma"))
            row_ok = True
            with jax.set_mesh(mesh):
                for label, gather in engines:
                    cfg = dataclasses.replace(
                        base,
                        backend="xla" if label == "xla" else "pallas",
                        gather_mode=gather,
                    )
                    fn = jax.jit(
                        lambda ks, cfg=cfg: dist_lib.pixie_walk_sharded_batched(
                            shg, pins, weights, ks, cfg, mesh, slack=slack
                        )
                    )
                    res, ms = timed(fn, keys)
                    counts = counter_lib.fold_sharded_counts(
                        res.counts, batch, N_SLOTS, shg.pins_per_shard
                    )[..., :g.n_pins]
                    bc = counter_lib.fold_sharded_counts(
                        res.board_counts, batch, N_SLOTS,
                        shg.boards_per_shard
                    )[..., :g.n_boards]
                    got = tuple(np.asarray(x)
                                for x in (counts, bc, res.steps_taken,
                                          res.n_high))
                    ok = all(np.array_equal(a, b)
                             for a, b in zip(got, refs[batch]))
                    ok = ok and int(res.dropped) == 0
                    row_ok &= ok
                    occ = int(res.max_occupancy)
                    row["engines"][label] = {
                        "walk_ms": round(ms, 2),
                        "per_superstep_ms": round(ms / supersteps, 3),
                        "dropped": int(res.dropped),
                        "max_occupancy": occ,
                        "occupancy_frac": round(occ / cap, 3),
                        "agrees_with_unsharded": ok,
                    }
            row["agree"] = row_ok
            agree_all &= row_ok
            sweep.append(row)

    # starved-slack illustration: drops are COUNTED, not silent (no parity
    # claim here — dropped walkers are bounded Monte Carlo slack)
    mesh = make_mesh((2,), ("model",))
    shg = dist_lib.shard_graph(g, 2, mesh)
    pins, weights = queries(8)
    keys = jax.random.split(jax.random.key(seed), 8)
    with jax.set_mesh(mesh):
        res = jax.block_until_ready(
            dist_lib.pixie_walk_sharded_batched(
                shg, pins, weights, keys, base, mesh, slack=0.05
            )
        )
    starved = {
        "n_shards": 2, "batch": 8, "slack": 0.05,
        "route_capacity": dist_lib.route_capacity(2, 8 * WALKERS_PER_QUERY,
                                                  0.05),
        "dropped": int(res.dropped),
        "max_occupancy": int(res.max_occupancy),
        "drops_counted": int(res.dropped) > 0,
    }

    return {
        "host_backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() == "cpu",
        "n_devices": len(jax.devices()),
        "graph": {"n_pins": g.n_pins, "n_boards": g.n_boards},
        "config": {"walkers_per_query": WALKERS_PER_QUERY,
                   "n_steps": base.n_steps, "chunk_steps": base.chunk_steps,
                   "supersteps": supersteps, "n_slots": N_SLOTS},
        "sweep": sweep,
        "starved": starved,
        "agree_all": agree_all,
        "drops_counted": starved["drops_counted"],
    }


def run(seed: int = 0) -> Dict:
    """Driver entry: the sweep on 8 devices (``run_multi_device``)."""
    from benchmarks.common import merge_serving_section, run_multi_device

    out: Dict = {"sharded": run_multi_device(
        "benchmarks.bench_sharded", N_DEVICES, seed, _child_sweep
    )}
    # verdict: fused sharded == xla sharded == unsharded batched engine,
    # bit-identically (counts, board counts, steps_taken, n_high), zero
    # drops at parity slack, for every (n_shards, batch) cell — and
    # capacity-overflow drops are counted when the fabric is starved
    out["sharded_engine_agrees"] = bool(
        out["sharded"]["agree_all"] and out["sharded"]["drops_counted"]
    )
    out["wrote"] = merge_serving_section("sharded", {
        "sharded_engine_agrees": out["sharded_engine_agrees"],
        "pallas_interpret": out["sharded"]["pallas_interpret"],
        "starved": out["sharded"]["starved"],
        "sweep": [
            {
                "n_shards": row["n_shards"],
                "batch": row["batch"],
                "agree": row["agree"],
                "route_capacity": row["route_capacity"],
                "per_superstep_ms": {
                    k: v["per_superstep_ms"]
                    for k, v in row["engines"].items()
                },
                "occupancy_frac": {
                    k: v["occupancy_frac"]
                    for k, v in row["engines"].items()
                },
                "dropped": {
                    k: v["dropped"] for k, v in row["engines"].items()
                },
            }
            for row in out["sharded"]["sweep"]
        ],
    })
    return out


def _child_main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(_child_sweep(args.seed)))
        return 0
    print(json.dumps(run(args.seed), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
