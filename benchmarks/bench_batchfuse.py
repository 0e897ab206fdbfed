"""Batch-native fused walk engine sweep: one Pallas program per chunk for
the whole query batch vs the vmapped per-query formulation.

Quantifies the batching tentpole on the serving path
(``core/service.serve_batch``): ``backend="pallas"`` routes through
``core/walk.pixie_random_walk_batched`` — all queries' walkers packed on
one walker axis, ONE fused ``pallas_call`` + ONE query-major counting call
per superstep chunk, a shared while loop with a per-(query, slot)
early-stop mask — swept over batch {1, 4, 16, 64} x gather mode, with two
controls, each the vmapped per-query engine called directly: on XLA (the
oracle) and on *pallas* (what serve_batch used to do: vmap prepends the
batch to every kernel grid).

The sweep holds SERVER CAPACITY fixed — a constant total walker pool and
step budget split evenly across the batch (the paper's serving framing: a
64-core machine amortizes over concurrent queries) — so "per-query ms vs
batch" is a real amortization curve and the dense count space
(batch x n_slots x n_pins bins) stays affordable under CPU interpret.

The agreement verdict is the regression signal: ``batch_engine_agrees``
asserts batched == vmapped bit-identically — ids, scores, and the
early-stop observables (steps_taken, n_high) — for every batch size and
gather mode.  Kernel-launch structure is recorded from the jaxpr: the
batched path keeps a CONSTANT number of pallas_call eqns with no
batch-sized grid dim (one program per chunk); the vmapped control's grids
lead with the batch axis (batch x chunks replication).  On CPU hosts the
kernels run in interpret mode — per-query ms there measures plumbing, not
kernel speed; regress on ``batch_engine_agrees``, not the CPU ratios.

Results land in ``results/bench.json`` AND merge into
``BENCH_serving.json`` as the ``batchfuse`` section.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import merge_serving_section, timed
from repro.core import service, walk as walk_lib
from repro.graphs.synthetic import SyntheticGraphConfig, generate
from repro.kernels.introspect import pallas_grids

BATCHES = (1, 4, 16, 64)
# fixed server capacity, split evenly across the batch (divisible by all
# swept batch sizes): every row runs the same max_chunks and emits the
# same total events per chunk, only the batch axis changes shape
TOTAL_WALKERS = 256
TOTAL_STEPS = 8_192


def _batch(g, seed, batch, n_slots=2):
    rng = np.random.default_rng(seed)
    degs = np.asarray(g.p2b.degrees()).astype(np.float64)
    qs = rng.choice(g.n_pins, size=batch * n_slots, replace=False,
                    p=degs / degs.sum())
    pins = qs.reshape(batch, n_slots).astype(np.int32)
    weights = np.tile(np.asarray([1.0, 0.6], np.float32), (batch, 1))
    return jnp.asarray(pins), jnp.asarray(weights)


def _launch_counts(g, pins, weights, feats, cfg) -> Dict:
    """Kernel-launch structure of one serve step, batched vs vmapped."""
    batch = int(pins.shape[0])

    def batched(key):
        return service.serve_batch(g, pins, weights, feats, key, cfg,
                                   backend="pallas")

    def vmapped(keys):
        pcfg = dataclasses.replace(cfg, backend="pallas")
        return jax.vmap(
            lambda qp, qw, uf, k: walk_lib.recommend_with_stats(
                g, qp, qw, uf, k, pcfg
            )
        )(pins, weights, feats, keys)

    bg = pallas_grids(jax.make_jaxpr(batched)(jax.random.key(0)))
    vg = pallas_grids(
        jax.make_jaxpr(vmapped)(jax.random.split(jax.random.key(0), batch))
    )
    return {
        # pallas_call eqns per while-loop body (x max_chunks trips/serve)
        "batched_calls_per_chunk": len(bg),
        "vmapped_calls_per_chunk": len(vg),
        "batched_grids": [list(x) for x in bg],
        "vmapped_grids": [list(x) for x in vg],
        # the structural claim: no batch-sized leading grid dim vs all
        # (only meaningful past batch 1 — vmap over a size-1 batch is a
        # no-op on the grid shape)
        "batched_batch_in_grid": any(x and x[0] == batch for x in bg)
        and batch > 1,
        "vmapped_batch_in_grid": batch > 1
        and all(x and x[0] == batch for x in vg),
        "max_chunks": cfg.max_chunks(),
    }


def _sweep(seed: int) -> Dict:
    sg = generate(SyntheticGraphConfig(
        n_pins=1_000, n_boards=100, n_topics=8, n_langs=2, seed=seed
    ))
    g = sg.graph
    key = jax.random.key(seed)

    sweep = []
    agree = True
    for batch in BATCHES:
        cfg = walk_lib.WalkConfig(
            n_steps=TOTAL_STEPS // batch, n_walkers=TOTAL_WALKERS // batch,
            chunk_steps=8, top_k=20, n_p=60, n_v=3,
        )
        pins, weights = _batch(g, seed, batch)
        feats = jnp.zeros((batch,), jnp.int32)
        keys = jax.random.split(key, batch)
        row: Dict = {"batch": batch, "n_walkers_per_query": cfg.n_walkers,
                     "n_steps_per_query": cfg.n_steps, "engines": {}}
        outs = {}

        def serve(backend, gather):
            ecfg = dataclasses.replace(cfg, gather_mode=gather)
            return jax.jit(lambda k: service.serve_batch(
                g, pins, weights, feats, k, ecfg, backend=backend,
                with_stats=True,
            ))

        def vmapped(backend):
            vcfg = dataclasses.replace(cfg, backend=backend)
            return jax.jit(lambda ks: jax.vmap(
                lambda qp, qw, uf, k: walk_lib.recommend_with_stats(
                    g, qp, qw, uf, k, vcfg
                )
            )(pins, weights, feats, ks))

        engines = {
            "xla_vmapped": (vmapped("xla"), keys),
            "pallas_batched_scalar": (serve("pallas", "scalar"), key),
            "pallas_batched_dma": (serve("pallas", "dma"), key),
            "pallas_vmapped": (vmapped("pallas"), keys),
        }
        for label, (fn, arg) in engines.items():
            t = timed(fn, arg, warmup=1, iters=2)
            scores, ids, steps, n_high = fn(arg)
            outs[label] = (np.asarray(scores), np.asarray(ids),
                           np.asarray(steps), np.asarray(n_high))
            row["engines"][label] = {
                "batch_ms": round(t["mean_ms"], 2),
                "per_query_ms": round(t["mean_ms"] / batch, 3),
            }
        ref_out = outs["xla_vmapped"]
        row["agree"] = bool(all(
            np.array_equal(a, b)
            for other in ("pallas_batched_scalar", "pallas_batched_dma",
                          "pallas_vmapped")
            for a, b in zip(ref_out, outs[other])
        ))
        agree &= row["agree"]
        row["batched_vs_vmapped_pallas_x"] = round(
            row["engines"]["pallas_vmapped"]["batch_ms"]
            / max(row["engines"]["pallas_batched_scalar"]["batch_ms"], 1e-9),
            3,
        )
        row["launch"] = _launch_counts(g, pins, weights, feats, cfg)
        sweep.append(row)
    # structural invariant across the sweep: batched call count constant
    # and batch-free, vmapped grids batch-replicated
    calls = {r["launch"]["batched_calls_per_chunk"] for r in sweep}
    structure_ok = (
        len(calls) == 1
        and not any(r["launch"]["batched_batch_in_grid"] for r in sweep)
        and all(r["launch"]["vmapped_batch_in_grid"] for r in sweep
                if r["batch"] > 1)
    )
    return {"graph": {"n_pins": g.n_pins, "n_boards": g.n_boards},
            "config": {"total_walkers": TOTAL_WALKERS,
                       "total_steps": TOTAL_STEPS, "chunk_steps": 8},
            "sweep": sweep, "agree_all": agree,
            "one_call_per_chunk": structure_ok}


def run(seed: int = 0) -> Dict:
    out: Dict = {
        "host_backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() == "cpu",
        "batchfuse": _sweep(seed),
    }
    # verdict: batched engine == vmapped per-query path bit-identically
    # (ids, scores, steps_taken, n_high) AND the lowering really is one
    # program per chunk, independent of batch size
    out["batch_engine_agrees"] = bool(
        out["batchfuse"]["agree_all"] and out["batchfuse"]["one_call_per_chunk"]
    )
    out["wrote"] = merge_serving_section("batchfuse", {
        "batch_engine_agrees": out["batch_engine_agrees"],
        "pallas_interpret": out["pallas_interpret"],
        "sweep": [
            {
                "batch": row["batch"],
                "agree": row["agree"],
                "per_query_ms": {
                    k: v["per_query_ms"] for k, v in row["engines"].items()
                },
                "batched_calls_per_chunk":
                    row["launch"]["batched_calls_per_chunk"],
                "vmapped_batch_in_grid":
                    row["launch"]["vmapped_batch_in_grid"],
            }
            for row in out["batchfuse"]["sweep"]
        ],
    })
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
