"""Degraded-mode serving under seeded chaos: elastic budget shedding,
dead-shard tolerance, and the fault-injection harness — the bad-day twin
of the ``traffic`` suite.

The paper's 1,200 QPS / 60 ms p99 (§3.3) is a fair-weather number.  This
suite stresses the resilience layer (``serving/resilience.py``) end to
end and pins its one load-bearing property: DEGRADATION IS DETERMINISTIC
AND ACCOUNTED, never silent.  Three legs feed one verdict,
``degraded_serving_agrees``:

  * **shed parity** — a chaos run (seeded latency spikes + traffic
    bursts, ``sample_fault_schedule``) against an elastic
    ``ResilienceConfig``: queue waits grow through the spike windows,
    per-request step budgets shrink (Eq. 2 is elastic — fewer steps is a
    valid coarser Monte Carlo estimate), and the recorded
    ``report.budgets`` replayed through an UNLOADED single-bucket oracle
    via ``submit(budget=...)`` must reproduce every score and id
    bit-for-bit — across backend x gather (xla/scalar, pallas/scalar,
    pallas/dma).  Shedding is a pure function of the virtual clock, and
    budgets are data on the ``(batch,)`` axis, so nothing retraces.
    Same seed twice must replay budgets AND results bit-identically.

  * **zero-fault parity** — an empty ``FaultSchedule`` plus resilience
    thresholds that never engage must be bit-identical to a plain PR 7
    open-loop run with no resilience layer at all: the bad-day machinery
    costs nothing on a good day.

  * **dead-shard tolerance** (8 forced host devices, 4-shard pod) — an
    all-``INT32_MAX`` death schedule is bit-identical to the healthy
    ``None`` path; a shard killed mid-walk has its walkers killed and
    reborn at home (``killed`` counted, distinct from capacity drops),
    its counts zeroed out of the merge, and the quality cost quantified
    as ``overlap_at_k`` against the all-alive oracle; ``revive_shards``
    restores bit-identical healthy serving.  Same death schedule replays
    bit-identically.

On CPU hosts the pallas legs run in interpret mode and the 8 "devices"
share one machine — regress on the agreement verdict, never on CPU
timings.  Needs 8 devices (``common.run_multi_device``): it runs in this
process where JAX already sees 8; a CPU host re-executes this module in a
child with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; an
accelerator host with fewer chips refuses (one process per chip).

Results land in ``results/bench.json`` AND merge into
``BENCH_serving.json`` as the ``chaos`` section.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict

N_DEVICES = 8
N_SHARDS = 4
BUCKETS = ((4, 2), (2, 8))    # small / large (batch, n_slots)
ORACLE_BATCH = 4              # single-bucket replay-oracle shape
MAX_WAIT_MS = 4.0
SHED_CELLS = (("xla", "scalar"), ("pallas", "scalar"), ("pallas", "dma"))


def _child_run(seed: int) -> Dict:
    """The suite body, on 8 devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import counter as counter_lib
    from repro.core import distributed as dist_lib
    from repro.core import walk as walk_lib
    from repro.graphs.synthetic import (
        SyntheticGraphConfig, generate, small_test_graph, top_degree_pins,
    )
    from repro.launch.mesh import make_mesh
    from repro.serving.resilience import ResilienceConfig, overlap_at_k
    from repro.serving.server import PixieServer
    from repro.serving.traffic import (
        ChaosConfig, FaultSchedule, OpenLoopConfig, poisson_requests,
        run_open_loop, sample_fault_schedule,
    )

    def hot_pins(g, n, s):
        rng = np.random.default_rng(s)
        degs = np.asarray(g.p2b.degrees()).astype(np.float64)
        return rng.choice(g.n_pins, size=n, replace=False,
                          p=degs / degs.sum()).astype(np.int32)

    # -- leg 1: elastic shed parity + reproducibility, backend x gather ----
    sg = generate(SyntheticGraphConfig(
        n_pins=1_000, n_boards=120, n_topics=8, n_langs=2, seed=seed,
    ))
    g = sg.graph
    base = walk_lib.WalkConfig(
        n_steps=400, n_walkers=32, chunk_steps=8, top_k=20, n_p=60, n_v=3,
    )
    candidates = hot_pins(g, 48, seed)
    workload = poisson_requests(candidates, OpenLoopConfig(
        offered_qps=300.0, n_requests=16, seed=seed, max_pins=6,
    ))
    horizon = workload[-1].t_arrival
    faults = sample_fault_schedule(ChaosConfig(
        horizon_s=horizon, seed=seed + 1, n_spikes=3, spike_duration_s=0.03,
        n_bursts=2, burst_duration_s=0.02, burst_factor=4.0,
    ))
    rcfg = ResilienceConfig(
        deadline_ms=60.0, shed_start_ms=5.0, min_budget_frac=0.25,
    )

    def chaos_run(cfg):
        srv = PixieServer(
            g, cfg, seed=seed, buckets=BUCKETS, max_wait_ms=MAX_WAIT_MS,
            resilience=rcfg,
        )
        return run_open_loop(srv, workload, max_backlog_s=None,
                             faults=faults)

    shed_cells = []
    shed_all_agree = True
    any_shed = False
    reproducible = True
    for backend, gather in SHED_CELLS:
        cfg = dataclasses.replace(base, backend=backend, gather_mode=gather)
        report = chaos_run(cfg)
        # replay oracle: UNLOADED single-bucket flush with the recorded
        # budgets — different batch composition, same per-request fold_in
        # streams, so bit-parity here proves budgets (not batching or
        # timing) are the whole degradation
        oracle = PixieServer(
            g, cfg, batch_size=ORACLE_BATCH, n_slots=8, seed=seed,
        )
        for req in workload:
            oracle.submit(list(req.pins), list(req.weights), req.user_feat,
                          req_id=req.req_id,
                          budget=report.budgets[req.req_id])
        oracle_out = {r.req_id: r for r in oracle.flush()}
        agree = len(report.results) == len(workload) == len(oracle_out)
        for req in workload:
            c = report.results.get(req.req_id)
            o = oracle_out.get(req.req_id)
            if c is None or o is None:
                agree = False
                break
            agree &= bool(np.array_equal(c.scores, o.scores))
            agree &= bool(np.array_equal(c.ids, o.ids))
            if not agree:
                break
        n_shrunk = sum(
            1 for b in report.budgets.values() if b < base.n_steps
        )
        any_shed |= n_shrunk > 0
        # reproducibility: the same seed + schedule replays bit-for-bit
        replay = chaos_run(cfg)
        rep_ok = replay.budgets == report.budgets
        for rid, c in report.results.items():
            r2 = replay.results.get(rid)
            rep_ok &= r2 is not None and bool(
                np.array_equal(c.ids, r2.ids)
                and np.array_equal(c.scores, r2.scores)
            )
            if not rep_ok:
                break
        shed_all_agree &= agree
        reproducible &= bool(rep_ok)
        shed_cells.append({
            "backend": backend, "gather_mode": gather,
            "shed_matches_budget_oracle": bool(agree),
            "replay_bit_identical": bool(rep_ok),
            "n_shrunk": n_shrunk,
            "min_budget": min(report.budgets.values()),
            "n_rejected": report.n_rejected,
        })

    # -- leg 2: zero faults + never-engaging thresholds == plain run -------
    cfg = dataclasses.replace(base, backend="xla")
    plain = PixieServer(
        g, cfg, seed=seed, buckets=BUCKETS, max_wait_ms=MAX_WAIT_MS,
    )
    plain_report = run_open_loop(plain, workload, max_backlog_s=None)
    idle = PixieServer(
        g, cfg, seed=seed, buckets=BUCKETS, max_wait_ms=MAX_WAIT_MS,
        resilience=ResilienceConfig(deadline_ms=1e6, shed_start_ms=1e5),
    )
    idle_report = run_open_loop(idle, workload, max_backlog_s=None,
                                faults=FaultSchedule())
    zero_fault_ok = (
        len(plain_report.results) == len(idle_report.results) == len(workload)
        and all(b == base.n_steps for b in idle_report.budgets.values())
    )
    for rid, p in plain_report.results.items():
        q = idle_report.results.get(rid)
        zero_fault_ok &= q is not None and bool(
            np.array_equal(p.ids, q.ids)
            and np.array_equal(p.scores, q.scores)
        )
        if not zero_fault_ok:
            break

    # -- leg 3: dead-shard tolerance on a 4-shard pod ----------------------
    tsg = small_test_graph(seed)
    tg = tsg.graph
    qs = top_degree_pins(tsg, 8)
    dcfg = walk_lib.WalkConfig(
        n_steps=2_048, n_walkers=32, chunk_steps=4, top_k=20,
        n_p=30, n_v=3, bias_beta=0.0, count_boards=True,
    )
    mesh = make_mesh((N_SHARDS,), ("model",))
    shg = dist_lib.shard_graph(tg, N_SHARDS, mesh)
    batch, n_slots = 4, 4
    pins = np.full((batch, n_slots), -1, np.int32)
    weights = np.zeros((batch, n_slots), np.float32)
    for b in range(batch):
        pins[b, :2] = qs[2 * b:2 * b + 2]
        weights[b, :2] = (1.0, 0.6)
    pins_j, weights_j = jnp.asarray(pins), jnp.asarray(weights)
    keys = jax.random.split(jax.random.key(seed), batch)
    never = np.iinfo(np.int32).max
    victim = 2
    death_step = 3
    dead_sched = np.full((N_SHARDS,), never, np.int32)
    dead_sched[victim] = death_step

    with jax.set_mesh(mesh):
        def engine(dead_at):
            return dist_lib.pixie_walk_sharded_batched(
                shg, pins_j, weights_j, keys, dcfg, mesh, slack=16.0,
                shard_dead_at=(
                    None if dead_at is None else jnp.asarray(dead_at)
                ),
            )

        healthy = jax.block_until_ready(engine(None))
        all_never = jax.block_until_ready(
            engine(np.full((N_SHARDS,), never, np.int32))
        )
        faulted = jax.block_until_ready(engine(dead_sched))
        faulted2 = jax.block_until_ready(engine(dead_sched))

        def folded(res):
            return np.asarray(counter_lib.fold_sharded_counts(
                res.counts, batch, n_slots, shg.pins_per_shard
            ))

        # an all-INT32_MAX schedule is value-identical to no schedule:
        # the server compiles ONE faulty program for both weathers
        never_parity = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in (
                (healthy.counts, all_never.counts),
                (healthy.steps_taken, all_never.steps_taken),
                (healthy.n_high, all_never.n_high),
            )
        ) and int(all_never.killed) == 0
        pps = shg.pins_per_shard
        dead_zeroed = bool(
            folded(faulted)[..., victim * pps:(victim + 1) * pps].sum() == 0
        )
        survivors_counted = bool(folded(faulted).sum() > 0)
        killed = int(faulted.killed)
        death_replay_ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in (
                (faulted.counts, faulted2.counts),
                (faulted.steps_taken, faulted2.steps_taken),
                (faulted.n_high, faulted2.n_high),
            )
        ) and int(faulted2.killed) == killed

        # server surface: kill_shard -> degraded top-k, quantified vs the
        # healthy oracle; revive_shards -> bit-identical healthy serving
        def serve(kill):
            srv = PixieServer(shg, dcfg, batch_size=batch, n_slots=n_slots,
                              seed=seed, mesh=mesh, slack=16.0)
            if kill:
                srv.kill_shard(victim, at_superstep=death_step)
            for i in range(batch):
                srv.submit([int(p) for p in pins[i] if p >= 0],
                           [float(w) for w in weights[i] if w > 0],
                           req_id=i)
            return srv, {r.req_id: r for r in srv.flush()}

        srv_h, out_h = serve(kill=False)
        srv_d, out_d = serve(kill=True)
        overlap = overlap_at_k(
            np.stack([np.asarray(out_d[i].ids) for i in range(batch)]),
            np.stack([np.asarray(out_h[i].ids) for i in range(batch)]),
        )
        degraded_differs = any(
            not np.array_equal(out_d[i].ids, out_h[i].ids)
            for i in range(batch)
        )
        srv_d.revive_shards()
        for i in range(batch):
            srv_d.submit([int(p) for p in pins[i] if p >= 0],
                         [float(w) for w in weights[i] if w > 0],
                         req_id=i)
        revived = {r.req_id: r for r in srv_d.flush()}
        revive_ok = all(
            np.array_equal(revived[i].ids, out_h[i].ids)
            and np.array_equal(revived[i].scores, out_h[i].scores)
            for i in range(batch)
        )

    dead_shard = {
        "n_shards": N_SHARDS, "victim": victim,
        "death_superstep": death_step,
        "never_schedule_matches_healthy": bool(never_parity),
        "killed": killed,
        "killed_counted": killed > 0,
        "dead_shard_counts_zeroed": dead_zeroed,
        "survivors_counted": survivors_counted,
        "death_replay_bit_identical": bool(death_replay_ok),
        "overlap_at_k": round(float(overlap), 4),
        "revive_restores_healthy": bool(revive_ok),
        "degraded_differs_from_oracle": bool(degraded_differs),
    }
    dead_shard["ok"] = bool(
        never_parity and killed > 0 and dead_zeroed and survivors_counted
        and death_replay_ok and 0.0 <= overlap <= 1.0 and revive_ok
    )

    return {
        "host_backend": jax.default_backend(),
        "pallas_interpret": jax.default_backend() == "cpu",
        "n_devices": len(jax.devices()),
        "buckets": [list(b) for b in BUCKETS],
        "n_requests": len(workload),
        "n_faults": len(faults.events),
        "shed": {
            "cells": shed_cells,
            "all_agree": bool(shed_all_agree),
            "reproducible": bool(reproducible),
            "any_shed": bool(any_shed),
        },
        "zero_fault": {"bit_identical": bool(zero_fault_ok)},
        "dead_shard": dead_shard,
    }


def run(seed: int = 0) -> Dict:
    """Driver entry: the chaos legs on 8 devices (``run_multi_device``)."""
    from benchmarks.common import merge_serving_section, run_multi_device

    ch: Dict = run_multi_device(
        "benchmarks.bench_chaos", N_DEVICES, seed, _child_run
    )
    out: Dict = {"chaos": ch}
    # verdict: (1) shed-budget chaos results bit-identical to an unloaded
    # oracle dispatched with the same shrunk budgets, across backend x
    # gather, with shedding actually engaged and the whole run seed-
    # reproducible; (2) zero-fault chaos bit-identical to the plain
    # open-loop run; (3) dead-shard serving kills-and-counts, zeroes the
    # dead shard's counts, quantifies overlap, and revives bit-clean
    out["degraded_serving_agrees"] = bool(
        ch["shed"]["all_agree"]
        and ch["shed"]["reproducible"]
        and ch["shed"]["any_shed"]
        and ch["zero_fault"]["bit_identical"]
        and ch["dead_shard"]["ok"]
    )
    out["wrote"] = merge_serving_section("chaos", {
        "degraded_serving_agrees": out["degraded_serving_agrees"],
        "pallas_interpret": ch["pallas_interpret"],
        "shed": ch["shed"],
        "zero_fault": ch["zero_fault"],
        "dead_shard": ch["dead_shard"],
    })
    return out


def _child_main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(_child_run(args.seed)))
        return 0
    print(json.dumps(run(args.seed), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
