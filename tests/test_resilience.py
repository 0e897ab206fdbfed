"""Degraded-mode serving: elastic shed budgets, admission accounting,
dead-shard tolerance, and the seeded fault-injection harness.

The contract under test (serving/resilience.py -> server dispatch ->
core/distributed.py -> serving/traffic.py chaos mode):

  * **Shedding is deterministic data, never shape**: a request whose
    queue wait passes ``shed_start_ms`` dispatches with a linearly
    shrunk Eq. 2 budget riding the ``(batch,)`` step_budgets axis — the
    shed result is BIT-identical to an unloaded oracle dispatched via
    ``submit(budget=...)`` with the same number, and shrinking never
    retraces the serve program.
  * **Degradation is accounted**: admission rejections land per-bucket
    in ``ServerStats.rejected`` (while ``dropped`` stays the historical
    total), shed budgets are visible on every ``QueryResult``, and dead
    shards report ``killed`` walkers and a quantified ``overlap_at_k``.
  * **Faults are pure functions of a seed**: the same ``ChaosConfig``
    draws the same ``FaultSchedule``; bursts warp arrivals monotonically
    and spikes defer dispatch to window ends, all on the virtual clock.
  * **Generation barrier** (swap-during-in-flight-user bugfix): a
    multi-interest user's generation is stamped at ``submit_user`` and
    ``swap_graph`` drains every queue before moving the handle, so one
    user's lanes can never mix graph generations.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import walk as walk_lib
from repro.graphs.synthetic import (
    sample_user_histories, small_test_graph, top_degree_pins,
    UserHistoryConfig,
)
from repro.serving.resilience import (
    ResilienceConfig, elastic_step_budget, overlap_at_k,
)
from repro.serving.server import LatencyRing, PixieServer
from repro.serving.traffic import (
    ChaosConfig, FaultEvent, FaultSchedule, OpenLoopConfig,
    apply_traffic_bursts, poisson_requests, run_open_loop,
    sample_fault_schedule,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    base = dict(n_steps=512, n_walkers=32, chunk_steps=8, top_k=20,
                n_p=60, n_v=3)
    base.update(kw)
    return walk_lib.WalkConfig(**base)


# ---------------------------------------------------------------------------
# elastic_step_budget / ResilienceConfig / overlap_at_k: the pure pieces
# ---------------------------------------------------------------------------


def test_elastic_step_budget_policy_curve():
    r = ResilienceConfig(deadline_ms=60.0, shed_start_ms=10.0,
                         min_budget_frac=0.25)
    # at or below shed_start: full budget, untouched
    assert elastic_step_budget(1000, 0.0, r) == 1000
    assert elastic_step_budget(1000, 10.0, r) == 1000
    # linear shrink across the remaining window: wait=35 is halfway
    assert elastic_step_budget(1000, 35.0, r) == 500
    # floor engages before the deadline and holds past it
    assert elastic_step_budget(1000, 60.0, r) == 250
    assert elastic_step_budget(1000, 10_000.0, r) == 250
    # never below one step, even for tiny lane budgets
    assert elastic_step_budget(2, 10_000.0, r) == 1
    assert elastic_step_budget(1, 10_000.0, r) == 1


def test_resilience_config_validates():
    with pytest.raises(ValueError, match="deadline_ms"):
        ResilienceConfig(deadline_ms=0.0)
    with pytest.raises(ValueError, match="shed_start_ms"):
        ResilienceConfig(deadline_ms=10.0, shed_start_ms=10.0)
    with pytest.raises(ValueError, match="min_budget_frac"):
        ResilienceConfig(min_budget_frac=0.0)
    with pytest.raises(ValueError, match="min_budget_frac"):
        ResilienceConfig(min_budget_frac=1.5)


def test_overlap_at_k_edges():
    a = np.array([[1, 2, 3], [4, 5, 6]])
    assert overlap_at_k(a, a) == 1.0
    assert overlap_at_k(a, np.array([[7, 8, 9], [10, 11, 12]])) == 0.0
    # half the oracle's ids recovered, averaged over rows
    got = overlap_at_k(np.array([[1, 2, 7], [4, 8, 9]]), a, k=2)
    assert got == pytest.approx(0.5 * (1.0 + 0.5))
    # padding (-1) is ignored on both sides
    assert overlap_at_k(np.array([[1, 2, -1]]), np.array([[1, 2, -1]])) == 1.0
    # an all-padding oracle row: perfect iff the degraded row is too
    assert overlap_at_k(np.array([[-1, -1]]), np.array([[-1, -1]])) == 1.0
    assert overlap_at_k(np.array([[3, -1]]), np.array([[-1, -1]])) == 0.0
    # 1-D inputs promote to one row
    assert overlap_at_k(np.array([1, 2]), np.array([2, 1])) == 1.0
    with pytest.raises(ValueError, match="rows"):
        overlap_at_k(np.zeros((2, 3)), np.zeros((3, 3)))


# ---------------------------------------------------------------------------
# LatencyRing.percentile edge cases
# ---------------------------------------------------------------------------


def test_latency_ring_percentile_empty_and_single():
    ring = LatencyRing(capacity=4)
    assert ring.percentile(50) == 0.0      # idle replica: 0, not NaN
    assert ring.percentile(99) == 0.0
    ring.append(7.5)
    for p in (0, 50, 99, 100):
        assert ring.percentile(p) == 7.5   # one sample IS every percentile


def test_latency_ring_percentile_exact_capacity_wraparound():
    ring = LatencyRing(capacity=4)
    ring.extend([1.0, 2.0, 3.0, 4.0])      # exactly full, head wrapped to 0
    np.testing.assert_array_equal(ring.values(), [1.0, 2.0, 3.0, 4.0])
    assert ring.percentile(0) == 1.0
    assert ring.percentile(100) == 4.0
    assert ring.percentile(50) == pytest.approx(2.5)
    ring.append(10.0)                      # evicts the oldest (1.0)
    np.testing.assert_array_equal(ring.values(), [2.0, 3.0, 4.0, 10.0])
    assert ring.percentile(0) == 2.0
    assert ring.percentile(100) == 10.0


# ---------------------------------------------------------------------------
# Elastic shed on the server: budgets are data, results match the oracle
# ---------------------------------------------------------------------------


def test_shed_budget_matches_submit_budget_oracle():
    """A request shed at dispatch serves BIT-identically to an unloaded
    server handed the same shrunk budget via submit(budget=...) — the
    whole degradation is the budget number, not timing or batching."""
    sg = small_test_graph()
    cfg = _cfg()
    qs = top_degree_pins(sg, 4)
    rcfg = ResilienceConfig(deadline_ms=60.0, shed_start_ms=10.0,
                            min_budget_frac=0.25)
    srv = PixieServer(sg.graph, cfg, batch_size=2, n_slots=4, seed=7,
                      max_wait_ms=5.0, resilience=rcfg)
    srv.submit([int(qs[0]), int(qs[1])], [1.0, 0.6], now=0.0, req_id=0)
    srv.submit([int(qs[2])], [1.0], now=0.0, req_id=1)
    srv.pump(now=0.035)                    # 35 ms wait: halfway shrink
    shed = {r.req_id: r for r in srv.harvest()}
    want = elastic_step_budget(cfg.n_steps, 35.0, rcfg)
    assert want < cfg.n_steps
    assert shed[0].budget == want and shed[1].budget == want

    oracle = PixieServer(sg.graph, cfg, batch_size=2, n_slots=4, seed=7)
    oracle.submit([int(qs[0]), int(qs[1])], [1.0, 0.6], req_id=0,
                  budget=want)
    oracle.submit([int(qs[2])], [1.0], req_id=1, budget=want)
    ref = {r.req_id: r for r in oracle.flush()}
    for rid in (0, 1):
        np.testing.assert_array_equal(shed[rid].scores, ref[rid].scores)
        np.testing.assert_array_equal(shed[rid].ids, ref[rid].ids)
        assert ref[rid].budget == want


def test_unloaded_resilient_server_is_bit_identical_to_plain():
    """Waits under shed_start_ms never shrink: the resilience layer costs
    nothing on a good day (the zero-fault half of verdict 17)."""
    sg = small_test_graph()
    cfg = _cfg()
    qs = top_degree_pins(sg, 2)

    def serve(resilience):
        srv = PixieServer(sg.graph, cfg, batch_size=2, n_slots=4, seed=3,
                          resilience=resilience)
        srv.submit([int(qs[0])], [1.0], now=0.0, req_id=0)
        srv.submit([int(qs[1])], [1.0], now=0.0, req_id=1)
        return {r.req_id: r for r in srv.flush(now=0.0)}

    plain = serve(None)
    idle = serve(ResilienceConfig(deadline_ms=60.0, shed_start_ms=10.0))
    for rid in (0, 1):
        np.testing.assert_array_equal(plain[rid].scores, idle[rid].scores)
        np.testing.assert_array_equal(plain[rid].ids, idle[rid].ids)
        assert idle[rid].budget == cfg.n_steps


def test_submit_budget_validates():
    sg = small_test_graph()
    srv = PixieServer(sg.graph, _cfg(), batch_size=2, n_slots=4)
    with pytest.raises(ValueError, match="budget"):
        srv.submit([1], [1.0], budget=0)
    with pytest.raises(ValueError, match="budget"):
        srv.submit([1], [1.0], budget=srv.cfg.n_steps + 1)
    assert srv.pending() == 0


def test_ranked_replica_rejects_elastic_resilience():
    import jax

    from repro.serving import ranker as ranker_lib

    sg = small_test_graph()
    rcfg = ranker_lib.RankerConfig(
        n_items=sg.graph.n_pins, d_model=16, n_neighbors=4,
        n_candidates=16, final_k=8,
    )
    ranker = ranker_lib.RankRequest(
        ranker_lib.init_ranker_params(jax.random.key(7), rcfg), rcfg
    )
    with pytest.raises(ValueError, match="elastic"):
        PixieServer(sg.graph, _cfg(), ranker=ranker,
                    resilience=ResilienceConfig())
    # admission-only resilience is fine on a ranked replica
    srv = PixieServer(sg.graph, _cfg(), ranker=ranker,
                      resilience=ResilienceConfig(elastic=False,
                                                  max_queue_per_bucket=4))
    assert srv.max_queue_per_bucket == 4


# ---------------------------------------------------------------------------
# Admission accounting: per-bucket rejections (satellite bugfix)
# ---------------------------------------------------------------------------


def test_rejections_accounted_per_bucket():
    """Submit-time rejections used to vanish into the undifferentiated
    ``dropped`` counter; they are now attributable per bucket while
    ``dropped`` keeps the historical total-refused-work meaning."""
    sg = small_test_graph()
    srv = PixieServer(sg.graph, _cfg(n_steps=256),
                      buckets=[(4, 2), (4, 8)], max_queue_per_bucket=1)
    qs = top_degree_pins(sg, 6)
    small = [int(qs[0])]
    large = [int(q) for q in qs[:6]]
    assert srv.submit(small, [1.0]) is not None
    assert srv.submit(small, [1.0]) is None          # 2-slot queue full
    assert srv.submit(small, [1.0]) is None
    assert srv.submit(large, [1.0] * 6) is not None
    assert srv.submit(large, [1.0] * 6) is None      # 8-slot queue full
    assert srv.stats.rejected == {2: 2, 8: 1}
    assert srv.stats.rejected_total == 3
    assert srv.stats.dropped == 3                    # total stays total
    srv.flush()


def test_open_loop_report_carries_rejections_and_budgets():
    """The harness surfaces admission rejections (part of n_dropped) and
    the per-request dispatched budgets — the replay record."""
    sg = small_test_graph()
    candidates = top_degree_pins(sg, 8).astype(np.int32)
    workload = poisson_requests(candidates, OpenLoopConfig(
        offered_qps=100_000.0, n_requests=10, seed=0, max_pins=2,
    ))
    # bucket batch (4) > queue bound (2): arrivals 10 us apart overflow
    # the queue before the 1 ms formation deadline can drain it
    srv = PixieServer(sg.graph, _cfg(n_steps=256), buckets=[(4, 2)],
                      max_wait_ms=1.0, max_queue_per_bucket=2)
    report = run_open_loop(srv, workload)
    assert report.n_rejected > 0
    assert report.n_rejected <= report.n_dropped     # part of, not extra
    assert report.n_served + report.n_dropped == report.n_offered
    assert report.summary()["n_rejected"] == report.n_rejected
    # every served request reports the budget it dispatched with
    assert set(report.budgets) == set(report.results)
    assert all(b == 256 for b in report.budgets.values())  # no resilience


# ---------------------------------------------------------------------------
# Seeded fault injection: pure functions of the chaos seed
# ---------------------------------------------------------------------------


def test_fault_schedule_is_seeded_and_validates():
    cfg = ChaosConfig(horizon_s=1.0, seed=9, n_spikes=3, n_bursts=2,
                      n_shard_deaths=2, n_shards=4)
    a = sample_fault_schedule(cfg)
    b = sample_fault_schedule(cfg)
    assert a == b                                    # frozen, bit-equal
    assert len(a.events) == 7
    assert len(a.of_kind("latency_spike")) == 3
    assert len(a.of_kind("traffic_burst")) == 2
    deaths = a.of_kind("shard_death")
    assert all(0 <= e.shard < 4 for e in deaths)
    assert sample_fault_schedule(
        ChaosConfig(horizon_s=1.0, seed=10, n_spikes=3)
    ) != a
    with pytest.raises(ValueError, match="horizon_s"):
        ChaosConfig(horizon_s=0.0)
    with pytest.raises(ValueError, match="burst_factor"):
        ChaosConfig(horizon_s=1.0, burst_factor=0.5)
    with pytest.raises(ValueError, match="n_shards"):
        ChaosConfig(horizon_s=1.0, n_shard_deaths=1)


def test_defer_slides_past_cascading_spike_windows():
    faults = FaultSchedule(events=(
        FaultEvent(kind="latency_spike", t_start=1.0, duration_s=0.5),
        FaultEvent(kind="latency_spike", t_start=1.4, duration_s=0.5),
    ))
    assert faults.defer(0.5) == 0.5                  # outside: untouched
    assert faults.defer(1.2) == 1.9                  # chains both windows
    assert faults.defer(1.9) == 1.9                  # boundary is open
    assert FaultSchedule().defer(3.0) == 3.0         # empty schedule


def test_traffic_bursts_warp_monotonically_and_keep_payloads():
    candidates = np.arange(50, dtype=np.int32)
    reqs = poisson_requests(candidates, OpenLoopConfig(
        offered_qps=100.0, n_requests=20, seed=4, max_pins=4,
    ))
    faults = FaultSchedule(events=(
        FaultEvent(kind="traffic_burst", t_start=0.05, duration_s=0.1,
                   factor=4.0),
    ))
    warped = apply_traffic_bursts(reqs, faults)
    ts = [r.t_arrival for r in warped]
    assert ts == sorted(ts)                          # order preserved
    assert any(w.t_arrival < r.t_arrival for w, r in zip(warped, reqs))
    for w, r in zip(warped, reqs):                   # payloads untouched
        assert (w.req_id, w.pins, w.weights) == (r.req_id, r.pins, r.weights)
        assert w.t_arrival <= r.t_arrival
        if not (0.05 <= r.t_arrival < 0.15):
            assert w.t_arrival == r.t_arrival


def test_zero_fault_chaos_run_is_bit_identical_to_plain():
    """An empty FaultSchedule plus never-engaging thresholds reproduce
    the plain open-loop run exactly (the verdict-17 zero-fault leg, in
    miniature)."""
    sg = small_test_graph()
    cfg = _cfg(n_steps=256)
    candidates = top_degree_pins(sg, 8).astype(np.int32)
    workload = poisson_requests(candidates, OpenLoopConfig(
        offered_qps=300.0, n_requests=8, seed=2, max_pins=4,
    ))

    def serve(resilience, faults):
        srv = PixieServer(sg.graph, cfg, seed=2, buckets=[(2, 2), (2, 4)],
                          max_wait_ms=3.0, resilience=resilience)
        return run_open_loop(srv, workload, faults=faults)

    plain = serve(None, None)
    idle = serve(ResilienceConfig(deadline_ms=1e6, shed_start_ms=1e5),
                 FaultSchedule())
    assert len(plain.results) == len(idle.results) == len(workload)
    for rid, p in plain.results.items():
        np.testing.assert_array_equal(p.scores, idle.results[rid].scores)
        np.testing.assert_array_equal(p.ids, idle.results[rid].ids)
    assert all(b == cfg.n_steps for b in idle.budgets.values())


# ---------------------------------------------------------------------------
# Generation barrier: swap during an in-flight multi-interest user
# ---------------------------------------------------------------------------


def test_swap_graph_never_mixes_generations_within_a_user():
    """Regression (satellite bugfix): a user whose lanes straddled a
    ``swap_graph`` used to walk SOME lanes on the old graph and the rest
    on the new one, max-folding the generations into one merged result.
    Now the generation is stamped at ``submit_user`` and the swap drains
    every queue first, so the user serves entirely on the graph it was
    admitted under — bit-identical to a no-swap oracle."""
    sg = small_test_graph()
    other = small_test_graph(123)        # same shape, different content
    assert not np.array_equal(
        np.asarray(sg.graph.p2b.targets), np.asarray(other.graph.p2b.targets)
    )
    hist = sample_user_histories(sg, UserHistoryConfig(
        n_users=1, n_interests=3, mean_actions=18, seed=5,
    ))[0]
    cfg = _cfg(n_steps=256, backend="xla")

    def serve(swap):
        srv = PixieServer(sg.graph, cfg, batch_size=2, n_slots=8, seed=11,
                          pin_topics=sg.pin_topics, n_clusters=3)
        rid = srv.submit_user(hist.actions, user_feat=1, now=0.0, req_id=42)
        srv.pump(now=0.0)                # full 2-lane batch dispatches
        if swap:
            assert srv.pending() >= 1    # a lane is still queued
            srv.swap_graph(other.graph, now=0.0)   # barrier drains it
            assert srv.pending() == 0
        while srv.pending():
            srv.pump(now=srv.next_deadline())
        out = {r.req_id: r for r in srv.harvest()}
        return srv, out[rid]

    srv_swap, swapped = serve(swap=True)
    assert srv_swap.stats.graph_generation == 1
    # the user was admitted under generation 0 and served entirely there
    assert swapped.generation == 0
    _, oracle = serve(swap=False)
    np.testing.assert_array_equal(swapped.scores, oracle.scores)
    np.testing.assert_array_equal(swapped.ids, oracle.ids)


# ---------------------------------------------------------------------------
# Dead-shard tolerance: the pod engine under a death schedule
# ---------------------------------------------------------------------------


def _run(n_devices: int, body: str) -> dict:
    """Execute `body` in a fresh python with n fake devices; body must
    print a single json object on its last line (same harness as
    test_distributed.py — jax locks its device count at import)."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_dead_shard_kills_walkers_and_renormalizes():
    """A shard dying mid-walk: its resident walkers are killed (counted,
    distinct from capacity drops) and reborn at home, its counts zero out
    of the merge, an all-INT32_MAX schedule is bit-identical to the
    healthy None path, and the same schedule replays bit-identically."""
    res = _run(2, """
        from repro.core import distributed as D, walk as W
        from repro.graphs.synthetic import small_test_graph, top_degree_pins

        sg = small_test_graph()
        g = sg.graph
        mesh = make_mesh((2,), ("model",))
        shg = D.shard_graph(g, 2)
        qs = top_degree_pins(sg, 4)
        cfg = W.WalkConfig(n_steps=1024, n_walkers=32, chunk_steps=4,
                           n_p=30, n_v=3, bias_beta=0.0, count_boards=True)
        pins = np.full((2, 2), -1, np.int32)
        weights = np.zeros((2, 2), np.float32)
        for b in range(2):
            pins[b] = qs[2 * b:2 * b + 2]
            weights[b] = (1.0, 0.6)
        keys = jax.random.split(jax.random.key(0), 2)
        never = np.iinfo(np.int32).max

        with jax.set_mesh(mesh):
            def walk(dead):
                return jax.block_until_ready(D.pixie_walk_sharded_batched(
                    shg, jnp.asarray(pins), jnp.asarray(weights), keys,
                    cfg, mesh, slack=8.0,
                    shard_dead_at=None if dead is None else jnp.asarray(
                        np.asarray(dead, np.int32)),
                ))

            healthy = walk(None)
            all_never = walk([never, never])
            faulted = walk([never, 2])
            faulted2 = walk([never, 2])

        def eq(a, b):
            return all(
                np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in ((a.counts, b.counts),
                             (a.steps_taken, b.steps_taken),
                             (a.n_high, b.n_high))
            )

        from repro.core import counter as C
        folded = np.asarray(C.fold_sharded_counts(
            faulted.counts, 2, 2, shg.pins_per_shard))
        pps = shg.pins_per_shard
        print(json.dumps({
            "never_is_healthy": eq(healthy, all_never)
                                 and int(all_never.killed) == 0,
            "healthy_killed_is_none": healthy.killed is None,
            "killed": int(faulted.killed),
            "dropped": int(faulted.dropped),
            "dead_zeroed": bool(folded[..., pps:].sum() == 0),
            "survivors": bool(folded[..., :pps].sum() > 0),
            "replays": eq(faulted, faulted2)
                        and int(faulted2.killed) == int(faulted.killed),
        }))
    """)
    assert res["never_is_healthy"], res
    assert res["healthy_killed_is_none"], res
    assert res["killed"] > 0, res
    assert res["dropped"] == 0, res          # kills are NOT capacity drops
    assert res["dead_zeroed"], res
    assert res["survivors"], res
    assert res["replays"], res


def test_dead_shard_validation_and_plain_replica_guards():
    """The fault surface fails loudly where it can't apply: wrong-shape
    schedules, unsharded serve_batch, kill_shard on a plain replica."""
    import jax
    import jax.numpy as jnp

    from repro.core import service

    sg = small_test_graph()
    srv = PixieServer(sg.graph, _cfg(n_steps=256), batch_size=2, n_slots=2)
    with pytest.raises(ValueError, match="sharded"):
        srv.kill_shard(0)
    with pytest.raises(ValueError, match="sharded"):
        srv.revive_shards()
    assert srv.dead_shards() == []
    with pytest.raises(ValueError, match="ShardedGraph"):
        service.serve_batch(
            sg.graph,
            jnp.asarray(np.full((1, 2), -1, np.int32)),
            jnp.zeros((1, 2), jnp.float32),
            jnp.zeros((1,), jnp.int32),
            jax.random.key(0),
            _cfg(n_steps=256),
            shard_dead_at=jnp.zeros((2,), jnp.int32),
        )
