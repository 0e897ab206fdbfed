"""Pod-sharded batched fused walk engine (core/distributed.py).

The acceptance claims of the sharded engine, each pinned here:

  * **Bit parity.**  On a replicated-graph control, the sharded engine's
    folded counts / board counts / ``steps_taken`` / ``n_high`` are
    bit-identical to ``walk.pixie_random_walk_batched`` — fused pallas
    supersteps (both gather modes) AND the plain-XLA oracle twin, across
    shard counts, with Algorithm 3's early stopping active and zero
    routed-walker drops.
  * **Drops are counted, never silent.**  Starving the ``_route`` fabric
    (tiny ``slack``) produces a positive ``dropped`` tally surfaced all
    the way through ``serve_batch(with_stats=True)``; raising ``slack``
    drives it back to zero — at which point sharded serving's scores
    match unsharded serving exactly.
  * **Per-shard supersteps, not per-query.**  The number of fused
    ``pallas_call``s in a sharded superstep is independent of the batch
    size (the whole batch shares each shard's kernels), and the
    early-stop fold inside the ``while`` body is the incremental carried
    tally — no reduction over a full count buffer.
  * **``shard_graph`` edge cases.**  Indivisible id spaces pad with
    degree-0 ghost rows, empty shard-local CSR rows survive the slicing,
    and ``abstract_sharded_graph`` (the dry-run stand-in) agrees with
    ``shard_graph``'s real output on shapes, dtypes and padded sizes.

Multi-device tests run in subprocesses (device count locks at jax init);
trace-only structural pins run in-process on a 1-device model mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import counter as counter_lib
from repro.core import distributed as dist_lib
from repro.core import walk as walk_lib
from repro.core.graph import build_graph
from repro.graphs.synthetic import small_test_graph, top_degree_pins
from repro.kernels.introspect import full_buffer_reduces, iter_eqns
from repro.launch.mesh import make_mesh
from test_distributed import _run


# ---------------------------------------------------------------------------
# bit parity vs the unsharded batched engine (replicated-graph control)
# ---------------------------------------------------------------------------

_PARITY_BODY = """
    import dataclasses
    from repro.graphs.synthetic import small_test_graph, top_degree_pins
    from repro.core import counter as C, distributed as D, walk as W

    n_shards = %d
    sg = small_test_graph()
    g = sg.graph
    mesh = make_mesh(%s)
    shg = D.shard_graph(g, n_shards)
    qs = top_degree_pins(sg, 4)
    qp = jnp.asarray([[int(qs[0]), int(qs[1]), -1, -1],
                      [int(qs[2]), int(qs[3]), int(qs[0]), -1]], jnp.int32)
    qw = jnp.asarray([[1.0, 0.7, 0.0, 0.0],
                      [1.0, 0.5, 0.25, 0.0]], jnp.float32)
    uf = jnp.zeros((2,), jnp.int32)
    keys = jax.random.split(jax.random.key(7), 2)
    base = W.WalkConfig(n_steps=6144, n_walkers=64, chunk_steps=4,
                        n_p=30, n_v=3, bias_beta=0.0, count_boards=True)

    out = {}
    with jax.set_mesh(mesh):
        for backend, gather in (("xla", "scalar"), ("pallas", "scalar"),
                                ("pallas", "dma")):
            cfg = dataclasses.replace(base, backend=backend,
                                      gather_mode=gather)
            ref = W.pixie_random_walk_batched(g, qp, qw, uf, keys, cfg)
            res = D.pixie_walk_sharded_batched(
                shg, qp, qw, keys, cfg, mesh, slack=2.0 * n_shards)
            counts = C.fold_sharded_counts(
                res.counts, 2, 4, shg.pins_per_shard)[..., :g.n_pins]
            bc = C.fold_sharded_counts(
                res.board_counts, 2, 4,
                shg.boards_per_shard)[..., :g.n_boards]
            out[backend + "/" + gather] = {
                "counts": bool((np.asarray(counts)
                                == np.asarray(ref.counts)).all()),
                "boards": bool((np.asarray(bc)
                                == np.asarray(ref.board_counts)).all()),
                "steps": bool((np.asarray(res.steps_taken)
                               == np.asarray(ref.steps_taken)).all()),
                "n_high": bool((np.asarray(res.n_high)
                                == np.asarray(ref.n_high)).all()),
                "dropped": int(res.dropped),
                "stopped_early": bool(
                    (np.asarray(ref.n_high) > cfg.n_p).any()),
            }
    print(json.dumps(out))
"""


@pytest.mark.parametrize(
    "n_shards,mesh_spec",
    [(2, '(2, 2), ("data", "model")'), (4, '(4,), ("model",)')],
)
def test_sharded_engine_bit_parity_with_unsharded_batched(
    n_shards, mesh_spec
):
    """Acceptance criterion: fused sharded == xla sharded == unsharded
    batched, bit-for-bit, with early stopping active and zero drops."""
    res = _run(4, _PARITY_BODY % (n_shards, mesh_spec))
    for combo, r in res.items():
        assert r["dropped"] == 0, (combo, r)
        assert r["counts"] and r["boards"], (combo, r)
        assert r["steps"] and r["n_high"], (combo, r)
        # the control is only meaningful if Algorithm 3 actually fired
        assert r["stopped_early"], (combo, r)


# ---------------------------------------------------------------------------
# routing-overflow drops: counted, surfaced, tunable to zero
# ---------------------------------------------------------------------------


def test_route_drops_counted_and_zeroed_by_slack():
    """Capacity overflow must never be silent: a starved fabric reports a
    positive ``dropped`` through ``serve_batch(with_stats=True)`` and
    through ``ShardedWalkConfig.slack``; raising slack zeroes it, and a
    drop-free sharded serve matches unsharded serving score-for-score."""
    res = _run(4, """
        import dataclasses
        from repro.graphs.synthetic import small_test_graph, top_degree_pins
        from repro.core import distributed as D, service as S, walk as W

        sg = small_test_graph()
        g = sg.graph
        mesh = make_mesh((2, 2), ("data", "model"))
        shg = D.shard_graph(g, 2)
        qs = top_degree_pins(sg, 4)
        qp = jnp.asarray([[int(qs[0]), int(qs[1]), -1, -1],
                          [int(qs[2]), int(qs[3]), -1, -1]], jnp.int32)
        qw = jnp.asarray([[1.0, 0.7, 0.0, 0.0],
                          [1.0, 0.5, 0.0, 0.0]], jnp.float32)
        uf = jnp.zeros((2,), jnp.int32)
        key = jax.random.key(11)
        cfg = W.WalkConfig(n_steps=8192, n_walkers=256, chunk_steps=4,
                           n_p=10**9, n_v=10**9, bias_beta=0.0, top_k=25)

        out = {}
        with jax.set_mesh(mesh):
            starved = S.serve_batch(shg, qp, qw, uf, key, cfg,
                                    with_stats=True, mesh=mesh, slack=0.05)
            roomy = S.serve_batch(shg, qp, qw, uf, key, cfg,
                                  with_stats=True, mesh=mesh, slack=4.0)
            plain = S.serve_batch(g, qp, qw, uf, key, cfg, with_stats=True)
            wcfg = D.ShardedWalkConfig(
                n_supersteps=32, walkers_per_shard=128, top_k=25, slack=0.05)
            starved_w = D.pixie_walk_sharded(
                shg, qp[0], qw[0], jax.random.key(3), wcfg, mesh)
            roomy_w = D.pixie_walk_sharded(
                shg, qp[0], qw[0], jax.random.key(3),
                dataclasses.replace(wcfg, slack=8.0), mesh)
        out["starved_len"] = len(starved)
        out["roomy_len"] = len(roomy)
        out["starved_dropped"] = int(starved[4])
        out["roomy_dropped"] = int(roomy[4])
        out["scores_match"] = bool(
            (np.asarray(roomy[0]) == np.asarray(plain[0])).all())
        out["steps_match"] = bool(
            (np.asarray(roomy[2]) == np.asarray(plain[2])).all())
        out["wrapper_starved"] = int(starved_w.dropped)
        out["wrapper_roomy"] = int(roomy_w.dropped)
        print(json.dumps(out))
    """)
    # the 5th stats element is the drop counter (scores, ids, steps,
    # n_high, dropped)
    assert res["starved_len"] == 5 and res["roomy_len"] == 5
    assert res["starved_dropped"] > 0, res
    assert res["roomy_dropped"] == 0, res
    assert res["wrapper_starved"] > 0, res
    assert res["wrapper_roomy"] == 0, res
    # drop-free sharded serving reproduces unsharded serving exactly
    assert res["scores_match"] and res["steps_match"], res


def test_pixie_server_serves_sharded_replica():
    """The serving fleet path: a PixieServer holding a ShardedGraph
    replica routes through the pod-sharded engine and returns the same
    scores as a plain replica on the unsharded graph (same seed, same
    batching); the daily graph swap re-jits the sharded program."""
    res = _run(4, """
        from repro.graphs.synthetic import small_test_graph, top_degree_pins
        from repro.core import distributed as D, walk as W
        from repro.serving.server import PixieServer

        sg = small_test_graph()
        mesh = make_mesh((2, 2), ("data", "model"))
        shg = D.shard_graph(sg.graph, 2)
        qs = [int(x) for x in top_degree_pins(sg, 4)]
        cfg = W.WalkConfig(n_steps=4096, n_walkers=128, chunk_steps=4,
                           n_p=10**9, n_v=10**9, bias_beta=0.0, top_k=15)
        with jax.set_mesh(mesh):
            srv = PixieServer(shg, cfg, batch_size=2, n_slots=4, seed=5,
                              mesh=mesh, slack=4.0)
            ref = PixieServer(sg.graph, cfg, batch_size=2, n_slots=4,
                              seed=5)
            for s in (srv, ref):
                s.submit(qs[:2], [1.0, 0.6])
                s.submit(qs[2:3], [1.0])
                s.submit(qs[3:4], [0.8])
            got = srv.flush()
            want = ref.flush()
            match = all(
                bool((np.asarray(a[0]) == np.asarray(b[0])).all())
                for a, b in zip(got, want)
            )
            srv.swap_graph(D.shard_graph(sg.graph, 2))
            srv.submit(qs[:1], [1.0])
            post_swap = srv.flush()
        print(json.dumps({
            "n": len(got), "match": match,
            "generation": srv.stats.graph_generation,
            "post_swap_scored": bool(np.asarray(post_swap[0][0]).max() > 0),
        }))
    """)
    assert res["n"] == 3
    assert res["match"], res
    assert res["generation"] == 1
    assert res["post_swap_scored"], res


# ---------------------------------------------------------------------------
# structural pins: per-shard kernels, incremental early-stop fold
# ---------------------------------------------------------------------------


def _traced_sharded_walk(n_queries, backend, count_boards=True):
    g = small_test_graph().graph
    mesh = make_mesh((1,), ("model",))
    shg = dist_lib.shard_graph(g, 1)
    qp = jnp.tile(jnp.asarray([[3, 9, -1, -1]], jnp.int32), (n_queries, 1))
    qw = jnp.tile(
        jnp.asarray([[1.0, 0.5, 0.0, 0.0]], jnp.float32), (n_queries, 1)
    )
    cfg = walk_lib.WalkConfig(
        n_steps=2048, n_walkers=64, chunk_steps=4, n_p=40, n_v=3,
        bias_beta=0.0, count_boards=count_boards, backend=backend,
    )
    jaxpr = jax.make_jaxpr(
        lambda ks: dist_lib.pixie_walk_sharded_batched(
            shg, qp, qw, ks, cfg, mesh
        )
    )(jax.random.split(jax.random.key(0), n_queries)).jaxpr
    return jaxpr, shg, cfg


def test_superstep_pallas_calls_per_shard_not_per_query():
    """Acceptance criterion: a sharded superstep runs the fused kernels
    once per SHARD — the pallas_call count in the traced program is
    independent of the batch size (the whole batch shares each shard's
    hop + counter kernels) and covers both hops plus both counters."""
    n_calls = {}
    for b in (1, 4):
        jaxpr, _, _ = _traced_sharded_walk(b, "pallas")
        n_calls[b] = sum(
            1 for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"
        )
    # 2 walk hops + visit counter + board counter per superstep trace
    assert n_calls[1] >= 4, n_calls
    assert n_calls[1] == n_calls[4], (
        f"pallas_call count scales with batch size: {n_calls}"
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_while_body_has_no_full_buffer_reduction(backend):
    """Acceptance criterion: the early-stop fold in the sharded chunk loop
    is the incrementally carried ``high`` tally — no reduction over the
    (query, slot, pin)-sized count buffer inside any while body."""
    n_queries = 2
    jaxpr, shg, _ = _traced_sharded_walk(n_queries, backend)
    whiles = [e for e in iter_eqns(jaxpr) if e.primitive.name == "while"]
    assert whiles, "sharded walk lost its chunk while loop?"
    n_bins = n_queries * 4 * shg.pins_per_shard
    for w in whiles:
        found = full_buffer_reduces(w.params["body_jaxpr"].jaxpr, n_bins)
        assert not found, (
            f"sharded while body reduces a full count buffer on "
            f"{backend}: {found}"
        )


def test_unrolled_cost_model_mode_is_loop_free():
    """launch/dryrun's cost-model mode (``unroll=True``) must contain no
    while/fori loops at all — XLA cost analysis needs a flat program."""
    g = small_test_graph().graph
    mesh = make_mesh((1,), ("model",))
    shg = dist_lib.shard_graph(g, 1)
    qp = jnp.asarray([[3, 9, -1, -1]], jnp.int32)
    qw = jnp.asarray([[1.0, 0.5, 0.0, 0.0]], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=512, n_walkers=64, chunk_steps=4, n_p=10**9, n_v=10**9,
        bias_beta=0.0,
    )
    jaxpr = jax.make_jaxpr(
        lambda ks: dist_lib.pixie_walk_sharded_batched(
            shg, qp, qw, ks, cfg, mesh, unroll=True
        )
    )(jax.random.split(jax.random.key(0), 1)).jaxpr
    assert not any(
        e.primitive.name in ("while", "scan") for e in iter_eqns(jaxpr)
    )


# ---------------------------------------------------------------------------
# shard_graph edge cases
# ---------------------------------------------------------------------------


def _tiny_graph(n_pins=10, n_boards=7):
    """10 pins / 7 boards with pins 4 and 7 deliberately degree-0 and
    board 5 empty — exercises ghost-row padding and empty CSR rows."""
    edges = [
        (0, 0), (0, 1), (1, 0), (2, 2), (3, 3), (5, 1), (5, 4),
        (6, 6), (8, 2), (9, 6), (9, 0),
    ]
    pins = np.asarray([e[0] for e in edges])
    boards = np.asarray([e[1] for e in edges])
    return build_graph(pins, boards, n_pins=n_pins, n_boards=n_boards)


def test_shard_graph_pads_indivisible_id_spaces():
    g = _tiny_graph()
    shg = dist_lib.shard_graph(g, 3)
    # 10 pins / 7 boards round up to 12 / 9 across 3 shards
    assert shg.n_pins == 12 and shg.pins_per_shard == 4
    assert shg.n_boards == 9 and shg.boards_per_shard == 3
    assert shg.p2b_offsets.shape == (3, 5)
    assert shg.b2p_offsets.shape == (3, 4)
    assert shg.max_pin_degree == g.max_pin_degree

    # per-pin degrees survive the slicing; ghost pins 10, 11 are degree 0
    ref_deg = np.diff(np.asarray(g.p2b.offsets))
    off = np.asarray(shg.p2b_offsets)
    for s in range(3):
        assert (np.diff(off[s]) >= 0).all()  # offsets stay monotone
        for r in range(4):
            pin = s * 4 + r
            want = int(ref_deg[pin]) if pin < g.n_pins else 0
            assert off[s, r + 1] - off[s, r] == want, (pin, s, r)

    # sliced targets are the original rows: board *indices* on p2b,
    # global pin ids on b2p
    p_tgt = np.asarray(g.p2b.targets) - g.n_pins
    s_tgt = np.asarray(shg.p2b_targets)
    for pin in range(g.n_pins):
        s, r = divmod(pin, 4)
        got = s_tgt[s, off[s, r]:off[s, r + 1]]
        want = p_tgt[
            int(g.p2b.offsets[pin]):int(g.p2b.offsets[pin + 1])
        ]
        np.testing.assert_array_equal(got, want)
    boff = np.asarray(shg.b2p_offsets)
    b_tgt = np.asarray(shg.b2p_targets)
    for s in range(3):
        seg = b_tgt[s, :boff[s, -1]]
        assert ((seg >= 0) & (seg < g.n_pins)).all()


def test_shard_graph_keeps_empty_local_rows():
    """Degree-0 pins/boards inside a shard's owned range stay empty rows
    (not dropped, not collapsed) so local hops on them dead-end cleanly."""
    g = _tiny_graph()
    shg = dist_lib.shard_graph(g, 2)  # pps=5: pins 4 (shard 0), 7 (shard 1)
    off = np.asarray(shg.p2b_offsets)
    assert off[0, 5] - off[0, 4] == 0        # pin 4, empty, mid-shard
    assert off[1, 3] - off[1, 2] == 0        # pin 7, empty
    boff = np.asarray(shg.b2p_offsets)
    s, r = divmod(5, shg.boards_per_shard)   # board 5 has no pins
    assert boff[s, r + 1] - boff[s, r] == 0
    # a walk on the sharded graph with an empty-row query pin still runs
    mesh = make_mesh((1,), ("model",))
    shg1 = dist_lib.shard_graph(g, 1)
    cfg = walk_lib.WalkConfig(
        n_steps=256, n_walkers=32, chunk_steps=4, n_p=10**9, n_v=10**9,
        bias_beta=0.0,
    )
    res = dist_lib.pixie_walk_sharded_batched(
        shg1,
        jnp.asarray([[4, 0, -1, -1]], jnp.int32),
        jnp.asarray([[1.0, 1.0, 0.0, 0.0]], jnp.float32),
        jax.random.split(jax.random.key(0), 1), cfg, mesh,
    )
    counts = counter_lib.fold_sharded_counts(
        res.counts, 1, 4, shg1.pins_per_shard
    )
    # the dead-end slot visits nothing; the live slot walks normally
    assert int(np.asarray(counts)[0, 0].sum()) == 0
    assert int(np.asarray(counts)[0, 1].sum()) > 0
    assert int(res.dropped) == 0


def test_abstract_sharded_graph_agrees_with_shard_graph():
    """The dry-run stand-in must lower with the same structure the real
    ``shard_graph`` output carries: identical offset shapes, int32 arrays
    throughout, padded id spaces, and target capacity >= reality."""
    g = small_test_graph().graph
    n_shards = 4
    real = dist_lib.shard_graph(g, n_shards)
    n_edges = int(np.asarray(g.p2b.offsets)[-1])
    abstract = dist_lib.abstract_sharded_graph(
        g.n_pins, g.n_boards, n_edges, n_shards
    )
    assert abstract.p2b_offsets.shape == real.p2b_offsets.shape
    assert abstract.b2p_offsets.shape == real.b2p_offsets.shape
    assert abstract.n_pins == real.n_pins
    assert abstract.n_boards == real.n_boards
    assert abstract.n_shards == real.n_shards
    for name in ("p2b_offsets", "p2b_targets", "b2p_offsets", "b2p_targets"):
        a, r = getattr(abstract, name), getattr(real, name)
        assert a.dtype == r.dtype == jnp.int32, name
        assert a.shape[0] == n_shards, name
        # abstract target capacity covers the real (balanced) slice widths
        assert a.shape[1] >= 1
    assert abstract.p2b_targets.shape[1] >= real.p2b_targets.shape[1]
    assert abstract.b2p_targets.shape[1] >= real.b2p_targets.shape[1]
    # the partition specs cover exactly the four device arrays
    specs = dist_lib.sharded_graph_specs()
    from jax.sharding import PartitionSpec as P

    for name in ("p2b_offsets", "p2b_targets", "b2p_offsets", "b2p_targets"):
        assert getattr(specs, name) == P("model", None), name
