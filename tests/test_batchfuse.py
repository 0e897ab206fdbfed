"""Batch-native fused walk engine vs the vmapped per-query path.

The contract under test (core/walk.pixie_random_walk_batched): the query
batch is a first-class axis of the fused engine — all queries' walkers
packed query-major on one walker axis, ONE fused chunk call and ONE
query-major counting call per superstep chunk, one shared while loop with
a per-(query, slot) early-stop mask — and the result is BIT-IDENTICAL to
``jax.vmap(pixie_random_walk)`` over the same ``jax.random.split``-derived
per-query keys: counts, board counts, ``steps_taken``, ``n_high``, scores
and ids, for every batch size, both gather modes, and queries that
early-stop at different chunks.

The lowering claim is pinned by jaxpr inspection: a batched serve step
contains a constant number of ``pallas_call`` eqns inside one
``max_chunks``-bounded while loop, with NO batch-sized leading grid
dimension — the vmapped pallas path (the positive control) prepends the
batch to every kernel grid, i.e. batch x chunks program replication.

``serve_batch`` runs the batched engine on BOTH backends whenever its
bins fit int32 (``service.walk_engine``), so its answers are checked
against the vmapped engine called directly, never through
``serve_batch``; the xla step's lowering is pinned to one flat count
carry with a scalar loop predicate, and the vmapped fallback to the
batched predicate that selects the whole carry each chunk.
"""

import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as dist_lib
from repro.core import service, walk as walk_lib
from repro.graphs.synthetic import small_test_graph, top_degree_pins
from repro.kernels.introspect import iter_eqns, pallas_grids
from repro.kernels.walk_step import DEFAULT_BLOCK_W
from repro.serving.server import PixieServer


@pytest.fixture(scope="module")
def sg():
    return small_test_graph()


def _cfg(**kw):
    kw = {
        "n_steps": 1536, "n_walkers": 64, "chunk_steps": 4, "top_k": 20,
        "n_p": 40, "n_v": 3, "backend": "pallas", **kw,
    }
    return walk_lib.WalkConfig(**kw)


def _mk_batch(sg, batch, n_slots=2):
    qs = top_degree_pins(sg, 2 * batch if 2 * batch <= 32 else 32)
    pins = np.full((batch, n_slots), -1, np.int32)
    weights = np.zeros((batch, n_slots), np.float32)
    for i in range(batch):
        pins[i, 0] = int(qs[(2 * i) % len(qs)])
        pins[i, 1] = int(qs[(2 * i + 1) % len(qs)])
        weights[i] = [1.0, 0.6]
    return (
        jnp.asarray(pins),
        jnp.asarray(weights),
        jnp.zeros((batch,), jnp.int32),
    )


def _vmapped_walk(graph, pins, weights, feats, keys, cfg):
    return jax.vmap(
        lambda qp, qw, uf, k: walk_lib.pixie_random_walk(
            graph, qp, qw, uf, k, cfg
        )
    )(pins, weights, feats, keys)


def _assert_results_equal(got, want):
    for name in ("counts", "board_counts", "steps_taken", "n_high"):
        a, b = getattr(got, name), getattr(want, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name
            )


@pytest.mark.parametrize("gather_mode", ["scalar", "dma"])
@pytest.mark.parametrize("batch", [1, 4, 16])
def test_batched_bit_identical_to_vmapped(sg, batch, gather_mode):
    """Acceptance matrix: batch {1, 4, 16} x gather modes, early stopping
    ACTIVE so the per-(query, slot) mask and the query-major n_high tally
    are on the line."""
    g = sg.graph
    cfg = _cfg(gather_mode=gather_mode)
    pins, weights, feats = _mk_batch(sg, batch)
    keys = jax.random.split(jax.random.key(11), batch)
    rb = walk_lib.pixie_random_walk_batched(g, pins, weights, feats, keys, cfg)
    rv = _vmapped_walk(g, pins, weights, feats, keys, cfg)
    _assert_results_equal(rb, rv)
    assert int(rb.counts.sum()) > 0  # the walk actually walked
    # the batched engine is also its own xla/pallas parity pair
    if gather_mode == "scalar":
        rx = walk_lib.pixie_random_walk_batched(
            g, pins, weights, feats, keys,
            dataclasses.replace(cfg, backend="xla"),
        )
        _assert_results_equal(rb, rx)


def test_batched_board_counts_bit_identical(sg):
    g = sg.graph
    cfg = _cfg(count_boards=True)
    pins, weights, feats = _mk_batch(sg, 4)
    keys = jax.random.split(jax.random.key(5), 4)
    rb = walk_lib.pixie_random_walk_batched(g, pins, weights, feats, keys, cfg)
    rv = _vmapped_walk(g, pins, weights, feats, keys, cfg)
    assert rb.board_counts is not None
    assert rb.board_counts.shape == (4, 2, g.n_boards)
    _assert_results_equal(rb, rv)


def test_queries_early_stop_at_different_chunks(sg):
    """One query's thresholds trip chunks before another's: the shared
    while loop must keep the fast query frozen (events masked, steps
    frozen) while its neighbours walk on — bit-identically to the
    per-query loops."""
    g = sg.graph
    # query 0: aggressive thresholds would stop it almost immediately if
    # they were global — give it a full-weight hot pin; query 1: a tiny
    # weight means a tiny Eq. 2 budget, so it runs out of steps at a
    # different chunk than query 0's n_high trip
    qs = top_degree_pins(sg, 4)
    pins = jnp.asarray(
        [[int(qs[0]), int(qs[1])], [int(qs[2]), int(qs[3])]], jnp.int32
    )
    weights = jnp.asarray([[1.0, 0.6], [0.05, 1.0]], jnp.float32)
    feats = jnp.zeros((2,), jnp.int32)
    cfg = _cfg(n_steps=2048, n_p=15, n_v=2)
    keys = jax.random.split(jax.random.key(2), 2)
    rb = walk_lib.pixie_random_walk_batched(g, pins, weights, feats, keys, cfg)
    rv = _vmapped_walk(g, pins, weights, feats, keys, cfg)
    _assert_results_equal(rb, rv)
    per_query_steps = np.asarray(rb.steps_taken).sum(axis=1)
    # the point of the test: the queries really stopped at different
    # points, AND before the full budget (early stopping fired)
    assert per_query_steps[0] != per_query_steps[1]
    assert (per_query_steps < cfg.n_steps).any()


def _oracle_serve(graph, pins, weights, feats, key, cfg, step_budgets):
    """``serve_batch``'s answers from the vmapped per-query engine, called
    directly: no route through ``serve_batch``'s engine choice."""
    keys = jax.random.split(key, pins.shape[0])
    cfg = dataclasses.replace(cfg, backend="xla")
    if step_budgets is None:
        return jax.vmap(
            lambda qp, qw, uf, k: walk_lib.recommend_with_stats(
                graph, qp, qw, uf, k, cfg
            )
        )(pins, weights, feats, keys)
    return jax.vmap(
        lambda qp, qw, uf, k, sb: walk_lib.recommend_with_stats(
            graph, qp, qw, uf, k, cfg, step_budget=sb
        )
    )(pins, weights, feats, keys, step_budgets)


def _assert_served_equal(got, want):
    for a, b, name in zip(got, want, ("scores", "ids", "steps", "n_high")):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name
        )


# ragged per-lane Eq. 2 budgets (cfg.n_steps is 1536); a batch takes the
# first ``batch`` of them
_RAGGED_BUDGETS = (700, 1536, 300, 1100)


@pytest.mark.parametrize("budgeted", [False, True])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_serve_batch_routes_pallas_through_batched_engine(
    sg, backend, batch, budgeted
):
    """serve_batch on either backend == the vmapped per-query oracle,
    called directly, bit-identically: scores and ids AND telemetry, with
    the static Eq. 2 budget and with ragged per-lane budgets."""
    g = sg.graph
    pins, weights, feats = _mk_batch(sg, batch)
    cfg = _cfg(backend="xla")
    key = jax.random.key(9)
    budgets = (
        jnp.asarray(_RAGGED_BUDGETS[:batch], jnp.int32) if budgeted else None
    )
    out = service.serve_batch(
        g, pins, weights, feats, key, cfg, backend=backend,
        with_stats=True, step_budgets=budgets,
    )
    _assert_served_equal(
        out, _oracle_serve(g, pins, weights, feats, key, cfg, budgets)
    )
    assert out[0].shape == (batch, cfg.top_k)
    assert out[2].shape == (batch, 2)


# ---------------------------------------------------------------------------
# Lowering pins: one pallas_call per chunk for the WHOLE batch
# ---------------------------------------------------------------------------


def test_batched_serve_lowers_to_one_call_per_chunk(sg):
    """The fusion claim: a batched serve step contains exactly 2
    pallas_call eqns (fused walk + query-major counter) inside the ONE
    max_chunks-bounded while loop, with rank-1 walk grids sized by total
    walkers — NOT a batch-sized leading grid dim.  The vmapped pallas
    path is the positive control: vmap prepends the batch to every grid
    (batch x chunks program replication), which is exactly what the
    batched engine removes."""
    g = sg.graph
    cfg = _cfg()
    w = cfg.n_walkers
    structures = {}
    for batch in (1, 16):
        pins, weights, feats = _mk_batch(sg, batch)

        def serve(key):
            return service.serve_batch(g, pins, weights, feats, key, cfg)

        grids = pallas_grids(jax.make_jaxpr(serve)(jax.random.key(0)))
        # one fused walk call + one fused count-and-tally call per chunk
        assert len(grids) == 2, grids
        walk_grid, count_grid = grids
        # walk: rank-1 grid over walker blocks covering the WHOLE batch
        # (block_w follows ops.walk_chunk_fused_batched's default rule)
        assert len(walk_grid) == 1, walk_grid
        w_total = batch * w
        block_w = (
            DEFAULT_BLOCK_W if w_total % DEFAULT_BLOCK_W == 0 else w_total
        )
        assert walk_grid[0] == w_total // block_w, (walk_grid, w_total)
        # counter: (n_tiles, n_chunks) — no batch axis
        assert len(count_grid) == 2, count_grid
        structures[batch] = (len(grids), len(walk_grid), len(count_grid))
    # pallas_call count and grid ranks are independent of batch size
    assert structures[1] == structures[16]

    # positive control: the vmapped pallas path replicates per query
    batch = 16
    pins, weights, feats = _mk_batch(sg, batch)
    keys = jax.random.split(jax.random.key(0), batch)

    def vmapped(keys):
        return jax.vmap(
            lambda qp, qw, uf, k: walk_lib.recommend_with_stats(
                g, qp, qw, uf, k, cfg
            )
        )(pins, weights, feats, keys)

    vgrids = pallas_grids(jax.make_jaxpr(vmapped)(keys))
    assert len(vgrids) == 2, vgrids
    for grid in vgrids:
        assert grid[0] == batch, (
            f"vmapped grid {grid} should lead with the batch axis"
        )


def test_batched_engine_fits_envelope():
    """The batched engine's query-major bins must fit int32; serve_batch
    consults this predicate to fall back to the vmapped formulation
    instead of erroring on a (graph, batch) shape the per-query path
    served fine (its flat indexing is per query)."""
    # benchmark scale: fits comfortably
    assert walk_lib.batched_engine_fits(64, 4, 20_000, 2_000, True)
    # production-ish: 64 queries x 4 slots x 10M pins = 2.56e9 bins — the
    # per-query path's 40M bins fit, the combined space does not
    assert not walk_lib.batched_engine_fits(64, 4, 10_000_000)
    assert walk_lib.batched_engine_fits(1, 4, 10_000_000)
    # board counting widens the bin space only when boards are counted
    assert walk_lib.batched_engine_fits(64, 4, 1_000, 10_000_000, False)
    assert not walk_lib.batched_engine_fits(64, 4, 1_000, 10_000_000, True)


def _count_loops(serve, n_bins):
    """``(while eqn, shapes of its int32 carries of n_bins elements)`` for
    every while loop of ``serve``'s jaxpr that carries count buffers."""
    jaxpr = jax.make_jaxpr(serve)(jax.random.key(0))
    loops = []
    for e in iter_eqns(jaxpr.jaxpr):
        if e.primitive.name != "while":
            continue
        carries = [
            tuple(v.aval.shape) for v in e.outvars
            if v.aval.dtype == jnp.int32 and v.aval.size == n_bins
        ]
        if carries:
            loops.append((e, carries))
    return loops


def _carry_selects(serve, shape):
    """The ``stablehlo.select`` ops of ``serve``'s lowering that produce an
    int32 array of ``shape``.  vmap turns a per-query while predicate into
    a batched one, and the lowered body then selects every carry against
    its old value each iteration; the jaxpr shows only the batched
    predicate."""
    dims = "x".join(str(d) for d in shape)
    text = jax.jit(serve).lower(jax.random.key(0)).as_text()
    return re.findall(
        rf"stablehlo\.select .*tensor<{dims}xi32>$", text, re.MULTILINE
    )


@pytest.mark.parametrize("budgeted", [False, True])
def test_xla_serve_step_carries_one_flat_count_buffer(sg, budgeted):
    """The xla serve step runs the batch-native loop: exactly one while
    loop carries counts, as ONE flat (batch * n_slots * n_pins,) int32
    buffer, and its body never selects that carry against its old value
    (the select that forces a copy of the table each chunk)."""
    g = sg.graph
    batch, n_slots = 4, 2
    n_bins = batch * n_slots * g.n_pins
    pins, weights, feats = _mk_batch(sg, batch, n_slots)
    cfg = _cfg(backend="xla")
    budgets = (
        jnp.asarray(_RAGGED_BUDGETS[:batch], jnp.int32) if budgeted else None
    )

    def serve(key):
        return service.serve_batch(g, pins, weights, feats, key, cfg,
                                   step_budgets=budgets, with_stats=True)

    loops = _count_loops(serve, n_bins)
    assert len(loops) == 1, [c for _, c in loops]
    loop, carries = loops[0]
    assert carries == [(n_bins,)], carries
    assert loop.params["cond_jaxpr"].out_avals[0].shape == ()
    assert _carry_selects(serve, (n_bins,)) == []


def test_serve_batch_falls_back_to_vmapped_past_envelope(sg, monkeypatch):
    """Past the batched envelope, serve_batch must keep serving rather than
    raising where it used to work: on pallas with vmapped grids,
    batch-replicated; on xla with the vmapped (batch, n_slots * n_pins)
    count carry, which vmap's batched predicate selects each chunk — and
    on both with the oracle's answers."""
    g = sg.graph
    batch, n_slots = 4, 2
    pins, weights, feats = _mk_batch(sg, batch, n_slots)
    cfg = _cfg()
    monkeypatch.setattr(walk_lib, "batched_engine_fits",
                        lambda *a, **k: False)

    def serve(key, backend="pallas"):
        return service.serve_batch(g, pins, weights, feats, key, cfg,
                                   backend=backend, with_stats=True)

    grids = pallas_grids(jax.make_jaxpr(serve)(jax.random.key(0)))
    assert all(grid[0] == batch for grid in grids), grids

    def serve_xla(key):
        return serve(key, backend="xla")

    n_bins = batch * n_slots * g.n_pins
    carry = (batch, n_slots * g.n_pins)
    loops = _count_loops(serve_xla, n_bins)
    assert [c for _, c in loops] == [[carry]], loops
    assert loops[0][0].params["cond_jaxpr"].out_avals[0].shape == (batch,)
    assert len(_carry_selects(serve_xla, carry)) == 1
    key = jax.random.key(4)
    _assert_served_equal(
        serve_xla(key),
        _oracle_serve(g, pins, weights, feats, key, cfg, None),
    )


def test_walk_engine_chooses_by_shape(sg):
    """``service.walk_engine``: the pod-sharded engine for a ShardedGraph,
    the batch-native one while its bins fit int32, vmapped past that —
    on either backend, since the backend only picks hop and count
    implementations."""
    g = sg.graph
    for backend in ("xla", "pallas"):
        cfg = _cfg(backend=backend)
        assert service.walk_engine(g, 8, 8, cfg) == "batched"
        assert service.walk_engine(
            dist_lib.shard_graph(g, 2), 8, 8, cfg) == "sharded"
        # the benchmark's buckets at 10M pins: 640M and 80M bins
        big = types.SimpleNamespace(n_pins=10_000_000, n_boards=5_000_000)
        assert service.walk_engine(big, 8, 8, cfg) == "batched"
        assert service.walk_engine(big, 8, 1, cfg) == "batched"
        # 64 queries x 4 slots x 10M pins = 2.56e9 bins: past int32
        assert service.walk_engine(big, 64, 4, cfg) == "vmapped"
        # boards widen the bin space only when they are counted
        wide = types.SimpleNamespace(n_pins=1_000, n_boards=10_000_000)
        assert service.walk_engine(wide, 64, 4, cfg) == "batched"
        assert service.walk_engine(
            wide, 64, 4, dataclasses.replace(cfg, count_boards=True)
        ) == "vmapped"


@pytest.mark.parametrize("fits", [True, False])
def test_server_counts_batch_native_batches(sg, monkeypatch, fits):
    """``ServerStats.batches_batch_native`` counts the batches dispatched
    on the batch-native engine: all of them for a fitting bucket, none
    when the envelope predicate says no (the vmapped fallback)."""
    if not fits:
        monkeypatch.setattr(walk_lib, "batched_engine_fits",
                            lambda *a, **k: False)
    server = PixieServer(sg.graph, _cfg(backend="xla"), batch_size=4,
                         n_slots=2)
    qs = top_degree_pins(sg, 6)
    for i in range(6):  # 6 requests -> 2 batches (one padded)
        server.submit([int(qs[i])], [1.0], user_feat=0)
    assert len(server.flush()) == 6
    assert server.stats.batches == 2
    assert server.stats.batches_batch_native == (2 if fits else 0)


def test_batched_engine_validates_inputs(sg):
    g = sg.graph
    pins, weights, feats = _mk_batch(sg, 2)
    keys = jax.random.split(jax.random.key(0), 2)
    with pytest.raises(ValueError, match="n_v must be >= 1"):
        walk_lib.pixie_random_walk_batched(
            g, pins, weights, feats, keys, _cfg(n_v=0)
        )
    with pytest.raises(ValueError, match=r"\(n_queries, n_slots\)"):
        walk_lib.pixie_random_walk_batched(
            g, pins[0], weights[0], feats, keys, _cfg()
        )
    with pytest.raises(ValueError, match="unknown gather_mode"):
        walk_lib.pixie_random_walk_batched(
            g, pins, weights, feats, keys, _cfg(gather_mode="warp")
        )
