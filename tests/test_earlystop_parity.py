"""Differential tests for the fused early-stop counting path (Algorithm 3).

The dense walk engine's while-loop no longer re-reduces the whole
``n_slots * n_pins`` count buffer per chunk to recompute ``n_high``; it
carries a running tally updated incrementally by
``counter_lib.accumulate_packed_events_with_high`` (xla: chunk-local sort +
gather at the touched bins; pallas: crossings emitted by the fused
``visit_counter_update_high`` kernel).  These tests pin down:

  * xla vs pallas bit-identity of counts / n_high / steps_taken across
    random graphs, chunk sizes, and (n_v, n_p) thresholds;
  * the tally == full-recount invariant, including chunk-boundary
    crossings (a bin reaching n_v across two accumulate calls, and a slot
    crossing n_p mid-walk);
  * the wide-lane scale contract: events are (slot, pin) int32 lane pairs
    on BOTH engines (no packed product, no int64, no fallback branch);
    dense counting rejects un-materializable bin spaces loudly at SHAPE
    level (no giant buffers materialized) — event mode has no such limit
    (tests/test_widepack.py);
  * the structural claim itself, by jaxpr inspection: the while-loop body
    contains no reduction over an ``n_slots * n_pins``-sized operand.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis, or seeded fallback

from repro.core import counter as counter_lib
from repro.core import walk as walk_lib
from repro.core.graph import CSR, PinBoardGraph
from repro.kernels import ref
from repro.kernels.introspect import full_buffer_reduces, iter_eqns


def _random_graph(seed: int, n_pins: int, n_boards: int, n_edges: int):
    rng = np.random.default_rng(seed)
    pins = rng.integers(0, n_pins, n_edges)
    boards = rng.integers(0, n_boards, n_edges)
    p2b_off = np.zeros(n_pins + 1, np.int32)
    np.cumsum(np.bincount(pins, minlength=n_pins), out=p2b_off[1:])
    p2b_tgt = (boards[np.argsort(pins, kind="stable")] + n_pins).astype(np.int32)
    b2p_off = np.zeros(n_boards + 1, np.int32)
    np.cumsum(np.bincount(boards, minlength=n_boards), out=b2p_off[1:])
    b2p_tgt = pins[np.argsort(boards, kind="stable")].astype(np.int32)
    return PinBoardGraph(
        p2b=CSR(offsets=jnp.asarray(p2b_off), targets=jnp.asarray(p2b_tgt)),
        b2p=CSR(offsets=jnp.asarray(b2p_off), targets=jnp.asarray(b2p_tgt)),
        n_pins=n_pins,
        n_boards=n_boards,
        max_pin_degree=max(1, int(np.diff(p2b_off).max())),
    )


def _walk_both(graph, qp, qw, key, cfg):
    rx = walk_lib.pixie_random_walk(
        graph, qp, qw, jnp.asarray(0, jnp.int32), key, cfg
    )
    rp = walk_lib.pixie_random_walk(
        graph, qp, qw, jnp.asarray(0, jnp.int32), key,
        dataclasses.replace(cfg, backend="pallas"),
    )
    return rx, rp


def _assert_walks_identical(rx, rp):
    np.testing.assert_array_equal(np.asarray(rx.counts), np.asarray(rp.counts))
    np.testing.assert_array_equal(np.asarray(rx.n_high), np.asarray(rp.n_high))
    np.testing.assert_array_equal(
        np.asarray(rx.steps_taken), np.asarray(rp.steps_taken)
    )


# ---------------------------------------------------------------------------
# property-style differential tests: xla vs pallas across random settings
# ---------------------------------------------------------------------------


# no deadline: each chunk_steps draw compiles its own kernel
@settings(max_examples=5, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    chunk_steps=st.integers(min_value=2, max_value=9),
    n_v=st.integers(min_value=1, max_value=5),
    n_p=st.integers(min_value=1, max_value=60),
)
def test_walk_parity_random_graphs_and_thresholds(seed, chunk_steps, n_v, n_p):
    """xla and pallas engines agree bit-for-bit on counts, n_high, and
    steps_taken for random graphs and random early-stop thresholds."""
    rng = np.random.default_rng(seed)
    g = _random_graph(
        seed,
        n_pins=int(rng.integers(40, 160)),
        n_boards=int(rng.integers(8, 32)),
        n_edges=int(rng.integers(150, 500)),
    )
    qp = jnp.asarray([int(rng.integers(0, g.n_pins)), -1], jnp.int32)
    qw = jnp.asarray([1.0, 0.0], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=1024, n_walkers=32, chunk_steps=chunk_steps,
        n_p=n_p, n_v=n_v, bias_beta=0.0,
    )
    rx, rp = _walk_both(g, qp, qw, jax.random.key(seed), cfg)
    _assert_walks_identical(rx, rp)
    # the running tally must equal a full recount of the final counts
    np.testing.assert_array_equal(
        np.asarray(rx.n_high),
        np.asarray(counter_lib.n_high_visited(rx.counts, n_v)),
    )


# no deadline: the first example compiles the kernel
@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    n_slots=st.integers(min_value=1, max_value=5),
    n_pins=st.integers(min_value=16, max_value=900),
    n_v=st.integers(min_value=1, max_value=6),
)
def test_counter_api_parity_and_tally_invariant(seed, n_slots, n_pins, n_v):
    """accumulate_packed_events_with_high: xla path == pallas path ==
    full-recount oracle, for random prior counts and event chunks."""
    n_bins = n_slots * n_pins
    kp, ks, ke = jax.random.split(jax.random.key(seed), 3)
    prior = jax.random.randint(kp, (n_bins,), 0, n_v + 2, dtype=jnp.int32)
    # include negatives and the slot sentinel among the wide lanes
    slot_ev = jax.random.randint(ks, (1024,), -1, n_slots + 2, dtype=jnp.int32)
    pin_ev = jax.random.randint(ke, (1024,), -2, n_pins + 3, dtype=jnp.int32)
    high0 = counter_lib.n_high_visited(
        prior.reshape(n_slots, n_pins), n_v
    )
    want_c, want_d = ref.visit_counter_update_high_ref(
        prior, slot_ev, pin_ev, n_slots, n_pins, n_v
    )
    for backend in ("xla", "pallas"):
        got_c, got_h = counter_lib.accumulate_packed_events_with_high(
            prior, high0, slot_ev, pin_ev, n_slots, n_pins, n_v, backend
        )
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
        np.testing.assert_array_equal(
            np.asarray(got_h), np.asarray(high0 + want_d)
        )
        # invariant: running tally == full recount of the new counts
        np.testing.assert_array_equal(
            np.asarray(got_h),
            np.asarray(
                counter_lib.n_high_visited(
                    got_c.reshape(n_slots, n_pins), n_v
                )
            ),
        )


# ---------------------------------------------------------------------------
# chunk-boundary crossings
# ---------------------------------------------------------------------------


def test_crossing_split_across_chunk_boundary():
    """A bin that reaches n_v-1 in one accumulate call and crosses in the
    next must be tallied exactly once, in the second call — on both paths."""
    n_slots, n_pins, n_v = 2, 300, 4
    bin_id = 1 * n_pins + 7  # slot 1, pin 7
    s1 = jnp.full((n_v - 1,), 1, jnp.int32)            # reaches n_v - 1
    p1 = jnp.full((n_v - 1,), 7, jnp.int32)
    s2 = jnp.asarray([1, 1], jnp.int32)                # crosses, then above
    p2 = jnp.asarray([7, 7], jnp.int32)
    for backend in ("xla", "pallas"):
        counts = jnp.zeros((n_slots * n_pins,), jnp.int32)
        high = jnp.zeros((n_slots,), jnp.int32)
        counts, high = counter_lib.accumulate_packed_events_with_high(
            counts, high, s1, p1, n_slots, n_pins, n_v, backend
        )
        assert high.tolist() == [0, 0], backend
        counts, high = counter_lib.accumulate_packed_events_with_high(
            counts, high, s2, p2, n_slots, n_pins, n_v, backend
        )
        assert high.tolist() == [0, 1], backend
        assert int(counts[bin_id]) == n_v + 1


def test_crossing_within_one_chunk_counts_once():
    """Many duplicates of one bin inside a single chunk: one crossing."""
    n_slots, n_pins, n_v = 1, 64, 3
    slot_ev = jnp.zeros((16,), jnp.int32)   # 16 visits to pin 5 at once
    pin_ev = jnp.full((16,), 5, jnp.int32)
    for backend in ("xla", "pallas"):
        counts, high = counter_lib.accumulate_packed_events_with_high(
            jnp.zeros((n_pins,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            slot_ev, pin_ev, n_slots, n_pins, n_v, backend,
        )
        assert high.tolist() == [1], backend
        assert int(counts[5]) == 16


def test_walk_parity_when_slot_crosses_n_p_mid_walk():
    """Early stop fires mid-walk (n_p crossed between chunks): both engines
    stop at the same chunk with identical tallies."""
    g = _random_graph(3, n_pins=120, n_boards=16, n_edges=500)
    qp = jnp.asarray([0, 11], jnp.int32)
    qw = jnp.asarray([1.0, 1.0], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=8192, n_walkers=64, chunk_steps=4, n_p=5, n_v=2,
        bias_beta=0.0,
    )
    rx, rp = _walk_both(g, qp, qw, jax.random.key(1), cfg)
    _assert_walks_identical(rx, rp)
    # the stop actually happened early (budget not exhausted)
    assert (np.asarray(rx.steps_taken) < cfg.n_steps).all()
    assert (np.asarray(rx.n_high) > cfg.n_p).any()


# ---------------------------------------------------------------------------
# production-scale shape contract (shape-level, nothing giant materialized)
# ---------------------------------------------------------------------------


def test_count_engine_selection_shape_level():
    """No fallback branch: the chooser returns the requested backend at
    every dense-materializable scale, and rejects un-materializable dense
    bin spaces loudly (event mode is the production path there)."""
    assert walk_lib.select_count_engine("pallas", 4, 1000) == "pallas"
    assert walk_lib.select_count_engine("xla", 4, 1000) == "xla"
    # close to the dense ceiling: still the requested backend, no fallback
    assert walk_lib.select_count_engine("pallas", 4, 2**28) == "pallas"
    # 4 slots * 2^29 pins = 2^31 bins: dense counting cannot materialize
    # that buffer on ANY backend — loud error pointing at event mode
    with pytest.raises(ValueError, match="event-mode"):
        walk_lib.select_count_engine("pallas", 4, 2**29)
    with pytest.raises(ValueError, match="event-mode"):
        walk_lib.select_count_engine("xla", 4, 1000, 2**29)
    with pytest.raises(ValueError, match="backend"):
        walk_lib.select_count_engine("tpu??", 4, 1000)
    # wide lanes: the per-lane dtype is int32 at EVERY id-space scale
    assert walk_lib.packed_event_dtype(4, 2**29) == jnp.int32
    assert walk_lib.packed_event_dtype(4, 1000) == jnp.int32


def test_pixie_random_walk_routes_through_engine_selection(monkeypatch):
    """pixie_random_walk consults select_count_engine and hands its verdict
    to the counting API — checked by forcing an answer on a small graph and
    recording what the counter receives."""
    g = _random_graph(0, n_pins=60, n_boards=10, n_edges=200)
    seen = {}

    def fake_select(backend, n_slots, n_pins, n_boards=0):
        seen["dims"] = (backend, n_slots, n_pins, n_boards)
        return "xla"  # forced verdict, must reach the counting API

    real_acc = counter_lib.accumulate_packed_events_with_high

    def recording_acc(counts, high, sev, pev, n_slots, n_pins, n_v, backend):
        seen["count_backend"] = backend
        return real_acc(counts, high, sev, pev, n_slots, n_pins, n_v, backend)

    monkeypatch.setattr(walk_lib, "select_count_engine", fake_select)
    monkeypatch.setattr(
        counter_lib, "accumulate_packed_events_with_high", recording_acc
    )
    cfg = walk_lib.WalkConfig(
        n_steps=256, n_walkers=32, chunk_steps=4, n_p=10**9, n_v=10**9 // 2,
        bias_beta=0.0, backend="pallas",
    )
    walk_lib.pixie_random_walk(
        g, jnp.asarray([1], jnp.int32), jnp.ones((1,), jnp.float32),
        jnp.asarray(0, jnp.int32), jax.random.key(0), cfg,
    )
    # count_boards=False: board ids are not counted, so they must not enter
    # the shape validation (a huge board space must not reject a pin walk)
    assert seen["dims"] == ("pallas", 1, g.n_pins, 0)
    assert seen["count_backend"] == "xla"


def test_board_space_only_gates_engine_when_counted(monkeypatch):
    g = _random_graph(1, n_pins=60, n_boards=10, n_edges=200)
    seen = {}
    real_select = walk_lib.select_count_engine

    def recording_select(backend, n_slots, n_pins, n_boards=0):
        seen["n_boards"] = n_boards
        return real_select(backend, n_slots, n_pins, n_boards)

    monkeypatch.setattr(walk_lib, "select_count_engine", recording_select)
    cfg = walk_lib.WalkConfig(
        n_steps=256, n_walkers=32, chunk_steps=4, n_p=10**9, n_v=10**9 // 2,
        bias_beta=0.0, count_boards=True,
    )
    walk_lib.pixie_random_walk(
        g, jnp.asarray([1], jnp.int32), jnp.ones((1,), jnp.float32),
        jnp.asarray(0, jnp.int32), jax.random.key(0), cfg,
    )
    assert seen["n_boards"] == g.n_boards


def test_one_sided_feat_bounds_rejected_for_biased_walks():
    g = _random_graph(2, n_pins=40, n_boards=8, n_edges=120)
    lopsided = PinBoardGraph(
        p2b=CSR(
            offsets=g.p2b.offsets, targets=g.p2b.targets,
            feat_bounds=jnp.zeros((g.n_pins, 3), jnp.int32),
        ),
        b2p=g.b2p,  # no feat_bounds on this side
        n_pins=g.n_pins, n_boards=g.n_boards,
        max_pin_degree=g.max_pin_degree,
    )
    qp = jnp.asarray([0], jnp.int32)
    qw = jnp.ones((1,), jnp.float32)
    biased = walk_lib.WalkConfig(n_steps=128, n_walkers=32, bias_beta=0.9)
    with pytest.raises(ValueError, match="feat_bounds"):
        walk_lib.pixie_random_walk(
            lopsided, qp, qw, jnp.asarray(0, jnp.int32),
            jax.random.key(0), biased,
        )
    # with biasing off the same graph walks fine
    res = walk_lib.pixie_random_walk(
        lopsided, qp, qw, jnp.asarray(0, jnp.int32), jax.random.key(0),
        dataclasses.replace(biased, bias_beta=0.0),
    )
    assert int(res.counts.sum()) >= 0


def test_counter_api_rejects_unmaterializable_dense_bins():
    """Dense counting with a >= 2^31 bin space must raise on BOTH backends
    (there is no buffer to scatter into), pointing at event mode — the
    wide-lane replacement for the old silent int64 fallback."""
    n_slots, n_pins = 4, 2**29
    counts = jnp.zeros((64,), jnp.int32)  # stand-in slice; never reached
    high = jnp.zeros((n_slots,), jnp.int32)
    sev = jnp.asarray([0, 0, 0], jnp.int32)
    pev = jnp.asarray([1, 2, 2], jnp.int32)
    for backend in ("xla", "pallas"):
        with pytest.raises(ValueError, match="event-mode"):
            counter_lib.accumulate_packed_events_with_high(
                counts, high, sev, pev, n_slots, n_pins, 2, backend
            )
        with pytest.raises(ValueError, match="event-mode"):
            counter_lib.accumulate_packed_events(
                counts, sev, pev, n_slots, n_pins, backend
            )


def test_counter_api_empty_events_both_backends():
    """Zero events: counts and tally unchanged on BOTH paths (the kernel
    wrapper must not build a zero-size grid)."""
    n_slots, n_pins = 2, 100
    counts = jnp.arange(n_slots * n_pins, dtype=jnp.int32) % 5
    high = counter_lib.n_high_visited(counts.reshape(n_slots, n_pins), 3)
    empty = jnp.zeros((0,), jnp.int32)
    for backend in ("xla", "pallas"):
        got_c, got_h = counter_lib.accumulate_packed_events_with_high(
            counts, high, empty, empty, n_slots, n_pins, 3, backend
        )
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(counts))
        np.testing.assert_array_equal(np.asarray(got_h), np.asarray(high))
        # the plain histogram API must tolerate empty lanes the same way
        got_p = counter_lib.accumulate_packed_events(
            counts, empty, empty, n_slots, n_pins, backend
        )
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(counts))


def test_counter_api_rejects_nonpositive_n_v():
    with pytest.raises(ValueError, match="n_v"):
        counter_lib.accumulate_packed_events_with_high(
            jnp.zeros((8,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            1, 8, 0, "xla",
        )
    bad_cfg = walk_lib.WalkConfig(n_steps=64, n_walkers=32, n_v=0)
    g = _random_graph(0, 30, 8, 60)
    qp = jnp.asarray([0], jnp.int32)
    qw = jnp.ones((1,), jnp.float32)
    uf = jnp.asarray(0, jnp.int32)
    with pytest.raises(ValueError, match="n_v"):
        walk_lib.pixie_random_walk(g, qp, qw, uf, jax.random.key(0), bad_cfg)
    # both engines reject the misconfiguration the same loud way
    with pytest.raises(ValueError, match="n_v"):
        walk_lib.pixie_walk_events(g, qp, qw, uf, jax.random.key(0), bad_cfg)


# ---------------------------------------------------------------------------
# the structural claim: no full-buffer reduction inside the while body
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_while_body_has_no_full_buffer_reduction(backend):
    """Acceptance criterion: the dense-mode while_loop body contains no
    reduction over an n_slots * n_pins-sized operand, on either engine."""
    g = _random_graph(7, n_pins=130, n_boards=20, n_edges=400)
    n_slots = 4
    qp = jnp.asarray([0, 5, -1, -1], jnp.int32)
    qw = jnp.asarray([1.0, 0.5, 0.0, 0.0], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=2048, n_walkers=64, chunk_steps=4, n_p=40, n_v=3,
        bias_beta=0.0, backend=backend,
    )
    jaxpr = jax.make_jaxpr(
        lambda k: walk_lib.pixie_random_walk(
            g, qp, qw, jnp.asarray(0, jnp.int32), k, cfg
        )
    )(jax.random.key(0)).jaxpr
    whiles = [e for e in iter_eqns(jaxpr) if e.primitive.name == "while"]
    assert whiles, "dense walk lost its while loop?"
    n_bins = n_slots * g.n_pins
    for w in whiles:
        found = full_buffer_reduces(w.params["body_jaxpr"].jaxpr, n_bins)
        assert not found, (
            f"while body reduces a full count buffer on {backend}: {found}"
        )


def test_reduction_checker_catches_the_old_pattern():
    """Positive control: the pre-fusion formulation (full n_high recount
    per chunk) IS flagged by the same checker."""
    n_slots, n_pins = 4, 130
    jaxpr = jax.make_jaxpr(
        lambda c: counter_lib.n_high_visited(c.reshape(n_slots, n_pins), 3)
    )(jnp.zeros((n_slots * n_pins,), jnp.int32)).jaxpr
    assert full_buffer_reduces(jaxpr, n_slots * n_pins)


# ---------------------------------------------------------------------------
# kernel-level: fused update kernel vs oracle across tilings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile,chunk", [(128, 256), (512, 2048)])
@pytest.mark.parametrize("n_slots,n_pins", [(1, 100), (3, 700), (8, 512)])
def test_update_high_kernel_matches_ref(tile, chunk, n_slots, n_pins):
    from repro.kernels.visit_counter import visit_counter_update_high

    n_bins = n_slots * n_pins
    kp, ks, ke = jax.random.split(jax.random.key(n_bins + tile), 3)
    prior = jax.random.randint(kp, (n_bins,), 0, 4, dtype=jnp.int32)
    slot_ev = jax.random.randint(ks, (3000,), -1, n_slots + 2, dtype=jnp.int32)
    pin_ev = jax.random.randint(ke, (3000,), -2, n_pins + 4, dtype=jnp.int32)
    got_c, got_d = visit_counter_update_high(
        prior, slot_ev, pin_ev, n_slots=n_slots, n_pins=n_pins, n_v=3,
        tile=tile, chunk=chunk, interpret=True,
    )
    want_c, want_d = ref.visit_counter_update_high_ref(
        prior, slot_ev, pin_ev, n_slots, n_pins, 3
    )
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


@pytest.mark.parametrize("tile,chunk", [(128, 256), (512, 2048)])
def test_wide_histogram_kernel_matches_ref(tile, chunk):
    from repro.kernels.visit_counter import visit_counter_wide

    n_slots, n_dim = 3, 700
    ks, ke = jax.random.split(jax.random.key(tile + chunk))
    slot_ev = jax.random.randint(ks, (3000,), -1, n_slots + 2, dtype=jnp.int32)
    id_ev = jax.random.randint(ke, (3000,), -2, n_dim + 4, dtype=jnp.int32)
    got = visit_counter_wide(
        slot_ev, id_ev, n_slots=n_slots, n_dim=n_dim,
        tile=tile, chunk=chunk, interpret=True,
    )
    want = ref.visit_counter_wide_ref(slot_ev, id_ev, n_slots, n_dim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
