"""Wide (slot, pin) event lanes + incremental event-mode early stopping.

The two production-scale claims of the wide-pack engine:

  * **No id-space cliff.**  Events are (slot, pin) int32 lane pairs — no
    lane ever holds the packed ``slot * n_pins + pin`` product — so a walk
    whose packed id space exceeds 2**31 runs on ``backend="pallas"`` with
    event-mode counting, bit-identical to the xla twin, with NO fallback
    branch anywhere (``select_count_engine`` validates, never reroutes).
  * **No full-buffer re-sort.**  The event walk's ``check_every`` body
    folds only the new window's events into a carried
    ``counter_lib.EventHighState`` (sorted runs per window): the only sort
    in the while body is window-sized, pinned by jaxpr inspection, and the
    running ``n_high`` tally is bit-identical to the old full-buffer
    re-sort (``check_mode="full"``) at every check point — including keys
    whose counts cross ``n_v`` across window boundaries.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import counter as counter_lib
from repro.core import walk as walk_lib
from repro.graphs.synthetic import sparse_wide_graph as _sparse_wide_graph
from repro.kernels.introspect import iter_eqns


# ---------------------------------------------------------------------------
# the acceptance walk: packed id space past 2**31, pallas == xla, top-k too
# ---------------------------------------------------------------------------


def test_event_walk_past_int32_packed_space_bit_identical():
    """65536 slots x 40000 pins = 2.6e9 packed ids (> 2**31): the fused
    pallas engine runs it in event mode — wide int32 lanes, memory
    O(events) — and every output (lane buffers, n_high, steps_taken,
    top-k) is bit-identical to the xla twin.  No fallback is consulted:
    select_count_engine never reroutes a backend anymore."""
    n_slots, n_pins = 65_536, 40_000
    assert n_slots * n_pins >= 2**31
    g = _sparse_wide_graph(
        0, n_pins=n_pins, n_boards=64, n_edges=4_000, hot_pins=2_000
    )
    qp = np.full((n_slots,), -1, np.int32)
    qw = np.zeros((n_slots,), np.float32)
    qp[0], qp[1] = 3, 17
    qw[0], qw[1] = 1.0, 0.5
    qp, qw = jnp.asarray(qp), jnp.asarray(qw)
    cfg = walk_lib.WalkConfig(
        n_steps=2_048, n_walkers=64, chunk_steps=4, n_p=500, n_v=3,
        bias_beta=0.0,
    )
    key = jax.random.key(1)
    res = {}
    for backend in ("xla", "pallas"):
        bcfg = dataclasses.replace(cfg, backend=backend)
        r = walk_lib.pixie_walk_events(
            g, qp, qw, jnp.asarray(0, jnp.int32), key, bcfg, check_every=2
        )
        s, i = walk_lib.recommend_from_events(r, n_slots, n_pins, qp, 20)
        res[backend] = tuple(np.asarray(x) for x in (*r, s, i))
    for a, b in zip(res["xla"], res["pallas"]):
        np.testing.assert_array_equal(a, b)
    # the walk actually visited pins and the top-k is non-trivial
    slot_ev = res["xla"][0]
    assert (slot_ev < n_slots).sum() > 0
    scores = res["xla"][5]  # tuple layout: 5 EventWalkResult fields, s, i
    assert (scores[:5] > 0).all()  # top-5 boosted scores positive


# ---------------------------------------------------------------------------
# incremental check body: only window-sized sorts, bit-identical to full
# ---------------------------------------------------------------------------


def _walk_sorts_in_while_body(g, qp, qw, cfg, check_every, check_mode):
    jaxpr = jax.make_jaxpr(
        lambda k: walk_lib.pixie_walk_events(
            g, qp, qw, jnp.asarray(0, jnp.int32), k, cfg,
            check_every=check_every, check_mode=check_mode,
        )
    )(jax.random.key(0)).jaxpr
    whiles = [e for e in iter_eqns(jaxpr) if e.primitive.name == "while"]
    assert whiles, "event walk lost its while loop?"
    sizes = []
    for w in whiles:
        for eqn in iter_eqns(w.params["body_jaxpr"].jaxpr):
            if eqn.primitive.name == "sort":
                sizes.append(
                    max(getattr(v.aval, "size", 0) for v in eqn.invars)
                )
    return sizes


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_event_check_body_sorts_only_the_window(backend):
    """Acceptance pin: with check_mode="incremental" every sort inside the
    while body is window-sized (check_every * per_chunk), never
    max_events-sized; the old full re-sort formulation IS flagged by the
    same inspection (positive control)."""
    g = _sparse_wide_graph(3, n_pins=500, n_boards=16, n_edges=600,
                           hot_pins=200)
    qp = jnp.asarray([0, 7], jnp.int32)
    qw = jnp.asarray([1.0, 1.0], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=4_096, n_walkers=32, chunk_steps=4, n_p=100, n_v=3,
        bias_beta=0.0, backend=backend,
    )
    check_every = 2
    per_chunk = cfg.n_walkers * cfg.chunk_steps
    window = check_every * per_chunk
    max_events = cfg.max_chunks() * per_chunk
    assert max_events >= 4 * window  # the distinction is real at this shape

    inc = _walk_sorts_in_while_body(g, qp, qw, cfg, check_every, "incremental")
    assert inc, "incremental check body should sort the new window"
    assert max(inc) <= window, (
        f"incremental body sorts {max(inc)} elements (> window {window})"
    )

    full = _walk_sorts_in_while_body(g, qp, qw, cfg, check_every, "full")
    assert max(full) >= max_events, (
        "positive control: the full re-sort formulation must be flagged"
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_incremental_matches_full_resort_walk(backend):
    """check_mode="incremental" and the old full-buffer re-sort make
    identical stop decisions: same chunks_run, steps_taken, n_high, and
    event buffers — with thresholds that fire mid-walk so the tally is
    load-bearing, and check_every > 1 so crossings straddle windows."""
    g = _sparse_wide_graph(5, n_pins=400, n_boards=12, n_edges=800,
                           hot_pins=120)
    qp = jnp.asarray([2, 9, -1], jnp.int32)
    qw = jnp.asarray([1.0, 0.8, 0.0], jnp.float32)
    key = jax.random.key(4)
    cfg = walk_lib.WalkConfig(
        n_steps=8_192, n_walkers=64, chunk_steps=4, n_p=40, n_v=2,
        bias_beta=0.0, backend=backend,
    )
    ri = walk_lib.pixie_walk_events(
        g, qp, qw, jnp.asarray(0, jnp.int32), key, cfg,
        check_every=3, check_mode="incremental",
    )
    rf = walk_lib.pixie_walk_events(
        g, qp, qw, jnp.asarray(0, jnp.int32), key, cfg,
        check_every=3, check_mode="full",
    )
    for a, b in zip(ri, rf):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # early stopping actually fired before the budget
    assert int(ri.chunks_run) < cfg.max_chunks()
    assert (np.asarray(ri.n_high)[:2] > cfg.n_p).any()


def test_event_walk_n_high_matches_full_oracle_post_hoc():
    """The walk's carried n_high equals a from-scratch full re-aggregation
    of exactly the checked prefix of the event buffer."""
    g = _sparse_wide_graph(8, n_pins=300, n_boards=10, n_edges=500,
                           hot_pins=100)
    qp = jnp.asarray([1, 4], jnp.int32)
    qw = jnp.asarray([1.0, 1.0], jnp.float32)
    cfg = walk_lib.WalkConfig(
        n_steps=4_096, n_walkers=32, chunk_steps=4, n_p=10**9,
        n_v=2, bias_beta=0.0,
    )
    check_every = 2
    r = walk_lib.pixie_walk_events(
        g, qp, qw, jnp.asarray(0, jnp.int32), jax.random.key(2), cfg,
        check_every=check_every,
    )
    per_chunk = cfg.n_walkers * cfg.chunk_steps
    checked_chunks = (int(r.chunks_run) // check_every) * check_every
    cut = checked_chunks * per_chunk
    n_slots = qp.shape[0]
    sev = np.asarray(r.slot_events).copy()
    sev[cut:] = n_slots  # mask events past the last completed check window
    want = counter_lib.events_n_high_per_slot(
        jnp.asarray(sev), r.pin_events, n_slots, g.n_pins, cfg.n_v,
        sev.shape[0],
    )
    np.testing.assert_array_equal(np.asarray(r.n_high), np.asarray(want))


def test_events_high_fold_cross_window_crossing_counts_once():
    """A (slot, pin) key that reaches n_v - 1 in window 1 and crosses in
    window 3 is tallied exactly once, in window 3 — the prior-count sum
    over stored segments is what makes the crossing unique."""
    n_slots, n_pins, n_v, seg_cap = 2, 50, 4, 16
    state = counter_lib.events_high_init(n_slots, 4, seg_cap)

    def window(pairs):
        s = np.full((seg_cap,), n_slots, np.int32)
        p = np.zeros((seg_cap,), np.int32)
        for i, (sl, pi) in enumerate(pairs):
            s[i], p[i] = sl, pi
        return jnp.asarray(s), jnp.asarray(p)

    # window 1: pin (1, 7) visited n_v - 1 times -> no crossing
    state = counter_lib.events_high_fold(
        state, *window([(1, 7)] * (n_v - 1)), n_slots, n_pins, n_v,
        seg_cap=seg_cap,
    )
    assert np.asarray(state.high).tolist() == [0, 0]
    # window 2: unrelated traffic -> still no crossing
    state = counter_lib.events_high_fold(
        state, *window([(0, 3), (0, 4)]), n_slots, n_pins, n_v,
        seg_cap=seg_cap,
    )
    assert np.asarray(state.high).tolist() == [0, 0]
    # window 3: one more visit crosses; extra duplicates don't re-count
    state = counter_lib.events_high_fold(
        state, *window([(1, 7), (1, 7), (1, 7)]), n_slots, n_pins, n_v,
        seg_cap=seg_cap,
    )
    assert np.asarray(state.high).tolist() == [0, 1]
    # window 4: the key stays above threshold; never tallied again
    state = counter_lib.events_high_fold(
        state, *window([(1, 7)]), n_slots, n_pins, n_v, seg_cap=seg_cap
    )
    assert np.asarray(state.high).tolist() == [0, 1]
    assert int(state.n_checks) == 4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_events_high_fold_random_windows_match_oracle(seed):
    """Property-style: random window streams, fold == full re-aggregation
    after every window."""
    rng = np.random.default_rng(seed)
    n_slots, n_pins, n_v, seg_cap, n_windows = 3, 40, 3, 64, 5
    state = counter_lib.events_high_init(n_slots, n_windows, seg_cap)
    all_s, all_p = [], []
    for _ in range(n_windows):
        s = rng.integers(0, n_slots + 1, seg_cap).astype(np.int32)
        p = np.where(s < n_slots, rng.integers(0, 10, seg_cap), 0).astype(
            np.int32
        )
        all_s.append(s)
        all_p.append(p)
        state = counter_lib.events_high_fold(
            state, jnp.asarray(s), jnp.asarray(p), n_slots, n_pins, n_v,
            seg_cap=seg_cap,
        )
        fs, fp = np.concatenate(all_s), np.concatenate(all_p)
        want = counter_lib.events_n_high_per_slot(
            jnp.asarray(fs), jnp.asarray(fp), n_slots, n_pins, n_v,
            fs.shape[0],
        )
        np.testing.assert_array_equal(np.asarray(state.high), np.asarray(want))


def test_events_high_fold_rejects_wrong_window_size():
    state = counter_lib.events_high_init(2, 2, 8)
    with pytest.raises(ValueError, match="seg_cap"):
        counter_lib.events_high_fold(
            state, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            2, 10, 2, seg_cap=8,
        )


def test_event_walk_rejects_unknown_check_mode():
    g = _sparse_wide_graph(0, n_pins=50, n_boards=4, n_edges=80, hot_pins=20)
    qp = jnp.asarray([0], jnp.int32)
    qw = jnp.ones((1,), jnp.float32)
    cfg = walk_lib.WalkConfig(n_steps=64, n_walkers=32)
    with pytest.raises(ValueError, match="check_mode"):
        walk_lib.pixie_walk_events(
            g, qp, qw, jnp.asarray(0, jnp.int32), jax.random.key(0), cfg,
            check_mode="sometimes",
        )
