"""DMA gather mode of the fused walk kernel + walk-path consistency fixes.

The contract under test (kernels/walk_step.py): ``gather_mode="dma"``
(phase-split double-buffered async-copy CSR prefetch) is bit-for-bit
interchangeable with ``gather_mode="scalar"`` (blocking scalar gathers) and
with the XLA reference engine — counts, top-k, early-stop observables
(``steps_taken``, ``n_high``), board counts — across walker block sizes,
chunk boundaries, bias on/off, and ``count_boards`` on/off.  The dma-mode
kernel must actually lower async copies when not interpreting (jaxpr pin),
and the same code path must run under interpret mode on CPU hosts (every
execution test in this file does exactly that).

Also pins the legacy-path ``_RMASK`` fix: raw uint32 random bits must be
masked BEFORE the int32 cast everywhere — a high-bit draw cast raw becomes
a negative modulo operand whose result depends on the lowering.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_walk_backends import _chunk_args  # shared CSR chunk fixture

from repro.core import walk as walk_lib
from repro.graphs.synthetic import small_test_graph, top_degree_pins
from repro.kernels import ops
from repro.kernels.walk_step import _RMASK, GATHER_MODES, walk_steps_fused


@pytest.fixture(scope="module")
def sg():
    return small_test_graph()


def _queries(sg, n_slots=4):
    qs = top_degree_pins(sg, 2)
    qp = jnp.full((n_slots,), -1, jnp.int32).at[:2].set(
        jnp.asarray([int(qs[0]), int(qs[1])], jnp.int32)
    )
    qw = jnp.zeros((n_slots,), jnp.float32).at[:2].set(
        jnp.asarray([1.0, 0.5])
    )
    return qp, qw


# ---------------------------------------------------------------------------
# parity matrix: dma == scalar == xla through the full dense walk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_w", [128, 256])
@pytest.mark.parametrize("bias_beta", [0.0, 0.9])
@pytest.mark.parametrize("count_boards", [False, True])
def test_walk_parity_matrix(sg, block_w, bias_beta, count_boards):
    """Bit-identity across the gather-mode matrix, early stopping ACTIVE
    (so steps_taken / n_high are live observables) and a step budget that
    crosses chunk boundaries (n_steps > n_walkers * chunk_steps)."""
    g = sg.graph
    qp, qw = _queries(sg)
    base = walk_lib.WalkConfig(
        n_steps=2_500, n_walkers=256, chunk_steps=4,
        n_p=60, n_v=3, bias_beta=bias_beta, count_boards=count_boards,
        pallas_block_w=block_w,
    )
    key = jax.random.key(13)
    results = {}
    for label, cfg in (
        ("xla", dataclasses.replace(base, backend="xla")),
        ("scalar", dataclasses.replace(base, backend="pallas",
                                       gather_mode="scalar")),
        ("dma", dataclasses.replace(base, backend="pallas",
                                    gather_mode="dma")),
    ):
        results[label] = walk_lib.pixie_random_walk(
            g, qp, qw, jnp.asarray(1, jnp.int32), key, cfg
        )
    rx = results["xla"]
    assert int(rx.counts.sum()) > 0  # the walk actually walked
    for label in ("scalar", "dma"):
        r = results[label]
        np.testing.assert_array_equal(
            np.asarray(rx.counts), np.asarray(r.counts), err_msg=label
        )
        np.testing.assert_array_equal(
            np.asarray(rx.steps_taken), np.asarray(r.steps_taken),
            err_msg=label,
        )
        np.testing.assert_array_equal(
            np.asarray(rx.n_high), np.asarray(r.n_high), err_msg=label
        )
        if count_boards:
            np.testing.assert_array_equal(
                np.asarray(rx.board_counts), np.asarray(r.board_counts),
                err_msg=label,
            )


def test_topk_recommendations_identical(sg):
    """The full recommend() path (walk -> booster -> top-k) is bit-identical
    across gather modes and against the xla engine."""
    g = sg.graph
    qp, qw = _queries(sg)
    base = walk_lib.WalkConfig(
        n_steps=3_000, n_walkers=128, chunk_steps=8, top_k=20,
        n_p=10**9, n_v=10**9,
    )
    key = jax.random.key(3)
    outs = {}
    for label, cfg in (
        ("xla", base),
        ("scalar", dataclasses.replace(base, backend="pallas")),
        ("dma", dataclasses.replace(base, backend="pallas",
                                    gather_mode="dma")),
    ):
        outs[label] = walk_lib.recommend(
            g, qp, qw, jnp.asarray(0, jnp.int32), key, cfg
        )
    for label in ("scalar", "dma"):
        np.testing.assert_array_equal(
            np.asarray(outs["xla"][1]), np.asarray(outs[label][1]),
            err_msg=label,
        )
        np.testing.assert_array_equal(
            np.asarray(outs["xla"][0]), np.asarray(outs[label][0]),
            err_msg=label,
        )


def test_event_buffers_identical_across_gather_modes(sg):
    """Event-mode walks (the production-scale path) emit identical wide
    lane buffers from both gather modes."""
    g = sg.graph
    qp, qw = _queries(sg)
    base = walk_lib.WalkConfig(
        n_steps=2_000, n_walkers=128, chunk_steps=8,
        n_p=10**9, n_v=10**9, backend="pallas",
    )
    key = jax.random.key(21)
    es = walk_lib.pixie_walk_events(
        g, qp, qw, jnp.asarray(0, jnp.int32), key, base, check_every=10**9
    )
    ed = walk_lib.pixie_walk_events(
        g, qp, qw, jnp.asarray(0, jnp.int32), key,
        dataclasses.replace(base, gather_mode="dma"), check_every=10**9
    )
    np.testing.assert_array_equal(
        np.asarray(es.slot_events), np.asarray(ed.slot_events)
    )
    np.testing.assert_array_equal(
        np.asarray(es.pin_events), np.asarray(ed.pin_events)
    )
    assert int(es.chunks_run) == int(ed.chunks_run)


# ---------------------------------------------------------------------------
# chunk-level: op parity and the lowering pin (CSR fixture shared with
# test_walk_backends._chunk_args)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alpha_u32", [0, 2**31, 2**32 - 1])
def test_dma_chunk_matches_scalar_and_ref(alpha_u32):
    a = _chunk_args(jax.random.key(alpha_u32 % 97))
    common = dict(alpha_u32=alpha_u32, beta_u32=0, count_boards=True)
    want = ops.walk_chunk_fused(use_kernel=False, **a, **common)
    scalar = ops.walk_chunk_fused(
        use_kernel=True, gather_mode="scalar", **a, **common
    )
    dma = ops.walk_chunk_fused(
        use_kernel=True, gather_mode="dma", **a, **common
    )
    for g_, s_, w_ in zip(dma, scalar, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(s_))


def _fused_jaxpr(a, gather_mode):
    """Trace (don't run) the fused kernel with interpret=False, so the pin
    sees what a TPU lowering would see."""
    return str(jax.make_jaxpr(lambda: walk_steps_fused(
        a["curr"], a["query"], a["feat"], a["slot"], a["rbits"],
        a["p2b_offsets"], a["p2b_targets"],
        a["b2p_offsets"], a["b2p_targets"],
        n_pins=a["n_pins"], n_slots=a["n_slots"],
        alpha_u32=2**30, beta_u32=0, block_w=128,
        gather_mode=gather_mode, interpret=False,
    ))())


def test_dma_mode_lowers_async_copies():
    """Both modes read HBM only through async copies (the TPU cannot load
    from an HBM ref); only the dma kernel pipelines them: its walker loop
    starts walker i+1's copies under a guard (a cond) before waiting on
    walker i's, while the scalar kernel waits on each copy at once."""
    a = _chunk_args(jax.random.key(5))
    dma_jaxpr = _fused_jaxpr(a, "dma")
    scalar_jaxpr = _fused_jaxpr(a, "scalar")
    for jaxpr in (dma_jaxpr, scalar_jaxpr):
        assert "dma_start" in jaxpr and "dma_wait" in jaxpr
    assert "cond[" in dma_jaxpr
    assert "cond[" not in scalar_jaxpr


def test_gather_mode_validated():
    a = _chunk_args(jax.random.key(1))
    with pytest.raises(ValueError, match="gather_mode"):
        walk_steps_fused(
            a["curr"], a["query"], a["feat"], a["slot"], a["rbits"],
            a["p2b_offsets"], a["p2b_targets"],
            a["b2p_offsets"], a["b2p_targets"],
            n_pins=a["n_pins"], n_slots=a["n_slots"],
            alpha_u32=0, beta_u32=0,
            gather_mode="bogus",
        )
    assert set(GATHER_MODES) == {"scalar", "dma"}


def test_walk_config_gather_mode_validated(sg):
    qp, qw = _queries(sg)
    cfg = walk_lib.WalkConfig(
        n_steps=256, n_walkers=64, n_p=10**9, n_v=10**9,
        gather_mode="turbo",
    )
    with pytest.raises(ValueError, match="gather_mode"):
        walk_lib.pixie_random_walk(
            sg.graph, qp, qw, jnp.asarray(0, jnp.int32),
            jax.random.key(0), cfg
        )


# ---------------------------------------------------------------------------
# legacy-path _RMASK regression (satellite bugfix)
# ---------------------------------------------------------------------------


def _numpy_walk_step(curr, query, rbits, p2b_off, p2b_tgt, b2p_off, b2p_tgt,
                     n_pins, alpha_u32):
    """Independent numpy model of one superstep with the MASKED arithmetic
    (the documented contract of both the kernel and the jnp reference)."""
    restart = rbits[:, 0] < np.uint32(alpha_u32)
    pos = np.where(restart, query, curr)
    r_board = (rbits[:, 1] & _RMASK).astype(np.int64)
    r_pin = (rbits[:, 2] & _RMASK).astype(np.int64)
    start = p2b_off[pos]
    deg = p2b_off[pos + 1] - start
    idx = start + (r_board % np.maximum(deg, 1))
    board = p2b_tgt[idx]
    board_ok = deg > 0
    b_local = np.where(board_ok, board - n_pins, 0)
    bstart = b2p_off[b_local]
    bdeg = b2p_off[b_local + 1] - bstart
    bidx = bstart + (r_pin % np.maximum(bdeg, 1))
    nxt = b2p_tgt[bidx]
    ok = board_ok & (bdeg > 0)
    return (np.where(ok, nxt, query).astype(np.int32),
            np.where(ok, nxt, 0).astype(np.int32), ok)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ref", "kernel"])
def test_legacy_walk_step_masks_high_random_bits(use_kernel):
    """Feed the single-step path draws >= 2**31: the raw int32 cast used to
    make these negative modulo operands (lowering-dependent picks); both
    the jnp reference and the Pallas kernel must match the masked model."""
    w = 256  # the legacy kernel's default walker block
    a = _chunk_args(jax.random.key(42), w=w)
    rng = np.random.default_rng(7)
    # every draw has the high bit set — the regression regime
    rbits = (rng.integers(2**31, 2**32, size=(w, 3), dtype=np.uint32))
    got = ops.walk_step(
        a["curr"], a["query"], jnp.asarray(rbits),
        a["p2b_offsets"], a["p2b_targets"],
        a["b2p_offsets"], a["b2p_targets"],
        n_pins=a["n_pins"], alpha_u32=2**31, use_kernel=use_kernel,
    )
    want = _numpy_walk_step(
        np.asarray(a["curr"]), np.asarray(a["query"]), rbits,
        np.asarray(a["p2b_offsets"]), np.asarray(a["p2b_targets"]),
        np.asarray(a["b2p_offsets"]), np.asarray(a["b2p_targets"]),
        a["n_pins"], 2**31,
    )
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), w_)
    # and the two legacy implementations agree with each other
    other = ops.walk_step(
        a["curr"], a["query"], jnp.asarray(rbits),
        a["p2b_offsets"], a["p2b_targets"],
        a["b2p_offsets"], a["b2p_targets"],
        n_pins=a["n_pins"], alpha_u32=2**31, use_kernel=not use_kernel,
    )
    for g_, o_ in zip(got, other):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(o_))
