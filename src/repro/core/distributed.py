"""Sharded Pixie: the 3B-node graph across a pod, walkers migrating via ICI.

The paper's central systems claim is "the whole graph fits in one machine's
RAM, so the walk never crosses machines".  A v5e chip has 16 GB HBM; the
pruned production graph (3B nodes / 17B edges, ~100 GB as int32/int64 CSR)
cannot replicate.  The TPU-native translation keeps the *principle* one
level up: the graph is **node-range sharded across the 'model' axis of one
pod**, and walkers migrate between shards over ICI (~50 GB/s/link) — the
walk never leaves the pod (multi-pod = query parallelism on the 'pod'
axis, zero cross-pod traffic in the walk itself).

The sharded engine is a first-class consumer of the batched fused walk
machinery (core/walk.py, kernels/walk_step.py), not a separate walk
implementation:

  * shard s owns pins  [s, s+1) * pins_per_shard  and boards
    [s, s+1) * boards_per_shard, with local CSR slices (padded to the max
    shard size — host-side `shard_graph` compiler does this);
  * a walker's identity is its GLOBAL walker id (query-major, walker
    ``q * n_walkers + i`` — the PR 5 batch packing), so its random stream
    is position-independent: every shard derives the whole batch's
    counter-RNG bits per chunk (``walk_lib._chunk_rbits`` — replicated
    arithmetic, bit-identical to the unsharded engines) and a walker
    consumes its own lane wherever it happens to reside;
  * one superstep = restart kill/rebirth -> per-shard fused hop kernel
    (pin->board, ``kernels/ops.walk_hop`` — ONE ``pallas_call`` for the
    whole routed walker buffer, both ``gather_mode="scalar"`` and
    ``"dma"``) -> **all_to_all route to the board owner** -> fused hop
    (board->pin) + shard-local board counting -> **all_to_all route to
    the pin owner** -> wide (query, slot, local_pin) event accumulation
    into the shard's owned dense bins with the incremental ``n_high``
    crossing tally (``counter.accumulate_packed_events_with_high``);
  * restarts are kill + rebirth-at-home: a restarting walker's resident
    copy dies wherever it is and the walker re-enters at the shard owning
    its query pin — restart teleports ride the ordinary hop routes, no
    third collective;
  * early stop is GLOBAL per (query, slot): each shard carries its owned
    subrange's incremental crossing tally and a chunk-boundary ``psum``
    folds them into the Algorithm 3 statistic — never a reduction over
    the count buffers.  Stopped rows' walkers are killed (excluded from
    routing capacity) exactly like the PR 5 freeze semantics;
  * routing uses fixed per-(shard, shard) capacity
    ``route_capacity(S, W, slack)``; walkers that overflow are dropped
    and respawn at their query pin on their next restart draw (Pixie is
    a Monte Carlo estimator — bounded drops are the same kind of slack
    as the paper's early stopping, and the drop count is surfaced as a
    serving metric, never silent).

With zero drops the engine is BIT-IDENTICAL to the unsharded batched
engine on the same graph (counts, board counts, steps_taken, n_high):
``backend="xla"`` is the plain-XLA oracle twin (structural parity via
``kernels/ref.walk_hop_ref``), ``backend="pallas"`` the fused kernels —
tests/test_sharded_engine.py pins all three against each other.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import counter as counter_lib
from repro.core import sampling
from repro.core import walk as walk_lib
from repro.core.graph import PinBoardGraph
from repro.kernels import ops
from repro.kernels.walk_step import GATHER_MODES

Array = jax.Array


# ---------------------------------------------------------------------------
# Host-side graph sharding (the production graph compiler's final stage)
# ---------------------------------------------------------------------------


class ShardedGraph(NamedTuple):
    """Node-range sharded CSR; every array has leading dim n_shards."""

    p2b_offsets: Array   # (S, pins_per_shard + 1) int
    p2b_targets: Array   # (S, max_p2b_edges) int32  (board *indices*)
    b2p_offsets: Array   # (S, boards_per_shard + 1)
    b2p_targets: Array   # (S, max_b2p_edges) int32  (global pin ids)
    n_pins: int
    n_boards: int
    n_shards: int
    # static degree cap for Eq. 2 scaling (graph.max_pin_degree); trailing
    # default keeps older positional constructions compiling
    max_pin_degree: int = 4096

    @property
    def pins_per_shard(self) -> int:
        return self.p2b_offsets.shape[1] - 1

    @property
    def boards_per_shard(self) -> int:
        return self.b2p_offsets.shape[1] - 1


def shard_graph(
    graph: PinBoardGraph,
    n_shards: int,
    mesh: Optional[Mesh] = None,
    axis: str = "model",
) -> ShardedGraph:
    """Split a host graph into node-range shards (padded to equal size).

    With ``mesh``, each shard row is placed straight onto the device that
    owns it along ``axis``, so no device ever holds the whole stacked
    CSR; without it the arrays land on the default device.
    """
    n_pins = -(-graph.n_pins // n_shards) * n_shards
    n_boards = -(-graph.n_boards // n_shards) * n_shards
    pps, bps = n_pins // n_shards, n_boards // n_shards

    p_off = np.asarray(graph.p2b.offsets)
    p_tgt = np.asarray(graph.p2b.targets)
    b_off = np.asarray(graph.b2p.offsets)
    b_tgt = np.asarray(graph.b2p.targets)

    def slice_csr(off, tgt, lo, hi, n_rows):
        o = off[lo:min(hi, len(off) - 1) + 1].astype(np.int64)
        seg = tgt[o[0]:o[-1]]
        o = o - o[0]
        if len(o) < n_rows + 1:  # pad ghost rows (degree 0)
            o = np.concatenate([o, np.full(n_rows + 1 - len(o), o[-1])])
        return o, seg

    po, pt, bo, bt = [], [], [], []
    for s in range(n_shards):
        o, t = slice_csr(p_off, p_tgt, s * pps, (s + 1) * pps, pps)
        po.append(o)
        pt.append(t - graph.n_pins)  # store board *indices*, not node ids
        o, t = slice_csr(b_off, b_tgt, s * bps, (s + 1) * bps, bps)
        bo.append(o)
        bt.append(t)
    max_pt = max(len(t) for t in pt)
    max_bt = max(len(t) for t in bt)
    pt = [np.pad(t, (0, max_pt - len(t))) for t in pt]
    bt = [np.pad(t, (0, max_bt - len(t))) for t in bt]

    rows = None if mesh is None else NamedSharding(mesh, P(axis, None))

    def stacked(parts):
        x = np.stack(parts).astype(np.int32)
        return jnp.asarray(x) if rows is None else jax.device_put(x, rows)

    return ShardedGraph(
        p2b_offsets=stacked(po),
        p2b_targets=stacked(pt),
        b2p_offsets=stacked(bo),
        b2p_targets=stacked(bt),
        n_pins=n_pins,
        n_boards=n_boards,
        n_shards=n_shards,
        max_pin_degree=graph.max_pin_degree,
    )


def abstract_sharded_graph(
    n_pins: int, n_boards: int, n_edges: int, n_shards: int
) -> ShardedGraph:
    """ShapeDtypeStruct stand-in at production scale (dry-run only)."""
    sds = jax.ShapeDtypeStruct
    pps = -(-n_pins // n_shards)
    bps = -(-n_boards // n_shards)
    eps = int(n_edges // n_shards * 1.25)  # 25% imbalance headroom
    return ShardedGraph(
        p2b_offsets=sds((n_shards, pps + 1), jnp.int32),
        p2b_targets=sds((n_shards, eps), jnp.int32),
        b2p_offsets=sds((n_shards, bps + 1), jnp.int32),
        b2p_targets=sds((n_shards, eps), jnp.int32),
        n_pins=pps * n_shards,
        n_boards=bps * n_shards,
        n_shards=n_shards,
    )


def sharded_graph_specs(axis: str = "model") -> ShardedGraph:
    """PartitionSpecs for the sharded graph arrays (leading dim = shard)."""
    e = P(axis, None)
    return ShardedGraph(
        p2b_offsets=e, p2b_targets=e, b2p_offsets=e, b2p_targets=e,
        n_pins=0, n_boards=0, n_shards=0,
    )


# ---------------------------------------------------------------------------
# Routing fabric
# ---------------------------------------------------------------------------


def route_capacity(n_shards: int, n_walkers_total: int, slack: float) -> int:
    """Per-(shard, shard) route capacity for a pool of W walkers.

    Balanced hops put ``W / n_shards**2`` walkers on each (source, dest)
    pair; ``slack`` is the skew headroom before drops start.  Rounded up
    to a multiple of 8 (lane-friendly buffers), floor 8.
    """
    c = int(slack * n_walkers_total / (n_shards * n_shards))
    return max(8, -(-c // 8) * 8)


def _route(
    axis: str,
    n_shards: int,
    capacity: int,
    dest: Array,      # (L,) destination shard per walker (>= n_shards = dead)
    payload: Tuple[Array, ...],   # each (L,) int32
) -> Tuple[Array, Tuple[Array, ...], Array, Array]:
    """all_to_all walker exchange with fixed per-pair capacity.

    Returns ``(valid_mask (S*C,), routed payload tuple (S*C,),
    n_dropped (), max_occupancy ())`` — the last being the fullest
    outbound bucket before the capacity clamp, the serving-telemetry
    signal for tuning ``slack``.
    """
    l = dest.shape[0]
    order = jnp.argsort(dest)
    dsort = dest[order]
    counts = jnp.bincount(jnp.minimum(dsort, n_shards), length=n_shards + 1)
    start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos = jnp.arange(l, dtype=jnp.int32) - jnp.take(start, dsort).astype(jnp.int32)
    live = dsort < n_shards
    keep = live & (pos < capacity)
    slot = jnp.where(keep, dsort * capacity + pos, n_shards * capacity)
    dropped = jnp.sum(live & ~keep)
    max_occ = jnp.max(counts[:n_shards]).astype(jnp.int32)

    out_payload = []
    for arr in payload:
        buf = jnp.zeros((n_shards * capacity + 1,), arr.dtype)
        buf = buf.at[slot].set(arr[order])
        routed = jax.lax.all_to_all(
            buf[:-1].reshape(n_shards, capacity), axis, 0, 0, tiled=False
        )  # (n_shards, capacity) received
        out_payload.append(routed.reshape(-1))
    vbuf = jnp.zeros((n_shards * capacity + 1,), jnp.bool_).at[slot].set(keep)
    valid = jax.lax.all_to_all(
        vbuf[:-1].reshape(n_shards, capacity), axis, 0, 0, tiled=False
    ).reshape(-1)
    return valid, tuple(out_payload), dropped, max_occ


# ---------------------------------------------------------------------------
# The pod-sharded batched fused walk engine
# ---------------------------------------------------------------------------


class ShardedBatchedWalkResult(NamedTuple):
    """Sharded twin of ``walk.WalkResult`` with routing telemetry.

    ``counts`` / ``board_counts`` stay SHARD-STACKED (each shard's
    query-major owned-subrange bins) — ``counter.fold_sharded_counts``
    reassembles the unsharded batched layout when a consumer needs the
    global id axis; serving keeps them sharded and runs the hierarchical
    top-k instead.
    """

    counts: Array                   # (S, B * n_slots * pins_per_shard) int32
    board_counts: Optional[Array]   # (S, B * n_slots * boards_per_shard)
    steps_taken: Array              # (B, n_slots) int32
    n_high: Array                   # (B, n_slots) int32, query pins debited
    dropped: Array                  # () int32 routing-overflow drops (total)
    max_occupancy: Array            # () int32 fullest route bucket seen
    # () int32 walkers lost to DEAD shards (``shard_dead_at``): residents
    # at the death superstep + walkers routed toward a dead shard after
    # it.  Telemetry distinct from ``dropped`` (capacity overflow): drops
    # tune ``slack``, kills quantify fault damage.  None on the healthy
    # code path (no fault schedule supplied).
    killed: Optional[Array] = None


def pixie_walk_sharded_batched(
    graph: ShardedGraph,
    query_pins: Array,      # (B, n_slots) int32 global pin ids, -1 pad
    query_weights: Array,   # (B, n_slots) f32, 0 for padding
    keys: Array,            # (B,) per-query PRNG keys (random.split)
    cfg: walk_lib.WalkConfig,
    mesh: Mesh,
    axis: str = "model",
    *,
    slack: float = 2.0,
    unroll: bool = False,
    shard_dead_at: Optional[Array] = None,
) -> ShardedBatchedWalkResult:
    """The batched fused walk engine on a node-range-sharded graph.

    The bit-parity twin of ``walk.pixie_random_walk_batched`` on the same
    (replicated) graph — identical counts, board counts, ``steps_taken``
    and ``n_high`` whenever no walker is dropped (raise ``slack`` until
    ``dropped == 0``; parity tests do).  Each per-shard superstep runs the
    fused hop kernel (``cfg.backend == "pallas"``, both gather modes) or
    its XLA oracle twin on the shard-local CSR slices; ONE bounded
    ``_route`` fabric per hop carries the whole query batch.

    ``cfg`` is the ordinary walk config; ``cfg.bias_beta`` must be 0 (the
    sharded CSR carries no feat_bounds).  ``unroll=True`` is cost-model
    mode (launch/dryrun.py): python loops instead of ``while``/``fori``,
    every chunk runs — mathematically identical (stopped rows are frozen
    by masking either way), just loop-free for XLA cost analysis.

    ``shard_dead_at`` (optional ``(n_shards,)`` int32) is DEGRADED MODE:
    shard ``s`` is dead from absolute superstep ``shard_dead_at[s]``
    onward (``np.iinfo(np.int32).max`` = never dies).  A dead shard's
    residents die with it, walkers routed toward it die in flight (both
    tallied in ``killed`` — distinct from capacity ``dropped``), its
    homed walkers stop being (re)injected, and any walker killed this way
    re-enters at its (live) home shard on its next restart draw — the
    ordinary PR 6 kill/rebirth-at-home machinery, no new collective.  At
    harvest a shard that died before the walk finished contributes ZERO
    counts/board counts and leaves the ``n_high`` tally: its HBM is gone,
    so Eq. 3 counting renormalizes over the surviving shards and the
    quality cost surfaces as overlap@k against an all-alive oracle
    (serving/resilience.py), never as a silent score shift.  Pure data on
    the replicated spec — flipping liveness never retraces — and
    ``None`` (every existing caller) traces the exact healthy program,
    byte-for-byte.  An all-``INT32_MAX`` schedule is value-identical to
    ``None`` (the masks it introduces are all-true), which is how the
    serving layer keeps one compiled program for both weathers.
    """
    if query_pins.ndim != 2:
        raise ValueError(
            f"query_pins must be (n_queries, n_slots), got {query_pins.shape}"
        )
    if cfg.n_v < 1:
        raise ValueError(
            f"n_v must be >= 1, got {cfg.n_v}; use "
            "cfg.without_early_stop() to disable early stopping"
        )
    if cfg.bias_beta > 0.0:
        raise ValueError(
            "the sharded graph carries no feat_bounds; set bias_beta=0 "
            "for sharded walks"
        )
    if cfg.gather_mode not in GATHER_MODES:
        raise ValueError(
            f"unknown gather_mode {cfg.gather_mode!r}; use {GATHER_MODES}"
        )
    n_queries, n_slots = query_pins.shape
    s_axis = mesh.shape[axis]
    if graph.n_shards not in (0, s_axis):
        raise ValueError(
            f"graph sharded {graph.n_shards} ways but mesh axis {axis!r} "
            f"has {s_axis} devices"
        )
    n_shards = s_axis
    # degraded mode is a PYTHON-level branch: shard_dead_at=None traces
    # the healthy program untouched (no dead masks in the jaxpr at all)
    faulty = shard_dead_at is not None
    if faulty:
        shard_dead_at = jnp.asarray(shard_dead_at, jnp.int32)
        if shard_dead_at.shape != (n_shards,):
            raise ValueError(
                f"shard_dead_at must be ({n_shards},) — one death "
                f"superstep per shard — got {shard_dead_at.shape}"
            )
    w = cfg.n_walkers
    w_total = n_queries * w
    pps = graph.pins_per_shard
    bps = graph.boards_per_shard
    cap = route_capacity(n_shards, w_total, slack)
    recv = n_shards * cap               # walker buffer after a route
    n_rows = n_queries * n_slots
    # per-shard dense bins must fit int32 indexing (the whole point of
    # sharding the count space: bins divide by n_shards)
    count_engine = walk_lib.select_count_engine(
        cfg.backend, n_rows, pps, bps if cfg.count_boards else 0
    )
    use_kernel = cfg.backend == "pallas"
    alpha_u32 = walk_lib._prob_u32(cfg.alpha)
    slot_sentinel = jnp.int32(n_slots)
    query_sentinel = jnp.int32(n_queries)

    valid_q = (query_pins >= 0) & (query_weights > 0)
    safe_q = jnp.where(valid_q, query_pins, 0)
    qid_of_walker = jnp.repeat(jnp.arange(n_queries, dtype=jnp.int32), w)

    def local_walk(p2b_off, p2b_tgt, b2p_off, b2p_tgt, qp, qw, vq, ks,
                   *fault):
        p2b_off, p2b_tgt = p2b_off[0], p2b_tgt[0]
        b2p_off, b2p_tgt = b2p_off[0], b2p_tgt[0]
        sid = jax.lax.axis_index(axis)
        pin_lo = sid * pps
        board_lo = sid * bps
        if faulty:
            (dead_at,) = fault            # (S,) replicated death schedule
            dead_self = jnp.take(dead_at, sid)

        # ---- replicated Eq. 1-2 setup: the same traced arithmetic as the
        # unsharded engine; query-pin degrees come from each shard's owned
        # rows, psum-replicated (ownership partitions the id space, so the
        # sum IS the lookup)
        owned_q = vq & (qp >= pin_lo) & (qp < pin_lo + pps)
        lq0 = jnp.where(owned_q, qp - pin_lo, 0)
        deg_own = (
            jnp.take(p2b_off, lq0 + 1) - jnp.take(p2b_off, lq0)
        ) * owned_q.astype(p2b_off.dtype)
        degs = jax.lax.psum(deg_own, axis)

        n_q = jax.vmap(
            lambda v, qwr, dg: sampling.allocate_steps(
                jnp.where(v, qwr, 0.0), dg,
                jnp.asarray(graph.max_pin_degree), cfg.n_steps,
            )
        )(vq, qw, degs)                                        # (B, S)
        slot_of_walker_q, _ = jax.vmap(
            lambda nq: sampling.allocate_walkers(nq, w)
        )(n_q)                                                 # (B, w)
        query_of_walker_q = jax.vmap(jnp.take)(qp, slot_of_walker_q)
        walkers_per_slot = jax.vmap(
            lambda so: jax.ops.segment_sum(
                jnp.ones((w,), jnp.int32), so, num_segments=n_slots
            )
        )(slot_of_walker_q).reshape(-1)                        # (B*S,)
        slot_of_walker = slot_of_walker_q.reshape(-1).astype(jnp.int32)
        query_of_walker = query_of_walker_q.reshape(-1).astype(jnp.int32)
        row_of_walker = qid_of_walker * n_slots + slot_of_walker
        home_of_walker = query_of_walker // pps

        valid_row = vq.reshape(-1)
        n_q_row = n_q.reshape(-1)

        def superstep(sstate, rb, row_active, first, step_abs):
            """One global hop for every live walker resident on this shard.

            ``rb`` is the whole batch's (w_total, 4) counter-RNG row for
            this absolute step; walkers index it by GLOBAL walker id, so
            each consumes bit-for-bit the unsharded engine's draws.
            ``step_abs`` is the absolute superstep index (None unless a
            fault schedule is active): liveness = ``step_abs < dead_at``.
            """
            if faulty:
                (res_v, res_g, res_p, counts, bcounts, high, dropped,
                 occ, killed) = sstate
                alive_vec = step_abs < dead_at                 # (S,) bool
                self_alive = step_abs < dead_self              # () bool
            else:
                (res_v, res_g, res_p, counts, bcounts, high, dropped,
                 occ) = sstate
            restart = rb[:, 0] < jnp.uint32(alpha_u32)         # (w_total,)
            active_w = jnp.take(row_active, row_of_walker)     # (w_total,)

            # kill + rebirth-at-home: restarting (or frozen-row) residents
            # leave the fabric; restarting walkers of active rows re-enter
            # at the shard owning their query pin with pos = query — the
            # unsharded `where(restart, query, curr)` applied BEFORE the
            # hop, so the reborn walker hops this same superstep
            res_live = (
                res_v
                & ~jnp.take(restart, res_g)
                & jnp.take(active_w, res_g)
            )
            inject = (restart | first) & active_w & (home_of_walker == sid)
            if faulty:
                # a dead shard kills its residents (tallied once, at the
                # death superstep) and stops (re)injecting its homed
                # walkers; a killed walker re-enters at its home on its
                # next restart draw — the ordinary rebirth path
                killed = killed + jnp.where(
                    step_abs == dead_self, jnp.sum(res_v), 0
                ).astype(jnp.int32)
                res_live = res_live & self_alive
                inject = inject & self_alive
            cand_v = jnp.concatenate([res_live, inject])
            cand_g = jnp.concatenate(
                [res_g, jnp.arange(w_total, dtype=jnp.int32)]
            )
            cand_p = jnp.concatenate([res_p, query_of_walker])
            order = jnp.argsort(~cand_v)       # stable: valid lanes first
            sel_v = jnp.take(cand_v, order)[:recv]
            sel_g = jnp.take(cand_g, order)[:recv]
            sel_p = jnp.take(cand_p, order)[:recv]
            d0 = (jnp.sum(cand_v) - jnp.sum(sel_v)).astype(jnp.int32)

            # ---- phase A: pin -> board, fused hop on the local p2b slice
            # (ONE pallas_call for the whole routed buffer, per shard)
            r1 = jnp.take(rb[:, 2], sel_g)
            b_pick, ok1 = ops.walk_hop(
                sel_p, sel_v, r1, p2b_off, p2b_tgt, pin_lo,
                use_kernel=use_kernel, gather_mode=cfg.gather_mode,
            )
            qpin = jnp.take(query_of_walker, sel_g)
            home = jnp.take(home_of_walker, sel_g)
            # dead-end pins force a restart: the walker routes home
            # carrying its query pin (flag 0 skips hop 2 and counting)
            dest1 = jnp.where(sel_v, jnp.where(ok1, b_pick // bps, home),
                              n_shards)
            pay1 = jnp.where(ok1, b_pick, qpin)
            if faulty:
                # walkers bound for a dead shard die in flight (the drop
                # sentinel keeps them out of the fabric); rebirth-at-home
                # on their next restart draw, like capacity drops
                tgt_dead1 = (dest1 < n_shards) & ~jnp.take(
                    alive_vec, jnp.minimum(dest1, n_shards - 1)
                )
                killed = killed + jnp.sum(tgt_dead1).astype(jnp.int32)
                dest1 = jnp.where(tgt_dead1, n_shards, dest1)
            v1, (g1, p1, f1), d1, o1 = _route(
                axis, n_shards, cap, dest1,
                (sel_g, pay1, ok1.astype(jnp.int32)),
            )

            # ---- phase B: board -> pin on the local b2p slice; board
            # visits count HERE, on the board's owner, gated by the full
            # step succeeding (the unsharded engine's bev validity)
            live1 = v1 & (f1 == 1)
            r2 = jnp.take(rb[:, 3], g1)
            pin_pick, ok2 = ops.walk_hop(
                p1, live1, r2, b2p_off, b2p_tgt, board_lo,
                use_kernel=use_kernel, gather_mode=cfg.gather_mode,
            )
            qpin1 = jnp.take(query_of_walker, g1)
            slot1 = jnp.take(slot_of_walker, g1)
            qid1 = jnp.take(qid_of_walker, g1)
            if cfg.count_boards:
                sev_b = jnp.where(ok2, slot1, slot_sentinel)
                qev_b = jnp.where(ok2, qid1, query_sentinel)
                bev = jnp.where(ok2, p1 - board_lo, 0)
                bcounts = counter_lib.accumulate_packed_events(
                    bcounts, sev_b, bev, n_slots, bps, count_engine,
                    query_events=qev_b, n_queries=n_queries,
                )
            # dead-end boards and in-flight restarts continue at the query
            nxt = jnp.where(ok2, pin_pick, qpin1)
            dest2 = jnp.where(v1, nxt // pps, n_shards)
            if faulty:
                tgt_dead2 = (dest2 < n_shards) & ~jnp.take(
                    alive_vec, jnp.minimum(dest2, n_shards - 1)
                )
                killed = killed + jnp.sum(tgt_dead2).astype(jnp.int32)
                dest2 = jnp.where(tgt_dead2, n_shards, dest2)
            v2, (g2, p2, e2), d2, o2 = _route(
                axis, n_shards, cap, dest2,
                (g1, nxt, ok2.astype(jnp.int32)),
            )

            # ---- arrival: wide (query, slot, local_pin) events into the
            # owned dense bins + the incremental crossing tally — never a
            # reduction over the count buffer
            cnt_ok = v2 & (e2 == 1)
            sev = jnp.where(
                cnt_ok, jnp.take(slot_of_walker, g2), slot_sentinel
            )
            qev = jnp.where(
                cnt_ok, jnp.take(qid_of_walker, g2), query_sentinel
            )
            pev = jnp.where(cnt_ok, p2 - pin_lo, 0)
            counts, high = counter_lib.accumulate_packed_events_with_high(
                counts, high, sev, pev, n_slots, pps, cfg.n_v, count_engine,
                query_events=qev, n_queries=n_queries,
            )
            occ = jnp.maximum(occ, jnp.maximum(o1, o2))
            out = (
                v2, g2, p2, counts, bcounts, high,
                dropped + d0 + d1 + d2, occ,
            )
            return out + (killed,) if faulty else out

        def chunk_body(it, state):
            if faulty:
                (res_v, res_g, res_p, counts, bcounts, high,
                 steps_taken, row_active, dropped, occ, killed) = state
            else:
                (res_v, res_g, res_p, counts, bcounts, high,
                 steps_taken, row_active, dropped, occ) = state
            step_base = it * cfg.chunk_steps
            # replicated whole-batch counter RNG: identical arithmetic to
            # _walk_chunk_batched, so walker q*w+i draws its unsharded bits
            rbits_q = jax.vmap(
                lambda k: walk_lib._chunk_rbits(
                    k, step_base, cfg.chunk_steps, w
                )
            )(ks)
            rbits = jnp.moveaxis(rbits_q, 0, 1).reshape(
                cfg.chunk_steps, w_total, 4
            )
            first0 = it == 0
            sstate = (res_v, res_g, res_p, counts, bcounts, high,
                      dropped, occ)
            if faulty:
                sstate = sstate + (killed,)
            if unroll:
                for s in range(cfg.chunk_steps):
                    sstate = superstep(
                        sstate, rbits[s], row_active, first0 & (s == 0),
                        (step_base + s) if faulty else None,
                    )
            else:
                sstate = jax.lax.fori_loop(
                    0, cfg.chunk_steps,
                    lambda s, st: superstep(
                        st, rbits[s], row_active, first0 & (s == 0),
                        (step_base + s) if faulty else None,
                    ),
                    sstate,
                )
            if faulty:
                (res_v, res_g, res_p, counts, bcounts, high,
                 dropped, occ, killed) = sstate
            else:
                (res_v, res_g, res_p, counts, bcounts, high,
                 dropped, occ) = sstate
            steps_taken = steps_taken + walkers_per_slot * row_active.astype(
                jnp.int32
            ) * cfg.chunk_steps
            # the chunk-boundary fold: psum of the carried per-shard
            # tallies IS the global Algorithm 3 statistic (ownership
            # partitions the bins, crossings sum)
            if faulty:
                # a dead shard's bins die with it, so its crossing tally
                # leaves the early-stop statistic the moment it does —
                # the statistic always describes HARVESTABLE counts
                alive_h = dead_self > (step_base + cfg.chunk_steps - 1)
                g_high = jax.lax.psum(
                    jnp.where(alive_h, high, 0), axis
                )
            else:
                g_high = jax.lax.psum(high, axis)
            row_active = (
                valid_row & (steps_taken < n_q_row) & (g_high <= cfg.n_p)
            )
            out = (res_v, res_g, res_p, counts, bcounts, high,
                   steps_taken, row_active, dropped, occ)
            return out + (killed,) if faulty else out

        state = (
            jnp.zeros((recv,), jnp.bool_),
            jnp.zeros((recv,), jnp.int32),
            jnp.zeros((recv,), jnp.int32),
            jnp.zeros((n_rows * pps,), jnp.int32),
            jnp.zeros((n_rows * bps,), jnp.int32)
            if cfg.count_boards else None,
            jnp.zeros((n_rows,), jnp.int32),
            jnp.zeros((n_rows,), jnp.int32),
            valid_row,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32),
        )
        if faulty:
            state = state + (jnp.asarray(0, jnp.int32),)   # killed tally
        if unroll:
            # cost-model mode: loop-free, every chunk runs (stopped rows
            # are frozen by masking, so the math is unchanged)
            for it in range(cfg.max_chunks()):
                state = chunk_body(jnp.asarray(it, jnp.int32), state)
            n_chunks = jnp.asarray(cfg.max_chunks(), jnp.int32)
        else:
            def cond(st_it):
                st, it = st_it
                return jnp.any(st[7]) & (it < cfg.max_chunks())

            state, n_chunks = jax.lax.while_loop(
                cond,
                lambda st_it: (
                    chunk_body(st_it[1], st_it[0]), st_it[1] + 1
                ),
                (state, jnp.asarray(0, jnp.int32)),
            )
        if faulty:
            (_, _, _, counts, bcounts, high,
             steps_taken, _, dropped, occ, killed) = state
            # harvest liveness: a shard that died before the walk ended
            # harvests NOTHING (its HBM left with it) — zero its counts,
            # board counts, and crossing tally BEFORE the query-pin
            # debit, so the merge renormalizes over survivors; a shard
            # whose death superstep the walk never reached was healthy
            # the whole time and harvests normally
            supersteps_run = n_chunks * cfg.chunk_steps
            keep = (dead_self >= supersteps_run).astype(jnp.int32)
            counts = counts * keep
            if cfg.count_boards:
                bcounts = bcounts * keep
            high = high * keep
        else:
            (_, _, _, counts, bcounts, high,
             steps_taken, _, dropped, occ) = state

        # ---- query-pin debit, mirroring the unsharded engine bit-for-bit
        # (position-only ownership: invalid slots hit all-zero bins, the
        # same no-op as the unsharded unconditional `.set(0)`)
        c3 = counts.reshape(n_queries, n_slots, pps)
        own_q = (qp >= pin_lo) & (qp < pin_lo + pps)
        lq = jnp.where(own_q, qp - pin_lo, 0)
        b_i = jnp.arange(n_queries)[:, None]
        s_i = jnp.arange(n_slots)[None, :]
        vals = c3[b_i, s_i, lq]
        q_reach = (own_q & (vals >= cfg.n_v)).astype(jnp.int32)
        c3 = c3.at[b_i, s_i, lq].set(jnp.where(own_q, 0, vals))
        q_reached = jax.lax.psum(q_reach, axis)
        g_high = jax.lax.psum(high, axis).reshape(n_queries, n_slots)
        n_high = g_high - q_reached
        dropped_total = jax.lax.psum(dropped, axis)
        occ_max = jax.lax.pmax(occ, axis)
        out = (
            c3.reshape(-1)[None],
            bcounts[None] if cfg.count_boards else None,
            steps_taken.reshape(n_queries, n_slots),
            n_high,
            dropped_total,
            occ_max,
        )
        if faulty:
            out = out + (jax.lax.psum(killed, axis),)
        return out

    shd = P(axis, None)
    rep = P()
    fn = jax.shard_map(
        local_walk,
        mesh=mesh,
        in_specs=(shd, shd, shd, shd, rep, rep, rep, rep)
        + ((rep,) if faulty else ()),
        out_specs=(
            shd, shd if cfg.count_boards else None, rep, rep, rep, rep
        ) + ((rep,) if faulty else ()),
        check_vma=False,
    )
    args = (
        graph.p2b_offsets, graph.p2b_targets,
        graph.b2p_offsets, graph.b2p_targets,
        safe_q, jnp.where(valid_q, query_weights, 0.0),
        valid_q, keys,
    )
    if faulty:
        counts, bcounts, steps_taken, n_high, dropped, occ, killed = fn(
            *args, shard_dead_at
        )
    else:
        counts, bcounts, steps_taken, n_high, dropped, occ = fn(*args)
        killed = None
    return ShardedBatchedWalkResult(
        counts=counts,
        board_counts=bcounts,
        steps_taken=steps_taken,
        n_high=n_high,
        dropped=dropped,
        max_occupancy=occ,
        killed=killed,
    )


def _hierarchical_topk(
    counts: Array,      # (S, B * n_slots * pps) shard-stacked counts
    n_shards: int,
    n_queries: int,
    n_slots: int,
    pps: int,
    k: int,
) -> Tuple[Array, Array]:
    """Exact global boosted top-k from shard-stacked counts.

    Eq. 3's boost is per-pin, so per-shard boost + top-k followed by a
    global re-top-k over ``S * k`` candidates is EXACT (never misses a
    global top-k pin: each shard forwards at least its own k best).
    """
    c = counts.reshape(n_shards, n_queries, n_slots, pps)

    def shard_topk(cs):  # (B, n_slots, pps) one shard's owned counts
        boosted = jax.vmap(counter_lib.boost_combine)(cs)       # (B, pps)
        return jax.vmap(lambda b: counter_lib.topk_dense(b, k))(boosted)

    scores, idx = jax.vmap(shard_topk)(c)                       # (S, B, k)
    pins = idx.astype(jnp.int32) + (
        jnp.arange(n_shards, dtype=jnp.int32) * pps
    )[:, None, None]
    flat_s = jnp.moveaxis(scores, 0, 1).reshape(n_queries, n_shards * k)
    flat_p = jnp.moveaxis(pins, 0, 1).reshape(n_queries, n_shards * k)
    gs, gi = jax.vmap(lambda v: jax.lax.top_k(v, k))(flat_s)
    gp = jnp.take_along_axis(flat_p, gi, axis=1)
    return gs, gp


def recommend_sharded_batched(
    graph: ShardedGraph,
    query_pins: Array,      # (B, n_slots)
    query_weights: Array,   # (B, n_slots)
    keys: Array,            # (B,) per-query PRNG keys
    cfg: walk_lib.WalkConfig,
    mesh: Mesh,
    axis: str = "model",
    *,
    slack: float = 2.0,
    shard_dead_at: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array, Array]:
    """Batch-native sharded serving: walk + hierarchical boosted top-k.

    Returns ``(scores (B, top_k), ids (B, top_k), steps_taken (B,
    n_slots), n_high (B, n_slots), dropped ())`` — the sharded twin of
    ``walk.recommend_with_stats_batched`` plus the routing-drop telemetry
    ``serve_batch(with_stats=True)`` surfaces.  ``shard_dead_at`` is the
    degraded-mode liveness schedule (``pixie_walk_sharded_batched``);
    the hierarchical top-k needs no change — a dead shard's owned counts
    arrive zeroed, so its candidates simply never win a slot.
    """
    res = pixie_walk_sharded_batched(
        graph, query_pins, query_weights, keys, cfg, mesh, axis,
        slack=slack, shard_dead_at=shard_dead_at,
    )
    n_queries, n_slots = query_pins.shape
    scores, ids = _hierarchical_topk(
        res.counts, mesh.shape[axis], n_queries, n_slots,
        graph.pins_per_shard, cfg.top_k,
    )
    return scores, ids, res.steps_taken, res.n_high, res.dropped


# ---------------------------------------------------------------------------
# Single-query convenience wrapper (launch cells, examples)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedWalkConfig:
    """Single-query sharded walk knobs (``pixie_walk_sharded``).

    A thin recipe over the batched engine: ``n_supersteps`` global hops
    with ``n_shards * walkers_per_shard`` walkers, no early stopping
    (Algorithm 1 semantics, like the original sharded path).  ``slack``
    scales routing capacity (``route_capacity``); ``backend`` /
    ``gather_mode`` select the per-shard hop engine; ``unroll`` is the
    loop-free cost-model mode (launch/dryrun.py).
    """

    n_supersteps: int = 64
    walkers_per_shard: int = 1024
    alpha: float = 0.5
    slack: float = 2.0
    top_k: int = 100
    unroll: bool = False     # cost-model mode (see launch/dryrun.py)
    backend: str = "xla"
    gather_mode: str = "scalar"

    def capacity(self, n_shards: int) -> int:
        return route_capacity(
            n_shards, n_shards * self.walkers_per_shard, self.slack
        )


class ShardedWalkResult(NamedTuple):
    top_scores: Array    # (top_k,) f32 boosted scores
    top_pins: Array      # (top_k,) int32 global pin ids
    dropped: Array       # () int32 walkers dropped by routing overflow


def _wrapper_walk_config(
    cfg: ShardedWalkConfig, n_shards: int
) -> walk_lib.WalkConfig:
    """Map the single-query recipe onto the batched engine's config."""
    w_total = n_shards * cfg.walkers_per_shard
    n_ss = cfg.n_supersteps
    chunk = 8 if n_ss % 8 == 0 else (4 if n_ss % 4 == 0 else 1)
    return walk_lib.WalkConfig(
        n_steps=w_total * n_ss,
        alpha=cfg.alpha,
        n_walkers=w_total,
        chunk_steps=chunk,
        bias_beta=0.0,
        top_k=cfg.top_k,
        count_boards=False,
        backend=cfg.backend,
        gather_mode=cfg.gather_mode,
    ).without_early_stop()


def pixie_walk_sharded(
    graph: ShardedGraph,
    query_pins: Array,      # (n_slots,) int32 global pin ids (-1 pad)
    query_weights: Array,   # (n_slots,) f32
    key: Array,
    cfg: ShardedWalkConfig,
    mesh: Mesh,
    axis: str = "model",
) -> ShardedWalkResult:
    """Multi-query Pixie walk on a node-range-sharded graph (batch of 1).

    Runs the pod-sharded batched fused engine
    (``pixie_walk_sharded_batched``) for one query and finishes with the
    exact hierarchical boosted top-k.
    """
    wcfg = _wrapper_walk_config(cfg, mesh.shape[axis])
    keys = jax.random.split(key, 1)
    res = pixie_walk_sharded_batched(
        graph, query_pins[None], query_weights[None], keys, wcfg, mesh,
        axis, slack=cfg.slack, unroll=cfg.unroll,
    )
    n_slots = query_pins.shape[0]
    scores, pins = _hierarchical_topk(
        res.counts, mesh.shape[axis], 1, n_slots, graph.pins_per_shard,
        cfg.top_k,
    )
    return ShardedWalkResult(
        top_scores=scores[0], top_pins=pins[0], dropped=res.dropped
    )
