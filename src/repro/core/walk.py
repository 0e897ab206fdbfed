"""The Pixie Random Walk engine (paper §3.1, Algorithms 1-3), vectorized.

The paper's walk is sequential pointer chasing; the TPU-native form runs W
independent walkers in lockstep.  One *step* for every walker is:

    maybe-restart -> sample board from E(pin) -> sample pin from E(board)
    -> record visit

which is exactly Algorithm 2's inner loop, with ``SampleWalkLength(alpha)``
realised as a per-step Bernoulli(alpha) restart (geometric segment lengths,
E[len] = 1/alpha; see core/sampling.py).

Two interchangeable step engines (``WalkConfig.backend``):

  * ``"xla"``    — pure-XLA two-level gathers (kernels/ref.walk_chunk_ref);
                   the numerical reference, runs anywhere.
  * ``"pallas"`` — the fused multi-superstep Pallas kernel
                   (kernels/walk_step.walk_steps_fused): ONE kernel launch
                   per ``chunk_steps`` steps with walker state resident in
                   SMEM across the whole chunk, wide (slot, pin) visit
                   events emitted in-kernel, and counts recovered with the
                   scatter-free tile-scan ``visit_counter`` kernels.  Its
                   CSR reads are row DMAs in two bit-identical orders
                   (``WalkConfig.gather_mode``): each walker's copies
                   waited on at once ("scalar"), or double-buffered so
                   each walker's HBM latency hides behind its neighbour's
                   ("dma").  On CPU hosts the kernel runs in interpret
                   mode.

Events are WIDE — two int32 lanes, (slot, pin), slot lane ``n_slots`` as
the invalid-step sentinel — never the packed ``slot * n_pins + pin``
product, so BOTH engines cover production id spaces past 2**31 (the
paper's 3B-pin regime) with no int64 anywhere and no fallback: backend
choice is a pure performance knob at every scale.

Both engines consume the SAME counter-based random bits (one uint32
quadruple per walker-step, threefry fold-in of the step index), do the same
integer arithmetic on them, and therefore produce bit-for-bit identical
visit events — backend choice is a pure performance knob, verified by
tests/test_walk_backends.py.

Two counting backends (see core/counter.py):
  * dense  — per-(query-slot, pin) counts; benchmark-scale and per-shard
             production counting (a dense buffer inherently needs
             n_slots * n_pins < 2**31).  The xla engine scatter-adds; the
             pallas engine histograms the event lanes (no scatters).
  * events — bounded wide (slot, pin) lane buffers + pair-sort aggregation;
             scale-free, memory O(N) like the paper's hash table, id space
             unlimited.  Both engines emit the lane buffers directly.

Serving batches are BATCH-NATIVE (``pixie_random_walk_batched``): the
whole batch's walkers run on one walker axis with a per-walker query lane,
each chunk is one fused call (one ``pallas_call`` on the pallas engine)
plus one query-major counting call over (query, slot, pin) triple bins,
and a single shared while loop carries a per-(query, slot) early-stop
mask — bit-identical to vmapping the per-query engine over
``jax.random.split`` keys, which remains the oracle twin
(tests/test_batchfuse.py).

Early stopping (Algorithm 2 lines 10-13) is evaluated every chunk: a query
slot stops once >= n_p pins reached n_v visits or its step budget N_q is
spent; the whole walk stops when every slot stopped.  The statistic is
maintained INCREMENTALLY: the while-loop carries a (n_slots,) running
``n_high`` tally updated by ``counter_lib.accumulate_packed_events_with_high``
from just the chunk's own events (xla: sort the chunk and gather old/new
counts at the touched bins; pallas: threshold crossings emitted by the fused
``visit_counter_update_high`` kernel while the count tile is in VMEM) — the
loop body never reduces the full n_slots * n_pins buffer.  Event mode is
incremental too: ``counter_lib.EventHighState`` keeps each check window's
sorted runs, and the ``check_every`` body sorts ONLY the new window's
events (``events_high_fold``) — never the whole ``max_events`` buffer.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import counter as counter_lib
from repro.core import sampling
from repro.core.graph import PinBoardGraph
from repro.kernels import ops
from repro.kernels.walk_step import GATHER_MODES

Array = jax.Array

BACKENDS = ("xla", "pallas")


def packed_event_dtype(n_slots: int, n_pins: int):
    """Dtype of EACH wide event lane — always int32.

    Events are (slot, pin) lane pairs; no lane ever holds the packed
    ``slot * n_pins + pin`` product, so the lane dtype is int32 at every
    id-space scale (including the 3B-pin production graph that used to
    force int64 packing).  Kept as the single documented statement of the
    lane-dtype contract — nothing in the engine branches on it anymore,
    and tests pin that it stays int32 at production shapes.
    """
    del n_slots, n_pins  # wide lanes: scale no longer changes the dtype
    return jnp.int32


def select_count_engine(
    backend: str, n_slots: int, n_pins: int, n_boards: int = 0
) -> str:
    """Counting engine for a (slot, pin/board) id space: the backend itself.

    Wide event lanes removed the int32 packing cliff, so there is no
    fallback branch left — ``backend="pallas"`` counts with the wide
    tile-scan kernels at every id-space scale that dense counting can
    materialize at all, and event-mode counting has no scale limit on
    either engine.  Still the single shape-level validation point: dense
    counting inherently needs ``n_slots * max(n_pins, n_boards) < 2**31``
    (the count buffer is materialized), checked here loudly so production
    configs fail before a giant allocation, pointing at event mode.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown walk backend {backend!r}; use {BACKENDS}")
    n_bins = n_slots * max(n_pins, n_boards)
    if n_bins + 1 >= 2**31:
        raise ValueError(
            f"dense counting materializes n_slots * n_dim = {n_bins} bins, "
            "past int32 indexing; use event-mode counting "
            "(pixie_walk_events) for production-scale id spaces"
        )
    return backend


def batched_engine_fits(
    n_queries: int,
    n_slots: int,
    n_pins: int,
    n_boards: int = 0,
    count_boards: bool = False,
    n_shards: int = 1,
) -> bool:
    """Whether the batch-native dense engine can materialize its bins.

    The batched engine's query-major count buffer has
    ``n_queries * n_slots * n_pins`` int32-indexed bins (boards too when
    counted) — a STRICTER envelope than the vmapped per-query path, whose
    flat indexing only spans ``n_slots * n_pins`` per query even though
    its total memory is the same.  ``serve_batch`` consults this to fall
    back to the vmapped formulation instead of turning a
    previously-serving (graph, batch) shape into a trace-time error.
    Pure-int predicate so callers (and tests) can probe production shapes
    without materializing anything.

    ``n_shards > 1`` probes the pod-sharded batched engine: each shard
    only counts its OWNED id subrange, so the per-shard bin space divides
    by the shard count — the mechanism that brings the paper's 3B-pin
    id space under the int32 dense-count envelope (2e9 pins / 16 shards
    at n_slots = 16, batch 1: 2e9 bins < 2**31).
    """
    per_shard = -(-max(n_pins, n_boards if count_boards else 0) // n_shards)
    n_bins = n_queries * n_slots * per_shard
    return n_bins + 1 < 2**31


# disables Algorithm 2's early stopping: no pin can ever reach this many
# visits.  int32-safe because the tally machinery only COMPARES counts
# against n_v (never adds to it) — see accumulate_packed_events_with_high.
NO_EARLY_STOP_NV = jnp.iinfo(jnp.int32).max // 2


def _prob_u32(p: float) -> int:
    """Map a probability to the uint32 threshold used by both step engines."""
    return max(0, min(int(round(p * 2.0**32)), 2**32 - 1))


@dataclasses.dataclass(frozen=True)
class WalkConfig:
    """Hyper-parameters of the Pixie random walk.

    n_steps:      N — total step budget across all query pins (Eq. 2).
    alpha:        restart probability; E[walk segment] = 1/alpha.
    n_walkers:    number of parallel walkers (TPU adaptation; the paper's
                  sequential walker is n_walkers=1).
    chunk_steps:  steps fused per while-loop iteration between early-stop
                  checks (the paper checks per step; chunking trades slack
                  for device efficiency).  With backend="pallas" this is
                  also the number of supersteps fused into one kernel
                  launch.
    n_p, n_v:     early-stopping thresholds (>= n_p pins with >= n_v visits).
    bias_beta:    probability a step uses the personalized feature subrange
                  (PersonalizedNeighbor); 0 disables biasing (Algorithm 1).
    top_k:        number of recommendations extracted from the counter.
    count_boards: also accumulate board visit counts (for board recs, §5.3).
    backend:      "xla" (reference two-level gathers + scatter-add counts)
                  or "pallas" (fused multi-superstep kernel + tile-scan
                  histogram counts).  Both produce bit-identical visits.
    pallas_block_w: walkers per Pallas grid cell (None = auto).
    gather_mode:  how the pallas engine issues its per-walker CSR reads,
                  each a copy of one 128-element HBM row into SMEM:
                  "scalar" (walker by walker, each copy waited on as soon
                  as it is started — one exposed HBM round trip per hop
                  phase per walker) or "dma" (phase by phase over the
                  block, walker i+1's copies started before walker i's
                  are waited on — one latency hides behind the next).
                  Bit-identical to each other and to the xla engine; a
                  pure memory-latency knob on TPU hosts (interpret-mode
                  CPU timings don't show it).  Ignored by backend="xla".
    """

    n_steps: int = 100_000
    alpha: float = 0.5
    n_walkers: int = 1024
    chunk_steps: int = 8
    n_p: int = 2_000
    n_v: int = 4
    bias_beta: float = 0.9
    top_k: int = 1_000
    count_boards: bool = False
    backend: str = "xla"
    pallas_block_w: Optional[int] = None
    gather_mode: str = "scalar"

    def max_chunks(self) -> int:
        per_chunk = self.n_walkers * self.chunk_steps
        return max(1, -(-self.n_steps // per_chunk))

    def without_early_stop(self) -> "WalkConfig":
        """Algorithm 1 mode: run the full step budget, never stop early.

        Uses thresholds no walk can reach (``NO_EARLY_STOP_NV`` is compared
        against counts, never added to them, so the sentinel cannot
        overflow the incremental high tally).
        """
        return dataclasses.replace(
            self, n_p=self.n_steps + 1, n_v=NO_EARLY_STOP_NV
        )


class WalkResult(NamedTuple):
    """Dense-mode walk output."""

    counts: Array           # (n_slots, n_pins) int32 per-query visit counts
    board_counts: Optional[Array]  # (n_slots, n_boards) or None
    steps_taken: Array      # (n_slots,) int32
    n_high: Array           # (n_slots,) int32 pins that reached n_v visits
                            # (the loop's running tally, query pins debited)


class EventWalkResult(NamedTuple):
    """Event-mode walk output (scale-free, wide lanes)."""

    slot_events: Array      # (max_events,) int32 slot lane (n_slots = invalid)
    pin_events: Array       # (max_events,) int32 pin lane
    steps_taken: Array      # (n_slots,) int32
    chunks_run: Array       # () int32
    n_high: Array           # (n_slots,) int32 incremental Algorithm 3 tally
                            # as of the last completed check window (zeros
                            # when early stopping never checked)


# ---------------------------------------------------------------------------
# One chunk of steps for all walkers (shared by both modes and backends)
# ---------------------------------------------------------------------------


def _chunk_rbits(key: Array, step_base: Array, chunk_steps: int, w: int) -> Array:
    """Counter-based random bits for one chunk: (chunk_steps, w, 4) uint32.

    Column 0 drives the restart decision (< alpha threshold), column 1 the
    personalization decision (< beta threshold), columns 2/3 the board/pin
    neighbour picks.  Keyed by absolute step index so a restarted run
    replays the identical walk (fault-tolerance contract).
    """
    steps = step_base + jnp.arange(chunk_steps, dtype=jnp.int32)
    keys = jax.vmap(lambda s: sampling.step_key(key, s))(steps)
    return jax.vmap(lambda k: jax.random.bits(k, (w, 4)))(keys)


def _validated_bias_bounds(
    graph: PinBoardGraph, cfg: WalkConfig
) -> Tuple[Optional[Array], Optional[Array]]:
    """(p2b, b2p) feat bounds for a biased walk, or (None, None).

    Shared by the per-query and batched chunk drivers so both refuse a
    one-sided graph identically: a graph with feat_bounds on only one CSR
    side can't answer a biased walk, and refusing loudly beats silently
    dropping personalization.
    """
    if cfg.backend not in BACKENDS:
        raise ValueError(f"unknown walk backend {cfg.backend!r}; use {BACKENDS}")
    if cfg.gather_mode not in GATHER_MODES:
        raise ValueError(
            f"unknown gather_mode {cfg.gather_mode!r}; use {GATHER_MODES}"
        )
    has_p2b = graph.p2b.feat_bounds is not None
    has_b2p = graph.b2p.feat_bounds is not None
    if has_p2b != has_b2p and cfg.bias_beta > 0.0:
        raise ValueError(
            "graph has feat_bounds on only one CSR side; build both sides "
            "for biased walks or set bias_beta=0"
        )
    use_bias = has_p2b and has_b2p and cfg.bias_beta > 0.0
    return (
        graph.p2b.feat_bounds if use_bias else None,
        graph.b2p.feat_bounds if use_bias else None,
    )


def _walk_chunk(
    graph: PinBoardGraph,
    curr: Array,             # (W,) int32 current pin per walker
    query_of_walker: Array,  # (W,) int32 restart target
    user_feat: Array,        # () or (W,) int32 personalization feature
    slot_of_walker: Array,   # (W,) int32 query slot per walker
    key: Array,
    step_base: Array,        # () int32 global step counter (for counter RNG)
    cfg: WalkConfig,
    n_slots: int,
    unroll: bool = False,
) -> Tuple[Array, Array, Array, Optional[Array]]:
    """Run cfg.chunk_steps steps.

    Returns ``(new_curr, slot_events, pin_events, board_events)`` — wide
    int32 event lanes, each (chunk_steps, W); the slot lane carries
    ``n_slots`` for uncountable steps (dead-end forced restarts) and is
    shared by the pin and board lanes.  board_events is None unless
    cfg.count_boards.  Dispatches on cfg.backend; both engines consume the
    same random bits and agree bit-for-bit at every id-space scale — wide
    lanes have no int32 packing cliff, so there is no fallback.
    """
    p2b_fb, b2p_fb = _validated_bias_bounds(graph, cfg)
    w = curr.shape[0]
    rbits = _chunk_rbits(key, step_base, cfg.chunk_steps, w)
    feat = jnp.broadcast_to(jnp.asarray(user_feat, jnp.int32), (w,))
    return ops.walk_chunk_fused(
        curr,
        query_of_walker,
        feat,
        slot_of_walker,
        rbits,
        graph.p2b.offsets,
        graph.p2b.targets,
        graph.b2p.offsets,
        graph.b2p.targets,
        p2b_fb,
        b2p_fb,
        n_pins=graph.n_pins,
        n_slots=n_slots,
        n_boards=graph.n_boards,
        alpha_u32=_prob_u32(cfg.alpha),
        beta_u32=_prob_u32(cfg.bias_beta),
        count_boards=cfg.count_boards,
        unroll=unroll,
        block_w=cfg.pallas_block_w,
        gather_mode=cfg.gather_mode,
        use_kernel=(cfg.backend == "pallas"),
    )


def _walk_chunk_batched(
    graph: PinBoardGraph,
    curr: Array,             # (n_queries * w,) int32 current pin per walker
    query_of_walker: Array,  # (n_queries * w,) int32 restart target
    feat_of_walker: Array,   # (n_queries * w,) int32 personalization feature
    slot_of_walker: Array,   # (n_queries * w,) int32 query slot per walker
    qid_of_walker: Array,    # (n_queries * w,) int32 query id per walker
    keys: Array,             # (n_queries,) per-query PRNG keys
    step_base: Array,        # () int32 global step counter (for counter RNG)
    cfg: WalkConfig,
    n_slots: int,
    n_queries: int,
) -> Tuple[Array, Array, Array, Array, Optional[Array]]:
    """Batch-native chunk: every query's walkers in ONE fused call.

    Returns ``(new_curr, query_events, slot_events, pin_events,
    board_events)`` — the wide (query, slot, pin) int32 event triple, each
    lane (chunk_steps, n_queries * w).  The random bits are the EXACT
    per-query streams of the vmapped path: each query's
    ``jax.random.split``-derived key generates its own
    ``(chunk_steps, w, 4)`` block (``_chunk_rbits``), and the blocks are
    laid out query-major along the walker axis — so walker ``q * w + i``
    consumes bit-for-bit the same draws it would inside
    ``pixie_random_walk`` for query ``q`` alone.
    """
    p2b_fb, b2p_fb = _validated_bias_bounds(graph, cfg)
    w_total = curr.shape[0]
    w = w_total // n_queries
    rbits_q = jax.vmap(
        lambda k: _chunk_rbits(k, step_base, cfg.chunk_steps, w)
    )(keys)                                     # (n_queries, chunk_steps, w, 4)
    rbits = jnp.moveaxis(rbits_q, 0, 1).reshape(cfg.chunk_steps, w_total, 4)
    return ops.walk_chunk_fused_batched(
        curr,
        query_of_walker,
        feat_of_walker,
        slot_of_walker,
        qid_of_walker,
        rbits,
        graph.p2b.offsets,
        graph.p2b.targets,
        graph.b2p.offsets,
        graph.b2p.targets,
        p2b_fb,
        b2p_fb,
        n_pins=graph.n_pins,
        n_slots=n_slots,
        n_queries=n_queries,
        n_boards=graph.n_boards,
        alpha_u32=_prob_u32(cfg.alpha),
        beta_u32=_prob_u32(cfg.bias_beta),
        count_boards=cfg.count_boards,
        block_w=cfg.pallas_block_w,
        gather_mode=cfg.gather_mode,
        use_kernel=(cfg.backend == "pallas"),
    )


# ---------------------------------------------------------------------------
# Dense-mode multi-query walk (Algorithms 2 + 3)
# ---------------------------------------------------------------------------


def pixie_random_walk(
    graph: PinBoardGraph,
    query_pins: Array,     # (n_slots,) int32, padded with -1
    query_weights: Array,  # (n_slots,) float32, 0 for padding
    user_feat: Array,      # () int32 personalization feature (e.g. language)
    key: Array,
    cfg: WalkConfig,
    step_budget=None,      # optional () int32 override of cfg.n_steps
) -> WalkResult:
    """PIXIERANDOMWALKMULTIPLE: biased, weighted, early-stopped, boosted.

    Returns dense per-slot visit counts; combine with
    ``counter_lib.boost_combine`` + ``topk_dense`` for recommendations.

    ``step_budget`` overrides the Eq. 2 total ``cfg.n_steps`` as DATA (a
    Python int or a traced int32 scalar) — the multi-interest query layer
    gives each interest-cluster lane its own budget without recompiling
    per budget value.  Budgets are CLAMPED to ``cfg.n_steps``: the while
    loop's static chunk bound stays ``cfg.max_chunks()``, so a smaller
    budget exhausts via the per-slot ``steps_taken < n_q`` check, and a
    larger one — which the loop could never actually walk — is bounded
    up front instead of silently truncating with inconsistent
    ``steps_taken`` bookkeeping.
    """
    if cfg.n_v < 1:
        raise ValueError(
            f"n_v must be >= 1, got {cfg.n_v}; use "
            "cfg.without_early_stop() to disable early stopping"
        )
    n_slots = query_pins.shape[0]
    n_pins = graph.n_pins
    w = cfg.n_walkers
    # board ids are only counted when count_boards: a pin-only walk must
    # not be rejected because a board id space nobody counts would not fit
    # a dense buffer (the shape-level chooser makes the same distinction)
    n_boards_packed = graph.n_boards if cfg.count_boards else 0
    slot_sentinel = jnp.int32(n_slots)
    count_engine = select_count_engine(
        cfg.backend, n_slots, n_pins, n_boards_packed
    )

    with jax.named_scope("pixie.query"):
        valid_q = (query_pins >= 0) & (query_weights > 0)
        safe_q = jnp.where(valid_q, query_pins, 0)
        degs = graph.pin_degree(safe_q) * valid_q.astype(
            graph.p2b.offsets.dtype
        )

        # Eq. 1-2: per-slot step budgets; walker pool apportioned to match.
        n_q = sampling.allocate_steps(
            jnp.where(valid_q, query_weights, 0.0),
            degs,
            jnp.asarray(graph.max_pin_degree),
            cfg.n_steps if step_budget is None
            else jnp.minimum(jnp.asarray(step_budget, jnp.int32),
                             cfg.n_steps),
        )
        slot_of_walker, _ = sampling.allocate_walkers(n_q, w)
        query_of_walker = jnp.take(safe_q, slot_of_walker).astype(jnp.int32)
        walkers_per_slot = jax.ops.segment_sum(
            jnp.ones((w,), jnp.int32), slot_of_walker, num_segments=n_slots
        )

    def cond(state):
        _, _, _, _, steps_taken, slot_active, it = state
        return jnp.any(slot_active) & (it < cfg.max_chunks())

    def body(state):
        curr, counts, bcounts, high, steps_taken, slot_active, it = state
        step_base = it * cfg.chunk_steps
        walker_active = jnp.take(slot_active, slot_of_walker)

        with jax.named_scope("pixie.walk.hop"):
            curr2, sev, pev, bev = _walk_chunk(
                graph, curr, query_of_walker, user_feat, slot_of_walker,
                key, step_base, cfg, n_slots,
            )
        curr = jnp.where(walker_active, curr2, curr)
        # masking the shared slot lane invalidates pin AND board events
        sev = jnp.where(walker_active[None, :], sev, slot_sentinel)
        # fused: accumulate the chunk AND update the running n_high tally —
        # no n_slots * n_pins reduction anywhere in this loop body
        with jax.named_scope("pixie.walk.count"):
            counts, high = counter_lib.accumulate_packed_events_with_high(
                counts, high, sev, pev, n_slots, n_pins, cfg.n_v,
                count_engine,
            )
            if cfg.count_boards:
                bcounts = counter_lib.accumulate_packed_events(
                    bcounts, sev, bev, n_slots, graph.n_boards, count_engine
                )

        steps_taken = steps_taken + walkers_per_slot * slot_active.astype(
            jnp.int32
        ) * cfg.chunk_steps

        # early stopping: slot stops when n_high > n_p or budget exhausted
        slot_active = (
            valid_q
            & (steps_taken < n_q)
            & (high <= cfg.n_p)
        )
        return curr, counts, bcounts, high, steps_taken, slot_active, it + 1

    with jax.named_scope("pixie.walk"):
        counts0 = jnp.zeros((n_slots * n_pins,), dtype=jnp.int32)
        bcounts0 = (
            jnp.zeros((n_slots * graph.n_boards,), dtype=jnp.int32)
            if cfg.count_boards
            else None
        )
        state0 = (
            query_of_walker,
            counts0,
            bcounts0,
            jnp.zeros((n_slots,), jnp.int32),
            jnp.zeros((n_slots,), jnp.int32),
            valid_q,
            jnp.asarray(0, jnp.int32),
        )
        curr, counts, bcounts, high, steps_taken, _, _ = jax.lax.while_loop(
            cond, body, state0
        )
    with jax.named_scope("pixie.eq3"):
        per_slot = counts.reshape(n_slots, n_pins)
        # never recommend the query pins themselves; the running tally
        # counted a query pin that reached n_v, so zeroing it must also
        # debit the tally
        q_rows = jnp.arange(n_slots)
        q_reached = (per_slot[q_rows, safe_q] >= cfg.n_v).astype(jnp.int32)
        per_slot = per_slot.at[q_rows, safe_q].set(0)
    return WalkResult(
        counts=per_slot,
        board_counts=None
        if bcounts is None
        else bcounts.reshape(n_slots, graph.n_boards),
        steps_taken=steps_taken,
        n_high=high - q_reached,
    )


def basic_random_walk(
    graph: PinBoardGraph,
    query_pin: Array,
    key: Array,
    cfg: WalkConfig,
) -> Array:
    """Algorithm 1: unbiased, single query pin, fixed budget. -> (n_pins,)"""
    cfg_basic = dataclasses.replace(cfg, bias_beta=0.0).without_early_stop()
    res = pixie_random_walk(
        graph,
        jnp.asarray([query_pin], jnp.int32),
        jnp.ones((1,), jnp.float32),
        jnp.asarray(0, jnp.int32),
        key,
        cfg_basic,
    )
    return res.counts[0]


def recommend_with_stats(
    graph: PinBoardGraph,
    query_pins: Array,
    query_weights: Array,
    user_feat: Array,
    key: Array,
    cfg: WalkConfig,
    step_budget=None,
) -> Tuple[Array, Array, Array, Array]:
    """recommend plus walk telemetry -> (scores, ids, steps_taken, n_high).

    ``steps_taken``/``n_high`` are Algorithm 3's early-stop observables —
    the serving layer exports them so a fleet can see how much of the step
    budget early stopping is actually saving (paper §4's latency lever).
    ``step_budget`` is the optional per-lane Eq. 2 budget override
    (see ``pixie_random_walk``).
    """
    res = pixie_random_walk(
        graph, query_pins, query_weights, user_feat, key, cfg,
        step_budget=step_budget,
    )
    with jax.named_scope("pixie.eq3"):
        boosted = counter_lib.boost_combine(res.counts)
    with jax.named_scope("pixie.topk"):
        scores, ids = counter_lib.topk_dense(boosted, cfg.top_k)
    return scores, ids, res.steps_taken, res.n_high


def recommend(
    graph: PinBoardGraph,
    query_pins: Array,
    query_weights: Array,
    user_feat: Array,
    key: Array,
    cfg: WalkConfig,
) -> Tuple[Array, Array]:
    """Full query path: walk -> Eq. 3 booster -> top-k (scores, pin ids).

    Dispatches on ``cfg.backend``: the whole walk loop runs on the fused
    Pallas engine when ``backend="pallas"``.
    """
    scores, ids, _, _ = recommend_with_stats(
        graph, query_pins, query_weights, user_feat, key, cfg
    )
    return scores, ids


# ---------------------------------------------------------------------------
# Batch-native multi-query walk: ONE fused engine for the whole serving batch
# ---------------------------------------------------------------------------


def pixie_random_walk_batched(
    graph: PinBoardGraph,
    query_pins: Array,     # (n_queries, n_slots) int32, padded with -1
    query_weights: Array,  # (n_queries, n_slots) float32, 0 for padding
    user_feats: Array,     # (n_queries,) int32 personalization features
    keys: Array,           # (n_queries,) per-query PRNG keys (random.split)
    cfg: WalkConfig,
    step_budgets: Optional[Array] = None,  # (n_queries,) int32 Eq. 2 totals
) -> WalkResult:
    """PIXIERANDOMWALKMULTIPLE over a whole serving batch, batch-natively.

    The bit-identical twin of ``jax.vmap(pixie_random_walk)`` over the same
    per-query keys — same counts, board counts, ``steps_taken`` and
    ``n_high`` for every batch size — but the batch is a first-class axis
    of the engine instead of a vmap wrapper:

      * every query's walkers are packed query-major along ONE walker axis,
        so each superstep chunk is a single fused call for the whole batch
        (with ``backend="pallas"``: one ``pallas_call`` per chunk, its DMA
        pipeline hiding latency behind ``n_queries * n_walkers`` rows,
        instead of a batch-sized leading grid dimension per query);
      * counting runs once per chunk over query-major ``(query, slot,
        pin)`` triple bins (``accumulate_packed_events_with_high`` with the
        query lane), not once per query over replicated dense buffers;
      * ONE shared ``while_loop`` carries a per-(query, slot) early-stop
        mask: a query that hits Algorithm 3's threshold stops emitting
        events and stops counting steps (its walker lanes are masked to
        the sentinel triple) while its batch neighbours keep walking —
        exactly the frozen-state semantics vmap gives the per-query loop.

    Per-query RNG streams are preserved exactly: walker ``q * w + i`` at
    global step ``s`` consumes the same ``_chunk_rbits(keys[q], ...)``
    draws as in the per-query engine.  Returns a ``WalkResult`` whose
    fields lead with the batch axis: counts ``(n_queries, n_slots,
    n_pins)``, board_counts ``(n_queries, n_slots, n_boards) | None``,
    steps_taken / n_high ``(n_queries, n_slots)``.

    ``step_budgets`` optionally overrides the Eq. 2 total PER QUERY LANE
    as data — the multi-interest layer rides its interest clusters on this
    axis, each with a budget proportional to cluster importance, and ragged
    users (different k) still share one compiled program because budgets
    are array values, not shapes.  Each budget is clamped to
    ``cfg.n_steps`` (the static chunk bound — a bigger budget could never
    be walked anyway); per-lane parity with the per-query engine at the
    same budget is preserved exactly.
    """
    if cfg.n_v < 1:
        raise ValueError(
            f"n_v must be >= 1, got {cfg.n_v}; use "
            "cfg.without_early_stop() to disable early stopping"
        )
    if query_pins.ndim != 2:
        raise ValueError(
            f"query_pins must be (n_queries, n_slots), got {query_pins.shape}"
        )
    n_queries, n_slots = query_pins.shape
    n_pins = graph.n_pins
    w = cfg.n_walkers
    n_rows = n_queries * n_slots
    n_boards_packed = graph.n_boards if cfg.count_boards else 0
    slot_sentinel = jnp.int32(n_slots)
    query_sentinel = jnp.int32(n_queries)
    # the dense buffers materialize n_queries * n_slots * n_pins bins
    count_engine = select_count_engine(
        cfg.backend, n_rows, n_pins, n_boards_packed
    )

    with jax.named_scope("pixie.query"):
        valid_q = (query_pins >= 0) & (query_weights > 0)      # (B, S)
        safe_q = jnp.where(valid_q, query_pins, 0)
        degs = graph.pin_degree(safe_q) * valid_q.astype(
            graph.p2b.offsets.dtype
        )

        # Eq. 1-2 per query — the same traced program the vmapped path runs
        if step_budgets is None:
            n_q = jax.vmap(
                lambda v, qw, dg: sampling.allocate_steps(
                    jnp.where(v, qw, 0.0), dg,
                    jnp.asarray(graph.max_pin_degree), cfg.n_steps,
                )
            )(valid_q, query_weights, degs)                    # (B, S)
        else:
            n_q = jax.vmap(
                lambda v, qw, dg, bt: sampling.allocate_steps(
                    jnp.where(v, qw, 0.0), dg,
                    jnp.asarray(graph.max_pin_degree), bt,
                )
            )(valid_q, query_weights, degs,
              jnp.minimum(jnp.asarray(step_budgets, jnp.int32),
                          cfg.n_steps))                        # (B, S)
        slot_of_walker_q, _ = jax.vmap(
            lambda nq: sampling.allocate_walkers(nq, w)
        )(n_q)                                                 # (B, w)
        query_of_walker_q = jax.vmap(jnp.take)(safe_q, slot_of_walker_q)
        walkers_per_slot = jax.vmap(
            lambda so: jax.ops.segment_sum(
                jnp.ones((w,), jnp.int32), so, num_segments=n_slots
            )
        )(slot_of_walker_q).reshape(-1)                        # (B*S,)

        # query-major walker packing: walkers of query q occupy
        # [q*w, (q+1)*w)
        qid_of_walker = jnp.repeat(jnp.arange(n_queries, dtype=jnp.int32), w)
        slot_of_walker = slot_of_walker_q.reshape(-1).astype(jnp.int32)
        query_of_walker = query_of_walker_q.reshape(-1).astype(jnp.int32)
        feat_of_walker = jnp.repeat(jnp.asarray(user_feats, jnp.int32), w)
        row_of_walker = qid_of_walker * n_slots + slot_of_walker
        valid_row = valid_q.reshape(-1)
        n_q_row = n_q.reshape(-1)

    def cond(state):
        _, _, _, _, _, row_active, it = state
        return jnp.any(row_active) & (it < cfg.max_chunks())

    def body(state):
        curr, counts, bcounts, high, steps_taken, row_active, it = state
        step_base = it * cfg.chunk_steps
        walker_active = jnp.take(row_active, row_of_walker)

        with jax.named_scope("pixie.walk.hop"):
            curr2, qev, sev, pev, bev = _walk_chunk_batched(
                graph, curr, query_of_walker, feat_of_walker, slot_of_walker,
                qid_of_walker, keys, step_base, cfg, n_slots, n_queries,
            )
        curr = jnp.where(walker_active, curr2, curr)
        # masking the shared lanes to the sentinel triple invalidates pin
        # AND board events of stopped queries/slots
        qev = jnp.where(walker_active[None, :], qev, query_sentinel)
        sev = jnp.where(walker_active[None, :], sev, slot_sentinel)
        # fused: ONE call accumulates the whole batch's chunk AND updates
        # every (query, slot) running n_high tally — no per-query loop, no
        # n_rows * n_pins reduction anywhere in this body
        with jax.named_scope("pixie.walk.count"):
            counts, high = counter_lib.accumulate_packed_events_with_high(
                counts, high, sev, pev, n_slots, n_pins, cfg.n_v,
                count_engine, query_events=qev, n_queries=n_queries,
            )
            if cfg.count_boards:
                bcounts = counter_lib.accumulate_packed_events(
                    bcounts, sev, bev, n_slots, graph.n_boards, count_engine,
                    query_events=qev, n_queries=n_queries,
                )

        steps_taken = steps_taken + walkers_per_slot * row_active.astype(
            jnp.int32
        ) * cfg.chunk_steps

        # per-(query, slot) early stopping, exactly the per-query rule
        row_active = (
            valid_row
            & (steps_taken < n_q_row)
            & (high <= cfg.n_p)
        )
        return curr, counts, bcounts, high, steps_taken, row_active, it + 1

    with jax.named_scope("pixie.walk"):
        counts0 = jnp.zeros((n_rows * n_pins,), dtype=jnp.int32)
        bcounts0 = (
            jnp.zeros((n_rows * graph.n_boards,), dtype=jnp.int32)
            if cfg.count_boards
            else None
        )
        state0 = (
            query_of_walker,
            counts0,
            bcounts0,
            jnp.zeros((n_rows,), jnp.int32),
            jnp.zeros((n_rows,), jnp.int32),
            valid_row,
            jnp.asarray(0, jnp.int32),
        )
        curr, counts, bcounts, high, steps_taken, _, _ = jax.lax.while_loop(
            cond, body, state0
        )
    with jax.named_scope("pixie.eq3"):
        per_slot = counts.reshape(n_queries, n_slots, n_pins)
        # never recommend the query pins themselves; debit the tally like
        # the per-query engine does
        b_idx = jnp.arange(n_queries)[:, None]
        s_idx = jnp.arange(n_slots)[None, :]
        q_reached = (per_slot[b_idx, s_idx, safe_q] >= cfg.n_v).astype(
            jnp.int32
        )
        per_slot = per_slot.at[b_idx, s_idx, safe_q].set(0)
    return WalkResult(
        counts=per_slot,
        board_counts=None
        if bcounts is None
        else bcounts.reshape(n_queries, n_slots, graph.n_boards),
        steps_taken=steps_taken.reshape(n_queries, n_slots),
        n_high=(high - q_reached.reshape(-1)).reshape(n_queries, n_slots),
    )


def recommend_with_stats_batched(
    graph: PinBoardGraph,
    query_pins: Array,     # (n_queries, n_slots)
    query_weights: Array,  # (n_queries, n_slots)
    user_feats: Array,     # (n_queries,)
    keys: Array,           # (n_queries,) per-query PRNG keys
    cfg: WalkConfig,
    step_budgets: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array]:
    """Batch-native ``recommend_with_stats``: one fused engine, whole batch.

    Returns ``(scores (B, top_k), ids (B, top_k), steps_taken (B, n_slots),
    n_high (B, n_slots))`` — bit-identical to vmapping
    ``recommend_with_stats`` over the same per-query keys; the walk runs on
    the batch-native engine and only the cheap Eq. 3 booster / top-k run
    under vmap.  ``step_budgets`` is the optional (B,) per-lane Eq. 2
    budget override (see ``pixie_random_walk_batched``).
    """
    res = pixie_random_walk_batched(
        graph, query_pins, query_weights, user_feats, keys, cfg,
        step_budgets=step_budgets,
    )
    with jax.named_scope("pixie.eq3"):
        boosted = jax.vmap(counter_lib.boost_combine)(res.counts)
    with jax.named_scope("pixie.topk"):
        scores, ids = jax.vmap(
            lambda b: counter_lib.topk_dense(b, cfg.top_k)
        )(boosted)
    return scores, ids, res.steps_taken, res.n_high


# ---------------------------------------------------------------------------
# Multi-interest merge: Eq. 3 across a user's interest-cluster lanes
# ---------------------------------------------------------------------------

# id-lane sentinel that sorts AFTER every real pin id
_MERGE_ID_SENTINEL = jnp.iinfo(jnp.int32).max


def merge_interest_topk(
    scores: Array,      # (k, top_k) float32 per-cluster boosted scores
    ids: Array,         # (k, top_k) int32 per-cluster pin ids, -1 padded
    importance: Array,  # (k,) float32 cluster importance, 0 for pad lanes
    top_k: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Merge one user's per-cluster top-k lists: Eq. 3 across clusters.

    The multi-hit booster applied a second time at the USER level:

        V[p] = (sum_c I_c * sqrt(V_c[p]))**2

    — the importance-weighted form of ``counter_lib.boost_combine``, so a
    pin surfacing in several of the user's interest clusters beats a
    same-mass single-cluster pin, exactly the paper's Eq. 3 rationale.

    Bit-reproducible BY CONSTRUCTION, which is what lets the fused serving
    path and the per-cluster oracle share this function and agree
    bit-identically (verdict ``multi_interest_agrees``):

      * entries are canonically ordered first — ``lax.sort`` on
        (id, contribution) — so equal inputs reach the sum in one order
        no matter how lanes were produced;
      * per-id sums are explicit left-to-right shift-adds (run length is
        bounded by k: within a lane ids are distinct), never a float
        ``Reduce`` whose association XLA may retile per program shape;
      * ties in the final top-k break on the id-sorted entry index, i.e.
        by ascending pin id — deterministic across batch compositions.

    Lanes with ``importance <= 0`` are padding (ragged users).  A user
    with exactly ONE live lane passes its lane through VERBATIM — k=1
    collapses bit-identically to the flat homefeed path instead of
    round-tripping scores through sqrt/square.

    Returns ``(scores (top_k,), ids (top_k,))``, id -1 / score 0 padded,
    with ``top_k`` defaulting to the per-lane top_k.
    """
    if scores.ndim != 2 or scores.shape != ids.shape:
        raise ValueError(
            f"scores/ids must be matching (k, top_k), got {scores.shape} "
            f"vs {ids.shape}"
        )
    k, per_lane_k = scores.shape
    out_k = per_lane_k if top_k is None else top_k
    live_lane = importance > 0
    valid = live_lane[:, None] & (ids >= 0) & (scores > 0)
    contrib = jnp.where(
        valid, importance[:, None] * jnp.sqrt(scores), 0.0
    ).reshape(-1)
    sort_ids = jnp.where(valid, ids, _MERGE_ID_SENTINEL).reshape(-1)
    sid, sc = jax.lax.sort((sort_ids, contrib), num_keys=2)

    # left-to-right sequential per-id sums via shift-adds: a pin appears in
    # at most k lanes (per-lane ids are distinct), so k-1 shifted adds
    # cover every run; each pass appends exactly one term to the running
    # sum, so the association is a fixed left-to-right chain — elementwise
    # adds XLA cannot reassociate, unlike a Reduce
    acc = sc
    for d in range(1, k):
        same = jnp.concatenate(
            [sid[d:] == sid[:-d],
             jnp.zeros((d,), bool)]
        )
        shifted = jnp.concatenate([sc[d:], jnp.zeros((d,), sc.dtype)])
        acc = acc + jnp.where(same, shifted, 0.0)

    first = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]]
    )
    owner = first & (sid != _MERGE_ID_SENTINEL)
    merged = jnp.where(owner, acc * acc, -jnp.inf)
    vals, idx = jax.lax.top_k(merged, out_k)
    got = vals > -jnp.inf
    merged_scores = jnp.where(got, vals, 0.0).astype(scores.dtype)
    merged_ids = jnp.where(got, jnp.take(sid, idx), -1).astype(jnp.int32)

    # exact k=1 collapse: a single live lane is returned verbatim
    if out_k == per_lane_k:
        single = jnp.sum(live_lane.astype(jnp.int32)) == 1
        lane = jnp.argmax(live_lane)
        merged_scores = jnp.where(single, scores[lane], merged_scores)
        merged_ids = jnp.where(single, ids[lane], merged_ids)
    return merged_scores, merged_ids


# ---------------------------------------------------------------------------
# Event-mode walk — scale-free path used by the sharded production graph
# ---------------------------------------------------------------------------


def pixie_walk_events(
    graph: PinBoardGraph,
    query_pins: Array,
    query_weights: Array,
    user_feat: Array,
    key: Array,
    cfg: WalkConfig,
    check_every: int = 4,
    check_mode: str = "incremental",
) -> EventWalkResult:
    """Event-buffer walk: O(N) memory independent of graph size AND id space.

    The wide (slot, pin) lane buffers play the role of the paper's N-sized
    hash table; because no lane ever holds the packed ``slot * n_pins +
    pin`` product, this path serves packed id spaces past 2**31 (8 slots x
    2**28 pins and beyond) on either backend with plain int32.  With
    ``backend="pallas"`` the lanes come straight out of the fused kernel
    and are appended to the buffers — no packing arithmetic in XLA at all.

    Early stopping checks every ``check_every`` chunks.  ``check_mode``:

      * ``"incremental"`` (default) — the check body folds ONLY the new
        window's events into a carried ``counter_lib.EventHighState``
        (sorted runs per window + running tally): O(window log window) per
        check, no sort over the ``max_events`` buffer anywhere in the loop
        (pinned by jaxpr inspection in tests/test_widepack.py).
      * ``"full"`` — the pre-incremental formulation (re-sort the whole
        buffer each check via ``events_n_high_per_slot``); kept as the
        bit-identical oracle the incremental path is verified against.
    """
    if cfg.n_v < 1:
        # same contract as the dense engine: n_v=0 would mark every touched
        # run "hot" and silently truncate the walk at the first check
        raise ValueError(
            f"n_v must be >= 1, got {cfg.n_v}; use "
            "cfg.without_early_stop() to disable early stopping"
        )
    if check_mode not in ("incremental", "full"):
        raise ValueError(
            f"unknown check_mode {check_mode!r}; use 'incremental' or 'full'"
        )
    if cfg.count_boards:
        # event mode only buffers pin visits; don't make the chunk engine
        # emit board events nobody reads
        cfg = dataclasses.replace(cfg, count_boards=False)
    n_slots = query_pins.shape[0]
    n_pins = graph.n_pins
    w = cfg.n_walkers
    per_chunk = w * cfg.chunk_steps
    max_chunks = cfg.max_chunks()
    max_events = max_chunks * per_chunk
    slot_sentinel = jnp.int32(n_slots)
    # number of check windows that can actually fire; sizes the run-segment
    # state (check_every past max_chunks means checks never fire at all —
    # e.g. the check_every=10**9 idiom — and must not size anything)
    n_windows = max_chunks // check_every
    seg_cap = check_every * per_chunk

    valid_q = (query_pins >= 0) & (query_weights > 0)
    safe_q = jnp.where(valid_q, query_pins, 0)
    degs = graph.pin_degree(safe_q) * valid_q.astype(graph.p2b.offsets.dtype)
    n_q = sampling.allocate_steps(
        jnp.where(valid_q, query_weights, 0.0),
        degs,
        jnp.asarray(graph.max_pin_degree),
        cfg.n_steps,
    )
    slot_of_walker, _ = sampling.allocate_walkers(n_q, w)
    query_of_walker = jnp.take(safe_q, slot_of_walker).astype(jnp.int32)
    walkers_per_slot = jax.ops.segment_sum(
        jnp.ones((w,), jnp.int32), slot_of_walker, num_segments=n_slots
    )

    sev0 = jnp.full((max_events,), slot_sentinel, jnp.int32)
    pev0 = jnp.zeros((max_events,), jnp.int32)
    incremental = check_mode == "incremental" and n_windows > 0
    hstate0 = counter_lib.events_high_init(
        n_slots, n_windows if incremental else 0, seg_cap if incremental else 1
    )

    def cond(state):
        _, _, _, _, _, slot_active, it = state
        return jnp.any(slot_active) & (it < max_chunks)

    def body(state):
        curr, sev_buf, pev_buf, hstate, steps_taken, slot_active, it = state
        step_base = it * cfg.chunk_steps
        walker_active = jnp.take(slot_active, slot_of_walker)
        curr2, sev, pev, _ = _walk_chunk(
            graph, curr, query_of_walker, user_feat, slot_of_walker,
            key, step_base, cfg, n_slots,
        )
        curr = jnp.where(walker_active, curr2, curr)
        # mask BOTH lanes: sentinel events are uniformly (n_slots, 0), the
        # kernel's own convention, so aggregated run arrays stay sorted
        # end to end (events_high_fold binary-searches them)
        sev = jnp.where(
            walker_active[None, :], sev, slot_sentinel
        ).reshape(-1)
        pev = jnp.where(walker_active[None, :], pev, 0).reshape(-1)
        off = it * per_chunk
        sev_buf = jax.lax.dynamic_update_slice(sev_buf, sev, (off,))
        pev_buf = jax.lax.dynamic_update_slice(pev_buf, pev, (off,))
        steps_taken = steps_taken + walkers_per_slot * slot_active.astype(
            jnp.int32
        ) * cfg.chunk_steps

        do_check = (it + 1) % check_every == 0

        if incremental:

            def check(args):
                sev_buf, pev_buf, hstate, steps_taken, it = args
                # fold ONLY this window's events: the last check_every
                # chunks, ending at the chunk just written
                start = (it + 1) * per_chunk - seg_cap
                hstate = counter_lib.events_high_fold(
                    hstate,
                    jax.lax.dynamic_slice(sev_buf, (start,), (seg_cap,)),
                    jax.lax.dynamic_slice(pev_buf, (start,), (seg_cap,)),
                    n_slots, n_pins, cfg.n_v, seg_cap=seg_cap,
                )
                active = (
                    valid_q & (steps_taken < n_q) & (hstate.high <= cfg.n_p)
                )
                return active, hstate

        else:

            def check(args):
                sev_buf, pev_buf, hstate, steps_taken, it = args
                n_high = counter_lib.events_n_high_per_slot(
                    sev_buf, pev_buf, n_slots, n_pins, cfg.n_v, max_events
                )
                hstate = hstate._replace(high=n_high)
                return valid_q & (steps_taken < n_q) & (
                    n_high <= cfg.n_p
                ), hstate

        slot_active, hstate = jax.lax.cond(
            do_check,
            check,
            lambda args: (valid_q & (args[3] < n_q), args[2]),
            (sev_buf, pev_buf, hstate, steps_taken, it),
        )
        return curr, sev_buf, pev_buf, hstate, steps_taken, slot_active, it + 1

    state0 = (
        query_of_walker,
        sev0,
        pev0,
        hstate0,
        jnp.zeros((n_slots,), jnp.int32),
        valid_q,
        jnp.asarray(0, jnp.int32),
    )
    _, sev_buf, pev_buf, hstate, steps_taken, _, it = jax.lax.while_loop(
        cond, body, state0
    )
    return EventWalkResult(
        slot_events=sev_buf,
        pin_events=pev_buf,
        steps_taken=steps_taken,
        chunks_run=it,
        n_high=hstate.high,
    )


def pixie_walk_events_fixed(
    graph: PinBoardGraph,
    query_pins: Array,
    query_weights: Array,
    user_feat: Array,
    key: Array,
    cfg: WalkConfig,
    n_chunks: int,
    unroll: bool = True,
) -> EventWalkResult:
    """Cost-model twin of pixie_walk_events: exactly n_chunks chunks via an
    unrolled scan (no early stopping, no while loop).

    Exists because XLA's cost analysis counts while-loop bodies ONCE; the
    dry-run lowers this variant at n_chunks = 1 and 2 and extrapolates the
    linear-in-chunks cost to cfg.max_chunks() (launch/dryrun.py).
    """
    if cfg.count_boards:
        cfg = dataclasses.replace(cfg, count_boards=False)
    n_slots = query_pins.shape[0]
    w = cfg.n_walkers

    valid_q = (query_pins >= 0) & (query_weights > 0)
    safe_q = jnp.where(valid_q, query_pins, 0)
    degs = graph.pin_degree(safe_q) * valid_q.astype(graph.p2b.offsets.dtype)
    n_q = sampling.allocate_steps(
        jnp.where(valid_q, query_weights, 0.0),
        degs,
        jnp.asarray(graph.max_pin_degree),
        cfg.n_steps,
    )
    slot_of_walker, _ = sampling.allocate_walkers(n_q, w)
    query_of_walker = jnp.take(safe_q, slot_of_walker).astype(jnp.int32)

    def body(curr, it):
        step_base = it * cfg.chunk_steps
        curr2, sev, pev, _ = _walk_chunk(
            graph, curr, query_of_walker, user_feat, slot_of_walker,
            key, step_base, cfg, n_slots, unroll=unroll,
        )
        return curr2, (sev.reshape(-1), pev.reshape(-1))

    curr, (sev_chunks, pev_chunks) = jax.lax.scan(
        body, query_of_walker, jnp.arange(n_chunks), unroll=True
    )
    steps = jnp.full((n_slots,), n_chunks * cfg.chunk_steps, jnp.int32)
    return EventWalkResult(
        slot_events=sev_chunks.reshape(-1),
        pin_events=pev_chunks.reshape(-1),
        steps_taken=steps,
        chunks_run=jnp.asarray(n_chunks, jnp.int32),
        n_high=jnp.zeros((n_slots,), jnp.int32),
    )


def recommend_from_events(
    result: EventWalkResult,
    n_slots: int,
    n_pins: int,
    query_pins: Array,
    top_k: int,
) -> Tuple[Array, Array]:
    """Eq. 3 + top-k from wide event lane buffers. -> (scores, pin ids).

    Pure pair-sort aggregation on the int32 lanes: serves id spaces past
    2**31 packed ids without 64-bit arithmetic anywhere.
    """
    max_events = result.slot_events.shape[0]
    uniq_slot, uniq_pin, counts = counter_lib.events_to_counts(
        result.slot_events, result.pin_events, n_slots, max_events
    )
    pin_ids, boosted = counter_lib.boosted_from_events(
        uniq_slot, uniq_pin, counts, n_slots, n_pins, max_events
    )
    # mask out query pins
    is_query = jnp.isin(pin_ids, query_pins)
    boosted = jnp.where(is_query, 0.0, boosted)
    return counter_lib.topk_events(pin_ids, boosted, top_k)
