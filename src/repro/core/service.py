"""Query construction and response shaping (paper §5 use cases).

* **Homefeed** (§5.1): every user action creates/updates a query — each acted
  pin gets an initial weight by action type, decayed with half-life lambda.
* **Related pins** (§5.2): single-pin queries with a *shorter* walk (higher
  alpha) for narrow recommendations.
* **Board recs** (§5.3): query = last pins of a board; board counting on.
* **Multi-interest users** (PinnerSage, PAPERS.md): a user's action history
  is clustered host-side into k interest clusters over pin topic vectors;
  each cluster is one weighted query lane with its own Eq. 2 step budget,
  all lanes of a user ride the batch axis of ONE
  ``walk.pixie_random_walk_batched`` call, and results merge back per user
  with ``walk.merge_interest_topk`` (Eq. 3 across clusters).

Queries are padded to a fixed slot count so batched serving stays SPMD.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as dist_lib
from repro.core import walk as walk_lib

ACTION_WEIGHTS: Dict[str, float] = {
    "save": 1.0,
    "click": 0.6,
    "like": 0.5,
    "view": 0.2,
}


@dataclasses.dataclass(frozen=True)
class UserAction:
    pin: int
    action: str
    age_hours: float


def _decayed_pin_weights(
    actions: Sequence[UserAction],
    half_life_hours: float,
    default_weight: float | None,
) -> Dict[int, float]:
    """Per-pin decayed action weights, summed in a CANONICAL order.

    Each pin's contributions are sorted ascending by value before the
    left-to-right float sum, so a pin's weight is a function of the
    MULTISET of its actions — reordering the action list can no longer
    move a weight by an ulp (regression-tested with a crafted history
    whose naive order-of-arrival sums round to different float32s).
    """
    contribs: Dict[int, List[float]] = {}
    for a in actions:
        base = ACTION_WEIGHTS.get(a.action, default_weight)
        if base is None:
            raise ValueError(
                f"unknown action type {a.action!r}; known: "
                f"{sorted(ACTION_WEIGHTS)} (pass default_weight to accept "
                "unrecognized actions)"
            )
        w = base * 0.5 ** (a.age_hours / half_life_hours)
        contribs.setdefault(a.pin, []).append(w)
    acc: Dict[int, float] = {}
    for pin, ws in contribs.items():
        total = 0.0
        for w in sorted(ws):
            total += w
        acc[pin] = total
    return acc


def build_query(
    actions: Sequence[UserAction],
    n_slots: int,
    half_life_hours: float = 24.0,
    default_weight: float | None = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Collapse a user's action history into (query_pins, weights).

    Weight = action weight * 0.5 ** (age / half_life); repeated pins sum.
    The top-``n_slots`` pins by weight are kept, rest padded with (-1, 0).
    Weight ties break by pin id, so for a given set of per-pin weights the
    truncation never depends on Python dict ordering, and each pin's float
    sum runs in a canonical (value-sorted) order so reordering the action
    list cannot move a weight by an ulp either — the query is a pure
    function of the action MULTISET.

    Unrecognized action types raise — a typo'd action silently weighted
    0.1 skews every downstream walk budget; pass ``default_weight`` to
    opt into a catch-all weight instead.
    """
    acc = _decayed_pin_weights(actions, half_life_hours, default_weight)
    # weight descending, pin id ascending on ties: the truncation below is
    # deterministic across Python dict insertion orders
    items = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:n_slots]
    pins = np.full((n_slots,), -1, dtype=np.int32)
    weights = np.zeros((n_slots,), dtype=np.float32)
    for i, (p, w) in enumerate(items):
        pins[i] = p
        weights[i] = w
    return pins, weights


def homefeed_config(base: walk_lib.WalkConfig) -> walk_lib.WalkConfig:
    """Broad, exploratory walk: longer segments (§5.1 / Explore)."""
    return dataclasses.replace(base, alpha=min(base.alpha, 0.3))


def related_pins_config(base: walk_lib.WalkConfig) -> walk_lib.WalkConfig:
    """Narrow walk — the §5.2 A/B result: shorter walks lift engagement."""
    return dataclasses.replace(base, alpha=max(base.alpha, 0.65))


def board_rec_config(base: walk_lib.WalkConfig) -> walk_lib.WalkConfig:
    return dataclasses.replace(base, count_boards=True)


def batch_queries(
    queries: List[Tuple[np.ndarray, np.ndarray]],
    user_feats: Sequence[int],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stack padded queries for batched serving.

    Validates the batch BEFORE stacking so a ragged or mistyped request
    fails with a message naming the offending query, not an opaque
    ``np.stack`` shape error three layers down: every query must have the
    same ``n_slots`` (pins and weights alike) and float weights.
    """
    if not queries:
        raise ValueError("batch_queries needs at least one query")
    if len(user_feats) != len(queries):
        raise ValueError(
            f"{len(queries)} queries but {len(user_feats)} user_feats; "
            "one personalization feature per query required"
        )
    slot_shape = np.asarray(queries[0][0]).shape
    n_slots = slot_shape[0] if len(slot_shape) == 1 else slot_shape
    for i, (q_pins, q_weights) in enumerate(queries):
        p = np.asarray(q_pins)
        w = np.asarray(q_weights)
        if p.shape != slot_shape or w.shape != slot_shape:
            raise ValueError(
                f"query {i} is ragged: pins shape {p.shape}, weights shape "
                f"{w.shape}, but the batch has {n_slots} slots; pad "
                "every query to the same n_slots (service.build_query does)"
            )
        if not np.issubdtype(w.dtype, np.floating):
            raise ValueError(
                f"query {i} weights have dtype {w.dtype}; weights must be "
                "float (integer weights silently skew Eq. 2 step budgets)"
            )
    pins = jnp.asarray(np.stack([np.asarray(q[0]) for q in queries]))
    weights = jnp.asarray(np.stack([np.asarray(q[1]) for q in queries]))
    feats = jnp.asarray(np.asarray(user_feats, dtype=np.int32))
    return pins, weights, feats


# ---------------------------------------------------------------------------
# Multi-interest user queries (PinnerSage-style clustering, PAPERS.md)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UserQuery:
    """One user's multi-interest query: k interest-cluster lanes.

    Built by ``build_user_query``.  Each row of ``cluster_pins`` /
    ``cluster_weights`` is a self-contained weighted query (the same shape
    ``build_query`` emits) for ONE interest cluster; ``importance`` is the
    cluster's share of the user's total action weight, normalized to sum
    to 1 over the live clusters.  Lanes are ordered by importance
    descending (ties: smallest member pin id), so a user's lane layout is
    deterministic.
    """

    cluster_pins: np.ndarray     # (k, n_slots) int32, -1 padded
    cluster_weights: np.ndarray  # (k, n_slots) float32, 0 padded
    importance: np.ndarray       # (k,) float32, sums to 1
    user_feat: int = 0

    @property
    def n_clusters(self) -> int:
        return int(self.cluster_pins.shape[0])

    @property
    def n_slots(self) -> int:
        return int(self.cluster_pins.shape[1])


def _agglomerate(
    vecs: np.ndarray, mass: np.ndarray, n_clusters: int
) -> List[List[int]]:
    """Deterministic average-linkage agglomeration to ``n_clusters``.

    Greedy centroid merging (PinnerSage's Ward-style host-side pass,
    shrunk to numpy): repeatedly merge the pair of clusters with the
    closest weighted centroids.  Distances are float64 and the argmin
    scans row-major, so ties break on the smallest (i, j) — no RNG, no
    dict-order dependence; the same action multiset always produces the
    same clustering.
    """
    members = [[i] for i in range(vecs.shape[0])]
    cent = np.asarray(vecs, np.float64).copy()
    mass = np.asarray(mass, np.float64).copy()
    while len(members) > n_clusters:
        diff = cent[:, None, :] - cent[None, :, :]
        d2 = np.einsum("ijk,ijk->ij", diff, diff)
        iu = np.triu_indices(len(members), k=1)
        flat = np.full_like(d2, np.inf)
        flat[iu] = d2[iu]
        i, j = np.unravel_index(int(np.argmin(flat)), flat.shape)
        tot = mass[i] + mass[j]
        cent[i] = (mass[i] * cent[i] + mass[j] * cent[j]) / tot
        mass[i] = tot
        members[i] = members[i] + members[j]
        del members[j]
        cent = np.delete(cent, j, axis=0)
        mass = np.delete(mass, j, axis=0)
    return members


def build_user_query(
    actions: Sequence[UserAction],
    pin_topics: np.ndarray,   # (n_pins, n_topics) pin embedding table
    n_slots: int,
    n_clusters: int = 3,
    half_life_hours: float = 24.0,
    default_weight: float | None = None,
    user_feat: int = 0,
) -> UserQuery:
    """Cluster a user's action history into a multi-interest ``UserQuery``.

    The PinnerSage translation of §5.1's flat homefeed query: instead of
    blending hundreds of acted pins into one weighted set (which washes
    distinct interests into a mushy centroid), the DISTINCT acted pins are
    agglomeratively clustered over their topic vectors and each cluster
    becomes its own weighted query lane:

      * per-pin weights are the same decayed action sums ``build_query``
        uses (canonical-order float sums — see ``_decayed_pin_weights``);
      * cluster importance I_c = the cluster's share of total action
        weight (``math.fsum`` over member pins, order-independent),
        normalized to sum to 1;
      * within a lane, pins keep their decayed weights, top-``n_slots``
        by (weight desc, pin asc) — ``build_query``'s truncation rule.

    Users with fewer distinct pins than ``n_clusters`` get one cluster per
    pin (k adapts down, never pads up); ``n_clusters=1`` reproduces the
    flat homefeed query exactly (same pins, same weights, one lane).
    Deterministic end to end — same action multiset, same ``UserQuery``.
    """
    if n_clusters < 1:
        raise ValueError(f"n_clusters must be >= 1, got {n_clusters}")
    acc = _decayed_pin_weights(actions, half_life_hours, default_weight)
    if not acc:
        raise ValueError("build_user_query needs at least one action")
    topics = np.asarray(pin_topics)
    pins = sorted(acc)
    if pins[0] < 0 or pins[-1] >= topics.shape[0]:
        raise ValueError(
            f"action pin ids span [{pins[0]}, {pins[-1]}] but pin_topics "
            f"covers [0, {topics.shape[0]})"
        )
    w64 = np.array([acc[p] for p in pins], dtype=np.float64)
    k = min(n_clusters, len(pins))
    members = _agglomerate(topics[pins].astype(np.float64), w64, k)

    clusters = []
    for mem in members:
        mem_pins = sorted(pins[m] for m in mem)
        imp = math.fsum(acc[p] for p in mem_pins)
        clusters.append((imp, mem_pins))
    # importance descending, smallest member pin breaking ties: lane order
    # is a pure function of the clustering, not of merge history
    clusters.sort(key=lambda c: (-c[0], c[1][0]))

    cluster_pins = np.full((k, n_slots), -1, dtype=np.int32)
    cluster_weights = np.zeros((k, n_slots), dtype=np.float32)
    imp64 = np.array([c[0] for c in clusters], dtype=np.float64)
    for ci, (_, mem_pins) in enumerate(clusters):
        items = sorted(
            ((p, acc[p]) for p in mem_pins), key=lambda kv: (-kv[1], kv[0])
        )[:n_slots]
        for si, (p, w) in enumerate(items):
            cluster_pins[ci, si] = p
            cluster_weights[ci, si] = w
    importance = (imp64 / imp64.sum()).astype(np.float32)
    return UserQuery(
        cluster_pins=cluster_pins,
        cluster_weights=cluster_weights,
        importance=importance,
        user_feat=int(user_feat),
    )


def cluster_step_budgets(importance: np.ndarray, n_steps: int) -> np.ndarray:
    """Eq. 2 applied at CLUSTER granularity: per-lane step totals.

    ``N_c = floor(I_c * N)`` with a min-1 floor for live clusters — the
    same shape as ``sampling.allocate_steps`` (clusters have no graph
    degree, so the Eq. 1 scaling s_p enters WITHIN each lane when the
    engine splits the lane total across its member pins).  Host-side
    numpy on normalized importance; every budget is <= ``n_steps``, the
    engine's static chunk bound.
    """
    imp = np.asarray(importance, np.float32)
    n_c = np.floor(imp * np.float32(n_steps)).astype(np.int32)
    return np.where(imp > 0, np.maximum(n_c, 1), 0).astype(np.int32)


class UserBatch(NamedTuple):
    """A batch of multi-interest users flattened to cluster lanes.

    The lane axis L = sum of every user's k is the SAME query axis the
    PR 5 batched engine fuses over — multi-interest serving adds lanes,
    never pallas_calls.  ``lane_user`` / ``lane_of_user`` are host-side
    numpy (static at trace time): the per-user lane map the merge uses to
    gather a user's lanes back together.
    """

    pins: jnp.ndarray          # (L, n_slots) int32
    weights: jnp.ndarray       # (L, n_slots) float32
    feats: jnp.ndarray         # (L,) int32
    importance: jnp.ndarray    # (L,) float32, per-user normalized
    step_budgets: jnp.ndarray  # (L,) int32 per-lane Eq. 2 totals
    lane_user: np.ndarray      # (L,) int32 lane -> user index
    lane_of_user: np.ndarray   # (n_users, k_max) int32 lane ids, -1 pad
    n_users: int


def batch_user_queries(
    users: Sequence[UserQuery], n_steps: int
) -> UserBatch:
    """Flatten users -> cluster lanes for one batched engine call.

    Ragged users (different k) flatten to different LANE COUNTS, not
    different shapes: every lane is (n_slots,) and budgets/importance are
    data, so any mix of users with the same total lane count shares one
    compiled program.  ``n_steps`` is the PER-USER walk budget (the flat
    path's ``cfg.n_steps``), split across each user's lanes by cluster
    importance — a k-cluster user costs the same step budget as a flat
    user, it just spends it per interest.
    """
    if not users:
        raise ValueError("batch_user_queries needs at least one user")
    n_slots = users[0].n_slots
    for i, u in enumerate(users):
        if u.n_slots != n_slots:
            raise ValueError(
                f"user {i} has {u.n_slots} slots but the batch has "
                f"{n_slots}; build every UserQuery with the same n_slots"
            )
    k_max = max(u.n_clusters for u in users)
    pins, weights, feats, imps, budgets, lane_user = [], [], [], [], [], []
    lane_of_user = np.full((len(users), k_max), -1, dtype=np.int32)
    for ui, u in enumerate(users):
        u_budgets = cluster_step_budgets(u.importance, n_steps)
        for ci in range(u.n_clusters):
            lane_of_user[ui, ci] = len(pins)
            lane_user.append(ui)
            pins.append(u.cluster_pins[ci])
            weights.append(u.cluster_weights[ci])
            feats.append(u.user_feat)
            imps.append(u.importance[ci])
            budgets.append(u_budgets[ci])
    return UserBatch(
        pins=jnp.asarray(np.stack(pins)),
        weights=jnp.asarray(np.stack(weights)),
        feats=jnp.asarray(np.asarray(feats, np.int32)),
        importance=jnp.asarray(np.asarray(imps, np.float32)),
        step_budgets=jnp.asarray(np.asarray(budgets, np.int32)),
        lane_user=np.asarray(lane_user, np.int32),
        lane_of_user=lane_of_user,
        n_users=len(users),
    )


def walk_engine(graph, batch: int, n_slots: int,
                cfg: walk_lib.WalkConfig) -> str:
    """The walk formulation ``serve_batch`` runs for a (graph, batch) shape.

    ``"sharded"`` for a ``distributed.ShardedGraph`` (the pod-sharded
    engine); ``"batched"`` when the batch-native engine's query-major bins
    fit int32 indexing (``walk_lib.batched_engine_fits``); ``"vmapped"``
    past that envelope.  Both unsharded formulations give bit-identical
    answers; the batch-native one moves less data, because a vmapped
    per-query ``while_loop`` gets a batched predicate and so selects (and
    copies) every query's whole count carry each chunk.  The choice reads
    only shapes and the graph's type: ``cfg.backend`` picks the hop and
    count implementations inside either loop.
    """
    if isinstance(graph, dist_lib.ShardedGraph):
        return "sharded"
    if walk_lib.batched_engine_fits(
        batch, n_slots, graph.n_pins, graph.n_boards, cfg.count_boards
    ):
        return "batched"
    return "vmapped"


def serve_batch(
    graph,
    pins: jnp.ndarray,      # (batch, n_slots)
    weights: jnp.ndarray,   # (batch, n_slots)
    user_feats: jnp.ndarray,  # (batch,)
    key: jax.Array,
    cfg: walk_lib.WalkConfig,
    backend: str | None = None,
    with_stats: bool = False,
    mesh=None,
    axis: str = "model",
    slack: float = 2.0,
    rank=None,
    scenario: jnp.ndarray | None = None,
    step_budgets: jnp.ndarray | None = None,
    shard_dead_at: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, ...]:
    """One SPMD serving step: Pixie over a whole query batch.

    This is the TPU replacement for the paper's worker-thread-per-query
    model: a batch of queries is one program.  ``backend`` overrides
    ``cfg.backend`` ("xla" | "pallas") for the whole batch, so a serving
    fleet can flip the hot path to the fused Pallas walk engine without
    rebuilding its configs; both engines return bit-identical
    recommendations for the same key (core/walk.py) — including the
    early-stop observables, since both maintain the same incremental
    ``n_high`` tally.

    Which walk formulation runs is ``walk_engine``'s choice, by shape
    alone.  Every unsharded batch whose query-major bins fit int32
    indexing (``walk_lib.batched_engine_fits``) runs the BATCH-NATIVE
    engine (``walk_lib.recommend_with_stats_batched``) on either backend:
    the whole batch's walkers share one ``while_loop`` with a scalar
    predicate, each superstep chunk is one hop call (one fused
    ``pallas_call`` on ``backend="pallas"``) and one query-major counting
    call, and the dense count carry is updated in place.  A (graph, batch)
    shape past that envelope falls back to vmapping the per-query engine
    — same results, the per-query bins may still fit — rather than
    erroring where it would serve.  The vmapped formulation is the oracle
    the batched engine is verified bit-identical against
    (tests/test_batchfuse.py).

    ``key`` is either a scalar PRNG key — split into one stream per query,
    the original behavior — or a ``(batch,)`` typed key array used
    directly as the per-query streams.  Per-query keys are what makes a
    query's result independent of BATCH COMPOSITION: the bucketed server
    (serving/server.py) assigns each request its key at submit time
    (``fold_in`` of the request id), so deadline-aware batch formation can
    group requests however load dictates and still return bit-identical
    recommendations to the single-bucket flush oracle on the same
    requests.  (Padding a query into a wider ``n_slots`` shape is also
    bit-invariant: zero-weight slots get zero step budget and zero
    walkers, so bucket shape never changes a query's walk.)

    Returns ``(scores, ids)``; with ``with_stats=True`` returns
    ``(scores, ids, steps_taken, n_high)`` (each leading with the batch
    axis) so the fleet can monitor how much step budget Algorithm 3's
    early stopping saves per query shape.

    A ``distributed.ShardedGraph`` routes through the pod-sharded batched
    engine instead (``mesh`` required; ``axis`` names the shard axis,
    ``slack`` scales routing capacity): the same walk semantics with the
    graph node-range-sharded across the mesh, bit-identical to the
    unsharded engines whenever routing drops nothing.  ``with_stats=True``
    then returns ``(scores, ids, steps_taken, n_high, dropped)`` — the
    extra scalar is the routing-overflow drop count, the serving signal
    for raising ``slack`` (drops are bounded Monte Carlo slack, never
    silent).

    ``rank`` (a ``serving.ranker.RankRequest``) turns the step TWO-STAGE:
    retrieval runs with ``top_k`` overridden to ``rank.cfg.n_candidates``,
    then `serving.ranker.rank_candidates` re-scores the candidates with
    the per-request ``scenario`` head (``(batch,)`` int32 head indices;
    default head 0 for every query) — still one jitted program, still a
    constant ``pallas_call`` count independent of batch size.  Returned
    ``(scores, ids)`` are then the ranked ``(batch, final_k)`` results;
    ``with_stats=True`` keeps appending the stage-1 walk telemetry.
    Stage 2's float math is ONE shared program for both backends (the bag
    op's lowering is platform-defaulted, never backend-derived), so ranked
    serving inherits the walk's bit-parity contract end to end
    (`two_stage_backends_agree`).  Ranked serving over a ``ShardedGraph``
    raises: stage 2 gathers candidate neighborhoods from the full CSR,
    which a node-range shard doesn't hold — rank on an unsharded replica,
    or rank host-side from the sharded walk's ``(scores, ids)``.

    ``step_budgets`` (optional ``(batch,)`` int32) overrides each query
    lane's Eq. 2 step total as DATA — the multi-interest layer rides its
    interest-cluster lanes on the batch axis with importance-proportional
    budgets (``batch_user_queries``), and ragged users share compiled
    programs because budgets never enter a shape.  ``None`` (every
    existing caller) leaves the classic static ``cfg.n_steps`` in place —
    same program, same results.  Unsupported over a ``ShardedGraph``.

    ``shard_dead_at`` (optional ``(n_shards,)`` int32, ``ShardedGraph``
    only) is the degraded-mode liveness schedule: shard ``s`` is dead
    from absolute superstep ``shard_dead_at[s]`` onward (``INT32_MAX`` =
    never).  Walkers routed to a dead shard are killed and reborn at
    home, dead shards' counts drop out of the merge, and the killed
    total is reported through the engine's telemetry — see
    ``distributed.pixie_walk_sharded_batched``.  Data, not shape: the
    serving layer flips liveness without retracing.
    """
    if backend is not None and backend != cfg.backend:
        cfg = dataclasses.replace(cfg, backend=backend)
    if scenario is not None and rank is None:
        raise ValueError(
            "scenario= selects a ranker head and needs rank=; a bare "
            "retrieval step has no scenario axis"
        )
    if rank is not None and cfg.top_k != rank.cfg.n_candidates:
        cfg = dataclasses.replace(cfg, top_k=rank.cfg.n_candidates)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) and key.ndim == 1:
        if key.shape[0] != pins.shape[0]:
            raise ValueError(
                f"per-query key array has {key.shape[0]} keys for a batch "
                f"of {pins.shape[0]} queries; one key per query required"
            )
        keys = key
    else:
        with jax.named_scope("pixie.query"):
            keys = jax.random.split(key, pins.shape[0])

    engine = walk_engine(graph, int(pins.shape[0]), int(pins.shape[1]), cfg)
    if engine == "sharded":
        if step_budgets is not None:
            raise ValueError(
                "serve_batch(step_budgets=...) over a ShardedGraph is not "
                "supported: the pod-sharded engine allocates Eq. 2 budgets "
                "from cfg.n_steps; serve multi-interest lanes on an "
                "unsharded replica"
            )
        if rank is not None:
            raise ValueError(
                "serve_batch(rank=...) over a ShardedGraph is not "
                "supported: stage 2 gathers candidate neighborhoods from "
                "the full CSR, which a node-range shard doesn't hold; rank "
                "on an unsharded replica or host-side from the sharded "
                "walk's (scores, ids)"
            )
        if mesh is None:
            raise ValueError(
                "serve_batch over a ShardedGraph needs the device mesh "
                "(pass mesh=...)"
            )
        with jax.named_scope("pixie.walk"):
            scores, ids, steps, n_high, dropped = (
                dist_lib.recommend_sharded_batched(
                    graph, pins, weights, keys, cfg, mesh, axis, slack=slack,
                    shard_dead_at=shard_dead_at,
                )
            )
        if with_stats:
            return scores, ids, steps, n_high, dropped
        return scores, ids

    if shard_dead_at is not None:
        raise ValueError(
            "serve_batch(shard_dead_at=...) needs a ShardedGraph: an "
            "unsharded replica has no shards to lose"
        )
    if engine == "batched":
        scores, ids, steps, n_high = walk_lib.recommend_with_stats_batched(
            graph, pins, weights, user_feats, keys, cfg,
            step_budgets=step_budgets,
        )
    elif step_budgets is None:

        def one(qp, qw, uf, k):
            return walk_lib.recommend_with_stats(graph, qp, qw, uf, k, cfg)

        scores, ids, steps, n_high = jax.vmap(one)(
            pins, weights, user_feats, keys
        )
    else:

        def one_budgeted(qp, qw, uf, k, sb):
            return walk_lib.recommend_with_stats(
                graph, qp, qw, uf, k, cfg, step_budget=sb
            )

        scores, ids, steps, n_high = jax.vmap(one_budgeted)(
            pins, weights, user_feats, keys,
            jnp.asarray(step_budgets, jnp.int32),
        )
    if rank is not None:
        from repro.serving import ranker as ranker_lib

        if scenario is None:
            scenario = jnp.zeros((pins.shape[0],), jnp.int32)
        with jax.named_scope("pixie.rank"):
            scores, ids = ranker_lib.rank_candidates(
                rank.params, rank.cfg, graph, ids, scores, scenario
            )
    if with_stats:
        return scores, ids, steps, n_high
    return scores, ids
