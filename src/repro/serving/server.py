"""Pixie serving fleet, TPU-shaped (paper §3.3 "Pixie Server").

The paper's server: IO threads deserialize queries, worker threads each own
a counter and run one query at a time; ~1,200 QPS / 60 ms p99 per machine.
The batch-SPMD translation, now shaped for CONTINUOUS traffic rather than a
synchronous flush loop:

  * requests route into **shape buckets** — small/medium/large
    ``(batch_size, n_slots)`` pairs, each lowering to its own cached jitted
    program (jit's compile cache is keyed on shape, so a straggler 16-pin
    query pads a 16-slot bucket, not the whole fleet shape);
  * batches form **deadline-aware**: a bucket dispatches when FULL or when
    its oldest request has waited ``max_wait_ms``, whichever first —
    freshness over batch occupancy ("Related Pins": tail latency, not
    throughput, is the production objective);
  * dispatch is **async**: the jitted call is enqueued and ``submit``/
    ``pump`` return immediately; ``jax.block_until_ready`` happens in
    ``harvest``, off the intake path;
  * every request gets its PRNG stream at submit time
    (``fold_in(server_key, req_id)``), so batch composition NEVER changes a
    query's walk — bucketed serving is bit-identical to the single-bucket
    ``flush()`` oracle on the same requests (the ``traffic_buckets_agree``
    CI verdict);
  * the graph array is the shared read-only segment (the paper's
    HugePages-backed mmap); ``swap_graph`` models the daily reload — the
    old graph serves until the new one is resident, in-flight batches
    complete on the generation they dispatched under, and every
    ``QueryResult`` carries its generation number.

Latency accounting is per query: ``latency = queue wait + dispatch +
compute`` (wait stamped at ``submit``, compute wall-clocked around the
device round-trip).  ``ServerStats`` keeps bounded ring buffers — a
long-lived replica never grows memory with traffic — beside integer
counters of batch fill and of the walk steps early stopping left unspent.

Host work is marked with ``jax.profiler.TraceAnnotation`` spans, on the
device trace's clock when a profile is taken: ``pixie.submit`` (``req_id``;
``lanes`` on ``submit_user``), ``pixie.dispatch`` (``batch_seq``,
``n_real``, ``batch_size``, ``slots``, ``queued``) with its children
``pixie.dispatch.form`` and ``pixie.dispatch.enqueue``, and per batch in
``harvest`` ``pixie.harvest.wait``, ``.fetch`` and ``.assemble``
(``batch_seq``).  The step itself carries ``jax.named_scope`` stages
(``pixie.query``, ``pixie.walk`` around ``pixie.walk.hop`` and
``pixie.walk.count``, ``pixie.eq3``, ``pixie.topk``, ``pixie.rank``; see
core/walk.py and core/service.py).  On CPU the Pallas
engine interprets, so the latency numbers measure plumbing; the
benchmarks/bench_traffic.py agreement verdict is the regression signal.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import service, walk as walk_lib
from repro.core.graph import PinBoardGraph
from repro.serving.resilience import ResilienceConfig, elastic_step_budget

# "this shard never dies": the liveness sentinel for sharded replicas
_NEVER_DIES = np.iinfo(np.int32).max

_span = jax.profiler.TraceAnnotation


def _steps_per_request(out):
    """``(scores, ids, steps)`` from ``serve_batch(..., with_stats=True)``,
    the walk steps summed over each request's query slots."""
    scores, ids, steps = out[:3]
    return scores, ids, jnp.sum(steps, axis=-1)


class LatencyRing:
    """Bounded float ring buffer with list-ish edges (append/extend/clear).

    Replaces the unbounded ``List[float]`` that leaked memory under
    continuous traffic: a long-lived replica keeps only the most recent
    ``capacity`` samples, and ``percentile`` is exact over that window.
    """

    __slots__ = ("capacity", "_buf", "_n", "_head")

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._buf = np.zeros((self.capacity,), np.float64)
        self._n = 0      # valid samples (<= capacity)
        self._head = 0   # next write position

    def append(self, x: float) -> None:
        self._buf[self._head] = float(x)
        self._head = (self._head + 1) % self.capacity
        self._n = min(self._n + 1, self.capacity)

    def extend(self, xs) -> None:
        for x in xs:
            self.append(x)

    def clear(self) -> None:
        self._n = 0
        self._head = 0

    def values(self) -> np.ndarray:
        """Samples oldest-first (only the retained window)."""
        if self._n < self.capacity:
            return self._buf[: self._n].copy()
        return np.roll(self._buf, -self._head)

    def percentile(self, p: float) -> float:
        """Exact percentile over the retained window; 0.0 when empty (an
        idle replica's dashboard shows 0, not a NaN crash)."""
        if not self._n:
            return 0.0
        return float(np.percentile(self.values(), p))

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        return iter(self.values())


@dataclasses.dataclass
class ServerStats:
    """Continuous-serving telemetry with bounded memory.

    ``latencies_ms[i] = wait_ms[i] + compute_ms[i]`` per query: queue wait
    (enqueue -> dispatch, stamped in ``submit``) plus dispatch+compute
    (host enqueue of the jitted call through ``block_until_ready``).  The
    old accounting dropped the wait term entirely — under load that hid
    exactly the queueing delay the paper's 60 ms p99 target is about.
    """

    capacity: int = 4096
    latencies_ms: LatencyRing = None
    wait_ms: LatencyRing = None
    compute_ms: LatencyRing = None
    queries: int = 0
    batches: int = 0
    # batches dispatched on the batch-native walk engine
    # (``service.walk_engine``); the rest ran vmapped or sharded
    batches_batch_native: int = 0
    # batch fill: lanes (batch rows) dispatched, and those holding a real
    # request rather than padding
    lanes_dispatched: int = 0
    lanes_filled: int = 0
    # walk steps over answered requests, taken and budgeted (Eq. 2): the
    # share early stopping (Algorithm 3) left unspent
    steps_taken: int = 0
    steps_budgeted: int = 0
    dropped: int = 0          # total refused work (rejections + harness drops)
    # submit-time admission rejections PER BUCKET (keyed by n_slots) —
    # previously these were folded into ``dropped`` with no bucket
    # attribution, so an operator couldn't see WHICH shape was overloaded
    rejected: Dict[int, int] = None
    graph_generation: int = 0

    def __post_init__(self):
        if self.latencies_ms is None:
            self.latencies_ms = LatencyRing(self.capacity)
        if self.wait_ms is None:
            self.wait_ms = LatencyRing(self.capacity)
        if self.compute_ms is None:
            self.compute_ms = LatencyRing(self.capacity)
        if self.rejected is None:
            self.rejected = {}

    @property
    def rejected_total(self) -> int:
        """Submit-time rejections across every bucket."""
        return sum(self.rejected.values())

    def percentile(self, p: float, which: str = "latency") -> float:
        ring = {
            "latency": self.latencies_ms,
            "wait": self.wait_ms,
            "compute": self.compute_ms,
        }[which]
        return ring.percentile(p)

    def qps(self, wall_seconds: float) -> float:
        return self.queries / max(wall_seconds, 1e-9)


class QueryResult:
    """Per-query serving result.

    Unpacks as ``scores, ids = result`` (the historical flush() contract)
    and additionally carries the request id, the graph generation the
    batch dispatched under (§3.3: results produced before a swap report
    the OLD generation), the latency split, and ``budget`` — the Eq. 2
    step total the request actually dispatched with (the full lane budget
    unless the resilience layer shed it; a multi-interest user reports
    the sum over its cluster lanes).  Degraded service is visible on the
    result, never silent.  ``steps_taken`` is the walk steps the request
    actually took, summed over its query slots (and a user's lanes): less
    than ``budget`` when early stopping ended the walk first.
    """

    __slots__ = ("req_id", "scores", "ids", "generation", "wait_ms",
                 "compute_ms", "latency_ms", "batch_seq", "budget",
                 "steps_taken")

    def __init__(self, req_id, scores, ids, generation, wait_ms,
                 compute_ms, batch_seq, budget=0, steps_taken=0):
        self.req_id = req_id
        self.scores = scores
        self.ids = ids
        self.generation = generation
        self.wait_ms = wait_ms
        self.compute_ms = compute_ms
        self.latency_ms = wait_ms + compute_ms
        self.batch_seq = batch_seq
        self.budget = budget
        self.steps_taken = steps_taken

    def __iter__(self):
        return iter((self.scores, self.ids))

    def __getitem__(self, i):
        return (self.scores, self.ids)[i]

    def __len__(self):
        return 2

    def __repr__(self):
        return (f"QueryResult(req_id={self.req_id}, gen={self.generation}, "
                f"wait={self.wait_ms:.2f}ms, compute={self.compute_ms:.2f}ms)")


@dataclasses.dataclass
class _Pending:
    req_id: int
    pins: np.ndarray      # (bucket n_slots,) int32, -1 padded
    weights: np.ndarray   # (bucket n_slots,) float32, 0 padded
    feat: int
    key: jax.Array        # per-request PRNG stream (fold_in at submit)
    t_enqueue: float      # logical seconds (wall by default)
    scenario: int = 0     # ranker head index (ranked servers only)
    budget: int = 0       # per-lane Eq. 2 step total (0 = cfg.n_steps)
    user_id: Optional[int] = None   # owning user request (cluster lanes)
    cluster_idx: int = 0  # lane index within the owning user


@dataclasses.dataclass
class _UserAssembly:
    """One multi-interest user awaiting its cluster-lane results.

    ``generation`` is stamped at ``submit_user`` — the user's lanes are
    guaranteed to dispatch under that generation because ``swap_graph``
    drains every queue before moving the handle (the generation barrier);
    the old harvest-side ``max`` over lane generations could silently
    blend walks from two graphs into one merged result.
    """

    n_clusters: int
    importance: np.ndarray           # (k,) float32, normalized
    t_enqueue: float
    generation: int
    parts: Dict[int, Tuple[np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=dict
    )
    wait_ms: float = 0.0
    compute_ms: float = 0.0
    batch_seq: int = -1
    budget: int = 0                  # summed dispatched lane budgets
    steps_taken: int = 0             # summed lane walk steps


@dataclasses.dataclass
class _InFlight:
    entries: List[_Pending]   # real requests only (padding not recorded)
    scores: jax.Array
    ids: jax.Array
    steps: jax.Array          # (batch,) walk steps taken, summed over slots
    generation: int           # stamped at DISPATCH: swaps don't rewrite it
    t_dispatch: float         # logical clock (matches submit's ``now``)
    t_dispatch_wall: float    # wall clock, for the compute measurement
    batch_seq: int
    budgets: List[int] = None  # per-entry dispatched Eq. 2 step totals


class PixieServer:
    """Single-host Pixie serving replica (bucketed, deadline-aware)."""

    def __init__(
        self,
        graph: PinBoardGraph,
        cfg: walk_lib.WalkConfig,
        batch_size: int = 8,
        n_slots: int = 8,
        seed: int = 0,
        backend: Optional[str] = None,
        mesh=None,
        axis: str = "model",
        slack: float = 2.0,
        buckets: Optional[Sequence[Tuple[int, int]]] = None,
        max_wait_ms: float = 5.0,
        max_queue_per_bucket: Optional[int] = None,
        stats_capacity: int = 4096,
        ranker=None,
        pin_topics: Optional[np.ndarray] = None,
        n_clusters: int = 3,
        resilience: Optional[ResilienceConfig] = None,
    ):
        """``backend`` overrides cfg.backend ("xla" | "pallas") so a fleet
        can flip every replica onto the fused Pallas walk engine at server
        construction; recommendations are bit-identical either way.

        ``buckets`` is the shape-specialization table: ``(batch_size,
        n_slots)`` pairs, e.g. ``[(8, 2), (4, 8), (2, 16)]``.  A request
        routes to the smallest bucket whose ``n_slots`` fits its pin
        count; each bucket shape lowers to its own cached jitted program.
        ``None`` keeps the single-bucket legacy shape ``[(batch_size,
        n_slots)]``.  ``max_wait_ms`` is the batch-formation deadline
        (``pump`` dispatches a partial bucket once its oldest request has
        waited this long); ``max_queue_per_bucket`` bounds admission —
        a full queue sheds the request (returns None, counted in
        ``stats.dropped``) instead of growing without bound.

        A ``distributed.ShardedGraph`` replica (graph too big for one
        chip) needs ``mesh``; ``axis``/``slack`` configure the walker
        routing fabric (core/distributed.py).  The sharded graph is
        closed over rather than passed through jit — its static int
        metadata must stay Python ints — so ``swap_graph`` re-jits on a
        sharded replica (the daily reload already pays a retrace for the
        new graph constants).

        ``ranker`` (a ``serving.ranker.RankRequest``) makes this a
        TWO-STAGE replica: every dispatched batch runs retrieval (top_k
        overridden to ``ranker.cfg.n_candidates``) + the scenario ranker
        head inside the same jitted program, and ``submit(scenario=...)``
        selects each request's head (related-pins vs homefeed).  Ranked
        results keep the ``(scores, ids)`` contract, now ``final_k`` wide.
        Ranker params are closed over like the walk config; a sharded
        replica rejects ``ranker`` (stage 2 needs the full CSR).

        ``pin_topics`` opens the MULTI-INTEREST intake (``submit_user``):
        action histories cluster host-side into up to ``n_clusters``
        interest lanes (``service.build_user_query`` over this topic
        table), each lane routes through the normal shape buckets with an
        importance-proportional Eq. 2 step budget, and ``harvest``
        reassembles users from their lane results via
        ``walk.merge_interest_topk``.  Budgets ride every dispatched batch
        as a ``(batch,)`` data array (flat requests carry the full
        ``cfg.n_steps`` — bit-identical to the budget-less program), so
        ragged users share the per-bucket compiled programs; bucket CHOICE
        keys on each cluster lane's own pin count, never on k.

        ``resilience`` (a ``serving.resilience.ResilienceConfig``) turns
        on degraded-mode serving: once a request's queue wait passes
        ``shed_start_ms``, it dispatches with a deadline-proportionally
        SHRUNK step budget instead of being dropped — budgets are data on
        the same ``(batch,)`` axis the multi-interest lanes use, so
        shedding never retraces.  Elastic shedding needs the budgets
        axis: ranked replicas must set ``elastic=False`` (their compiled
        program carries a scenario axis instead) and sharded replicas
        reject elastic configs (the pod engine allocates from
        ``cfg.n_steps``).  A sharded replica additionally gets the shard
        liveness controls ``kill_shard``/``revive_shards``: dead shards
        ride every dispatched batch as a ``(n_shards,)`` death-superstep
        array (data, no retrace), walkers routed to them are killed and
        reborn at home, and counting renormalizes over survivors
        (core/distributed.py)."""
        if backend is not None and backend != cfg.backend:
            cfg = dataclasses.replace(cfg, backend=backend)
        if pin_topics is not None and ranker is not None:
            raise ValueError(
                "a multi-interest replica can't rank in-batch: stage 2 "
                "re-scores the MERGED per-user candidate bag, which only "
                "exists after harvest; rank via "
                "recommend.recommend_multi_interest(rank=...) instead"
            )
        if n_clusters < 1:
            raise ValueError(f"n_clusters must be >= 1, got {n_clusters}")
        self.pin_topics = (
            None if pin_topics is None else np.asarray(pin_topics)
        )
        self.n_clusters = int(n_clusters)
        self.ranker = ranker
        self.graph = graph
        self.cfg = cfg
        self.batch_size = batch_size
        self.n_slots = n_slots
        self.mesh = mesh
        self.axis = axis
        self.slack = slack
        self.max_wait_ms = float(max_wait_ms)
        if resilience is not None:
            if ranker is not None and resilience.elastic:
                raise ValueError(
                    "elastic shedding rides the step_budgets data axis, "
                    "which a ranked replica's compiled program doesn't "
                    "carry (its batch axis is scenario); use "
                    "ResilienceConfig(elastic=False) for admission-only"
                )
            if resilience.max_queue_per_bucket is not None:
                if (max_queue_per_bucket is not None
                        and max_queue_per_bucket
                        != resilience.max_queue_per_bucket):
                    raise ValueError(
                        f"max_queue_per_bucket given twice and disagreeing: "
                        f"server={max_queue_per_bucket} vs "
                        f"resilience={resilience.max_queue_per_bucket}"
                    )
                max_queue_per_bucket = resilience.max_queue_per_bucket
        self.resilience = resilience
        self.max_queue_per_bucket = max_queue_per_bucket
        self.stats = ServerStats(capacity=stats_capacity)
        self._key = jax.random.key(seed)
        # one deterministic stream for padding lanes (results discarded)
        self._pad_key = jax.random.fold_in(self._key, jnp.iinfo(jnp.int32).max)
        self._seq = 0        # next auto-assigned request id
        self._batch_seq = 0  # dispatch order (monotone)
        if buckets is None:
            buckets = [(batch_size, n_slots)]
        if not buckets:
            raise ValueError("need at least one (batch_size, n_slots) bucket")
        # smallest-slots-first: routing picks the tightest fitting shape
        self._buckets: List[Tuple[int, int]] = sorted(
            ((int(b), int(s)) for b, s in buckets), key=lambda bs: bs[1]
        )
        seen = set()
        for b, s in self._buckets:
            if b < 1 or s < 1:
                raise ValueError(f"bucket ({b}, {s}) must be positive")
            if s in seen:
                raise ValueError(
                    f"two buckets share n_slots={s}; routing by pin count "
                    "needs distinct slot shapes"
                )
            seen.add(s)
        self.max_slots = self._buckets[-1][1]
        self._queues: Dict[int, List[_Pending]] = {
            s: [] for _, s in self._buckets
        }
        self._inflight: List[_InFlight] = []
        self._users: Dict[int, _UserAssembly] = {}
        # jit cache keys on (k, top_k) shapes: users with the same cluster
        # count share one compiled merge program
        self._merge = jax.jit(walk_lib.merge_interest_topk)
        self._build_serve()

    def _build_serve(self) -> None:
        from repro.core import distributed as dist_lib

        cfg = self.cfg
        if isinstance(self.graph, dist_lib.ShardedGraph):
            if self.ranker is not None:
                raise ValueError(
                    "a sharded replica can't rank: stage 2 gathers "
                    "candidate neighborhoods from the full CSR, which a "
                    "node-range shard doesn't hold; rank on an unsharded "
                    "replica"
                )
            if self.pin_topics is not None:
                raise ValueError(
                    "a sharded replica can't serve multi-interest users: "
                    "per-lane step budgets are not threaded through the "
                    "pod-sharded engine; serve them on an unsharded replica"
                )
            if self.resilience is not None and self.resilience.elastic:
                raise ValueError(
                    "a sharded replica can't shed elastically: the pod "
                    "engine allocates every walker from the static "
                    "cfg.n_steps bound; use ResilienceConfig(elastic="
                    "False) for admission control + dead-shard tolerance"
                )
            graph, mesh, axis, slack = (
                self.graph, self.mesh, self.axis, self.slack
            )
            # shard liveness rides every dispatch as a (n_shards,) DATA
            # array of death supersteps (INT32_MAX = never dies), so
            # kill_shard/revive_shards never retrace; a graph swap
            # revives everything (the daily reload replaces the pods)
            self._shard_dead_at = np.full(
                (graph.n_shards,), _NEVER_DIES, np.int32
            )
            sharded = jax.jit(
                lambda pins, weights, feats, keys, dead: _steps_per_request(
                    service.serve_batch(
                        graph, pins, weights, feats, keys, cfg, mesh=mesh,
                        axis=axis, slack=slack, shard_dead_at=dead,
                        with_stats=True,
                    )
                )
            )
            self._serve = self._keeping_steps(
                lambda _g, p, w, f, k: sharded(
                    p, w, f, k, jnp.asarray(self._shard_dead_at)
                )
            )
            self._takes_budgets = False
        else:
            # ONE jitted callable for every bucket: jit's compile cache is
            # keyed on argument shapes, so each (batch, n_slots) bucket
            # gets its own cached program, and a same-shape daily graph
            # swap reuses the compiled program (no retrace) — pinned by
            # _plain_serve._cache_size() in tests/test_traffic.py
            if getattr(self, "_plain_serve", None) is None:
                if self.ranker is None:
                    # EVERY non-ranker replica compiles the budgeted
                    # program: per-lane Eq. 2 budgets ride every batch as
                    # a (batch,) DATA array.  Flat requests carry
                    # cfg.n_steps, which allocates bit-identically to the
                    # static budget (core/sampling.allocate_steps), so
                    # multi-interest lanes, elastic shed budgets, and
                    # plain traffic all share the same cached programs —
                    # shedding can never retrace
                    self._plain_serve = jax.jit(
                        lambda graph, pins, weights, feats, keys, budgets:
                            _steps_per_request(service.serve_batch(
                                graph, pins, weights, feats, keys, cfg,
                                step_budgets=budgets, with_stats=True,
                            ))
                    )
                else:
                    # ranker params close over like cfg; scenario rides as
                    # a (batch,) argument so one cached program serves
                    # every head mix
                    rank = self.ranker
                    self._plain_serve = jax.jit(
                        lambda graph, pins, weights, feats, keys, scen:
                            _steps_per_request(service.serve_batch(
                                graph, pins, weights, feats, keys, cfg,
                                rank=rank, scenario=scen, with_stats=True,
                            ))
                    )
            self._serve = self._keeping_steps(self._plain_serve)
            self._takes_budgets = self.ranker is None

    def _keeping_steps(self, program):
        """The serving step as ``_dispatch`` calls it: ``(scores, ids)``,
        the contract a wrapper of ``_serve`` sees, while the same call's
        per-request walk steps wait in ``self._steps`` for ``_dispatch``."""

        def serve(*args):
            scores, ids, self._steps = program(*args)
            return scores, ids

        # ahead-of-time compiles (pixiebench/rehearse.py) lower the program
        serve.lower = getattr(program, "lower", None)
        return serve

    # -- request path ---------------------------------------------------------
    def _route(self, n_pins: int) -> Tuple[int, int]:
        """Smallest bucket whose n_slots fits the query; raises past the
        largest — a query must NEVER be silently truncated (dropping pins
        silently skews every Eq. 2 step budget downstream)."""
        for b, s in self._buckets:
            if n_pins <= s:
                return b, s
        raise ValueError(
            f"query has {n_pins} pins but the largest bucket holds "
            f"{self.max_slots} slots; shrink the query (service.build_query "
            f"keeps the top-n_slots pins by weight) or add a larger bucket"
        )

    def submit(
        self,
        pins: Sequence[int],
        weights: Sequence[float],
        user_feat: int = 0,
        now: Optional[float] = None,
        req_id: Optional[int] = None,
        scenario: int = 0,
        budget: Optional[int] = None,
    ) -> Optional[int]:
        """Enqueue one request; returns its request id (None if shed).

        ``budget`` pins the request's Eq. 2 step total (1..cfg.n_steps)
        instead of the full ``cfg.n_steps`` — the replay knob the chaos
        verdict uses to dispatch an unloaded oracle with the exact shrunk
        budgets a loaded run shed to.  Elastic shedding may shrink it
        further at dispatch, never grow it.

        ``scenario`` picks the request's ranker head on a two-stage
        replica (``ranker.cfg.scenario_id`` maps names to indices);
        validated here so a bad surface id fails at intake, not as a
        garbage gather inside a dispatched batch.

        Validates up front: ``len(weights)`` must equal ``len(pins)`` (a
        mismatch used to either crash with an opaque NumPy broadcast error
        or silently misalign weights to the wrong pins), and the pin count
        must fit a bucket (no silent truncation).  Stamps the enqueue time
        for the wait component of latency; ``now`` injects a logical clock
        (the open-loop traffic harness), defaulting to wall time.
        ``req_id`` overrides the auto-assigned id — the id seeds the
        request's PRNG stream (``fold_in``), so a workload replayed with
        the same ids gets bit-identical walks regardless of batching.
        """
        with _span("pixie.submit") as span:
            if len(weights) != len(pins):
                raise ValueError(
                    f"query has {len(pins)} pins but {len(weights)} weights; "
                    "one weight per pin required (mismatched lengths silently "
                    "misalign weights to the wrong pins)"
                )
            if self.ranker is None:
                if scenario != 0:
                    raise ValueError(
                        f"scenario={scenario} on a retrieval-only server; "
                        "pass ranker= to PixieServer to open the scenario "
                        "axis"
                    )
            elif not 0 <= int(scenario) < self.ranker.cfg.n_scenarios:
                raise ValueError(
                    f"scenario={scenario} out of range for heads "
                    f"{list(self.ranker.cfg.scenarios)}"
                )
            if budget is not None and not 1 <= int(budget) <= self.cfg.n_steps:
                raise ValueError(
                    f"budget={budget} outside [1, cfg.n_steps="
                    f"{self.cfg.n_steps}]: the engine's chunk grid is sized "
                    "for cfg.n_steps and a zero-step walk is a drop"
                )
            if budget is not None and not getattr(self, "_takes_budgets",
                                                  False):
                raise ValueError(
                    "this replica's compiled program has no budgets axis "
                    "(ranked or sharded); per-request budgets need a plain "
                    "or multi-interest replica"
                )
            n = len(pins)
            _, slots = self._route(n)
            if now is None:
                now = time.perf_counter()
            if req_id is None:
                req_id = self._seq
                self._seq += 1
            else:
                self._seq = max(self._seq, req_id + 1)
            span.set_metadata(req_id=req_id)
            queue = self._queues[slots]
            if (self.max_queue_per_bucket is not None
                    and len(queue) >= self.max_queue_per_bucket):
                # dropped stays the TOTAL refused-work counter; rejected is
                # the per-bucket breakdown an operator needs to see WHICH
                # shape is overloaded
                self.stats.dropped += 1
                self.stats.rejected[slots] = (
                    self.stats.rejected.get(slots, 0) + 1
                )
                return None
            qp = np.full(slots, -1, np.int32)
            qw = np.zeros(slots, np.float32)
            qp[:n] = np.asarray(pins, np.int32)
            qw[:n] = np.asarray(weights, np.float32)
            queue.append(_Pending(
                req_id=req_id, pins=qp, weights=qw, feat=int(user_feat),
                key=jax.random.fold_in(self._key, req_id), t_enqueue=now,
                scenario=int(scenario),
                budget=0 if budget is None else int(budget),
            ))
            return req_id

    def submit_user(
        self,
        actions: Sequence[service.UserAction],
        user_feat: int = 0,
        now: Optional[float] = None,
        req_id: Optional[int] = None,
        half_life_hours: float = 24.0,
    ) -> Optional[int]:
        """Enqueue one multi-interest USER (an action history, not a query).

        The PinnerSage intake: the history clusters host-side into up to
        ``n_clusters`` interest lanes (``service.build_user_query`` over
        the replica's ``pin_topics``), and EACH lane enqueues like a flat
        request — routed to the smallest bucket fitting its own pin count,
        budgeted by cluster importance (``service.cluster_step_budgets``
        splits the flat path's ``cfg.n_steps`` across the user's lanes),
        keyed ``fold_in(fold_in(server_key, req_id), cluster_idx)`` so
        every (user, cluster) pair owns a PRNG stream independent of batch
        composition.  ``harvest`` reassembles the user once all lanes
        return and emits ONE merged ``QueryResult`` under the returned
        request id (Eq. 3 across clusters via ``walk.merge_interest_topk``;
        a single-cluster user's lane passes through verbatim — the flat
        homefeed path).

        Admission is all-or-nothing: if any lane would overflow its bucket
        queue the WHOLE user sheds (returns None, one ``stats.dropped``) —
        partially-walked users would silently skew the merge.
        """
        with _span("pixie.submit") as span:
            if self.pin_topics is None:
                raise ValueError(
                    "submit_user needs a multi-interest replica; pass "
                    "pin_topics= to PixieServer to open the clustered intake"
                )
            uq = service.build_user_query(
                actions, self.pin_topics, n_slots=self.max_slots,
                n_clusters=self.n_clusters, half_life_hours=half_life_hours,
                user_feat=user_feat,
            )
            budgets = service.cluster_step_budgets(uq.importance,
                                                   self.cfg.n_steps)
            if now is None:
                now = time.perf_counter()
            if req_id is None:
                req_id = self._seq
                self._seq += 1
            else:
                self._seq = max(self._seq, req_id + 1)
            span.set_metadata(req_id=req_id, lanes=uq.n_clusters)
            # all-or-nothing admission: count this user's demand per bucket
            lanes = []
            demand: Dict[int, int] = {}
            for ci in range(uq.n_clusters):
                n = int(np.sum(uq.cluster_pins[ci] >= 0))
                _, slots = self._route(n)
                demand[slots] = demand.get(slots, 0) + 1
                lanes.append((ci, slots, n))
            if self.max_queue_per_bucket is not None:
                for slots, extra in demand.items():
                    if (len(self._queues[slots]) + extra
                            > self.max_queue_per_bucket):
                        self.stats.dropped += 1
                        self.stats.rejected[slots] = (
                            self.stats.rejected.get(slots, 0) + 1
                        )
                        return None
            user_key = jax.random.fold_in(self._key, req_id)
            for ci, slots, n in lanes:
                # cluster rows fill valid entries first, so the prefix copy is
                # the whole lane; padding past it is bit-invariant to the walk
                qp = np.full(slots, -1, np.int32)
                qw = np.zeros(slots, np.float32)
                qp[:n] = uq.cluster_pins[ci][:n]
                qw[:n] = uq.cluster_weights[ci][:n]
                self._queues[slots].append(_Pending(
                    req_id=req_id, pins=qp, weights=qw, feat=int(user_feat),
                    key=jax.random.fold_in(user_key, ci), t_enqueue=now,
                    budget=int(budgets[ci]), user_id=req_id, cluster_idx=ci,
                ))
            self._users[req_id] = _UserAssembly(
                n_clusters=uq.n_clusters,
                importance=np.asarray(uq.importance, np.float32),
                t_enqueue=now,
                # stamped HERE, not at harvest: swap_graph's drain barrier
                # guarantees every lane dispatches under this generation
                generation=self.stats.graph_generation,
            )
            return req_id

    # -- batch formation ------------------------------------------------------
    def _dispatch(self, batch_size: int, slots: int, now: float) -> None:
        """Form one batch from a bucket queue and enqueue the jitted call.

        Async: no ``block_until_ready`` here — the device round-trip is
        paid in ``harvest``, off the intake path."""
        queue = self._queues[slots]
        entries = queue[:batch_size]
        del queue[:batch_size]
        n_real = len(entries)
        with _span("pixie.dispatch", batch_seq=self._batch_seq,
                   n_real=n_real, batch_size=batch_size, slots=slots,
                   queued=len(queue)):
            with _span("pixie.dispatch.form"):
                args, entry_budgets = self._form(entries, batch_size, slots,
                                                 now)
            t_wall = time.perf_counter()
            with _span("pixie.dispatch.enqueue"):
                scores, ids = self._serve(*args)
            self._inflight.append(_InFlight(
                entries=entries, scores=scores, ids=ids, steps=self._steps,
                generation=self.stats.graph_generation,
                t_dispatch=now, t_dispatch_wall=t_wall,
                batch_seq=self._batch_seq, budgets=entry_budgets,
            ))
        self._batch_seq += 1
        self.stats.batches += 1
        if service.walk_engine(self.graph, batch_size, slots,
                               self.cfg) == "batched":
            self.stats.batches_batch_native += 1
        self.stats.lanes_dispatched += batch_size
        self.stats.lanes_filled += n_real

    def _form(self, entries: List[_Pending], batch_size: int, slots: int,
              now: float) -> Tuple[tuple, List[int]]:
        """The serving step's arguments for one batch (padded to
        ``batch_size``), and each real entry's dispatched Eq. 2 budget."""
        n_real = len(entries)
        pad = batch_size - n_real
        pins = np.full((batch_size, slots), -1, np.int32)
        weights = np.zeros((batch_size, slots), np.float32)
        feats = np.zeros((batch_size,), np.int32)
        scen = np.zeros((batch_size,), np.int32)
        for i, e in enumerate(entries):
            pins[i] = e.pins
            weights[i] = e.weights
            feats[i] = e.feat
            scen[i] = e.scenario
        keys = jnp.stack(
            [e.key for e in entries] + [self._pad_key] * pad
        )
        args = (
            self.graph, jnp.asarray(pins), jnp.asarray(weights),
            jnp.asarray(feats), keys,
        )
        if self.ranker is not None:
            args += (jnp.asarray(scen),)
        if not self._takes_budgets:
            return args, [self.cfg.n_steps] * n_real
        rcfg = self.resilience
        shed = rcfg is not None and rcfg.elastic
        budgets = np.full((batch_size,), self.cfg.n_steps, np.int32)
        for i, e in enumerate(entries):
            b = e.budget if e.budget else self.cfg.n_steps
            if shed:
                # deadline-aware elastic shed: queue wait on the LOGICAL
                # clock, so a chaos replay reproduces every shrink
                # bit-for-bit
                wait_ms = max(0.0, (now - e.t_enqueue) * 1e3)
                b = elastic_step_budget(b, wait_ms, rcfg)
            budgets[i] = b
        args += (jnp.asarray(budgets),)
        return args, [int(budgets[i]) for i in range(n_real)]

    def _deadline_of(self, entry: _Pending) -> float:
        """Logical dispatch deadline of one queued request.  The SINGLE
        float expression shared by ``pump`` and ``next_deadline`` — a
        caller pumping at exactly ``next_deadline()`` must trigger the
        dispatch (two differently-rounded formulations would make the
        returned deadline land an ulp short of its own check)."""
        return entry.t_enqueue + self.max_wait_ms / 1e3

    def pump(self, now: Optional[float] = None) -> int:
        """Deadline-aware batch formation: dispatch every FULL bucket, and
        every bucket whose oldest request has waited >= ``max_wait_ms``
        (dispatch on max-wait OR full, whichever first).  Returns the
        number of batches dispatched.  Non-blocking."""
        if now is None:
            now = time.perf_counter()
        dispatched = 0
        for batch_size, slots in self._buckets:
            queue = self._queues[slots]
            while len(queue) >= batch_size:
                self._dispatch(batch_size, slots, now)
                dispatched += 1
            if queue and now >= self._deadline_of(queue[0]):
                self._dispatch(batch_size, slots, now)
                dispatched += 1
        return dispatched

    def next_deadline(self) -> Optional[float]:
        """Logical time at which the oldest queued request hits its
        max-wait deadline (None when every queue is empty) — the traffic
        harness uses this to fire deadline dispatches deterministically."""
        heads = [
            self._deadline_of(q[0]) for q in self._queues.values() if q
        ]
        return min(heads) if heads else None

    def pending(self) -> int:
        """Requests queued but not yet dispatched."""
        return sum(len(q) for q in self._queues.values())

    # -- completion path ------------------------------------------------------
    def harvest(self) -> List[QueryResult]:
        """Collect every in-flight batch (blocking) and account latency.

        Per query: ``wait = dispatch - enqueue`` on the logical clock,
        ``compute = harvest_wall - dispatch_wall`` (host dispatch enqueue
        + device compute + transfer), ``latency = wait + compute``.
        Results carry the generation their batch dispatched under.
        """
        out: List[QueryResult] = []
        for fl in self._inflight:
            with _span("pixie.harvest.wait", batch_seq=fl.batch_seq):
                jax.block_until_ready(fl.scores)
            t_done_wall = time.perf_counter()
            compute_ms = (t_done_wall - fl.t_dispatch_wall) * 1e3
            with _span("pixie.harvest.fetch", batch_seq=fl.batch_seq):
                s_np, i_np, st_np = jax.device_get(
                    (fl.scores, fl.ids, fl.steps)
                )
            with _span("pixie.harvest.assemble", batch_seq=fl.batch_seq):
                out += self._assemble(fl, s_np, i_np, st_np, compute_ms)
        self._inflight = []
        # emit users whose lanes all returned: Eq. 3 across clusters via
        # the SAME bit-reproducible merge the fused service path uses.
        # wait/compute are the max over the user's lanes (the user is done
        # when its slowest interest is), batch_seq the last lane's, the
        # generation the one stamped at submit_user (the swap_graph drain
        # barrier guarantees every lane ran under it) — one queries/
        # latency sample per USER, not per lane.
        done = [rid for rid, a in self._users.items()
                if len(a.parts) == a.n_clusters]
        if done:
            with _span("pixie.harvest.assemble", users=len(done)):
                for rid in sorted(done):
                    out.append(self._merge_user(rid))
        return out

    def _assemble(self, fl: _InFlight, s_np: np.ndarray, i_np: np.ndarray,
                  st_np: np.ndarray, compute_ms: float) -> List[QueryResult]:
        """Results of one harvested batch; a multi-interest user's cluster
        lanes are parked in its assembly instead."""
        out = []
        for i, e in enumerate(fl.entries):
            wait_ms = max(0.0, (fl.t_dispatch - e.t_enqueue) * 1e3)
            if e.user_id is not None:
                # a cluster lane: park it in the user's assembly; the
                # merged user-level result is emitted once every lane has
                # returned
                asm = self._users[e.user_id]
                asm.parts[e.cluster_idx] = (s_np[i], i_np[i])
                asm.wait_ms = max(asm.wait_ms, wait_ms)
                asm.compute_ms = max(asm.compute_ms, compute_ms)
                asm.batch_seq = max(asm.batch_seq, fl.batch_seq)
                asm.budget += fl.budgets[i]
                asm.steps_taken += int(st_np[i])
                continue
            out.append(QueryResult(
                req_id=e.req_id, scores=s_np[i], ids=i_np[i],
                generation=fl.generation, wait_ms=wait_ms,
                compute_ms=compute_ms, batch_seq=fl.batch_seq,
                budget=fl.budgets[i], steps_taken=int(st_np[i]),
            ))
            self._account(out[-1])
        return out

    def _merge_user(self, rid: int) -> QueryResult:
        """The merged result of a user whose lanes have all returned."""
        asm = self._users.pop(rid)
        scores = jnp.asarray(
            np.stack([asm.parts[c][0] for c in range(asm.n_clusters)])
        )
        ids = jnp.asarray(
            np.stack([asm.parts[c][1] for c in range(asm.n_clusters)])
        )
        ms, mi = self._merge(scores, ids, jnp.asarray(asm.importance))
        res = QueryResult(
            req_id=rid, scores=np.asarray(ms), ids=np.asarray(mi),
            generation=asm.generation, wait_ms=asm.wait_ms,
            compute_ms=asm.compute_ms, batch_seq=asm.batch_seq,
            budget=asm.budget, steps_taken=asm.steps_taken,
        )
        self._account(res)
        return res

    def _account(self, res: QueryResult) -> None:
        """One answered request into the stats."""
        st = self.stats
        st.queries += 1
        st.wait_ms.append(res.wait_ms)
        st.compute_ms.append(res.compute_ms)
        st.latencies_ms.append(res.latency_ms)
        st.steps_taken += res.steps_taken
        st.steps_budgeted += res.budget

    def flush(self, now: Optional[float] = None) -> List[QueryResult]:
        """Serve every queued request synchronously (padding partials).

        The single-bucket oracle path: with one bucket this reproduces the
        historical flush loop — batches formed in submit order — and the
        bucketed deadline path is verified score-for-score identical to it
        (``traffic_buckets_agree``).  Results return in request-id order
        and still unpack as ``(scores, ids)`` pairs.
        """
        if now is None:
            now = time.perf_counter()
        for batch_size, slots in self._buckets:
            while self._queues[slots]:
                self._dispatch(batch_size, slots, now)
        out = self.harvest()
        out.sort(key=lambda r: r.req_id)
        return out

    # -- graph swap (the daily reload, §3.3) -----------------------------------
    def swap_graph(self, new_graph, now: Optional[float] = None) -> None:
        """Swap in the freshly built daily graph, under load.

        Increments the generation exactly once; batches already in flight
        (or already dispatched) keep serving from the OLD graph handle —
        the swap never blocks serving, and their results report the old
        generation.  A same-shape plain-graph swap reuses the compiled
        serve programs (the graph is a jit ARGUMENT, not a closure).

        The GENERATION BARRIER: every still-queued request dispatches on
        the old graph (partial batches padded, async — the swap doesn't
        block on compute) before the handle moves.  Without it a multi-
        interest user whose cluster lanes straddled the swap would merge
        walks from two different graphs into one result; with it the
        generation stamped at ``submit_user`` is always the generation
        every lane actually ran under.  ``now`` injects the logical clock
        for deterministic harness replays (defaults to wall time).

        A sharded replica's swap also revives all shards (the daily
        reload replaces the pods)."""
        if now is None:
            now = time.perf_counter()
        for batch_size, slots in self._buckets:
            while self._queues[slots]:
                self._dispatch(batch_size, slots, now)
        self.graph = new_graph
        self.stats.graph_generation += 1
        self._build_serve()

    # -- shard liveness (degraded-mode serving) --------------------------------
    def kill_shard(self, shard: int, at_superstep: int = 0) -> None:
        """Mark one pod shard dead from absolute superstep ``at_superstep``
        of every subsequently dispatched walk (0 = dead from the start).

        Pure data: the liveness array rides the next dispatch, nothing
        retraces.  Walkers routed to a dead shard are killed and reborn
        at their home shard, walkers homed there stop being (re)injected,
        and its counts drop out of the merge — counting renormalizes over
        the survivors (core/distributed.py).  The quality cost is
        quantified by ``resilience.overlap_at_k`` against an all-alive
        oracle in benchmarks/bench_chaos.py, never silent."""
        from repro.core import distributed as dist_lib

        if not isinstance(self.graph, dist_lib.ShardedGraph):
            raise ValueError(
                "kill_shard needs a sharded replica; a plain graph has "
                "no shards to lose"
            )
        if not 0 <= int(shard) < self._shard_dead_at.shape[0]:
            raise ValueError(
                f"shard {shard} out of range for "
                f"{self._shard_dead_at.shape[0]} shards"
            )
        if int(at_superstep) < 0:
            raise ValueError(
                f"at_superstep={at_superstep} must be >= 0"
            )
        self._shard_dead_at[int(shard)] = int(at_superstep)

    def revive_shards(self) -> None:
        """Bring every shard back to life (subsequent dispatches only)."""
        from repro.core import distributed as dist_lib

        if not isinstance(self.graph, dist_lib.ShardedGraph):
            raise ValueError("revive_shards needs a sharded replica")
        self._shard_dead_at[:] = _NEVER_DIES

    def dead_shards(self) -> List[int]:
        """Shards currently marked dead (empty on a healthy replica)."""
        dead = getattr(self, "_shard_dead_at", None)
        if dead is None:
            return []
        return [int(i) for i in np.flatnonzero(dead != _NEVER_DIES)]
