"""Pallas TPU kernel: tiled dense histogram over a bounded event buffer.

This is the TPU-native replacement for Pixie's open-addressing visit-count
hash table (paper §3.3).  The paper bounds the table by the step budget N;
we keep the same bound on the event buffer and flip the data structure
inside-out: instead of scattering events into a table (random writes — the
worst TPU access pattern), each grid cell owns a *tile of the count table*
in VMEM and scans the event buffer with vectorized compares:

    counts[t] = sum_m 1[events[m] == tile_base + t]

The compare matrix (event_chunk x tile) lives entirely in VREGs/VMEM, the
event buffer streams through VMEM once per count tile, and there are no
scatters anywhere.  Grid = (n_tiles, n_chunks); the chunk axis is innermost
so each tile block accumulates across event chunks in place.

VMEM budget per program: tile (TILE,) int32 + chunk (CHUNK,) int32 + the
(CHUNK, TILE) one-hot intermediate = 4*(1024 + 2048 + 1024*2048) B ~ 8 MiB,
inside the 16 MiB of VMEM a v5e kernel may claim by default.

Three entry points share the tile-scan core:

* ``visit_counter`` — plain histogram of a flat-id event buffer (kept as
  the minimal kernel; generic id histograms).
* ``visit_counter_wide`` — histogram of WIDE (slot, id) int32 event lane
  pairs; the flat ``slot * n_dim + id`` bin id is formed inside the
  kernel, so the lanes themselves never carry the packed product.
* ``visit_counter_update_high`` — the fused early-stop counter for the
  dense walk engine (Algorithm 3), also wide: takes the PRIOR running counts as an
  input, accumulates the chunk's events on top of them *inside VMEM*, and
  additionally emits, per query slot, how many count-table entries crossed
  the ``n_v`` visit threshold during this update.  The walk loop's
  early-stop condition then reads a ``(n_slots,)`` running tally instead of
  re-reducing the whole ``n_slots * n_pins`` buffer every while-loop
  iteration — the last O(n_slots*n_pins)-per-chunk cost on the dense path.

This kernel is the aggregation half of the fused walk engine
(``WalkConfig(backend="pallas")``): ``kernels/walk_step.walk_steps_fused``
emits WIDE (slot, pin) int32 event lanes (slot lane sentinel ``n_slots``
for invalid steps) and ``core/counter.accumulate_packed_events[_with_high]``
histograms each chunk over ``n_slots * n_pins`` bins with the ``*_wide``
kernels instead of XLA scatter-add.  The wide kernels pack
``slot * n_pins + pin`` INSIDE the kernel, in VMEM: dense counting
inherently requires the flat bin space to fit a materialized buffer
(< 2**31 bins — enforced by the wrapper), so the in-kernel product is
always int32-safe; sentinel events map to bin ``n_slots * n_pins`` which
never matches a live tile and drops out of the histogram for free.
Id spaces PAST 2**31 never reach these kernels — they use the event-mode
(sort-based) counting path, which consumes the wide lanes directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# 1-D int32 arrays sit in HBM in 1024-element tiles, and a kernel block of
# a 1-D operand must be a whole number of them: both sizes stay multiples
DEFAULT_TILE = 1024    # count-table entries per grid cell
DEFAULT_CHUNK = 2048   # events streamed per inner grid step
SLOT_PAD = 8           # padding of the per-row crossing tally (lane dim)


def _visit_counter_kernel(events_ref, counts_ref, *, tile: int, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    tile_base = pl.program_id(0) * tile
    ev = events_ref[...]                                   # (chunk,)
    # (chunk, tile) one-hot compare — vectorized, no scatter
    ids = tile_base + jax.lax.broadcasted_iota(jnp.int32, (chunk, tile), 1)
    hit = (ev[:, None] == ids).astype(jnp.int32)
    counts_ref[...] += jnp.sum(hit, axis=0)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "tile", "chunk", "interpret")
)
def visit_counter(
    events: jax.Array,
    n_bins: int,
    *,
    tile: int = DEFAULT_TILE,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> jax.Array:
    """Histogram of `events` over [0, n_bins). Out-of-range ids are dropped.

    events: (m,) int32 — visited pin ids; pad/invalid entries may be any
    value outside [0, n_bins) (the walk uses -1).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m = events.shape[0]
    # pad events to a chunk multiple with an out-of-range sentinel
    m_pad = -(-m // chunk) * chunk
    if m_pad != m:
        events = jnp.concatenate(
            [events, jnp.full((m_pad - m,), -1, events.dtype)]
        )
    n_pad = -(-n_bins // tile) * tile
    grid = (n_pad // tile, m_pad // chunk)
    out = pl.pallas_call(
        functools.partial(_visit_counter_kernel, tile=tile, chunk=chunk),
        grid=grid,
        in_specs=[pl.BlockSpec((chunk,), lambda i, j: (j,))],
        out_specs=pl.BlockSpec((tile,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(events.astype(jnp.int32))
    return out[:n_bins]


# ---------------------------------------------------------------------------
# Wide-event tile-scan histogram: (slot, id) int32 lanes in, flat bins out
# ---------------------------------------------------------------------------


def _require_dense_bins(n_bins: int) -> None:
    """Dense counting materializes an (n_bins,) buffer: must fit int32."""
    if n_bins + 1 >= 2**31:
        raise ValueError(
            f"dense counting needs n_slots * n_dim < 2**31, got {n_bins}; "
            "id spaces past int32 use event-mode (sort-based) counting"
        )


def _flat_ids_from_lanes(
    slot_ev, id_ev, n_slots: int, n_dim: int, q_ev=None, n_queries: int = 0
):
    """Pack wide lanes to flat bin ids in-register; invalid events -> -1.

    With a query lane (``q_ev``, batch-native mode) the bins are
    query-major — ``(query * n_slots + slot) * n_dim + id`` — formed right
    here in VMEM, so no lane ever carries a packed product outside the
    kernel; validity then additionally requires ``0 <= query < n_queries``
    (the walk's query sentinel is ``n_queries``).  The products are
    int32-safe because the wide wrappers only accept bin spaces that fit a
    dense buffer (``n_rows * n_dim < 2**31``).
    """
    valid = (
        (slot_ev >= 0) & (slot_ev < n_slots)
        & (id_ev >= 0) & (id_ev < n_dim)
    )
    row = slot_ev
    if q_ev is not None:
        valid &= (q_ev >= 0) & (q_ev < n_queries)
        row = q_ev * jnp.int32(n_slots) + slot_ev
    flat = (
        jnp.where(valid, row, 0) * jnp.int32(n_dim)
        + jnp.where(valid, id_ev, 0)
    )
    return jnp.where(valid, flat, jnp.int32(-1))


def _visit_counter_wide_kernel(
    *refs, tile: int, chunk: int, n_slots: int, n_dim: int,
    n_queries: int = 0,
):
    """Tile-scan histogram over wide lanes; with ``n_queries > 0`` the
    event refs lead with a query lane and bins are query-major."""
    j = pl.program_id(1)
    counts_ref = refs[-1]

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    tile_base = pl.program_id(0) * tile
    if n_queries:
        q_ref, slot_ref, id_ref = refs[:3]
        ev = _flat_ids_from_lanes(
            slot_ref[...], id_ref[...], n_slots, n_dim,
            q_ev=q_ref[...], n_queries=n_queries,
        )                                                  # (chunk,)
    else:
        slot_ref, id_ref = refs[:2]
        ev = _flat_ids_from_lanes(
            slot_ref[...], id_ref[...], n_slots, n_dim
        )                                                  # (chunk,)
    ids = tile_base + jax.lax.broadcasted_iota(jnp.int32, (chunk, tile), 1)
    hit = (ev[:, None] == ids).astype(jnp.int32)
    counts_ref[...] += jnp.sum(hit, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_slots", "n_dim", "n_queries", "tile", "chunk", "interpret"
    ),
)
def visit_counter_wide(
    slot_events: jax.Array,
    id_events: jax.Array,
    query_events: jax.Array | None = None,
    *,
    n_slots: int,
    n_dim: int,
    n_queries: int = 0,
    tile: int = DEFAULT_TILE,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> jax.Array:
    """Histogram of wide (slot, id) lanes over ``n_slots * n_dim`` flat bins.

    slot_events / id_events: (m,) int32; an event counts iff
    ``0 <= slot < n_slots`` and ``0 <= id < n_dim`` (the walk's invalid
    sentinel, slot = ``n_slots``, is dropped for free).  Returns
    ``(n_slots * n_dim,)`` int32.

    Batch-native mode: pass ``query_events`` (the third wide lane, query
    sentinel ``n_queries``) and ``n_queries > 0`` to histogram a whole
    serving batch's events in one call over
    ``n_queries * n_slots * n_dim`` query-major bins — the triple is
    packed to flat bin ids inside the kernel, in VMEM.
    """
    with_query = query_events is not None
    if with_query and n_queries <= 0:
        raise ValueError("query_events given but n_queries not set (> 0)")
    n_rows = n_queries * n_slots if with_query else n_slots
    n_bins = n_rows * n_dim
    _require_dense_bins(n_bins)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m = slot_events.shape[0]
    if m == 0:  # zero-size grid is illegal; nothing to count either way
        return jnp.zeros((n_bins,), jnp.int32)
    lanes = ([query_events] if with_query else []) + [slot_events, id_events]
    lanes = [l.astype(jnp.int32) for l in lanes]
    m_pad = -(-m // chunk) * chunk
    if m_pad != m:
        pad = jnp.full((m_pad - m,), -1, jnp.int32)
        lanes = [jnp.concatenate([l, pad]) for l in lanes]
    n_pad = -(-n_bins // tile) * tile
    grid = (n_pad // tile, m_pad // chunk)
    ev_spec = pl.BlockSpec((chunk,), lambda i, j: (j,))
    out = pl.pallas_call(
        functools.partial(
            _visit_counter_wide_kernel, tile=tile, chunk=chunk,
            n_slots=n_slots, n_dim=n_dim,
            n_queries=n_queries if with_query else 0,
        ),
        grid=grid,
        in_specs=[ev_spec] * len(lanes),
        out_specs=pl.BlockSpec((tile,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(*lanes)
    return out[:n_bins]


# ---------------------------------------------------------------------------
# Fused count-update + incremental early-stop tally (dense walk hot path)
# ---------------------------------------------------------------------------


def _visit_counter_high_kernel(
    *refs,
    tile: int, chunk: int, n_chunks: int, n_slots: int, n_pins: int,
    n_v: int, slot_pad: int, n_queries: int = 0,
):
    """Tile-scan histogram on top of PRIOR counts, plus threshold crossings.

    Events arrive as wide (slot, pin) int32 lanes — led by a query lane in
    batch-native mode (``n_queries > 0``) — and are packed to flat bin ids
    in-register (int32-safe: the wrapper enforces the dense-bin
    precondition; query-major ``(query * n_slots + slot) * n_pins + pin``
    when the query lane is present).  The count tile is initialised from
    the prior running counts, stays in VMEM while every event chunk
    streams past (inner grid axis), and after the last chunk the tile is
    compared against its prior values: entries that crossed
    ``count >= n_v`` during this update are summed per count row
    (``bin // n_pins`` — the query slot, or the (query, slot) pair in
    batch mode) by a one-hot matmul — no scatter, no full-buffer
    reduction outside the kernel.  The ``(1, slot_pad)`` tally block maps
    to the same place at every grid step, so it stays resident and
    accumulates across all tiles.
    """
    if n_queries:
        q_ref, slot_ref, pin_ref, prior_ref, counts_ref, high_ref = refs
    else:
        slot_ref, pin_ref, prior_ref, counts_ref, high_ref = refs
        q_ref = None
    j = pl.program_id(1)
    tile_base = pl.program_id(0) * tile

    @pl.when((pl.program_id(0) == 0) & (j == 0))
    def _init_high():
        high_ref[...] = jnp.zeros_like(high_ref)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = prior_ref[...]

    ev = _flat_ids_from_lanes(
        slot_ref[...], pin_ref[...], n_slots, n_pins,
        q_ev=None if q_ref is None else q_ref[...],
        n_queries=n_queries,
    )                                                      # (chunk,)
    ids = tile_base + jax.lax.broadcasted_iota(jnp.int32, (chunk, tile), 1)
    hit = (ev[:, None] == ids).astype(jnp.int32)
    counts_ref[...] += jnp.sum(hit, axis=0)

    @pl.when(j == n_chunks - 1)
    def _emit_high():
        prior = prior_ref[...]                             # (tile,)
        new = counts_ref[...]
        # n_v is compared, never added: a huge disable-early-stop sentinel
        # (e.g. int32max // 2) cannot overflow anything here.
        crossed = ((prior < n_v) & (new >= n_v)).astype(jnp.float32)
        bin_col = tile_base + jax.lax.broadcasted_iota(
            jnp.int32, (tile, slot_pad), 0
        )
        slot_ids = jax.lax.broadcasted_iota(jnp.int32, (tile, slot_pad), 1)
        onehot = (bin_col // n_pins == slot_ids).astype(jnp.float32)
        # 0/1 operands and sums <= tile < 2**24: the f32 matmul is exact
        per_row = jnp.dot(
            crossed[None, :], onehot, preferred_element_type=jnp.float32
        )                                                  # (1, slot_pad)
        high_ref[...] += per_row.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_slots", "n_pins", "n_v", "n_queries", "tile", "chunk", "interpret"
    ),
)
def visit_counter_update_high(
    prior_counts: jax.Array,
    slot_events: jax.Array,
    pin_events: jax.Array,
    query_events: jax.Array | None = None,
    *,
    n_slots: int,
    n_pins: int,
    n_v: int,
    n_queries: int = 0,
    tile: int = DEFAULT_TILE,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused ``new = prior + hist(events)`` plus per-slot n_v crossings.

    prior_counts: (n_slots * n_pins,) int32 running visit counts.
    slot_events / pin_events: (m,) int32 wide event lanes; an event counts
                  iff ``0 <= slot < n_slots`` and ``0 <= pin < n_pins``
                  (the walk's invalid-step sentinel, slot = ``n_slots``,
                  is dropped).
    Returns ``(new_counts (n_slots * n_pins,), delta_high (n_slots,))``
    where ``delta_high[s]`` counts bins of slot s whose visit count crossed
    from below ``n_v`` to ``>= n_v`` during this update.  Requires
    ``n_v >= 1`` (counts start at zero, so a non-positive threshold would
    be "already crossed" and never increment the tally).

    Batch-native mode: pass ``query_events`` (query sentinel
    ``n_queries``) and ``n_queries > 0`` to update a whole serving batch's
    running counts in one call — ``prior_counts`` then has
    ``n_queries * n_slots * n_pins`` query-major bins and ``delta_high``
    one entry per (query, slot) row, query-major.
    """
    if n_v < 1:
        raise ValueError(f"n_v must be >= 1 for crossing tallies, got {n_v}")
    with_query = query_events is not None
    if with_query and n_queries <= 0:
        raise ValueError("query_events given but n_queries not set (> 0)")
    n_rows = n_queries * n_slots if with_query else n_slots
    n_bins = n_rows * n_pins
    _require_dense_bins(n_bins)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m = slot_events.shape[0]
    if m == 0:  # zero-size grid is illegal; nothing to count either way
        return (
            prior_counts.astype(jnp.int32),
            jnp.zeros((n_rows,), jnp.int32),
        )
    lanes = ([query_events] if with_query else []) + [slot_events, pin_events]
    lanes = [l.astype(jnp.int32) for l in lanes]
    m_pad = -(-m // chunk) * chunk
    if m_pad != m:
        pad = jnp.full((m_pad - m,), -1, jnp.int32)
        lanes = [jnp.concatenate([l, pad]) for l in lanes]
    n_pad = -(-n_bins // tile) * tile
    prior = prior_counts.astype(jnp.int32)
    if n_pad != n_bins:
        prior = jnp.concatenate(
            [prior, jnp.zeros((n_pad - n_bins,), jnp.int32)]
        )
    slot_pad = -(-n_rows // SLOT_PAD) * SLOT_PAD
    n_tiles, n_chunks = n_pad // tile, m_pad // chunk
    ev_spec = pl.BlockSpec((chunk,), lambda i, j: (j,))
    counts, high = pl.pallas_call(
        functools.partial(
            _visit_counter_high_kernel,
            tile=tile, chunk=chunk, n_chunks=n_chunks,
            n_slots=n_slots, n_pins=n_pins, n_v=n_v, slot_pad=slot_pad,
            n_queries=n_queries if with_query else 0,
        ),
        grid=(n_tiles, n_chunks),
        in_specs=[ev_spec] * len(lanes) + [
            pl.BlockSpec((tile,), lambda i, j: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda i, j: (i,)),
            # one resident tally row for the whole grid
            pl.BlockSpec((1, slot_pad), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((1, slot_pad), jnp.int32),
        ],
        interpret=interpret,
    )(*lanes, prior)
    return counts[:n_bins], high[0, :n_rows]
