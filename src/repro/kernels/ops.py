"""Public jit'd entry points for the Pallas kernels, with oracle fallbacks.

Every op takes `use_kernel`:
  * True  — run the Pallas kernel (interpret mode on CPU, compiled on TPU);
  * False — run the pure-jnp oracle from ref.py (always available, used by
    the distributed paths where the op must trace under shard_map/jit with
    shapes the kernel grid doesn't cover).

The default is the oracle on CPU hosts and the kernel on TPU: the oracle
*is* the mathematically identical program, so higher layers never branch on
backend.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.embedding_bag import embedding_bag as _bag_kernel
from repro.kernels.embedding_bag import (
    embedding_bag_batched as _bag_batched_kernel,
)
from repro.kernels.visit_counter import visit_counter as _counter_kernel
from repro.kernels.visit_counter import (
    visit_counter_wide as _counter_wide_kernel,
)
from repro.kernels.visit_counter import (
    visit_counter_update_high as _counter_high_kernel,
)
from repro.kernels.walk_step import walk_step as _walk_kernel
from repro.kernels.walk_step import DEFAULT_BLOCK_W as _DEFAULT_BLOCK_W
from repro.kernels.walk_step import walk_steps_fused as _fused_kernel
from repro.kernels.walk_step import walk_hop_fused as _hop_kernel

Array = jax.Array


def _default_use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def visit_counts(
    events: Array, n_bins: int, *, use_kernel: Optional[bool] = None
) -> Array:
    """Histogram of visit events over [0, n_bins)."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _counter_kernel(events, n_bins)
    return ref.visit_counter_ref(events, n_bins)


def visit_counts_wide(
    slot_events: Array,
    id_events: Array,
    *,
    n_slots: int,
    n_dim: int,
    query_events: Optional[Array] = None,
    n_queries: int = 0,
    use_kernel: Optional[bool] = None,
) -> Array:
    """Histogram of wide (slot, id) event lanes over n_slots * n_dim bins.

    With a ``query_events`` lane (batch-native mode, ``n_queries > 0``)
    the bins are the ``n_queries * n_slots * n_dim`` query-major triple
    space and one call covers a whole serving batch.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _counter_wide_kernel(
            slot_events, id_events, query_events,
            n_slots=n_slots, n_dim=n_dim, n_queries=n_queries,
        )
    return ref.visit_counter_wide_ref(
        slot_events, id_events, n_slots, n_dim, query_events, n_queries
    )


def visit_counts_update_high(
    prior_counts: Array,
    slot_events: Array,
    pin_events: Array,
    *,
    n_slots: int,
    n_pins: int,
    n_v: int,
    query_events: Optional[Array] = None,
    n_queries: int = 0,
    use_kernel: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Fused running-count update + per-slot n_v-crossing tally (wide events).

    Returns ``(new_counts (n_slots * n_pins,), delta_high (n_slots,))`` —
    the incremental early-stop statistic of the dense walk engine
    (Algorithm 3): the while-loop carries a running ``n_high`` tally instead
    of re-reducing the whole count buffer each chunk.  With a
    ``query_events`` lane (batch-native mode, ``n_queries > 0``) the bins
    are query-major over the whole batch and ``delta_high`` has one entry
    per (query, slot) row.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _counter_high_kernel(
            prior_counts, slot_events, pin_events, query_events,
            n_slots=n_slots, n_pins=n_pins, n_v=n_v, n_queries=n_queries,
        )
    return ref.visit_counter_update_high_ref(
        prior_counts, slot_events, pin_events, n_slots, n_pins, n_v,
        query_events, n_queries,
    )


def walk_step(
    curr: Array,
    query: Array,
    rbits: Array,
    p2b_offsets: Array,
    p2b_targets: Array,
    b2p_offsets: Array,
    b2p_targets: Array,
    *,
    n_pins: int,
    alpha_u32: int,
    use_kernel: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """One fused biased walk superstep -> (next, visited, valid)."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _walk_kernel(
            curr, query, rbits,
            p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
            n_pins=n_pins, alpha_u32=alpha_u32,
        )
    return ref.walk_step_ref(
        curr, query, rbits,
        p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
        n_pins=n_pins, alpha_u32=alpha_u32,
    )


def walk_chunk_fused(
    curr: Array,
    query: Array,
    feat: Array,
    slot: Array,
    rbits: Array,
    p2b_offsets: Array,
    p2b_targets: Array,
    b2p_offsets: Array,
    b2p_targets: Array,
    p2b_feat_bounds: Optional[Array] = None,
    b2p_feat_bounds: Optional[Array] = None,
    *,
    n_pins: int,
    n_slots: int,
    n_boards: int,
    alpha_u32: int,
    beta_u32: int,
    count_boards: bool = False,
    unroll: bool = False,
    block_w: Optional[int] = None,
    gather_mode: str = "scalar",
    use_kernel: Optional[bool] = None,
) -> Tuple[Array, Array, Array, Optional[Array]]:
    """chunk_steps fused walk supersteps.

    Returns ``(next, slot_events, pin_events, board_events | None)`` —
    wide (slot, pin) int32 event lanes (slot lane sentinel ``n_slots`` for
    invalid steps; the board lane shares the slot lane), so both engines
    cover packed id spaces past 2**31 with no fallback.  The kernel path
    runs ALL chunk_steps steps in one pallas_call with walker state
    resident in SMEM; the oracle path is the same arithmetic as two-level
    XLA gathers (this is the walk's "xla" backend).  Both consume the same
    (chunk_steps, w, 4) uint32 counter-RNG bits, so their emitted events
    agree bit-for-bit.

    ``gather_mode`` ("scalar" | "dma") selects how the kernel path orders
    its CSR row copies — each waited on at once, or double-buffered
    across walkers; both are bit-identical to the oracle.  The oracle
    path has no gather modes (XLA vector gathers) and ignores it.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        w = curr.shape[0]
        if block_w is None:
            # one grid cell per DEFAULT_BLOCK_W walkers when it divides the
            # pool; otherwise a single block (small / odd walker counts)
            block_w = _DEFAULT_BLOCK_W if w % _DEFAULT_BLOCK_W == 0 else w
        return _fused_kernel(
            curr, query, feat, slot, rbits,
            p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
            p2b_feat_bounds, b2p_feat_bounds,
            n_pins=n_pins, n_slots=n_slots,
            alpha_u32=alpha_u32, beta_u32=beta_u32,
            count_boards=count_boards, block_w=block_w,
            gather_mode=gather_mode,
        )
    return ref.walk_chunk_ref(
        curr, query, feat, slot, rbits,
        p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
        p2b_feat_bounds, b2p_feat_bounds,
        n_pins=n_pins, n_slots=n_slots, n_boards=n_boards,
        alpha_u32=alpha_u32, beta_u32=beta_u32,
        count_boards=count_boards, unroll=unroll,
    )


def walk_chunk_fused_batched(
    curr: Array,
    query: Array,
    feat: Array,
    slot: Array,
    qid: Array,
    rbits: Array,
    p2b_offsets: Array,
    p2b_targets: Array,
    b2p_offsets: Array,
    b2p_targets: Array,
    p2b_feat_bounds: Optional[Array] = None,
    b2p_feat_bounds: Optional[Array] = None,
    *,
    n_pins: int,
    n_slots: int,
    n_queries: int,
    n_boards: int,
    alpha_u32: int,
    beta_u32: int,
    count_boards: bool = False,
    unroll: bool = False,
    block_w: Optional[int] = None,
    gather_mode: str = "scalar",
    use_kernel: Optional[bool] = None,
) -> Tuple[Array, Array, Array, Array, Optional[Array]]:
    """Batch-native chunk: a whole serving batch's walkers in ONE call.

    Identical contract to :func:`walk_chunk_fused` except the walker axis
    packs every query's pool back to back (``qid`` says which query each
    walker serves) and the return grows the query event lane:
    ``(next, query_events, slot_events, pin_events, board_events | None)``
    — the wide (query, slot, pin) int32 triple, query lane sentinel
    ``n_queries`` sharing the slot lane's validity.  The kernel path is
    ONE ``pallas_call`` per chunk for the whole batch (vs a batch-sized
    leading grid dim when the per-query op is vmapped); the oracle path is
    ``ref.walk_chunk_batched_ref`` — the same single-copy walk arithmetic
    as the per-query oracle, so parity is structural.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        w = curr.shape[0]
        if block_w is None:
            block_w = _DEFAULT_BLOCK_W if w % _DEFAULT_BLOCK_W == 0 else w
        return _fused_kernel(
            curr, query, feat, slot, rbits,
            p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
            p2b_feat_bounds, b2p_feat_bounds, qid,
            n_pins=n_pins, n_slots=n_slots,
            n_queries=n_queries,
            alpha_u32=alpha_u32, beta_u32=beta_u32,
            count_boards=count_boards, block_w=block_w,
            gather_mode=gather_mode,
        )
    return ref.walk_chunk_batched_ref(
        curr, query, feat, slot, qid, rbits,
        p2b_offsets, p2b_targets, b2p_offsets, b2p_targets,
        p2b_feat_bounds, b2p_feat_bounds,
        n_pins=n_pins, n_slots=n_slots, n_queries=n_queries,
        n_boards=n_boards,
        alpha_u32=alpha_u32, beta_u32=beta_u32,
        count_boards=count_boards, unroll=unroll,
    )


def walk_hop(
    pos: Array,
    gate: Array,
    r: Array,
    offsets: Array,
    targets: Array,
    row_base: Array,
    *,
    use_kernel: Optional[bool] = None,
    block_l: Optional[int] = None,
    gather_mode: str = "scalar",
) -> Tuple[Array, Array]:
    """ONE walk hop on a shard-local CSR slice -> (tgt, ok).

    The half-step twin of :func:`walk_chunk_fused` used by the sharded
    superstep: walkers hop once (pin->board or board->pin) on a node-range
    CSR slice whose first owned row is ``row_base``, then migrate over the
    routing fabric before the next hop.  The kernel path is ONE
    ``pallas_call`` for the whole routed walker buffer (per shard, not per
    query); the oracle path (``ref.walk_hop_ref``) is the same arithmetic
    as XLA gathers, bit-identical per the usual twin contract.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if not use_kernel:
        return ref.walk_hop_ref(pos, gate, r, offsets, targets, row_base)
    l = pos.shape[0]
    if block_l is None:
        block_l = _DEFAULT_BLOCK_W if l % _DEFAULT_BLOCK_W == 0 else l
    return _hop_kernel(
        pos, gate, r, row_base, offsets, targets,
        block_l=block_l, gather_mode=gather_mode,
    )


def embedding_bag(
    table: Array,
    ids: Array,
    weights: Optional[Array] = None,
    *,
    mode: str = "sum",
    use_kernel: Optional[bool] = None,
) -> Array:
    """Pooled (sum/mean) embedding lookup -> (b, d)."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _bag_kernel(table, ids, weights, mode=mode)
    return ref.embedding_bag_ref(table, ids, weights, mode=mode)


def embedding_bag_batched(
    table: Array,
    ids: Array,
    weights: Optional[Array] = None,
    *,
    mode: str = "sum",
    use_kernel: Optional[bool] = None,
) -> Array:
    """Query-batched pooled embedding lookup: (b, k, l) bags -> (b, k, d).

    The two-stage serving path's bag op.  `use_kernel` keeps the module's
    platform default (kernel on TPU, oracle on CPU) and — deliberately —
    is NOT derived from the walk backend by the serving path: stage 2's
    float math runs as ONE shared program under both ``backend="xla"`` and
    ``backend="pallas"``, so `two_stage_backends_agree` is exact by
    construction (the same design that keeps walk scores exact: shared
    float boost over bit-identical integer counts).  Kernel-vs-oracle
    parity is pinned separately at tight tolerance (matched accumulation
    order; only compiler FMA contraction may differ in the last ulp).
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _bag_batched_kernel(table, ids, weights, mode=mode)
    return ref.embedding_bag_batched_ref(table, ids, weights, mode=mode)


def decode_attention(
    q: Array,
    k: Array,
    v: Array,
    lengths: Array,
    *,
    use_kernel: Optional[bool] = None,
) -> Array:
    """Single-token GQA decode attention -> (b, h, dh) f32."""
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if use_kernel:
        return _decode_kernel(q, k, v, lengths)
    return ref.decode_attention_ref(q, k, v, lengths)
