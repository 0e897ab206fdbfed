"""Pallas TPU kernels for the Pixie walk inner loop.

Two generations of kernel live here:

* ``walk_step``       — the original one-superstep-per-``pallas_call`` kernel
                        (kept as the minimal reference kernel; one launch per
                        walk step).
* ``walk_steps_fused``— the serving-path engine: ONE ``pallas_call`` executes
                        ``chunk_steps`` supersteps.  Walker state (``curr``,
                        per-walker restart pin, per-walker personalization
                        feature, query-slot id) is loaded into SMEM once and
                        stays resident across every step of the chunk; only
                        the unavoidable CSR gathers touch HBM.  Each step the
                        kernel also *emits* wide (slot, pin) visit events —
                        two int32 lanes per event, slot lane sentinel
                        ``n_slots`` for invalid / dead-end steps — straight
                        into bounded ``(chunk_steps, w)`` event buffers, so
                        the host-side walk loop never scatter-adds: events
                        are aggregated afterwards by the tile-scan
                        ``visit_counter`` kernels.  Wide lanes mean the
                        packed id space ``n_slots * n_pins`` may exceed
                        2**31 (the paper's 3B-pin regime): no lane ever
                        holds the packed product, so there is no int32
                        cliff and no xla fallback.

The paper's inner loop (Algorithm 2 lines 6-13) is three dependent random
memory accesses per step: offsets[pin] -> targets[...] (board), then
offsets[board] -> targets[...] (pin).  On TPU the CSR arrays live in HBM
(memory_space=ANY — gigabytes, never blockable into VMEM); the fused kernel
keeps everything *else* out of HBM: the chunk's random bits, the walker
state and the event blocks sit in SMEM, where the scalar unit reads and
writes them at walker granularity, and only the per-walker two-level CSR
reads touch HBM (they are data-dependent random access — there is no
vector shape for them).  The paper's "walk never leaves the machine"
becomes "walker state never leaves on-chip memory between supersteps; one
kernel launch per *chunk*, not per step".

A TPU kernel cannot load from an HBM ref, and a loaded vector cannot be
indexed by a loop counter, so every CSR read is a DMA: the arrays are
viewed as ``(rows, 128)`` int32 and the row holding the wanted element is
copied into an SMEM row slot, from which the scalar unit picks the
element.  A superstep is four gather *phases* — hop-1 offset rows (plus
the bias-bound rows), hop-1 target, hop-2 offset (plus bias bounds), hop-2
target — each ending in the scalar decision arithmetic that yields the
next phase's addresses.  ``gather_mode`` only orders the copies:

* ``"scalar"`` — walker by walker: each phase's row copies (two to four)
  are started and waited on at once, so every phase of every walker
  exposes one full HBM round trip.
* ``"dma"``    — phase by phase over the block, double-buffered: walker
  *i+1*'s copies are started before walker *i*'s are waited on, so one
  HBM latency hides behind the neighbouring walker's.

The same code runs under interpret mode on CPU hosts (the interpreter
executes the copies synchronously), so CI exercises both modes
bit-for-bit.  They do identical integer arithmetic on identical random
bits and are bit-for-bit interchangeable with each other and with the XLA
reference (tests/test_dma_gather.py); the mode is purely a memory-latency
knob for real TPU hosts.

Random bits are generated *outside* (counter-based threefry, one uint32
quadruple per walker-step) so the kernel is a pure function and byte-for-byte
reproducible across restarts — the fault-tolerance contract of the runtime.
The XLA reference backend (`kernels/ref.walk_chunk_ref`) consumes the *same*
bits with the same arithmetic, which is what makes the two backends
bit-for-bit comparable (tests/test_walk_backends.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_W = 256  # walkers per grid cell

GATHER_MODES = ("scalar", "dma")

_RMASK = 0x7FFFFFFF  # keep modulo operands non-negative int32


def _walk_step_kernel(
    # scalar-ish VMEM blocks
    curr_ref, query_ref, rbits_ref,
    # full CSR arrays, left in HBM/ANY
    p2b_off_ref, p2b_tgt_ref, b2p_off_ref, b2p_tgt_ref,
    # outputs
    next_ref, visited_ref, valid_ref,
    *,
    n_pins: int,
    alpha_u32: int,
    block_w: int,
):
    curr = curr_ref[...]
    query = query_ref[...]
    restart = rbits_ref[:, 0] < jnp.uint32(alpha_u32)
    pos = jnp.where(restart, query, curr)
    # mask BEFORE the int32 cast: a high-bit draw would otherwise become a
    # negative modulo operand whose result depends on the lowering (same
    # contract as the fused kernel; pinned in tests/test_dma_gather.py)
    r_board = (rbits_ref[:, 1] & jnp.uint32(_RMASK)).astype(jnp.int32)
    r_pin = (rbits_ref[:, 2] & jnp.uint32(_RMASK)).astype(jnp.int32)

    def body(i, carry):
        nxt, vis, ok_acc = carry
        p = pos[i]
        # hop 1: pin -> board
        start = p2b_off_ref[pl.ds(p, 1)][0]
        end = p2b_off_ref[pl.ds(p + 1, 1)][0]
        deg = end - start
        eidx = start + r_board[i] % jnp.maximum(deg, 1)
        board = p2b_tgt_ref[pl.ds(eidx, 1)][0]
        board_ok = deg > 0
        b_local = jnp.where(board_ok, board - n_pins, 0)
        # hop 2: board -> pin
        bstart = b2p_off_ref[pl.ds(b_local, 1)][0]
        bend = b2p_off_ref[pl.ds(b_local + 1, 1)][0]
        bdeg = bend - bstart
        bidx = bstart + r_pin[i] % jnp.maximum(bdeg, 1)
        pin = b2p_tgt_ref[pl.ds(bidx, 1)][0]
        ok = board_ok & (bdeg > 0)
        nxt = nxt.at[i].set(jnp.where(ok, pin, query[i]))
        vis = vis.at[i].set(jnp.where(ok, pin, 0))
        ok_acc = ok_acc.at[i].set(ok)
        return nxt, vis, ok_acc

    init = (
        jnp.zeros((block_w,), jnp.int32),
        jnp.zeros((block_w,), jnp.int32),
        jnp.zeros((block_w,), jnp.bool_),
    )
    nxt, vis, ok = jax.lax.fori_loop(0, block_w, body, init)
    next_ref[...] = nxt
    visited_ref[...] = vis
    valid_ref[...] = ok


@functools.partial(
    jax.jit, static_argnames=("n_pins", "alpha_u32", "block_w", "interpret")
)
def walk_step(
    curr: jax.Array,         # (w,) int32
    query: jax.Array,        # (w,) int32
    rbits: jax.Array,        # (w, 3) uint32
    p2b_offsets: jax.Array,  # (n_pins + 1,) int32
    p2b_targets: jax.Array,  # (e,) int32
    b2p_offsets: jax.Array,  # (n_boards + 1,) int32
    b2p_targets: jax.Array,  # (e,) int32
    *,
    n_pins: int,
    alpha_u32: int,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool | None = None,
):
    """One superstep for all walkers. Returns (next, visited, valid)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    w = curr.shape[0]
    if w % block_w != 0:
        raise ValueError(f"n_walkers {w} must be a multiple of {block_w}")
    grid = (w // block_w,)
    blk = lambda i: (i,)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_sds = jax.ShapeDtypeStruct((w,), jnp.int32)
    return pl.pallas_call(
        functools.partial(
            _walk_step_kernel,
            n_pins=n_pins,
            alpha_u32=alpha_u32,
            block_w=block_w,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_w,), blk),
            pl.BlockSpec((block_w,), blk),
            pl.BlockSpec((block_w, 3), lambda i: (i, 0)),
            any_spec, any_spec, any_spec, any_spec,
        ],
        out_specs=[
            pl.BlockSpec((block_w,), blk),
            pl.BlockSpec((block_w,), blk),
            pl.BlockSpec((block_w,), blk),
        ],
        out_shape=[out_sds, out_sds, jax.ShapeDtypeStruct((w,), jnp.bool_)],
        interpret=interpret,
    )(
        curr.astype(jnp.int32),
        query.astype(jnp.int32),
        rbits.astype(jnp.uint32),
        p2b_offsets.astype(jnp.int32),
        p2b_targets.astype(jnp.int32),
        b2p_offsets.astype(jnp.int32),
        b2p_targets.astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Fused multi-superstep kernel — the serving hot path
# ---------------------------------------------------------------------------

_LANES = 128       # int32 elements per DMA row of an (rows, 128) HBM view
_LANE_BITS = 7     # log2(_LANES)
_READS = 4         # row copies one gather phase issues per walker, at most
_SMEM_BUDGET = 256 * 1024  # int32 words of SMEM a walk kernel may claim


def _pick_edge(start, deg, r, use_b, fb, gate):
    """Sampled CSR edge index for one hop: uniform over [start, start+deg),
    or the personalized feat subrange when the bias draw fires and the
    subrange is non-empty; 0 where ``gate`` is off.  Both gather modes and
    the hop kernel call this ONE copy of the decision arithmetic the
    bit-identity contract rests on.  ``fb`` is a (lo, hi) bound pair, or
    None when biasing is off.
    """
    base, span = start, jnp.maximum(deg, 1)
    if fb is not None:
        lo, hi = fb
        sub_ok = use_b & (hi > lo)
        base = jnp.where(sub_ok, start + lo, base)
        span = jnp.where(sub_ok, hi - lo, span)
    return jnp.where(gate, base + r % span, 0)


def _as_rows(x: jax.Array) -> jax.Array:
    """View a CSR array as ``(rows, 128)`` int32 for row-granular DMA.

    The TPU DMA engine addresses a 1-D int32 array in 1024-element tiles,
    too coarse for one walker's element read; a ``(1, 128)`` row of the
    2-D view is the narrowest window it fetches.  The reshape is free when
    the length is a lane multiple (both layouts hold the same bytes);
    otherwise the array is padded, which copies it.
    """
    flat = x.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    pad = -n % _LANES if n else _LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES)


class _RowReader:
    """Element reads from an ``(rows, 128)`` HBM view via SMEM row slots.

    ``copy(e, k)`` is the DMA of the row holding element ``e`` into slot
    ``k``; the row is clamped into bounds, so a stray address can never
    fault the DMA engine.  ``value(e, k)`` reads the element once landed.
    """

    def __init__(self, ref, buf, sem):
        self.ref, self.buf, self.sem = ref, buf, sem

    def copy(self, e, k):
        row = jnp.clip(e >> _LANE_BITS, 0, self.ref.shape[0] - 1)
        return pltpu.make_async_copy(
            self.ref.at[pl.ds(row, 1)], self.buf.at[k], self.sem.at[k]
        )

    def value(self, e, k):
        return self.buf[k, 0, e & (_LANES - 1)]


def _run_phases(phases, n: int, gather_mode: str, buf, sem) -> None:
    """Run per-walker gather phases over a block of ``n`` walkers.

    A phase is ``(reads, finish)``: ``reads(i)`` lists the ``(reader,
    element)`` pairs walker ``i`` needs from HBM (at most ``_READS``),
    ``finish(i, values)`` consumes them with scalar arithmetic and leaves
    its results in SMEM for the next phase.

    * ``"scalar"`` — walker by walker, all phases of walker i before walker
      i+1: each phase's row copies are started and waited on at once, one
      exposed HBM round trip per phase per walker.
    * ``"dma"``    — phase by phase over the whole block: walker i+1's
      copies are started before walker i's are waited on (two slots of row
      buffers), so one HBM latency hides behind the neighbour's.

    Both run the same ``finish`` code on the same values.
    """

    def start(i, slot, reads):
        for j, (rd, e) in enumerate(reads(i)):
            rd.copy(e, slot * _READS + j).start()

    def land(i, slot, reads, finish):
        vals = []
        for j, (rd, e) in enumerate(reads(i)):
            k = slot * _READS + j
            rd.copy(e, k).wait()
            vals.append(rd.value(e, k))
        finish(i, vals)

    if gather_mode == "scalar":

        def walker(i, carry):
            for reads, finish in phases:
                start(i, 0, reads)
                land(i, 0, reads, finish)
            return carry

        jax.lax.fori_loop(0, n, walker, 0)
        return

    for reads, finish in phases:
        start(0, 0, reads)

        def body(i, carry, reads=reads, finish=finish):
            @pl.when(i + 1 < n)
            def _prefetch():
                start(i + 1, (i + 1) % 2, reads)

            land(i, i % 2, reads, finish)
            return carry

        jax.lax.fori_loop(0, n, body, 0)


def _gather_scratch(n: int, n_state: int):
    """SMEM row-buffer slots, their DMA semaphores, and ``n_state``
    per-walker int32 SMEM vectors carrying results between phases."""
    return [
        pltpu.SMEM((2 * _READS, 1, _LANES), jnp.int32),
        pltpu.SemaphoreType.DMA((2 * _READS,)),
    ] + [pltpu.SMEM((n,), jnp.int32) for _ in range(n_state)]


def _smem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.SMEM)


def _walk_steps_fused_kernel(
    *refs,
    n_pins: int,
    n_slots: int,
    n_queries: int,
    alpha_u32: int,
    beta_u32: int,
    chunk_steps: int,
    block_w: int,
    fb_width: int,
    use_bias: bool,
    count_boards: bool,
    gather_mode: str,
):
    """chunk_steps supersteps for one walker block, state resident in SMEM.

    Ref layout (inputs, outputs, scratch; qid / query_events present only
    when ``n_queries > 0``, bias bounds only if use_bias):
      curr, query, feat, slot, [qid], rbits,
      p2b_off, p2b_tgt, b2p_off, b2p_tgt, [p2b_fb, b2p_fb],
      -> next, [query_events], slot_events, pin_events, [board_events],
      row buffers, semaphores, cur, pos, eidx, board_ok, b_local, bidx, ok

    Walker state, the chunk's random bits (``(chunk_steps, block_w * 4)``,
    walker-major quadruples) and the event blocks live in SMEM, where the
    scalar unit reads and writes them at walker granularity; the CSR
    arrays are ``(rows, 128)`` HBM views read only by row DMA.  A step is
    four gather phases — hop-1 offset (+ bias-bound) rows, hop-1 target,
    hop-2 offset (+ bias-bound) rows, hop-2 target — each finishing with
    the decision arithmetic that produces the next phase's addresses.

    ``n_queries > 0`` is the batch-native mode: the walker block carries a
    per-walker query id and each step additionally emits a query event
    lane — sentinel ``n_queries`` for invalid steps, sharing the slot
    lane's validity mask exactly like the board lane does.  This is what
    lets ONE ``pallas_call`` execute a chunk for a whole serving batch.
    """
    with_query = n_queries > 0
    refs = list(refs)

    def take(k):
        out, refs[:k] = refs[:k], []
        return out

    curr_ref, query_ref, feat_ref, slot_ref = take(4)
    (qid_ref,) = take(1) if with_query else (None,)
    (rbits_ref,) = take(1)
    p2b_off, p2b_tgt, b2p_off, b2p_tgt = take(4)
    p2b_fb, b2p_fb = take(2) if use_bias else (None, None)
    (next_ref,) = take(1)
    (qev_ref,) = take(1) if with_query else (None,)
    sev_ref, pev_ref = take(2)
    (bev_ref,) = take(1) if count_boards else (None,)
    buf, sem, cur, pos, eidx, bok, bloc, bidx, okv = take(9)

    def reader(ref):
        return None if ref is None else _RowReader(ref, buf, sem)

    p2b_off_rd, p2b_tgt_rd = reader(p2b_off), reader(p2b_tgt)
    b2p_off_rd, b2p_tgt_rd = reader(b2p_off), reader(b2p_tgt)
    p2b_fb_rd, b2p_fb_rd = reader(p2b_fb), reader(b2p_fb)

    def load_state(i, carry):
        cur[i] = curr_ref[0, i]
        return carry

    jax.lax.fori_loop(0, block_w, load_state, 0)

    def step(s, carry):
        def draw(i, k):
            return rbits_ref[s, i * 4 + k]

        def pick_bits(i, k):
            return (draw(i, k) & jnp.uint32(_RMASK)).astype(jnp.int32)

        def use_b(i):
            return draw(i, 1) < jnp.uint32(beta_u32)

        def place(i, c):
            restart = draw(i, 0) < jnp.uint32(alpha_u32)
            pos[i] = jnp.where(restart, query_ref[0, i], cur[i])
            return c

        jax.lax.fori_loop(0, block_w, place, 0)

        def row_reads(off_rd, fb_rd, node, i):
            reads = [(off_rd, node), (off_rd, node + 1)]
            if use_bias:
                f = node * fb_width + feat_ref[0, i]
                reads += [(fb_rd, f), (fb_rd, f + 1)]
            return reads

        def bounds(v):
            return (v[2], v[3]) if use_bias else None

        def hop1_rows(i):
            return row_reads(p2b_off_rd, p2b_fb_rd, pos[i], i)

        def hop1_pick(i, v):
            start, deg = v[0], v[1] - v[0]
            board_ok = deg > 0
            eidx[i] = _pick_edge(
                start, deg, pick_bits(i, 2), use_b(i), bounds(v), board_ok
            )
            bok[i] = board_ok.astype(jnp.int32)

        def hop1_target(i):
            return [(p2b_tgt_rd, eidx[i])]

        def hop1_land(i, v):
            bloc[i] = jnp.where(bok[i] != 0, v[0] - n_pins, 0)

        def hop2_rows(i):
            return row_reads(b2p_off_rd, b2p_fb_rd, bloc[i], i)

        def hop2_pick(i, v):
            bstart, bdeg = v[0], v[1] - v[0]
            ok = (bok[i] != 0) & (bdeg > 0)
            bidx[i] = _pick_edge(
                bstart, bdeg, pick_bits(i, 3), use_b(i), bounds(v), ok
            )
            okv[i] = ok.astype(jnp.int32)

        def hop2_target(i):
            return [(b2p_tgt_rd, bidx[i])]

        def hop2_land(i, v):
            pin, ok = v[0], okv[i] != 0
            cur[i] = jnp.where(ok, pin, query_ref[0, i])
            # wide lane emission: pin, board and query lanes share the
            # slot lane's validity mask
            sev_ref[s, i] = jnp.where(ok, slot_ref[0, i], n_slots)
            pev_ref[s, i] = jnp.where(ok, pin, 0)
            if with_query:
                qev_ref[s, i] = jnp.where(ok, qid_ref[0, i], n_queries)
            if count_boards:
                bev_ref[s, i] = jnp.where(ok, bloc[i], 0)

        _run_phases(
            [(hop1_rows, hop1_pick), (hop1_target, hop1_land),
             (hop2_rows, hop2_pick), (hop2_target, hop2_land)],
            block_w, gather_mode, buf, sem,
        )
        return carry

    jax.lax.fori_loop(0, chunk_steps, step, 0)

    def store_state(i, carry):
        next_ref[0, i] = cur[i]
        return carry

    jax.lax.fori_loop(0, block_w, store_state, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_pins", "n_slots", "n_queries", "alpha_u32", "beta_u32",
        "count_boards", "block_w", "gather_mode", "interpret",
    ),
)
def walk_steps_fused(
    curr: jax.Array,          # (w,) int32 current pin per walker
    query: jax.Array,         # (w,) int32 restart pin per walker
    feat: jax.Array,          # (w,) int32 personalization feature per walker
    slot: jax.Array,          # (w,) int32 query-slot id per walker
    rbits: jax.Array,         # (chunk_steps, w, 4) uint32
    p2b_offsets: jax.Array,   # (n_pins + 1,)
    p2b_targets: jax.Array,   # (e,)
    b2p_offsets: jax.Array,   # (n_boards + 1,)
    b2p_targets: jax.Array,   # (e,)
    p2b_feat_bounds: Optional[jax.Array] = None,  # (n_pins, n_feats + 1)
    b2p_feat_bounds: Optional[jax.Array] = None,  # (n_boards, n_feats + 1)
    qid: Optional[jax.Array] = None,  # (w,) int32 query id per walker
    *,
    n_pins: int,
    n_slots: int,
    n_queries: int = 0,
    alpha_u32: int,
    beta_u32: int,
    count_boards: bool = False,
    block_w: int = DEFAULT_BLOCK_W,
    gather_mode: str = "scalar",
    interpret: bool | None = None,
):
    """``chunk_steps`` fused walk supersteps in ONE ``pallas_call``.

    rbits columns: 0 = restart draw (< alpha_u32 restarts), 1 = bias draw
    (< beta_u32 uses the personalized subrange), 2 = board pick, 3 = pin
    pick.  Returns ``(next_curr (w,), slot_events (chunk_steps, w),
    pin_events (chunk_steps, w))`` plus ``board_events (chunk_steps, w)``
    when ``count_boards``.  Events are WIDE: the slot lane holds the query
    slot (sentinel ``n_slots`` for invalid / dead-end steps, value lanes 0)
    and the pin/board lanes hold the visited id — no lane ever carries the
    packed ``slot * n_pins + pin`` product, so id spaces past 2**31 (the
    production 3B-pin regime) run on this kernel with plain int32 lanes.
    The board lane shares the slot lane (identical validity mask).
    Aggregate with the tile-scan ``visit_counter`` kernels — no scatters
    anywhere on the hot path.

    The kernel reads the feat bounds, like the CSR arrays, as flat
    row-major int32 in ``(rows, 128)`` DMA rows.

    BATCH-NATIVE MODE: pass ``qid`` (per-walker query id) and
    ``n_queries > 0`` to run a whole serving batch's walkers in this one
    call.  The walker axis then packs all queries' pools back to back and
    the return grows a query event lane: ``(next_curr, query_events,
    slot_events, pin_events, board_events | None)`` — query lane sentinel
    ``n_queries``, sharing the slot lane's validity mask.  ONE
    ``pallas_call`` per chunk with ``n_queries * w`` walker rows.

    ``gather_mode`` picks how the CSR row copies are issued (module
    docstring); the modes are bit-identical to each other and to the XLA
    reference, and interpret-safe on CPU hosts.
    """
    if gather_mode not in GATHER_MODES:
        raise ValueError(
            f"unknown gather_mode {gather_mode!r}; use {GATHER_MODES}"
        )
    with_query = qid is not None
    if with_query and n_queries <= 0:
        raise ValueError("qid given but n_queries not set (> 0 required)")
    if not with_query:
        n_queries = 0  # one kernel variant per (qid, n_queries) pairing
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    chunk_steps, w = rbits.shape[0], rbits.shape[1]
    if w % block_w != 0:
        raise ValueError(f"n_walkers {w} must be a multiple of {block_w}")
    use_bias = (
        p2b_feat_bounds is not None
        and b2p_feat_bounds is not None
        and beta_u32 > 0
    )
    n_lanes = 2 + int(with_query) + int(count_boards)
    # pipelined SMEM blocks are double-buffered
    smem_words = 2 * block_w * (
        5 + 4 * chunk_steps + n_lanes * chunk_steps
    ) + 7 * block_w + 2 * _READS * _LANES
    if not interpret and smem_words > _SMEM_BUDGET:
        raise ValueError(
            f"walk block of {block_w} walkers x {chunk_steps} steps needs "
            f"{smem_words} SMEM words (> {_SMEM_BUDGET}); lower "
            "pallas_block_w or chunk_steps"
        )

    # per-walker vectors ride as (1, w) rows: a 1-D SMEM operand would
    # have to be blocked in 1024-element tiles
    walker = _smem_spec((1, block_w), lambda i: (0, i))
    in_specs = [walker] * 4                          # curr, query, feat, slot
    args = [curr, query, feat, slot]
    if with_query:
        in_specs.append(walker)
        args.append(qid)
    args = [a.astype(jnp.int32).reshape(1, w) for a in args]
    in_specs.append(_smem_spec((chunk_steps, block_w * 4), lambda i: (0, i)))
    args.append(rbits.astype(jnp.uint32).reshape(chunk_steps, w * 4))
    csr = [p2b_offsets, p2b_targets, b2p_offsets, b2p_targets]
    if use_bias:
        csr += [p2b_feat_bounds, b2p_feat_bounds]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(csr)
    args += [_as_rows(a) for a in csr]

    ev_spec = _smem_spec((chunk_steps, block_w), lambda i: (0, i))
    ev_sds = jax.ShapeDtypeStruct((chunk_steps, w), jnp.int32)
    out_specs = [walker] + [ev_spec] * n_lanes
    out_shape = [jax.ShapeDtypeStruct((1, w), jnp.int32)] + [ev_sds] * n_lanes

    out = pl.pallas_call(
        functools.partial(
            _walk_steps_fused_kernel,
            n_pins=n_pins,
            n_slots=n_slots,
            n_queries=n_queries,
            alpha_u32=alpha_u32,
            beta_u32=beta_u32,
            chunk_steps=chunk_steps,
            block_w=block_w,
            fb_width=p2b_feat_bounds.shape[1] if use_bias else 0,
            use_bias=use_bias,
            count_boards=count_boards,
            gather_mode=gather_mode,
        ),
        grid=(w // block_w,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_gather_scratch(block_w, 7),
        interpret=interpret,
    )(*args)
    out = list(out)
    nxt = out.pop(0).reshape(w)
    qev = out.pop(0) if with_query else None
    sev, pev = out.pop(0), out.pop(0)
    bev = out.pop(0) if count_boards else None
    if with_query:
        return nxt, qev, sev, pev, bev
    return nxt, sev, pev, bev


# ---------------------------------------------------------------------------
# Hop-phase fused kernel — walk_steps_fused split at the hop boundary
# ---------------------------------------------------------------------------


def _walk_hop_kernel(
    pos_ref, gate_ref, r_ref, base_ref,
    off_ref, tgt_ref,            # shard-local CSR slice, (rows, 128) HBM
    out_ref, ok_ref,
    buf, sem, eidx, okv,
    *,
    block_l: int,
    gather_mode: str,
):
    """One CSR hop for a block of routed walkers.

    ``walk_steps_fused`` runs both hops of a step back to back because the
    replicated graph owns every row; the sharded engine must ``_route``
    walkers between hops, so this kernel is the fused kernel's per-hop
    half: the same ``_RMASK`` decode, the same ``_pick_edge`` arithmetic,
    the same gather phases — over a shard-local CSR slice whose rows are
    rebased by the traced ``row_base`` scalar (the shard-local subrange
    offset, ``shard_id * rows_per_shard``).
    """
    off_rd = _RowReader(off_ref, buf, sem)
    tgt_rd = _RowReader(tgt_ref, buf, sem)

    def offset_rows(i):
        # non-gated walkers read row 0: their position may be a global id
        # another shard owns (or a sentinel) — the result is masked anyway
        local = jnp.where(gate_ref[0, i] != 0, pos_ref[0, i] - base_ref[0], 0)
        return [(off_rd, local), (off_rd, local + 1)]

    def pick(i, v):
        start, deg = v[0], v[1] - v[0]
        ok = (gate_ref[0, i] != 0) & (deg > 0)
        r = (r_ref[0, i] & jnp.uint32(_RMASK)).astype(jnp.int32)
        eidx[i] = _pick_edge(start, deg, r, False, None, ok)
        okv[i] = ok.astype(jnp.int32)

    def target(i):
        return [(tgt_rd, eidx[i])]

    def land(i, v):
        out_ref[0, i] = jnp.where(okv[i] != 0, v[0], 0)
        ok_ref[0, i] = okv[i]

    _run_phases(
        [(offset_rows, pick), (target, land)], block_l, gather_mode, buf, sem
    )


@functools.partial(
    jax.jit, static_argnames=("block_l", "gather_mode", "interpret")
)
def walk_hop_fused(
    pos: jax.Array,       # (l,) int32 global node ids
    gate: jax.Array,      # (l,) bool — walkers allowed to hop
    r: jax.Array,         # (l,) uint32 raw bits for the edge pick
    row_base: jax.Array,  # (1,) int32 traced shard-local subrange offset
    offsets: jax.Array,   # (rows + 1,) shard-local CSR offsets
    targets: jax.Array,   # (edges,) shard-local CSR targets
    *,
    block_l: int = DEFAULT_BLOCK_W,
    gather_mode: str = "scalar",
    interpret: bool | None = None,
):
    """ONE walk hop in one ``pallas_call`` (the sharded superstep phase).

    Returns ``(tgt (l,) int32, ok (l,) bool)`` — the sampled neighbour
    where ``ok`` (= ``gate`` and the row has edges), 0 elsewhere —
    bit-identical to ``kernels/ref.walk_hop_ref`` and to the matching
    half of ``walk_steps_fused``'s superstep.  ``row_base`` is a traced
    (1,) array, NOT a static int: every shard of a ``shard_map`` runs the
    same program with its own ``axis_index``-derived base, so baking it
    in would force one kernel variant per shard.
    """
    if gather_mode not in GATHER_MODES:
        raise ValueError(
            f"unknown gather_mode {gather_mode!r}; use {GATHER_MODES}"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    l = pos.shape[0]
    if l % block_l != 0:
        raise ValueError(f"walker count {l} must be a multiple of {block_l}")
    walker = _smem_spec((1, block_l), lambda i: (0, i))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    tgt, ok = pl.pallas_call(
        functools.partial(
            _walk_hop_kernel, block_l=block_l, gather_mode=gather_mode
        ),
        grid=(l // block_l,),
        in_specs=[
            walker, walker, walker,                  # pos, gate, r
            pl.BlockSpec(memory_space=pltpu.SMEM),   # row_base
            any_spec, any_spec,                      # CSR slice
        ],
        out_specs=[walker, walker],
        out_shape=[jax.ShapeDtypeStruct((1, l), jnp.int32)] * 2,
        scratch_shapes=_gather_scratch(block_l, 2),
        interpret=interpret,
    )(
        pos.astype(jnp.int32).reshape(1, l),
        gate.astype(jnp.int32).reshape(1, l),
        r.astype(jnp.uint32).reshape(1, l),
        jnp.asarray(row_base, jnp.int32).reshape((1,)),
        _as_rows(offsets),
        _as_rows(targets),
    )
    return tgt.reshape(l), ok.reshape(l) != 0
