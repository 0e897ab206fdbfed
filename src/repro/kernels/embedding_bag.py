"""Pallas TPU kernel: EmbeddingBag (gather + pool) for the recsys substrate.

JAX has no native EmbeddingBag; the oracle is `take + segment-style pooling`
(ref.py).  The kernel tiles the *batch* of bags across the grid, keeps
each block's ids and weights in SMEM, leaves the embedding table in HBM
(memory_space=ANY — recsys tables are 10^6..10^9 rows and never fit VMEM),
and DMAs each id's row into VMEM, pooling with the feature dimension
vectorized across lanes.  This is the v5e analogue of the SparseCore
lookup: each id costs one HBM row copy of d*4 bytes, pooling is free
(accumulated in VREGs).

Fixed bag size with -1 padding keeps every shape static (SPMD-friendly);
multi-hot recsys features and DLRM single-hot lookups (bag size 1) are both
instances.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 64  # bags per grid cell
_LANES = 128


def _embedding_bag_kernel(
    ids_ref, weights_ref, table_ref, out_ref, rows, sem,
    *, block_b: int, bag: int, mean: bool,
):
    """Pool ``block_b`` bags; ids and weights are SMEM scalars, table rows
    arrive by DMA from HBM into VMEM row slots.

    Bag b+1's row copies are started before bag b's are waited on (two
    slot sets of ``bag`` rows), so one bag's HBM latency hides behind the
    previous bag's pooling.  Pooling runs in ascending element order.
    """
    d = out_ref.shape[-1]

    def row_id(b, l):
        return jnp.maximum(ids_ref[b, l], 0)

    def copy(b, l, slot):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(row_id(b, l), 1)],
            rows.at[slot * bag + l],
            sem.at[slot],
        )

    def start(b, slot):
        for l in range(bag):
            copy(b, l, slot).start()

    start(0, 0)

    def bag_body(b, carry):
        slot = b % 2

        @pl.when(b + 1 < block_b)
        def _prefetch():
            start(b + 1, 1 - slot)

        # the bag's copies share one semaphore, so a single wait does not
        # say which row landed: wait for all of them before reading any
        for l in range(bag):
            copy(b, l, slot).wait()
        acc = jnp.zeros((1, d), jnp.float32)
        wsum = jnp.zeros((1, d), jnp.float32)
        for l in range(bag):
            valid = (ids_ref[b, l] >= 0).astype(jnp.float32)
            w = jnp.full((1, d), weights_ref[b, l] * valid, jnp.float32)
            acc = acc + rows[slot * bag + l].astype(jnp.float32) * w
            wsum = wsum + w
        if mean:
            acc = acc / jnp.maximum(wsum, 1.0)
        out_ref[pl.ds(b, 1), :] = acc.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_b, bag_body, 0)


def _bag_pallas_call(
    ids2: jax.Array,      # (n, bag) int32, -1 padding
    weights2: jax.Array,  # (n, bag) f32
    table: jax.Array,     # (v, d)
    *,
    mode: str,
    block_b: int,
    interpret: bool,
) -> jax.Array:
    """Shared launch: tile flattened bags ``block_b`` rows per grid cell.

    ONE copy of the pad-and-launch plumbing for both the per-bag and the
    query-batched entry points, wrapping the ONE kernel body
    (`_embedding_bag_kernel`) — bit-parity between the two public shapes is
    structural, not re-proved.
    """
    n, bag = ids2.shape
    v, d = table.shape
    # a table row is DMA'd whole, and the DMA engine moves 128-lane rows:
    # pad the feature dim (zero lanes pool to zero and are sliced off)
    d_pad = -(-d // _LANES) * _LANES
    if d_pad != d:
        table = jnp.pad(table, ((0, 0), (0, d_pad - d)))
    n_pad = -(-n // block_b) * block_b
    if n_pad != n:
        ids2 = jnp.concatenate(
            [ids2, jnp.full((n_pad - n, bag), -1, ids2.dtype)]
        )
        weights2 = jnp.concatenate(
            [weights2, jnp.zeros((n_pad - n, bag), weights2.dtype)]
        )
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(
            _embedding_bag_kernel,
            block_b=block_b,
            bag=bag,
            mean=(mode == "mean"),
        ),
        grid=(n_pad // block_b,),
        in_specs=[
            smem((block_b, bag), lambda i: (i, 0)),
            smem((block_b, bag), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_b, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((2 * bag, 1, d_pad), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(ids2.astype(jnp.int32), weights2.astype(jnp.float32), table)
    return out[:n, :d]


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "interpret")
)
def embedding_bag_batched(
    table: jax.Array,                 # (v, d)
    ids: jax.Array,                   # (b, k, l) int32, -1 padding
    weights: Optional[jax.Array] = None,  # (b, k, l) f32
    *,
    mode: str = "sum",
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
) -> jax.Array:
    """Query-batched pooled lookup: (b, k, l) bags -> (b, k, d).

    The serving-path shape of :func:`embedding_bag`: a whole batch of
    queries' candidate neighborhoods pooled together.  Bags are flattened
    query-major onto the row axis and tiled ``block_b`` rows per grid cell
    over a rank-1 grid, so a batched two-stage serve step stays at ONE
    ``pallas_call`` per bag op regardless of batch size (the two-stage
    lowering pin in tests/test_two_stage.py counts on this) — batch only
    changes the number of grid cells, never the number of launches.

    Accumulation inside each bag runs in ascending element order (the
    kernel's inner fori_loop), the same chain order as
    ``ref.embedding_bag_batched_ref`` — the tightest parity two separately
    compiled float programs can promise: the compiler may still contract a
    mul+add into an FMA on one side and not the other, so kernel-vs-oracle
    is pinned at tight tolerance, not array_equal.  EXACT cross-backend
    serving parity (`two_stage_backends_agree`) comes from the layer above:
    both walk backends share ONE stage-2 bag lowering
    (ops.embedding_bag_batched's platform default), the same trick that
    keeps the walk's float scores exact (shared boost over integer counts).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if ids.ndim != 3:
        raise ValueError(
            f"embedding_bag_batched wants (batch, bags, bag_size) ids, got "
            f"shape {ids.shape}; for plain (bags, bag_size) use embedding_bag"
        )
    bq, k, bag = ids.shape
    n = bq * k
    ids2 = ids.reshape(n, bag)
    if weights is None:
        weights2 = jnp.ones((n, bag), jnp.float32)
    else:
        weights2 = weights.reshape(n, bag)
    out = _bag_pallas_call(
        ids2, weights2, table,
        mode=mode, block_b=block_b, interpret=interpret,
    )
    return out.reshape(bq, k, table.shape[1])


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "interpret")
)
def embedding_bag(
    table: jax.Array,                 # (v, d)
    ids: jax.Array,                   # (b, l) int32, -1 padding
    weights: Optional[jax.Array] = None,  # (b, l) f32
    *,
    mode: str = "sum",
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
) -> jax.Array:
    """Pooled embedding lookup -> (b, d), dtype = table dtype."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, bag = ids.shape
    if weights is None:
        weights = jnp.ones((b, bag), jnp.float32)
    return _bag_pallas_call(
        ids, weights, table,
        mode=mode, block_b=block_b, interpret=interpret,
    )
