"""Sharded mega-table embeddings for the recsys substrate.

All per-feature tables are concatenated into ONE (total_rows, dim) array
("mega table") with per-feature row offsets — the standard production recsys
layout (a 10^8..10^9-row table that only exists row-sharded).  Two lookup
paths:

  * `lookup`         — plain `jnp.take`; correct under any sharding but lets
    GSPMD choose the comm pattern (fine replicated; may all-gather sharded).
  * `lookup_sharded` — explicit shard_map over the 'model' axis: each shard
    masks ids outside its row range, gathers locally, and one psum combines.
    Traffic per lookup = ids + (batch, dim) partial sums — never the table.
    This is the TPU-native EmbeddingBag the assignment calls out, and it is
    also the access pattern of Pixie's board->pin gathers, which is why the
    recsys substrate and the paper's serving layer share this module.

Multi-hot features pool with segment-sum semantics (kernels/embedding_bag.py
is the Pallas twin of the pooled path).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import layers

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MegaTableConfig:
    feature_rows: Tuple[int, ...]   # rows per sparse feature
    dim: int
    pad_to_multiple: int = 512      # row padding so any mesh axis divides

    @property
    def n_features(self) -> int:
        return len(self.feature_rows)

    @property
    def total_rows(self) -> int:
        raw = int(sum(self.feature_rows))
        m = self.pad_to_multiple
        return -(-raw // m) * m

    def offsets(self) -> jnp.ndarray:
        import numpy as np

        return jnp.asarray(
            np.concatenate([[0], np.cumsum(self.feature_rows)[:-1]]),
            jnp.int32,
        )


def init_table(key: Array, cfg: MegaTableConfig, dtype=jnp.float32) -> Array:
    scale = cfg.dim ** -0.5
    return jax.random.normal(key, (cfg.total_rows, cfg.dim), dtype) * scale


def abstract_table(cfg: MegaTableConfig, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((cfg.total_rows, cfg.dim), dtype)


def table_logical() -> Tuple[str, str]:
    return ("rows", "dim")


def global_ids(ids: Array, cfg: MegaTableConfig) -> Array:
    """Per-feature local ids (b, f) -> global mega-table rows."""
    return ids + cfg.offsets()[None, :]


def lookup(table: Array, ids: Array, cfg: MegaTableConfig) -> Array:
    """(b, f) local ids -> (b, f, dim). GSPMD chooses the comm pattern."""
    return jnp.take(table, global_ids(ids, cfg), axis=0)


def lookup_sharded(
    table: Array,
    ids: Array,
    cfg: MegaTableConfig,
    mesh: Mesh,
    *,
    shard_axis: str = "model",
    batch_axes: Tuple[str, ...] = ("data",),
) -> Array:
    """Row-sharded lookup: local masked take + one psum over `shard_axis`.

    table must be sharded P(shard_axis, None) and its row count divisible by
    the axis size; ids (b, f) sharded over batch_axes.
    """
    n_shards = mesh.shape[shard_axis]
    rows_per = cfg.total_rows // n_shards
    batch_spec = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch_spec if len(batch_spec) > 1 else (
        batch_spec[0] if batch_spec else None
    )

    def local_lookup(local_table, ids_local):
        # which shard owns each row
        rows = global_ids(ids_local, cfg)
        shard_id = jax.lax.axis_index(shard_axis)
        lo = shard_id * rows_per
        mine = (rows >= lo) & (rows < lo + rows_per)
        local_rows = jnp.where(mine, rows - lo, 0)
        vals = jnp.take(local_table, local_rows, axis=0)        # (b, f, d)
        vals = vals * mine[..., None].astype(vals.dtype)
        return jax.lax.psum(vals, axis_name=shard_axis)

    return jax.shard_map(
        local_lookup,
        mesh=mesh,
        in_specs=(P(shard_axis, None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(table, ids)


def pooled_lookup(
    table: Array,
    ids: Array,          # (b, f, l) multi-hot ids, -1 padding
    cfg: MegaTableConfig,
    mode: str = "sum",
) -> Array:
    """Multi-hot pooled lookup -> (b, f, dim) (EmbeddingBag semantics)."""
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0) + cfg.offsets()[None, :, None]
    rows = jnp.take(table, safe, axis=0)                 # (b, f, l, d)
    w = valid.astype(table.dtype)[..., None]
    pooled = jnp.sum(rows * w, axis=2)
    if mode == "mean":
        denom = jnp.maximum(jnp.sum(w, axis=2), 1.0)
        pooled = pooled / denom
    return pooled
