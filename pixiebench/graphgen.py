"""Seeded pin-board graph, generated on the device in one jitted call.

The graph keeps the planted structure of the repository's host generator
(log-normal board sizes, Zipf pin popularity, topic-focused boards with a
noise share, a dominant language, language-sorted CSR subranges) but draws
every edge at once with ``jax.random`` and builds both CSR directions with
sorts, counts and running sums, so a 10M-pin graph costs seconds of device time
instead of minutes of Python.

Every shape depends only on the configuration, never on the seed: the
edge arrays are ``edge_draws`` long, edges removed as duplicates or by the
pin-degree cap sort to the tail of ``targets`` (past ``offsets[-1]``, where
nothing reads), and ``max_pin_degree`` is the configured cap, which the
most popular pins always reach.  So one compiled serving program serves
every seed.

Edge model, per drawn edge of board ``b``:
  * with ``noise_edge_frac``: a pin by global popularity (Zipf rank);
  * otherwise a topic (diverse boards: uniform; focused boards: the main
    topic with ``main_topic_frac``, else the board's second topic) and a
    language (the board's with ``same_lang_frac``, else drawn from the
    language mix), then a pin of that (topic, language) group by its
    popularity rank within the group (Zipf).
A board holds each pin at most once; a pin keeps at most ``max_pin_degree``
boards (its first ones in (board language, board id) order).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

class GraphSpec(NamedTuple):
    n_pins: int
    n_boards: int
    edge_draws: int
    n_topics: int
    n_langs: int
    lang_weights: Tuple[float, ...]
    board_size_sigma: float
    popularity_exponent: float
    noise_edge_frac: float
    diverse_board_frac: float
    main_topic_frac: float
    same_lang_frac: float
    max_pin_degree: int


def spec_from_config(graph_cfg: Dict) -> GraphSpec:
    missing = [k for k in GraphSpec._fields if k not in graph_cfg]
    if missing:
        raise ValueError(f"graph configuration lacks {missing}")
    vals = {k: graph_cfg[k] for k in GraphSpec._fields}
    vals["lang_weights"] = tuple(float(x) for x in vals["lang_weights"])
    if len(vals["lang_weights"]) != vals["n_langs"]:
        raise ValueError("lang_weights needs one weight per language")
    return GraphSpec(**vals)


def seed_words(seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Split a non-negative seed of up to 64 bits into two int32 words."""
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    lo = np.array(seed & 0xFFFFFFFF, np.uint32).view(np.int32)
    hi = np.array(seed >> 32, np.uint32).view(np.int32)
    return lo, hi


def _categorical(u, weights):
    """Index of the cumulative-weight bucket each uniform ``u`` falls in."""
    cum = np.cumsum(np.asarray(weights, np.float64))
    cum = jnp.asarray(cum[:-1] / cum[-1], jnp.float32)
    return jnp.sum(u[..., None] >= cum, axis=-1).astype(jnp.int32)


def _zipf_rank(u, n, s):
    """Rank in [0, n) of a continuous Zipf(s) draw by inverse CDF."""
    n = n.astype(jnp.float32)
    e = 1.0 - s
    top = jnp.power(n + 1.0, e)
    x = jnp.power(1.0 - u * (1.0 - top), 1.0 / e)
    r = jnp.floor(x).astype(jnp.int32) - 1
    return jnp.clip(r, 0, jnp.maximum(n.astype(jnp.int32) - 1, 0))


def _starts(keys, n_keys):
    """For sorted ``keys`` in [0, n_keys], the index of the first key >= v
    for every v in [0, n_keys]: a count per key and a running sum, in one
    pass instead of a binary search per v."""
    counts = jnp.zeros((n_keys + 1,), jnp.int32).at[keys].add(1)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts)[:-1]])


def _small_take(tables, idx):
    """``t[idx]`` for each short table ``t``: one compare-and-select pass
    over ``idx`` per table entry, cheaper on the TPU than a gather of a
    long index array."""
    def body(k, outs):
        hit = idx == k
        return tuple(jnp.where(hit, t[k], o) for t, o in zip(tables, outs))

    zeros = tuple(jnp.zeros_like(idx) for _ in tables)
    return jax.lax.fori_loop(0, tables[0].shape[0], body, zeros)


def _device_graph(seed_lo, seed_hi, spec: GraphSpec):
    n, nb, m = spec.n_pins, spec.n_boards, spec.edge_draws
    nt, nl = spec.n_topics, spec.n_langs
    key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    ks = jax.random.split(key, 12)

    # pins: random attributes; popularity rank r belongs to pin perm[r]
    pin_topic = jax.random.randint(ks[0], (n,), 0, nt, jnp.int32)
    pin_lang = _categorical(jax.random.uniform(ks[1], (n,)), spec.lang_weights)
    perm = jax.random.permutation(ks[2], n).astype(jnp.int32)
    n_groups = nt * nl
    group_of_rank = (pin_topic * nl + pin_lang)[perm]
    order = jnp.argsort(group_of_rank, stable=True)
    pin_by_group = perm[order]           # group-major, popular first
    group_sorted = group_of_rank[order]
    group_start = jnp.searchsorted(
        group_sorted, jnp.arange(n_groups + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    group_size = group_start[1:] - group_start[:-1]

    # boards: language, topics, log-normal sizes scaled to edge_draws
    board_lang = _categorical(
        jax.random.uniform(ks[3], (nb,)), spec.lang_weights
    )
    main_topic = jax.random.randint(ks[4], (nb,), 0, nt, jnp.int32)
    second_topic = jax.random.randint(ks[5], (nb,), 0, nt, jnp.int32)
    diverse = jax.random.uniform(ks[6], (nb,)) < spec.diverse_board_frac
    raw = jnp.exp(spec.board_size_sigma * jax.random.normal(ks[7], (nb,)))
    cum = jnp.cumsum(raw / jnp.sum(raw))
    # a float32 running sum on the TPU can step back by an ulp between
    # neighbours; the running max keeps the board blocks in order
    bounds = jax.lax.cummax(jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.clip(jnp.round(cum * m).astype(jnp.int32), 0, m),
    ]).at[-1].set(m))
    # board of edge e: the boards whose block ends at or before e
    board = jnp.cumsum(
        jnp.zeros((m + 1,), jnp.int32).at[bounds[1:]].add(1)
    )[:m]
    board = jnp.clip(board, 0, nb - 1)

    # each edge's board attributes, packed into one int32 per board and
    # spread over the board's block of edges by a running sum of their
    # differences (a gather per edge costs seconds on the TPU)
    tb = max(1, (nt - 1).bit_length())
    packed = (diverse.astype(jnp.int32) | (main_topic << 1)
              | (second_topic << (1 + tb)) | (board_lang << (1 + 2 * tb)))
    step = packed - jnp.concatenate([jnp.zeros((1,), jnp.int32), packed[:-1]])
    per_edge = jnp.cumsum(
        jnp.zeros((m + 1,), jnp.int32).at[bounds[:-1]].add(step)
    )[:m]
    low = (1 << tb) - 1
    blang_e = per_edge >> (1 + 2 * tb)

    # edges: noise picks by global popularity, the rest by (topic, lang)
    u = jax.random.uniform(ks[8], (5, m))
    noise = u[0] < spec.noise_edge_frac
    topic = jnp.where(
        (per_edge & 1).astype(bool),
        jnp.minimum((u[1] * nt).astype(jnp.int32), nt - 1),
        jnp.where(u[1] < spec.main_topic_frac, (per_edge >> 1) & low,
                  (per_edge >> (1 + tb)) & low),
    )
    lang = jnp.where(
        u[2] < spec.same_lang_frac, blang_e,
        _categorical(u[3], spec.lang_weights),
    )
    g = topic * nl + lang
    gsize, gstart = _small_take((group_size, group_start[:-1]), g)
    in_group = _zipf_rank(u[4], gsize, spec.popularity_exponent)
    global_rank = _zipf_rank(u[4], jnp.asarray(n), spec.popularity_exponent)
    # one gather from [pins by group | pins by popularity rank]
    pin = jnp.concatenate([pin_by_group, perm])[jnp.where(
        noise | (gsize == 0), n + global_rank,
        jnp.clip(gstart + in_group, 0, n - 1))]

    # pin-major order: (pin, board language, board); duplicates adjacent
    pin_s, blang_s, board_s = jax.lax.sort((pin, blang_e, board), num_keys=3)
    first = jnp.concatenate([jnp.ones((1,), bool), jnp.zeros((m - 1,), bool)])
    dup = ~first & (pin_s == jnp.roll(pin_s, 1)) & (board_s == jnp.roll(board_s, 1))
    fresh = jnp.cumsum((~dup).astype(jnp.int32))
    # fresh is non-decreasing, so its running max over pin starts is its
    # value at the start of each edge's pin
    starts = first | (pin_s != jnp.roll(pin_s, 1))
    rank_in_pin = fresh - jax.lax.cummax(jnp.where(starts, fresh, 0))
    keep = ~dup & (rank_in_pin < spec.max_pin_degree)

    # p2b: kept edges by (pin, board language); dropped ones to the tail
    k1 = jnp.where(keep, pin_s * nl + blang_s, n * nl)
    k1, p2b_tgt = jax.lax.sort((k1, board_s), num_keys=2)
    p2b_tgt = jnp.where(k1 < n * nl, p2b_tgt + n, n)
    p2b_pos = _starts(k1, n * nl)
    p2b_off = p2b_pos[::nl]
    p2b_fb = (
        p2b_pos[: n * nl].reshape(n, nl) - p2b_off[:-1, None]
    )
    p2b_fb = jnp.concatenate(
        [p2b_fb, (p2b_off[1:] - p2b_off[:-1])[:, None]], axis=1
    )

    # b2p: the same kept edges by (board, pin language)
    k2 = jnp.where(keep, board_s * nl + pin_lang[pin_s], nb * nl)
    k2, b2p_tgt = jax.lax.sort((k2, pin_s), num_keys=2)
    b2p_tgt = jnp.where(k2 < nb * nl, b2p_tgt, 0)
    b2p_pos = _starts(k2, nb * nl)
    b2p_off = b2p_pos[::nl]
    b2p_fb = b2p_pos[: nb * nl].reshape(nb, nl) - b2p_off[:-1, None]
    b2p_fb = jnp.concatenate(
        [b2p_fb, (b2p_off[1:] - b2p_off[:-1])[:, None]], axis=1
    )

    degs = p2b_off[1:] - p2b_off[:-1]
    stats = jnp.stack([
        p2b_off[-1],                          # edges kept
        jnp.sum(dup.astype(jnp.int32)),       # duplicate draws removed
        jnp.max(degs),                        # max pin degree
        jnp.sum((degs > 0).astype(jnp.int32)),  # pins with an edge
        jnp.max(b2p_off[1:] - b2p_off[:-1]),  # max board degree
    ])
    arrays = {
        "p2b_offsets": p2b_off, "p2b_targets": p2b_tgt, "p2b_feat_bounds": p2b_fb,
        "b2p_offsets": b2p_off, "b2p_targets": b2p_tgt, "b2p_feat_bounds": b2p_fb,
        "pin_lang": pin_lang, "board_lang": board_lang,
    }
    return arrays, stats


_JITTED = {}


def device_graph_fn(spec: GraphSpec):
    """The jitted generator for ``spec``: ``(seed_lo, seed_hi) -> (arrays,
    stats)``.  One compiled program per configuration, shared by all seeds."""
    if spec not in _JITTED:
        _JITTED[spec] = jax.jit(lambda lo, hi: _device_graph(lo, hi, spec))
    return _JITTED[spec]


STAT_NAMES = ("edges_kept", "duplicates_removed", "max_pin_degree",
              "pins_with_edges", "max_board_degree")


def generate(spec: GraphSpec, seed: int):
    """Build the graph for ``seed`` on the default device.

    Returns ``(PinBoardGraph, langs, stats)``: ``langs`` holds the device
    arrays ``pin_lang`` and ``board_lang``, ``stats`` maps ``STAT_NAMES`` to
    host ints.  Raises when the most popular pins do not
    reach the configured degree cap, since the serving program takes the
    cap as the graph's maximum pin degree (Eq. 1's C).
    """
    from repro.core.graph import CSR, PinBoardGraph

    lo, hi = seed_words(seed)
    arrays, stats = device_graph_fn(spec)(lo, hi)
    stats = dict(zip(STAT_NAMES, (int(x) for x in np.asarray(stats))))
    if stats["max_pin_degree"] != spec.max_pin_degree:
        raise RuntimeError(
            f"max pin degree {stats['max_pin_degree']} != configured cap "
            f"{spec.max_pin_degree}: the popularity tail is too light for "
            "this cap"
        )
    graph = PinBoardGraph(
        p2b=CSR(arrays["p2b_offsets"], arrays["p2b_targets"],
                arrays["p2b_feat_bounds"]),
        b2p=CSR(arrays["b2p_offsets"], arrays["b2p_targets"],
                arrays["b2p_feat_bounds"]),
        n_pins=spec.n_pins,
        n_boards=spec.n_boards,
        max_pin_degree=spec.max_pin_degree,
    )
    langs = {k: arrays[k] for k in ("pin_lang", "board_lang")}
    return graph, langs, stats
