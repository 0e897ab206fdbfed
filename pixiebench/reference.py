"""Plain reference of one served Pixie request, in NumPy.

It follows the paper's Algorithms 2-3 and Eqs. 1-4 as the serving
configuration states them, request by request, with no batching, no kernel
and no dense count table, and imports nothing of the program under test:

  * Eq. 1-2: per-slot step budgets ``N_q`` from query weights and pin
    degrees, ``C`` = the graph's maximum pin degree; the walker pool split
    across slots by largest remainder;
  * each step: restart to the walker's query pin with probability alpha,
    then a board from the pin and a pin from the board, each within the
    user's language subrange with probability beta (the whole list when the
    subrange is empty); a dead end sends the walker home and counts nothing;
  * every ``chunk_steps`` steps, a slot stops once more than ``n_p`` pins
    reached ``n_v`` visits or its steps reached ``N_q``;
  * the query pin is removed from its own slot's counts, slots combine by
    Eq. 3, ``V[p] = (sum_q sqrt(V_q[p]))**2``, and the top ``top_k`` pins
    are the answer;
  * stage 2, where the configuration states a ranker (``rank``): the
    answer's top ``n_candidates`` pins, each scored by the cell's head from
    its own row, a fixed 2-hop fan of its neighbours and the pool of the
    candidates themselves; the top ``final_k`` are the answer.

Random bits: a served request draws its walk from its own stream, the
request key ``fold_in(key(server_seed), request_id)``; step ``s`` takes
``jax.random.bits(fold_in(request_key, s), (n_walkers, 4))`` as uint32
(restart, language, board pick, pin pick).  The reference draws the same
bits with JAX's public PRNG and does everything else in NumPy, so a served
answer and the reference agree exactly when the walk, the counting, the
stop rule and the combine are right.  Float32 arithmetic mirrors the
program's precision for the budget split (Eq. 1-2); the combine is float64,
with a float32 twin that orders the candidates handed to stage 2.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

_RMASK = np.uint32(0x7FFFFFFF)


class HostGraph(NamedTuple):
    """Host copy of the CSR arrays the reference walks."""

    n_pins: int
    max_pin_degree: int
    p2b_off: np.ndarray
    p2b_tgt: np.ndarray
    p2b_fb: np.ndarray
    b2p_off: np.ndarray
    b2p_tgt: np.ndarray
    b2p_fb: np.ndarray


def host_graph(arrays: Dict, n_pins: int, max_pin_degree: int) -> HostGraph:
    """Copy the generator's device arrays to host NumPy."""
    get = lambda k: np.asarray(jax.device_get(arrays[k]))
    return HostGraph(
        n_pins=int(n_pins), max_pin_degree=int(max_pin_degree),
        p2b_off=get("p2b_offsets").astype(np.int64),
        p2b_tgt=get("p2b_targets").astype(np.int64),
        p2b_fb=get("p2b_feat_bounds").astype(np.int64),
        b2p_off=get("b2p_offsets").astype(np.int64),
        b2p_tgt=get("b2p_targets").astype(np.int64),
        b2p_fb=get("b2p_feat_bounds").astype(np.int64),
    )


def _prob_u32(p: float) -> np.uint32:
    return np.uint32(max(0, min(int(round(p * 2.0**32)), 2**32 - 1)))


def step_budgets(weights: np.ndarray, degrees: np.ndarray, max_degree: int,
                 n_steps: int) -> np.ndarray:
    """Eq. 1-2 in float32: ``s_q = d (C - log d)``, ``N_q = floor(w s N / sum)``
    with at least one step for every live slot."""
    f32 = np.float32
    deg = degrees.astype(f32)
    c = f32(max(max_degree, 1))
    s = deg * (c - np.log(np.maximum(deg, f32(1.0))))
    s = np.where(degrees > 0, np.maximum(s, f32(0.0)), f32(0.0)).astype(f32)
    w = (weights.astype(f32) * s).astype(f32)
    denom = np.maximum(np.sum(w, dtype=f32), f32(1e-9))
    frac = (w / denom).astype(f32)
    n_q = np.floor(frac * f32(n_steps)).astype(np.int32)
    return np.where(w > 0, np.maximum(n_q, 1), 0).astype(np.int32)


def split_walkers(n_q: np.ndarray, n_walkers: int) -> np.ndarray:
    """Walker pool split in proportion to the budgets, largest remainder
    first; returns the slot of every walker."""
    f32 = np.float32
    n_slots = n_q.shape[0]
    total = max(int(n_q.sum()), 1)
    ideal = (n_q.astype(f32) * (f32(n_walkers) / f32(total))).astype(f32)
    base = np.floor(ideal).astype(np.int32)
    base = np.where(n_q > 0, np.maximum(base, 1), 0)
    short = n_walkers - int(base.sum())
    frac = (ideal - np.floor(ideal)).astype(f32)
    rank = np.argsort(np.argsort(-frac, kind="stable"), kind="stable")
    per_slot = np.maximum(base + (rank < short).astype(np.int32), 0)
    over = int(per_slot.sum()) - n_walkers
    trim = np.argsort(np.argsort(-per_slot, kind="stable"), kind="stable")
    per_slot = np.where((trim < over) & (per_slot > 0), per_slot - 1, per_slot)
    bounds = np.cumsum(per_slot)
    slot = np.searchsorted(bounds, np.arange(n_walkers), side="right")
    return np.clip(slot, 0, n_slots - 1).astype(np.int64)


_BITS = {}


def _bits_fn(n_steps_total: int, n_walkers: int):
    """Jitted ``(request_key) -> (n_steps_total, n_walkers, 4)`` uint32."""
    k = (n_steps_total, n_walkers)
    if k not in _BITS:
        steps = jnp.arange(n_steps_total, dtype=jnp.int32)

        def bits(rkey):
            return jax.vmap(
                lambda s: jax.random.bits(
                    jax.random.fold_in(rkey, s), (n_walkers, 4)
                )
            )(steps)

        _BITS[k] = jax.jit(bits)
    return _BITS[k]


def request_key(server_seed: int, request_id: int):
    return jax.random.fold_in(jax.random.key(server_seed), request_id)


def _hop(off, tgt, fb, node, r, feat, use_b, use_bias):
    """One CSR hop per walker: (next node, ok)."""
    start = off[node]
    deg = off[node + 1] - start
    base, span = start, np.maximum(deg, 1)
    if use_bias:
        lo = fb[node, feat]
        hi = fb[node, feat + 1]
        sub = use_b & (hi > lo)
        base = np.where(sub, start + lo, base)
        span = np.where(sub, hi - lo, span)
    ok = deg > 0
    idx = np.where(ok, base + r % span, 0)
    return tgt[idx], ok


class Answer(NamedTuple):
    ids: np.ndarray        # visited pins, after the query-pin removal
    scores: np.ndarray     # Eq. 3 scores (float64) of ``ids``
    steps_taken: np.ndarray
    n_high: np.ndarray
    scores32: Optional[np.ndarray] = None   # Eq. 3 in float32, slot order


def recommend(hg: HostGraph, pins: np.ndarray, weights: np.ndarray,
              feat: int, rkey, walk: Dict, chunk_steps: int,
              bits: Optional[np.ndarray] = None) -> Answer:
    """The reference answer of one request (all visited pins, scored).

    ``pins``/``weights`` are the request's slots as served (-1 / 0 padded),
    ``rkey`` its PRNG key, ``walk`` the configuration's walk settings.
    """
    n_slots = pins.shape[0]
    n_walkers = int(walk["n_walkers"])
    n_steps = int(walk["n_steps"])
    n_p, n_v = int(walk["n_p"]), int(walk["n_v"])
    alpha_u, beta_u = _prob_u32(walk["alpha"]), _prob_u32(walk["bias_beta"])
    use_bias = beta_u > 0
    max_chunks = max(1, -(-n_steps // (n_walkers * chunk_steps)))
    if bits is None:
        bits = np.asarray(
            _bits_fn(max_chunks * chunk_steps, n_walkers)(rkey)
        )

    valid = (pins >= 0) & (weights > 0)
    safe_q = np.where(valid, pins, 0).astype(np.int64)
    degs = (hg.p2b_off[safe_q + 1] - hg.p2b_off[safe_q]) * valid
    n_q = step_budgets(np.where(valid, weights, 0).astype(np.float32), degs,
                       hg.max_pin_degree, n_steps)
    slot_of = split_walkers(n_q, n_walkers)
    home = safe_q[slot_of]
    per_slot_walkers = np.bincount(slot_of, minlength=n_slots)

    curr = home.copy()
    feat = int(feat)
    steps_taken = np.zeros(n_slots, np.int64)
    active = valid.copy()
    keys = []           # visit keys slot * n_pins + pin, chunk by chunk
    high = np.zeros(n_slots, np.int64)
    it = 0
    while active.any() and it < max_chunks:
        w_act = active[slot_of]
        for s in range(chunk_steps):
            b = bits[it * chunk_steps + s]
            restart = b[:, 0] < alpha_u
            use_b = b[:, 1] < beta_u
            r_board = (b[:, 2] & _RMASK).astype(np.int64)
            r_pin = (b[:, 3] & _RMASK).astype(np.int64)
            pos = np.where(restart, home, curr)
            board, ok_b = _hop(hg.p2b_off, hg.p2b_tgt, hg.p2b_fb, pos,
                               r_board, feat, use_b, use_bias)
            b_local = np.where(ok_b, board - hg.n_pins, 0)
            pin, ok_p = _hop(hg.b2p_off, hg.b2p_tgt, hg.b2p_fb, b_local,
                             r_pin, feat, use_b, use_bias)
            ok = ok_b & ok_p
            nxt = np.where(ok, pin, home)
            curr = np.where(w_act, nxt, curr)
            counted = ok & w_act
            keys.append(slot_of[counted] * hg.n_pins + pin[counted])
        uniq, cnt = np.unique(np.concatenate(keys), return_counts=True)
        high = np.bincount(uniq[cnt >= n_v] // hg.n_pins, minlength=n_slots)
        steps_taken = steps_taken + per_slot_walkers * active * chunk_steps
        active = valid & (steps_taken < n_q) & (high <= n_p)
        it += 1

    allkeys = np.concatenate(keys) if keys else np.zeros(0, np.int64)
    uniq, cnt = np.unique(allkeys, return_counts=True)
    slot = uniq // hg.n_pins
    pin = uniq % hg.n_pins
    q_reached = np.zeros(n_slots, np.int64)
    own = pin == safe_q[slot]
    q_reached[slot[own & (cnt >= n_v)]] = 1
    slot, pin, cnt = slot[~own], pin[~own], cnt[~own]
    ids, inv = np.unique(pin, return_inverse=True)
    root = np.zeros(ids.shape[0], np.float64)
    np.add.at(root, inv, np.sqrt(cnt.astype(np.float64)))
    # the program sums float32 roots over the slot axis; np.add.at adds in
    # the keys' order, which is slot order for each pin
    root32 = np.zeros(ids.shape[0], np.float32)
    np.add.at(root32, inv, np.sqrt(cnt.astype(np.float32)))
    return Answer(ids=ids, scores=root * root,
                  steps_taken=steps_taken.astype(np.int32),
                  n_high=(high - q_reached).astype(np.int32),
                  scores32=root32 * root32)


def answer_gap(served_scores: np.ndarray, served_ids: np.ndarray,
               ref: Answer) -> float:
    """How far a served top-k list lies from the reference answer.

    The larger of (a) the widest gap between a served pin's score and the
    reference's score for that pin, and (b) the widest gap between the
    i-th served score and the reference's i-th best score, both over the
    reference's best score (at least 1).  Ties in score may order pins
    either way, so the comparison is by score, pin by pin and rank by rank.
    A repeated pin id reads as a gap of 1.
    """
    s = np.asarray(served_scores, np.float64)
    i = np.asarray(served_ids, np.int64)
    k = s.shape[0]
    if np.unique(i).shape[0] != k:
        return 1.0
    ref_of_served = np.zeros(k)
    if ref.ids.size:
        pos = np.clip(np.searchsorted(ref.ids, i), 0, ref.ids.size - 1)
        ref_of_served = np.where(ref.ids[pos] == i, ref.scores[pos], 0.0)
    best = np.sort(ref.scores)[::-1][:k]
    best = np.concatenate([best, np.zeros(k - best.shape[0])])
    scale = max(float(best[0]) if k else 1.0, 1.0)
    by_pin = np.max(np.abs(s - ref_of_served)) if k else 0.0
    by_rank = np.max(np.abs(np.sort(s)[::-1] - best)) if k else 0.0
    return float(max(by_pin, by_rank) / scale)


# -- stage 2 ------------------------------------------------------------------

FAN_STRIDE, FAN_OFFSET = 31, 7     # neighbour j's pick on its board


class Stage2(NamedTuple):
    """Host copy of the ranker one cell serves: the item table, the head
    its mix names, and the configuration's ``ranker`` sizes."""

    items: np.ndarray      # (n_pins, d)
    w_self: np.ndarray     # (d, d)
    w_neigh: np.ndarray    # (d, d)
    w_query: np.ndarray    # (d, d)
    b: np.ndarray          # (d,)
    n_candidates: int
    n_neighbors: int
    final_k: int


def stage2(params: Dict, ranker: Dict, head: str) -> Stage2:
    """The cell's head out of the host parameters (heads stacked on a
    leading axis in the order of the configuration's ``scenarios``)."""
    h = list(ranker["scenarios"]).index(head)
    heads = params["heads"]
    pick = lambda k: np.asarray(heads[k][h], np.float64)
    return Stage2(np.asarray(params["items"]), pick("w_self"),
                  pick("w_neigh"), pick("w_query"), pick("b"),
                  int(ranker["n_candidates"]), int(ranker["n_neighbors"]),
                  int(ranker["final_k"]))


class Ranked(NamedTuple):
    cand_ids: np.ndarray     # stage-1 candidates, best first
    cand_scores: np.ndarray  # their head scores, -inf where not valid
    ids: np.ndarray          # the final_k answer, -1 past the valid ones
    scores: np.ndarray       # its head scores, -inf past the valid ones


def fan(hg: HostGraph, cand: np.ndarray, valid: np.ndarray,
        n_neighbors: int) -> tuple:
    """Candidate c's neighbour j is pin ``(j*31 + 7) % deg(b)`` of board
    ``b``, the ``j % deg(c)``-th of c's boards, with weight ``1 / (1 + j)``;
    id -1 and weight 0 where the fan dead-ends (invalid candidate, isolated
    pin, empty board)."""
    safe = np.where(valid, cand, 0)
    start = hg.p2b_off[safe]
    deg = hg.p2b_off[safe + 1] - start
    j = np.arange(n_neighbors)
    board_ok = (deg > 0)[:, None]
    at = np.where(board_ok, start[:, None] + j % np.maximum(deg, 1)[:, None],
                  0)
    board = np.where(board_ok, hg.p2b_tgt[at] - hg.n_pins, 0)
    bstart = hg.b2p_off[board]
    bdeg = hg.b2p_off[board + 1] - bstart
    ok = valid[:, None] & board_ok & (bdeg > 0)
    pick = np.where(ok, bstart + (j * FAN_STRIDE + FAN_OFFSET)
                    % np.maximum(bdeg, 1), 0)
    return (np.where(ok, hg.b2p_tgt[pick], -1),
            ok / (1.0 + j))


def mean_bag(items: np.ndarray, ids: np.ndarray, w: np.ndarray) -> np.ndarray:
    """``sum w_i e_i / max(sum w_i, 1)`` over each row's ids (-1: none)."""
    w = np.where(ids >= 0, w, 0.0)
    rows = items[np.maximum(ids, 0)].astype(np.float64)
    return (np.einsum("...l,...ld->...d", w, rows)
            / np.maximum(w.sum(-1), 1.0)[..., None])


def rank(hg: HostGraph, ans: Answer, st: Stage2,
         head_dtype=None) -> Ranked:
    """Stage 2 of one request in float64, from its stage-1 ``ans``.

    Candidates: the top ``n_candidates`` pins by (float32 Eq. 3 score
    descending, pin id ascending), valid where the score is above 0.  Each
    is scored ``relu(e_c W_self + n_c W_neigh + b) . (q W_query) / sqrt(d)``,
    where ``e_c`` is its row, ``n_c`` the mean of its fan's rows and ``q``
    the mean of the valid candidates' rows weighted by ``sqrt(score)``.
    The answer is the top ``final_k`` valid candidates, ties to the better
    stage-1 place.  ``head_dtype`` rounds the inputs of the head's three
    products to that type (the control: ``ml_dtypes.bfloat16``).
    """
    k = st.n_candidates
    order = np.lexsort((ans.ids, -ans.scores32))[:k]
    cand = np.zeros(k, np.int64)
    score = np.zeros(k, np.float32)
    cand[:order.size] = ans.ids[order]
    score[:order.size] = ans.scores32[order]
    valid = score > 0
    nbr, w = fan(hg, cand, valid, st.n_neighbors)
    neigh = mean_bag(st.items, nbr, w)
    own = st.items[cand].astype(np.float64) * valid[:, None]
    query = mean_bag(st.items, np.where(valid, cand, -1),
                     np.sqrt(score.astype(np.float64)))
    if head_dtype is None:
        cast = lambda x: x
    else:
        cast = lambda x: np.asarray(x).astype(head_dtype).astype(np.float64)
    h = np.maximum(cast(own) @ cast(st.w_self)
                   + cast(neigh) @ cast(st.w_neigh) + st.b, 0.0)
    qv = cast(query) @ cast(st.w_query)
    head = cast(h) @ cast(qv) / np.sqrt(st.items.shape[1])
    head = np.where(valid, head, -np.inf)
    top = np.argsort(-head, kind="stable")[:st.final_k]
    live = np.isfinite(head[top])
    return Ranked(cand, head, np.where(live, cand[top], -1),
                  np.where(live, head[top], -np.inf))


def rank_gap(served_scores: np.ndarray, served_ids: np.ndarray,
             ref: Ranked) -> float:
    """How far a ranked answer lies from the reference's, as ``answer_gap``
    reads a walk's: the larger of the widest gap between a served pin's head
    score and the reference's score of that candidate, and the widest gap
    rank by rank, over the largest of the reference's answer scores.  A
    served pin that is no valid reference candidate, a repeated pin, or
    another number of answers than the reference's reads as a gap of 1."""
    i = np.asarray(served_ids, np.int64)
    s = np.asarray(served_scores, np.float64)
    n = int(np.sum(ref.ids >= 0))
    live = i >= 0
    if int(live.sum()) != n or np.unique(i[live]).size != n:
        return 1.0
    if n == 0:
        return 0.0
    i, s = i[live], s[live]
    known = np.isfinite(ref.cand_scores)
    where = {int(c): x for c, x in zip(ref.cand_ids[known],
                                         ref.cand_scores[known])}
    if any(int(p) not in where for p in i):
        return 1.0
    want = ref.scores[:n]
    scale = float(np.max(np.abs(want))) or 1.0
    by_pin = np.max(np.abs(s - np.array([where[int(p)] for p in i])))
    by_rank = np.max(np.abs(np.sort(s)[::-1] - want))
    return float(max(by_pin, by_rank) / scale)
