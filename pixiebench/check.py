"""The comparison that decides ``correct``.

Once the window has closed, a sample of the answered requests, drawn from
the seed and always holding the widest queries, is recomputed by the plain
reference (``reference.py``) from the request as the client sent it: its
slots, its language, its request id and the server's seed.  A served answer
matches when its top-k list lies within ``gap_tol`` of the reference's
(``reference.answer_gap``: scores pin by pin and rank by rank, relative to
the best score).  On a configuration with a ``ranker`` the answer is stage
2's: it matches when it lies within ``rank_gap_tol`` of the reference's
ranking of the reference's own candidates (``reference.rank`` and
``rank_gap``: head scores pin by pin and rank by rank, relative to the
largest).  The numbers compared, each with its limit from the
configuration's ``check`` section:

  * ``mismatch_share``: share of the sample whose answer does not match;
  * ``missing``: requests sent in the window that never got an answer;
  * ``compared_at_least``: answers compared, which may not fall below its
    limit (the other two may not rise above theirs).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from pixiebench import reference


class Served(NamedTuple):
    req_id: int
    pins: np.ndarray       # slots as sent, -1 / 0 padded to the query width
    weights: np.ndarray
    feat: int
    scores: np.ndarray
    ids: np.ndarray


def sample(widths: np.ndarray, answered: np.ndarray, seed: int, k: int,
           widest: int) -> List[int]:
    """Indices of ``k`` answered requests: the ``widest`` widest ones, the
    rest drawn from the seed."""
    idx = np.flatnonzero(answered)
    if idx.size <= k:
        return idx.tolist()
    by_width = idx[np.argsort(-widths[idx], kind="stable")]
    chosen = list(by_width[:widest])
    rest = np.setdiff1d(idx, chosen)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    chosen += rng.choice(rest, size=k - len(chosen), replace=False).tolist()
    return sorted(int(i) for i in chosen)


def gaps(hg: reference.HostGraph, served: Sequence[Served], server_seed: int,
         walk: Dict, chunk_steps: int,
         stage2: Optional[reference.Stage2] = None) -> np.ndarray:
    """``answer_gap`` of every served answer against the reference, or
    with ``stage2`` the ``rank_gap`` of every ranked answer."""
    out = []
    for s in served:
        ref = reference.recommend(
            hg, s.pins, s.weights, s.feat,
            reference.request_key(server_seed, s.req_id), walk, chunk_steps,
        )
        if stage2 is None:
            out.append(reference.answer_gap(s.scores, s.ids, ref))
        else:
            out.append(reference.rank_gap(
                s.scores, s.ids, reference.rank(hg, ref, stage2)))
    return np.asarray(out, np.float64)


def judge(gap: np.ndarray, missing: int, check_cfg: Dict,
          ranked: bool = False) -> Tuple[bool, Dict]:
    """``(correct, numbers)``; each number is ``{"value", "limit"}``.  A
    ranked answer's gaps are held to ``rank_gap_tol``, a walk's to
    ``gap_tol``."""
    limits = check_cfg["limits"]
    tol = check_cfg["rank_gap_tol" if ranked else "gap_tol"]
    share = float(np.mean(gap > tol)) if gap.size else 1.0
    numbers = {
        "mismatch_share": {"value": share, "limit": limits["mismatch_share"]},
        "missing": {"value": int(missing), "limit": limits["missing"]},
        "compared_at_least": {"value": int(gap.size),
                              "limit": check_cfg["min_compared"]},
    }
    ok = (share <= limits["mismatch_share"]
          and missing <= limits["missing"]
          and gap.size >= check_cfg["min_compared"])
    return ok, numbers
