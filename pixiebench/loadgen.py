"""Open-loop request schedules from a traffic file and a seed.

One general generator for every traffic mix: the mix's JSON file gives the
arrival rate, the arrival trace and the payload shape, and ``schedule``
turns them into a fixed list of due times and payloads.  Every seed offers
the same arrivals and the same sizes, in another order: ``n =
round(rate_rps * seconds)`` requests whose gaps are the ``n`` quantiles of
the exponential distribution (Poisson arrivals' gaps), scaled to fill the
window, in one order that the mix's ``arrivals_seed`` fixes; the seed
draws the payloads, and the order in which the languages
(``lang_weights``' shares of ``n``) and, for histories, the lengths
(evenly over ``history_min``..``history_max``) meet the arrivals.
Payloads:

  * ``history``: a user's recent actions (the Homefeed query, paper §5.1):
    each action on a pin drawn by popularity (degree-weighted: a uniform
    edge's pin), an action type drawn from ``actions``, an age drawn
    exponential with mean ``age_mean_hours``; the client folds it into a
    query at its due time;
  * ``single_pin``: one pin drawn by popularity (Related Pins, §5.2).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

PAYLOADS = ("history", "single_pin")


class Request(NamedTuple):
    due_s: float                     # due time from the window's start
    feat: int                        # user language
    pins: np.ndarray                 # single_pin: the pin; history: acted pins
    actions: Optional[Tuple[str, ...]] = None
    ages_h: Optional[np.ndarray] = None


def validate(traffic: Dict) -> None:
    payload = traffic.get("payload")
    if payload not in PAYLOADS:
        raise ValueError(f"traffic payload {payload!r} not in {PAYLOADS}")
    if not traffic.get("rate_rps", 0) > 0:
        raise ValueError("traffic needs a positive rate_rps")
    if not isinstance(traffic.get("arrivals_seed"), int):
        raise ValueError("traffic needs an integer arrivals_seed")
    if payload == "history":
        lo, hi = traffic["history_min"], traffic["history_max"]
        if not 1 <= lo <= hi:
            raise ValueError(f"history length range [{lo}, {hi}] is empty")


def popular_pins(rng: np.random.Generator, p2b_offsets: np.ndarray,
                 k: int) -> np.ndarray:
    """``k`` pins drawn in proportion to their degree."""
    n_edges = int(p2b_offsets[-1])
    e = rng.integers(0, n_edges, k)
    return (np.searchsorted(p2b_offsets, e, side="right") - 1).astype(np.int64)


def exponential_gaps(rng: np.random.Generator, n: int,
                     seconds: float) -> np.ndarray:
    """The ``n`` mid-quantiles of the exponential distribution, scaled to
    sum to ``seconds``, in an order drawn from ``rng``."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return rng.permutation(gaps * (seconds / gaps.sum()))


def shares(rng: np.random.Generator, n: int, weights) -> np.ndarray:
    """``n`` category indices in the proportions of ``weights`` (largest
    remainders), in an order drawn from ``rng``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(counts - exact, kind="stable")[:n - counts.sum()]] += 1
    return rng.permutation(np.repeat(np.arange(w.size), counts))


def schedule(traffic: Dict, seed: int, seconds: float,
             p2b_offsets: np.ndarray) -> List[Request]:
    """The window's requests, in due order."""
    validate(traffic)
    rng = np.random.default_rng([seed, 0x7AFF1C])
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    gaps = exponential_gaps(
        np.random.default_rng(traffic["arrivals_seed"]), n, seconds)
    due = np.cumsum(gaps) - gaps
    feats = shares(rng, n, traffic["lang_weights"])
    out = []
    if traffic["payload"] == "single_pin":
        pins = popular_pins(rng, p2b_offsets, n)
        for i in range(n):
            out.append(Request(float(due[i]), int(feats[i]), pins[i:i + 1]))
        return out
    lo, hi = traffic["history_min"], traffic["history_max"]
    lens = rng.permutation(lo + np.arange(n) % (hi - lo + 1))
    pins = popular_pins(rng, p2b_offsets, int(lens.sum()))
    kinds = rng.choice(len(traffic["actions"]), size=int(lens.sum()))
    ages = rng.exponential(traffic["age_mean_hours"], int(lens.sum()))
    at = 0
    for i in range(n):
        sl = slice(at, at + int(lens[i]))
        at += int(lens[i])
        out.append(Request(
            float(due[i]), int(feats[i]), pins[sl],
            tuple(traffic["actions"][j] for j in kinds[sl]), ages[sl],
        ))
    return out


def warmup_requests(traffic: Dict, seed: int, count: int,
                    p2b_offsets: np.ndarray) -> List[Request]:
    """``count`` requests of the mix's shape for warm-up (own stream)."""
    t = dict(traffic, rate_rps=float(count))
    return schedule(t, seed ^ 0x5EED, 1.0, p2b_offsets)[:count]
