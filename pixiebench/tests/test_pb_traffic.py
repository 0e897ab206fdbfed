"""The open-loop traffic generator (CPU, no JAX device work)."""

import numpy as np
import pytest

from pixiebench import loadgen, registry, run

# a degree sequence with a heavy head: pin 0 holds half the edges
DEGREES = np.array([500, 0, 100, 50, 0, 200, 150] + [1] * 993)
OFFSETS = np.concatenate([[0], np.cumsum(DEGREES)])


@pytest.fixture(scope="module")
def mixes():
    return {name: registry.traffic(name) for name in ("homefeed", "related")}


@pytest.mark.parametrize("name", ["homefeed", "related"])
def test_same_seed_same_schedule(mixes, name):
    a = loadgen.schedule(mixes[name], 2**35 + 1, 5.0, OFFSETS)
    b = loadgen.schedule(mixes[name], 2**35 + 1, 5.0, OFFSETS)
    c = loadgen.schedule(mixes[name], 2**35 + 2, 5.0, OFFSETS)
    key = lambda s: [(r.due_s, r.feat, tuple(r.pins)) for r in s]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", ["homefeed", "related"])
def test_rate_and_window(mixes, name):
    seconds = 7.0
    reqs = loadgen.schedule(mixes[name], 11, seconds, OFFSETS)
    due = np.array([r.due_s for r in reqs])
    assert len(reqs) == round(mixes[name]["rate_rps"] * seconds)
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < seconds
    # Poisson given the count: uniform due times, mean half the window
    assert abs(due.mean() - seconds / 2) < seconds / 8


@pytest.mark.parametrize("name", ["homefeed", "related"])
def test_every_seed_offers_the_same_work(mixes, name):
    seconds = 9.0
    a = loadgen.schedule(mixes[name], 2**33 + 5, seconds, OFFSETS)
    b = loadgen.schedule(mixes[name], 17, seconds, OFFSETS)
    # the same arrivals; the same languages and lengths in another order
    assert [r.due_s for r in a] == [r.due_s for r in b]
    for f in (lambda s: [r.feat for r in s], lambda s: [len(r.pins) for r in s]):
        assert sorted(f(a)) == sorted(f(b))
    assert [r.feat for r in a] != [r.feat for r in b]
    # another arrivals_seed: the same gaps in another order
    c = loadgen.schedule(dict(mixes[name], arrivals_seed=2), 17, seconds,
                         OFFSETS)

    def gaps(s):
        return np.diff([r.due_s for r in s] + [seconds])

    np.testing.assert_allclose(np.sort(gaps(a)), np.sort(gaps(c)),
                               rtol=1e-12)
    assert not np.allclose(gaps(a), gaps(c))


def test_shares_follow_weights():
    rng = np.random.default_rng(1)
    got = np.bincount(loadgen.shares(rng, 23, [8, 1, 1, 1]), minlength=4)
    assert got.sum() == 23 and got.tolist() == [17, 2, 2, 2]


def test_pins_follow_degree():
    rng = np.random.default_rng(0)
    pins = loadgen.popular_pins(rng, OFFSETS, 20000)
    assert set(np.unique(pins)) <= set(np.flatnonzero(DEGREES))
    share0 = np.mean(pins == 0)
    assert abs(share0 - DEGREES[0] / DEGREES.sum()) < 0.02


def test_homefeed_queries_fit_eight_slots(mixes):
    from repro.core import service

    reqs = loadgen.schedule(mixes["homefeed"], 5, 20.0, OFFSETS)
    lens = [len(r.pins) for r in reqs]
    t = mixes["homefeed"]
    assert min(lens) >= t["history_min"] and max(lens) <= t["history_max"]
    widths = []
    for r in reqs:
        pins, weights = run._query(r, 8, service)
        assert len(pins) == len(weights) and all(w > 0 for w in weights)
        assert len(set(pins)) == len(pins)
        widths.append(len(pins))
    assert max(widths) <= 8 and max(widths) > 1


def test_related_queries_are_one_pin(mixes):
    from repro.core import service

    for r in loadgen.schedule(mixes["related"], 5, 5.0, OFFSETS):
        pins, weights = run._query(r, 1, service)
        assert len(pins) == 1 and weights == [1.0]


def test_arrivals_seed_is_required(mixes):
    t = {k: v for k, v in mixes["related"].items() if k != "arrivals_seed"}
    with pytest.raises(ValueError, match="arrivals_seed"):
        loadgen.schedule(t, 0, 1.0, OFFSETS)


def test_unknown_payload_is_refused():
    with pytest.raises(ValueError, match="payload"):
        loadgen.schedule({"payload": "video", "rate_rps": 1.0}, 0, 1.0,
                         OFFSETS)
