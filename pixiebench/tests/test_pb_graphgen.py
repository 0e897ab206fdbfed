"""The device graph generator at a tiny size (CPU)."""

import numpy as np
import pytest

from pixiebench import graphgen

TINY = dict(
    n_pins=3000, n_boards=1286, edge_draws=30000, n_topics=6, n_langs=4,
    lang_weights=[8, 1, 1, 1], board_size_sigma=1.0, popularity_exponent=1.1,
    noise_edge_frac=0.05, diverse_board_frac=0.1, main_topic_frac=0.85,
    same_lang_frac=0.7, max_pin_degree=64,
)


@pytest.fixture(scope="module")
def built():
    spec = graphgen.spec_from_config(TINY)
    graph, langs, stats = graphgen.generate(spec, 2**33 + 12345)
    host = {
        side: {k: np.asarray(getattr(getattr(graph, side), k))
               for k in ("offsets", "targets", "feat_bounds")}
        for side in ("p2b", "b2p")
    }
    langs = {k: np.asarray(v) for k, v in langs.items()}
    return spec, graph, host, langs, stats


def _edges(host, side, n_src):
    off, tgt = host[side]["offsets"], host[side]["targets"]
    src = np.repeat(np.arange(n_src), np.diff(off))
    return src, tgt[: off[-1]]


def test_offsets_monotone_and_targets_in_range(built):
    spec, _, host, _, stats = built
    for side, n_src, lo, hi in (
        ("p2b", spec.n_pins, spec.n_pins, spec.n_pins + spec.n_boards),
        ("b2p", spec.n_boards, 0, spec.n_pins),
    ):
        off, tgt = host[side]["offsets"], host[side]["targets"]
        assert off.shape == (n_src + 1,) and off[0] == 0
        assert np.all(np.diff(off) >= 0)
        assert off[-1] == stats["edges_kept"]
        assert tgt.shape == (spec.edge_draws,)
        # every entry is a valid id, the tail past offsets[-1] included
        assert tgt.min() >= lo and tgt.max() < hi


def test_feature_bounds_match_language_sorted_subranges(built):
    spec, _, host, langs, _ = built
    for side, n_src, lang_of in (
        ("p2b", spec.n_pins,
         lambda t: langs["board_lang"][t - spec.n_pins]),
        ("b2p", spec.n_boards, lambda t: langs["pin_lang"][t]),
    ):
        off, tgt, fb = (host[side][k]
                        for k in ("offsets", "targets", "feat_bounds"))
        deg = np.diff(off)
        assert fb.shape == (n_src, spec.n_langs + 1)
        assert np.all(fb[:, 0] == 0) and np.all(fb[:, -1] == deg)
        assert np.all(np.diff(fb, axis=1) >= 0)
        src, t = _edges(host, side, n_src)
        pos = np.arange(t.shape[0]) - off[src]
        want = np.sum(pos[:, None] >= fb[src, 1:-1], axis=1)
        assert np.array_equal(lang_of(t), want)


def test_no_duplicate_edges_and_both_directions_agree(built):
    spec, _, host, _, _ = built
    pins, boards = _edges(host, "p2b", spec.n_pins)
    boards = boards - spec.n_pins
    key = boards.astype(np.int64) * spec.n_pins + pins
    assert np.unique(key).shape == key.shape
    b_src, b_pins = _edges(host, "b2p", spec.n_boards)
    key_b = b_src.astype(np.int64) * spec.n_pins + b_pins
    assert np.array_equal(np.sort(key), np.sort(key_b))


def test_edge_ratios_and_degree_cap(built):
    spec, graph, host, _, stats = built
    deg = np.diff(host["p2b"]["offsets"])
    assert deg.max() == spec.max_pin_degree == graph.max_pin_degree
    assert stats["max_pin_degree"] == spec.max_pin_degree
    per_board = stats["edges_kept"] / spec.n_boards
    assert 0.6 * spec.edge_draws / spec.n_boards < per_board \
        <= spec.edge_draws / spec.n_boards
    # popularity is heavy-tailed: the top 1% of pins hold over 5x their
    # uniform share, even under the degree cap
    top = np.sort(deg)[::-1][: spec.n_pins // 100].sum()
    assert top > 0.05 * deg.sum()


def test_same_seed_same_graph_other_seed_other_graph(built):
    spec, _, host, _, _ = built
    again, _, _ = graphgen.generate(spec, 2**33 + 12345)
    other, _, _ = graphgen.generate(spec, 7)
    assert np.array_equal(np.asarray(again.p2b.targets),
                          host["p2b"]["targets"])
    assert np.array_equal(np.asarray(again.b2p.feat_bounds),
                          host["b2p"]["feat_bounds"])
    assert not np.array_equal(np.asarray(other.p2b.targets),
                              host["p2b"]["targets"])


def test_seed_words_cover_64_bits():
    lo, hi = graphgen.seed_words(2**40 + 3)
    assert (int(lo), int(hi)) == (3, 256)
    with pytest.raises(ValueError):
        graphgen.seed_words(-1)


def test_missing_graph_key_is_refused():
    cfg = dict(TINY)
    del cfg["max_pin_degree"]
    with pytest.raises(ValueError, match="max_pin_degree"):
        graphgen.spec_from_config(cfg)
