"""The plain reference's Eq. 1-2 budget split and its walk against the
program's own functions, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pixiebench import reference


@pytest.mark.parametrize("seed", range(6))
def test_step_budgets_and_walker_split_match_the_program(seed):
    from repro.core import sampling

    rng = np.random.default_rng(seed)
    n_slots = 8
    live = rng.integers(1, n_slots + 1)
    degrees = np.zeros(n_slots, np.int32)
    weights = np.zeros(n_slots, np.float32)
    degrees[:live] = rng.integers(1, 4097, live)
    weights[:live] = rng.random(live).astype(np.float32) * 2 + 0.01
    n_q = reference.step_budgets(weights, degrees, 4096, 200_000)
    want = sampling.allocate_steps(jnp.asarray(weights), jnp.asarray(degrees),
                                   jnp.asarray(4096), 200_000)
    assert np.array_equal(n_q, np.asarray(want))
    slot = reference.split_walkers(n_q, 8192)
    want_slot, _ = sampling.allocate_walkers(jnp.asarray(n_q), 8192)
    assert np.array_equal(slot, np.asarray(want_slot))


def test_request_key_is_the_servers_stream():
    # PixieServer keys request r as fold_in(key(seed), r) at submit
    base = jax.random.key(123)
    k = reference.request_key(123, 45)
    assert np.array_equal(jax.random.key_data(k),
                          jax.random.key_data(jax.random.fold_in(base, 45)))


def test_answer_gap_reads_scores_not_tie_order():
    ref = reference.Answer(ids=np.array([3, 5, 9]),
                           scores=np.array([4.0, 4.0, 1.0]),
                           steps_taken=None, n_high=None)
    # ties in either order match; a wrong score or a repeated id does not
    assert reference.answer_gap(np.array([4.0, 4.0]), np.array([5, 3]),
                                ref) == 0.0
    assert reference.answer_gap(np.array([4.0, 4.0]), np.array([3, 9]),
                                ref) == pytest.approx(0.75)
    assert reference.answer_gap(np.array([4.0, 4.0]), np.array([3, 3]),
                                ref) == 1.0


# -- stage 2 -------------------------------------------------------------------

RANKER = {"d_model": 32, "n_neighbors": 8, "n_candidates": 64, "final_k": 16,
          "scenarios": ["related_pins", "homefeed"]}
CHECK = {"rank_gap_tol": 1e-4, "min_compared": 8,
         "limits": {"mismatch_share": 0.02, "missing": 0}}


@pytest.fixture(scope="module")
def stage_one():
    """A tiny graph, seeded ranker parameters and eight requests' stage-1
    answers from the reference walk."""
    from pixiebench import graphgen, rankgen

    spec = graphgen.spec_from_config({
        "n_pins": 4000, "n_boards": 1714, "edge_draws": 40000,
        "n_topics": 24, "n_langs": 4, "lang_weights": [8, 1, 1, 1],
        "board_size_sigma": 1.0, "popularity_exponent": 1.1,
        "noise_edge_frac": 0.05, "diverse_board_frac": 0.1,
        "main_topic_frac": 0.85, "same_lang_frac": 0.7,
        "max_pin_degree": 128})
    graph, _, _ = graphgen.generate(spec, 21)
    arrays = {f"{side}_{part}": getattr(getattr(graph, side), field)
              for side in ("p2b", "b2p")
              for part, field in (("offsets", "offsets"),
                                  ("targets", "targets"),
                                  ("feat_bounds", "feat_bounds"))}
    hg = reference.host_graph(arrays, spec.n_pins, spec.max_pin_degree)
    params = rankgen.ranker_params(2**31 + 5, spec.n_pins, RANKER)
    walk = {"n_steps": 8192, "n_walkers": 128, "top_k": 64, "alpha": 0.3,
            "n_p": 64, "n_v": 4, "bias_beta": 0.9}
    rng = np.random.default_rng(4)
    live = np.flatnonzero(np.diff(hg.p2b_off))
    answers = []
    for rid in range(8):
        pins = np.full(8, -1, np.int32)
        pins[:3] = rng.choice(live, 3, replace=False)
        weights = np.where(pins >= 0, 1.0, 0.0).astype(np.float32)
        answers.append(reference.recommend(
            hg, pins, weights, rid % 4, reference.request_key(9, rid), walk,
            8))
    return graph, hg, params, answers


def _candidates(answers, k):
    """Each answer's top ``k`` by (float32 score, pin id), as the program's
    stage-1 top-k hands them to stage 2."""
    ids = np.zeros((len(answers), k), np.int32)
    scores = np.zeros((len(answers), k), np.float32)
    for r, a in enumerate(answers):
        order = np.lexsort((a.ids, -a.scores32))[:k]
        ids[r, :order.size] = a.ids[order]
        scores[r, :order.size] = a.scores32[order]
    return ids, scores


def _served(graph, params, answers, head, n_candidates=64, scenario=None,
            square_scores=False):
    """The program's ``rank_candidates`` on the reference's candidates."""
    from repro.serving import ranker as ranker_lib

    rcfg = ranker_lib.RankerConfig(
        n_items=graph.n_pins, **dict(RANKER, n_candidates=n_candidates,
                                     scenarios=tuple(RANKER["scenarios"])))
    ids, scores = _candidates(answers, n_candidates)
    if square_scores:       # q weights sqrt(s**2) = s: the sqrt dropped
        scores = scores * scores
    h = RANKER["scenarios"].index(head) if scenario is None else scenario
    out_scores, out_ids = ranker_lib.rank_candidates(
        params, rcfg, graph, jnp.asarray(ids), jnp.asarray(scores),
        jnp.full((len(answers),), h, jnp.int32))
    return np.asarray(out_scores), np.asarray(out_ids)


def _gaps(hg, params, answers, head, served):
    st = reference.stage2(jax.device_get(params), RANKER, head)
    return np.array([reference.rank_gap(s, i, reference.rank(hg, a, st))
                     for s, i, a in zip(*served, answers)])


@pytest.mark.parametrize("head", RANKER["scenarios"])
def test_stage_two_reference_matches_the_ranker(stage_one, head):
    from pixiebench import check

    graph, hg, params, answers = stage_one
    gaps = _gaps(hg, params, answers, head,
                 _served(graph, params, answers, head))
    assert gaps.max() <= CHECK["rank_gap_tol"] / 10
    ok, _ = check.judge(gaps, 0, CHECK, ranked=True)
    assert ok


def _stride_changed(graph, hg, params, answers, head, monkeypatch):
    """The reference put in the program's place, its fan stride 37."""
    st = reference.stage2(jax.device_get(params), RANKER, head)
    monkeypatch.setattr(reference, "FAN_STRIDE", 37)
    got = [reference.rank(hg, a, st) for a in answers]
    return (np.stack([g.scores for g in got]), np.stack([g.ids for g in got]))


def _heads_in_bfloat16(graph, hg, params, answers, head, monkeypatch):
    """The control: the reference with its heads' products in bfloat16."""
    import ml_dtypes

    st = reference.stage2(jax.device_get(params), RANKER, head)
    got = [reference.rank(hg, a, st, head_dtype=ml_dtypes.bfloat16)
           for a in answers]
    return (np.stack([g.scores for g in got]), np.stack([g.ids for g in got]))


@pytest.mark.parametrize("fault", [
    lambda g, hg, p, a, head, mp: _served(g, p, a, head, scenario=0),
    _stride_changed,
    lambda g, hg, p, a, head, mp: _served(g, p, a, head, square_scores=True),
    _heads_in_bfloat16,
    lambda g, hg, p, a, head, mp: _served(g, p, a, head, n_candidates=63),
], ids=["scenario_ignored", "fan_stride_changed", "query_sqrt_dropped",
        "heads_in_bfloat16", "one_candidate_too_few"])
def test_ranked_check_fails_each_fault(stage_one, monkeypatch, fault):
    from pixiebench import check

    graph, hg, params, answers = stage_one
    head = "homefeed"
    served = fault(graph, hg, params, answers, head, monkeypatch)
    monkeypatch.undo()
    gaps = _gaps(hg, params, answers, head, served)
    ok, numbers = check.judge(gaps, 0, CHECK, ranked=True)
    assert not ok
    assert numbers["mismatch_share"]["value"] >= 0.5
