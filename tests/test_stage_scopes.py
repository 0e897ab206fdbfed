"""Stage scopes of the serving step and the serving counters.

* Every ``pixie.*`` ``named_scope`` stage reaches the compiled program's
  ``op_name`` metadata on both walk engines (and ``pixie.rank`` on a
  ranked step), where a profiler's op events can name them.
* Scopes and ``with_stats=True`` leave ``(scores, ids)`` bit-identical.
* ``QueryResult.steps_taken`` is ``serve_batch(with_stats=True)``'s walk
  steps summed over the request's slots; ``ServerStats`` counts lanes
  dispatched and filled, and steps taken and budgeted, right on a
  partial batch.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import service, walk as walk_lib
from repro.graphs.synthetic import small_test_graph, top_degree_pins
from repro.serving import ranker as ranker_lib
from repro.serving.server import PixieServer

WALK_STAGES = {"pixie.query", "pixie.walk", "pixie.walk.hop",
               "pixie.walk.count", "pixie.eq3", "pixie.topk"}
CFG = walk_lib.WalkConfig(n_steps=3_000, n_walkers=128, chunk_steps=8,
                          top_k=20, n_p=60, n_v=3)


@pytest.fixture(scope="module")
def sg():
    return small_test_graph()


def _batch(sg, batch=4, n_slots=2):
    qs = top_degree_pins(sg, 2 * batch)
    pins = np.full((batch, n_slots), -1, np.int32)
    weights = np.zeros((batch, n_slots), np.float32)
    for i in range(batch):
        pins[i] = [int(qs[2 * i]), int(qs[2 * i + 1])]
        weights[i] = [1.0, 0.6]
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(5), i))(
        jnp.arange(batch))
    return (jnp.asarray(pins), jnp.asarray(weights),
            jnp.zeros((batch,), jnp.int32), keys)


def _stages_in(fn, *args):
    """The innermost ``pixie.*`` stage of every op_name in ``fn``'s
    compiled program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        stages = re.findall(r"pixie\.[a-z0-9_.]*[a-z0-9_]", name)
        if stages:
            found.add(stages[-1])
    return found


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_compiled_step_carries_every_walk_stage(sg, backend):
    pins, weights, feats, keys = _batch(sg)

    def step(p, w, f, k):
        return service.serve_batch(sg.graph, p, w, f, k, CFG,
                                   backend=backend, with_stats=True)

    assert _stages_in(step, pins, weights, feats, keys) == WALK_STAGES


def test_ranked_step_carries_the_rank_stage(sg):
    rcfg = ranker_lib.RankerConfig(n_items=sg.graph.n_pins, d_model=16,
                                   n_neighbors=4, n_candidates=16,
                                   final_k=8)
    rank = ranker_lib.RankRequest(
        ranker_lib.init_ranker_params(jax.random.key(7), rcfg), rcfg)
    pins, weights, feats, keys = _batch(sg)

    def step(p, w, f, k):
        return service.serve_batch(sg.graph, p, w, f, k, CFG, rank=rank)

    assert _stages_in(step, pins, weights, feats, keys) == (
        WALK_STAGES | {"pixie.rank"})


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_stats_leave_answers_bit_identical(sg, backend):
    args = _batch(sg)
    plain = service.serve_batch(sg.graph, *args, CFG, backend=backend)
    stats = service.serve_batch(sg.graph, *args, CFG, backend=backend,
                                with_stats=True)
    for a, b in zip(plain, stats[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_query_result_steps_and_counters_on_a_partial_batch(sg):
    seed, batch, slots = 3, 4, 2
    server = PixieServer(sg.graph, CFG, batch_size=batch, n_slots=slots,
                         seed=seed)
    pins, weights, feats, _ = _batch(sg, batch, slots)
    n_real = 3
    rids = [server.submit(pins[i].tolist(), weights[i].tolist())
            for i in range(n_real)]
    out = {r.req_id: r for r in server.flush()}
    assert server.stats.lanes_dispatched == batch
    assert server.stats.lanes_filled == n_real

    # the same batch, padded as the server pads it, served directly
    key = jax.random.key(seed)
    keys = jnp.stack(
        [jax.random.fold_in(key, r) for r in rids]
        + [jax.random.fold_in(key, jnp.iinfo(jnp.int32).max)])
    pad_pins = pins.at[n_real:].set(-1)
    pad_weights = weights.at[n_real:].set(0.0)
    scores, ids, steps, _ = service.serve_batch(
        sg.graph, pad_pins, pad_weights, feats, keys, CFG,
        step_budgets=jnp.full((batch,), CFG.n_steps, jnp.int32),
        with_stats=True)
    steps = np.asarray(steps).sum(axis=1)
    for i, rid in enumerate(rids):
        res = out[rid]
        assert res.steps_taken == int(steps[i]) > 0
        assert res.budget == CFG.n_steps
        np.testing.assert_array_equal(res.ids, np.asarray(ids[i]))
        np.testing.assert_array_equal(res.scores, np.asarray(scores[i]))
    assert server.stats.steps_taken == int(steps[:n_real].sum())
    assert server.stats.steps_budgeted == n_real * CFG.n_steps
