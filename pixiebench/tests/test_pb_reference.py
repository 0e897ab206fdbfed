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
