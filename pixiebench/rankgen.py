"""The stage-2 ranker's parameters, made on the device from the seed.

A configuration with a ``ranker`` section serves Pixie's candidates through
``serving/ranker.py``'s scenario heads.  The benchmark makes the
parameters itself, in the structure ``RankRequest`` takes (an item table
and one head per scenario, stacked on a leading axis in the order of the
configuration's ``scenarios``), in one jitted call in float32, the type they
are served in:

  * ``items`` (n_pins, d): normal, standard deviation 0.02 (the repo's
    embedding initialisation);
  * ``w_self``, ``w_neigh``, ``w_query`` (heads, d, d): normal over
    ``sqrt(d)`` (fan-in scaling);
  * ``b`` (heads, d): normal, standard deviation 0.02, so that the bias
    takes part in every score the check compares.

The key is ``fold_in(key(server_seed), RANKER_STREAM)``: the server's own
root key (``run.set_up``'s ``server_seed``, the seed modulo 2**31 - 1),
folded with a tag no request id of a run reaches.  The same seed gives the
same parameters.
"""

from __future__ import annotations

import functools
from typing import Dict

RANKER_STREAM = 2**31 - 2


@functools.lru_cache(maxsize=None)
def _params_fn(n_items: int, d: int, n_heads: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        k_items, k_self, k_neigh, k_query, k_b = jax.random.split(key, 5)
        normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
        head = lambda k: normal(k, (n_heads, d, d)) / jnp.sqrt(float(d))
        return {
            "items": 0.02 * normal(k_items, (n_items, d)),
            "heads": {"w_self": head(k_self), "w_neigh": head(k_neigh),
                      "w_query": head(k_query),
                      "b": 0.02 * normal(k_b, (n_heads, d))},
        }

    return jax.jit(make)


def ranker_params(server_seed: int, n_items: int, ranker: Dict) -> Dict:
    """Device parameters for the configuration's ``ranker`` section."""
    import jax

    key = jax.random.fold_in(jax.random.key(server_seed), RANKER_STREAM)
    return _params_fn(int(n_items), int(ranker["d_model"]),
                      len(ranker["scenarios"]))(key)
