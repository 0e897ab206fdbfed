"""The served path's kernels compile for a TPU v5e at deployment widths.

Nothing here runs: each test lowers one kernel entry point with
``interpret=False`` against a *described* v5e chip (no chip attached) and
asks the TPU compiler for the executable, so a kernel the chip's compiler
would refuse — an unaligned block, a vector indexed by a loop counter, a
load from an HBM ref — fails here instead of on the chip.  Widths are the
served configuration's: ``FULL.walk`` (8192 walkers per query) for a
serving batch of 8 queries x 8 slots, 256-walker blocks, and the CSR of
the ``serve_200m_replicated`` deployment (140M pins, 60M boards, 1.2B
edges per side) left in HBM.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.pixie import FULL, PIXIE_SHAPES
from repro.kernels import embedding_bag, visit_counter, walk_step
from repro.serving.ranker import RankerConfig

_DEPLOY = {c.name: c.params for c in PIXIE_SHAPES}["serve_200m_replicated"]
N_PINS, N_BOARDS = _DEPLOY["n_pins"], _DEPLOY["n_boards"]
N_EDGES = _DEPLOY["n_edges"]
N_SLOTS = _DEPLOY["n_slots"]
BATCH = 8
WALKERS = BATCH * FULL.walk.n_walkers
CHUNK = FULL.walk.chunk_steps
EVENTS = CHUNK * WALKERS
N_FEATS = 4                       # synthetic graphs' languages
# the batch's dense count bins must fit int32 indexing; 16M pins keeps
# prior + new counts (2 x 4 GiB) inside one chip's 16 GiB
COUNT_PINS = 16_000_000
RANKER = RankerConfig(n_items=1_000_000)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip):
    """``compile(fn, *shapes)`` -> HLO text of the chip executable.

    The persistent compilation cache is off meanwhile: an executable for a
    described chip is written but can never be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache

    def shapes(*specs):
        return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]

    def compile_(fn, *specs):
        return jax.jit(fn).lower(*shapes(*specs)).compile().as_text()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
CSR = [((N_PINS + 1,), i32), ((N_EDGES,), i32),
       ((N_BOARDS + 1,), i32), ((N_EDGES,), i32)]


@pytest.mark.parametrize("gather_mode", ["scalar", "dma"])
def test_walk_steps_fused_compiles(compile_for_chip, gather_mode):
    def fn(curr, query, feat, slot, qid, rbits, *csr_and_bounds):
        # the bounds arrive flat: a (rows, n_feats + 1) argument would be
        # padded to 128 lanes in HBM; the kernel reads them flat again
        *csr, p2b_fb, b2p_fb = csr_and_bounds
        return walk_step.walk_steps_fused(
            curr, query, feat, slot, rbits, *csr,
            p2b_fb.reshape(N_PINS, -1), b2p_fb.reshape(N_BOARDS, -1), qid,
            n_pins=N_PINS, n_slots=N_SLOTS, n_queries=BATCH,
            alpha_u32=2**31, beta_u32=2**31,
            block_w=walk_step.DEFAULT_BLOCK_W,
            gather_mode=gather_mode, interpret=False,
        )

    hlo = compile_for_chip(
        fn, *[((WALKERS,), i32)] * 5, ((CHUNK, WALKERS, 4), u32), *CSR,
        ((N_PINS * (N_FEATS + 1),), i32), ((N_BOARDS * (N_FEATS + 1),), i32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("gather_mode", ["scalar", "dma"])
def test_walk_hop_fused_compiles(compile_for_chip, gather_mode):
    def fn(pos, gate, r, row_base, offsets, targets):
        return walk_step.walk_hop_fused(
            pos, gate, r, row_base, offsets, targets,
            gather_mode=gather_mode, interpret=False,
        )

    hlo = compile_for_chip(
        fn, ((WALKERS,), i32), ((WALKERS,), jnp.bool_), ((WALKERS,), u32),
        ((1,), i32), ((N_PINS + 1,), i32), ((N_EDGES,), i32),
    )
    assert "tpu_custom_call" in hlo


def test_visit_counter_update_high_compiles(compile_for_chip):
    def fn(prior, q_ev, s_ev, p_ev):
        return visit_counter.visit_counter_update_high(
            prior, s_ev, p_ev, q_ev, n_slots=N_SLOTS, n_pins=COUNT_PINS,
            n_v=FULL.walk.n_v, n_queries=BATCH, interpret=False,
        )

    hlo = compile_for_chip(
        fn, ((BATCH * N_SLOTS * COUNT_PINS,), i32), *[((EVENTS,), i32)] * 3
    )
    assert "tpu_custom_call" in hlo


def test_visit_counter_wide_compiles(compile_for_chip):
    def fn(q_ev, s_ev, p_ev):
        return visit_counter.visit_counter_wide(
            s_ev, p_ev, q_ev, n_slots=N_SLOTS, n_dim=COUNT_PINS,
            n_queries=BATCH, interpret=False,
        )

    hlo = compile_for_chip(fn, *[((EVENTS,), i32)] * 3)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "bags",
    [
        # candidate side: each candidate's 2-hop neighbourhood
        (BATCH, RANKER.n_candidates, RANKER.n_neighbors),
        # query side: the retrieved set as one bag per query
        (BATCH, 1, RANKER.n_candidates),
    ],
    ids=["candidates", "query"],
)
def test_embedding_bag_batched_compiles(compile_for_chip, bags):
    def fn(table, ids, weights):
        return embedding_bag.embedding_bag_batched(
            table, ids, weights, mode="mean", interpret=False
        )

    hlo = compile_for_chip(
        fn, ((RANKER.n_items, RANKER.d_model), f32), (bags, i32), (bags, f32)
    )
    assert "tpu_custom_call" in hlo
