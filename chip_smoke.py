"""Smoke run of the Pixie serving path on a TPU, through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the node-range-sharded replica

One chip: builds the benchmarks' "large" synthetic graph (100k pins, 10k
boards, 24 topics, seed 7), prunes it as ``examples/serve_fleet.py`` does,
and serves 16 homefeed-shaped requests (decayed user action histories, up
to 8 query pins) through ``PixieServer`` with the full serving walk
(``configs.pixie.FULL.walk``: 8192 walkers, 200k steps, top 1000) and a
batch of 8: on the batch-native Pallas engine in both gather modes, and on
a two-stage (ranked) replica.  A ``backend="xla"`` replica serves the same
requests as the oracle, and every request's ids and scores must equal it.

Four chips: ``serve_batch`` over ``shard_graph(graph, 4, mesh)`` on a
``("model",)`` mesh with the Pallas engine, against the unsharded batched
engine on the same graph: identical results with no routing drops, and
each chip holding only its own rows of the CSR.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.  The wall times it prints are smoke timings of one
cold run, not metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

N_REQUESTS = 16
BATCH = 8
N_SLOTS = 8
N_SHARDS = 4
SEED = 0
ACTIONS = ("save", "click", "view")


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform}"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"--chips {n_chips} needs {n_chips} chips; JAX found "
            f"{len(devices)}"
        )
    return devices


def build_graph():
    """The benchmarks' "large" graph, pruned as the serving example does."""
    from benchmarks.common import bench_graph
    from repro.core import pruning

    sg = bench_graph("large")
    graph, _ = pruning.prune_graph(
        sg.graph, sg.pin_topics, None,
        pruning.PruneConfig(entropy_board_frac=0.1, delta=0.9),
        board_lang=sg.board_lang, pin_lang=sg.pin_lang, n_langs=4,
    )
    return graph


def homefeed_requests(graph, n: int, seed: int):
    """``n`` (pins, weights, user_feat) queries from user action histories
    over the graph's most-saved pins (the Homefeed query shape, §5.1)."""
    from repro.core import service

    rng = np.random.default_rng(seed)
    hot = np.argsort(-np.asarray(graph.p2b.degrees()))[:500]
    requests = []
    for _ in range(n):
        history = [
            service.UserAction(
                pin=int(rng.choice(hot)),
                action=str(rng.choice(ACTIONS)),
                age_hours=float(rng.exponential(12.0)),
            )
            for _ in range(rng.integers(1, 2 * N_SLOTS))
        ]
        pins, weights = service.build_query(history, n_slots=N_SLOTS)
        keep = pins >= 0
        requests.append(
            (pins[keep].tolist(), weights[keep].tolist(),
             int(rng.integers(0, 4)))
        )
    return requests


def serve_twice(server, requests, scenario: int = 0):
    """Serve the requests twice under the same ids (the first round
    compiles); returns the second round's results and both wall times."""
    rounds, walls = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        for rid, (pins, weights, feat) in enumerate(requests):
            server.submit(pins, weights, user_feat=feat, req_id=rid,
                          scenario=scenario)
        rounds.append(server.flush())
        walls.append(time.perf_counter() - t0)
    check_equal("second round vs first", rounds[1], rounds[0])
    return rounds[1], walls


def check_equal(what: str, got, want) -> None:
    if len(got) != len(want):
        raise AssertionError(f"{what}: {len(got)} results vs {len(want)}")
    for a, b in zip(got, want):
        if a.req_id != b.req_id:
            raise AssertionError(f"{what}: request {a.req_id} vs {b.req_id}")
        if not np.array_equal(a.ids, b.ids):
            raise AssertionError(f"{what}: request {a.req_id} ids differ")
        if not np.array_equal(a.scores, b.scores):
            raise AssertionError(f"{what}: request {a.req_id} scores differ")


def check_scored(what: str, results, n_pins: int) -> None:
    for r in results:
        scores, ids = np.asarray(r.scores), np.asarray(r.ids)
        if not np.isfinite(scores).all() or scores.max() <= 0:
            raise AssertionError(f"{what}: request {r.req_id} has no scores")
        hit = ids[scores > 0]
        if hit.min() < 0 or hit.max() >= n_pins:
            raise AssertionError(f"{what}: request {r.req_id} ids off graph")


def kernel_calls(graph, cfg, rank=None) -> int:
    """Mosaic kernels in the lowered serving program at the served shapes
    (what ``PixieServer`` dispatches for one bucket)."""
    import jax
    import jax.numpy as jnp

    from repro.core import service
    from repro.serving.ranker import RankRequest

    pins = jnp.zeros((BATCH, N_SLOTS), jnp.int32)
    weights = jnp.ones((BATCH, N_SLOTS), jnp.float32)
    lane = jnp.zeros((BATCH,), jnp.int32)
    keys = jax.random.split(jax.random.key(SEED), BATCH)
    if rank is None:
        def fn(g, p, w, f, k, params):
            return service.serve_batch(g, p, w, f, k, cfg, step_budgets=f)
        params = None
    else:
        def fn(g, p, w, f, k, params):
            return service.serve_batch(
                g, p, w, f, k, cfg, rank=RankRequest(params, rank.cfg),
                scenario=f,
            )
        params = rank.params
    text = jax.jit(fn).lower(graph, pins, weights, lane, keys, params)
    return text.as_text().count("tpu_custom_call")


def one_chip(graph, walk_cfg, requests) -> None:
    """PixieServer on the Pallas engine, both gather modes, plus a ranked
    replica, each against the ``backend="xla"`` oracle."""
    import jax

    from repro.core import walk as walk_lib
    from repro.serving.ranker import (
        RankerConfig, RankRequest, init_ranker_params,
    )
    from repro.serving.server import PixieServer

    if not walk_lib.batched_engine_fits(
        BATCH, N_SLOTS, graph.n_pins, graph.n_boards, walk_cfg.count_boards
    ):
        raise AssertionError("the batch-native engine does not fit")
    rcfg = RankerConfig(n_items=graph.n_pins)
    rank = RankRequest(init_ranker_params(jax.random.key(SEED + 1), rcfg),
                       rcfg)
    homefeed = rcfg.scenario_id("homefeed")
    cells = [
        ("retrieval", "scalar", None),
        ("retrieval", "dma", None),
        ("ranked", "scalar", rank),
    ]
    oracles = {}
    for kind, mode, ranker in cells:
        cfg = dataclasses.replace(walk_cfg, gather_mode=mode)
        scenario = 0 if ranker is None else homefeed
        if kind not in oracles:
            xla = PixieServer(graph, walk_cfg, batch_size=BATCH,
                              n_slots=N_SLOTS, seed=SEED, backend="xla",
                              ranker=ranker)
            want, walls = serve_twice(xla, requests, scenario)
            check_scored(f"{kind} xla", want, graph.n_pins)
            oracles[kind] = want
            log(f"{kind} xla oracle: {len(want)} requests; smoke timings "
                f"{walls[0]:.2f} s cold (compile + serve), {walls[1]:.2f} s "
                "warm")
        n_kernels = kernel_calls(
            graph, dataclasses.replace(cfg, backend="pallas"), ranker
        )
        if n_kernels == 0:
            raise AssertionError(f"{kind} {mode}: no Mosaic kernel lowered")
        srv = PixieServer(graph, cfg, batch_size=BATCH, n_slots=N_SLOTS,
                          seed=SEED, backend="pallas", ranker=ranker)
        got, walls = serve_twice(srv, requests, scenario)
        check_equal(f"{kind} pallas/{mode} vs xla", got, oracles[kind])
        log(f"{kind} pallas/{mode}: {len(got)} requests identical to the "
            f"xla oracle; {n_kernels} Mosaic kernel calls; smoke timings "
            f"{walls[0]:.2f} s cold (compile + serve), {walls[1]:.2f} s warm")


def four_chips(graph, walk_cfg, requests) -> None:
    """The node-range-sharded replica against the unsharded batched
    engine, on one batch of the requests."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as dist_lib
    from repro.core import service
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((N_SHARDS,), ("model",))
    t0 = time.perf_counter()
    shg = dist_lib.shard_graph(graph, N_SHARDS, mesh)
    for name in ("p2b_offsets", "p2b_targets", "b2p_offsets", "b2p_targets"):
        arr = getattr(shg, name)
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        if devices != set(mesh.devices.flat) or any(
            s.data.shape[0] != 1 for s in shards
        ):
            raise AssertionError(f"{name} is not one shard row per chip")
    log(f"sharded graph placed in {time.perf_counter() - t0:.2f} s: each of "
        f"{N_SHARDS} chips holds one shard row ({shg.pins_per_shard} pins, "
        f"{shg.boards_per_shard} boards)")

    cfg = dataclasses.replace(walk_cfg, backend="pallas", bias_beta=0.0)
    pins = np.full((BATCH, N_SLOTS), -1, np.int32)
    weights = np.zeros((BATCH, N_SLOTS), np.float32)
    feats = np.zeros((BATCH,), np.int32)
    for b, (p, w, f) in enumerate(requests[:BATCH]):
        pins[b, :len(p)], weights[b, :len(w)], feats[b] = p, w, f
    args = (jnp.asarray(pins), jnp.asarray(weights), jnp.asarray(feats),
            jax.random.split(jax.random.key(SEED), BATCH))

    unsharded = jax.jit(
        lambda g, *a: service.serve_batch(g, *a, cfg, with_stats=True)
    )
    t0 = time.perf_counter()
    want = jax.block_until_ready(unsharded(graph, *args))
    log(f"unsharded batched engine: smoke timing "
        f"{time.perf_counter() - t0:.2f} s cold")

    with jax.set_mesh(mesh):
        sharded = jax.jit(
            lambda *a: service.serve_batch(
                shg, *a, cfg, with_stats=True, mesh=mesh,
                slack=2.0 * N_SHARDS,
            )
        )
        t0 = time.perf_counter()
        got = jax.block_until_ready(sharded(*args))
    log(f"sharded pallas engine: smoke timing "
        f"{time.perf_counter() - t0:.2f} s cold")
    dropped = int(got[4])
    if dropped:
        raise AssertionError(f"sharded serve dropped {dropped} walkers")
    for name, a, b in zip(("scores", "ids", "steps", "n_high"), got, want):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(f"sharded {name} differ from unsharded")
    log(f"sharded == unsharded for {BATCH} queries (scores, ids, steps, "
        "n_high), 0 walkers dropped")
    for d in mesh.devices.flat:
        log(f"{d}: bytes_in_use {(d.memory_stats() or {}).get('bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded replica and its oracle")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = require_tpu(args.chips)
    dev = devices[0]
    log(f"device_kind {dev.device_kind}, {len(devices)} chip(s); compile "
        f"cache {cache}")

    from repro.configs.pixie import FULL, PIXIE_SHAPES

    t0 = time.perf_counter()
    graph = build_graph()
    n_edges = int(graph.p2b.targets.shape[0])
    deploy = {c.name: c.params for c in PIXIE_SHAPES}["serve_200m_replicated"]
    log(f"graph {graph.n_pins} pins, {graph.n_boards} boards, {n_edges} "
        f"edges per side, built in {time.perf_counter() - t0:.1f} s; cut "
        f"from serve_200m_replicated ({deploy['n_pins']} pins, "
        f"{deploy['n_boards']} boards, {deploy['n_edges']} edges) by "
        f"{deploy['n_pins'] / graph.n_pins:.0f}x pins, "
        f"{deploy['n_edges'] / n_edges:.0f}x edges")
    requests = homefeed_requests(graph, N_REQUESTS, SEED)

    if args.chips == 4:
        four_chips(graph, FULL.walk, requests)
    else:
        one_chip(graph, FULL.walk, requests)
    log(f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
