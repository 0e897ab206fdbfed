"""Shared benchmark substrate: one synthetic graph + helpers, reused by all
paper-table benchmarks so the suite builds the graph once."""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import walk as walk_lib
from repro.graphs.synthetic import SyntheticGraph, SyntheticGraphConfig, generate

BENCH_SERVING_PATH = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "BENCH_serving.json"
)

# sections other suites merge into BENCH_serving.json; bench_smoke (which
# rewrites the base file) preserves exactly this list, so registering a new
# merged suite means adding its section name HERE, nowhere else
MERGED_SECTIONS = (
    "widepack", "dma", "batchfuse", "sharded", "traffic", "two_stage",
    "multi_interest", "chaos",
)


def merge_serving_section(name: str, payload: Dict) -> str:
    """Merge one suite's section into BENCH_serving.json; returns the path.

    The file may not exist yet (suite run before bench_smoke) or may be
    unreadable — either way the section still lands.
    """
    data: Dict = {}
    if os.path.exists(BENCH_SERVING_PATH):
        try:
            with open(BENCH_SERVING_PATH) as f:
                data = json.load(f)
        except Exception:
            data = {}
    data[name] = payload
    with open(BENCH_SERVING_PATH, "w") as f:
        json.dump(data, f, indent=2)
    return BENCH_SERVING_PATH


def run_multi_device(
    module: str, n_devices: int, seed: int, body: Callable[[int], Dict]
) -> Dict:
    """``body(seed)`` on ``n_devices`` devices, one process per chip.

    Where this process already sees ``n_devices`` devices, the body runs
    here.  A CPU host with fewer re-executes ``python -m <module> --child
    --seed <seed>`` with that many forced host devices and returns the
    child's last JSON line.  An accelerator host with fewer refuses: this
    process now holds its chips, so a child could not reach them.
    """
    if len(jax.devices()) >= n_devices:
        return body(seed)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{module} needs {n_devices} devices; this "
            f"{jax.default_backend()} host has {len(jax.devices())}, and a "
            "child process cannot share the chips this one holds"
        )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), repo, env.get("PYTHONPATH", "")]
    )
    proc = subprocess.run(
        [sys.executable, "-m", module, "--child", "--seed", str(seed)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"{module} child failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


@functools.lru_cache(maxsize=2)
def bench_graph(scale: str = "small") -> SyntheticGraph:
    if scale == "small":
        cfg = SyntheticGraphConfig(
            n_pins=20_000, n_boards=2_000, n_topics=16, n_langs=4, seed=7
        )
    else:
        cfg = SyntheticGraphConfig(
            n_pins=100_000, n_boards=10_000, n_topics=24, n_langs=4, seed=7
        )
    return generate(cfg)


def timed(fn, *args, warmup: int = 1, iters: int = 3) -> Dict[str, float]:
    for _ in range(warmup):
        out = fn(*args)
        jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            out,
        )
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            out,
        )
        times.append(time.perf_counter() - t0)
    return {"mean_ms": 1e3 * float(np.mean(times)),
            "min_ms": 1e3 * float(np.min(times))}


def sample_query_pins(sg: SyntheticGraph, n: int, seed: int = 0) -> np.ndarray:
    """Query pins sampled weighted by degree (active pins, like real queries)."""
    rng = np.random.default_rng(seed)
    degs = np.asarray(sg.graph.p2b.degrees()).astype(np.float64)
    p = degs / degs.sum()
    return rng.choice(sg.graph.n_pins, size=n, replace=False, p=p).astype(np.int32)
