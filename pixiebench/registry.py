"""Everything the harness runs, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's file is
the one ``BENCHMARK.json`` gives, the mix is ``traffic/<name>.json`` and
each metric is read by ``metrics/<name>.py`` (a module with
``read(run) -> float | None``).  Adding a cell, a mix or a metric is adding
files and entries; no code here names any of them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: Dict, name: str) -> Dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: Dict, name: str, root: Path = ROOT) -> Dict:
    entry = _named(bench["configs"], name, "config")
    with open(root / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"pixiebench_metric_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Dict, cell_name: str,
             reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return reported is None or metric["moves"] in reported


def metrics_for(bench: Dict, cell_name: str, trace: bool) -> List[Dict]:
    """The end-to-end metrics the cell reports (``trace`` false) or its
    per-layer metrics (``trace`` true)."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if _applies(m, cell_name, reported)]
