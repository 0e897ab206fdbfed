"""Cells, configurations, mixes and metrics are found by name (CPU)."""

import json

import pytest

from pixiebench import registry


def test_committed_benchmark_resolves():
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        cfg = registry.config(bench, cell["config"])
        assert cfg["name"] == cell["config"]
        registry.traffic(cell["traffic"])
        for trace in (False, True):
            for m in registry.metrics_for(bench, cell["name"], trace):
                assert callable(registry.metric_reader(m["name"]))
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def _added(tmp_path):
    """A benchmark tree with one new config, mix and metric, as files."""
    (tmp_path / "pixiebench" / "configs").mkdir(parents=True)
    (tmp_path / "pixiebench" / "traffic").mkdir()
    (tmp_path / "pixiebench" / "metrics").mkdir()
    (tmp_path / "pixiebench" / "configs" / "new-cfg.json").write_text(
        json.dumps({"name": "new-cfg", "n_slots": 3}))
    (tmp_path / "pixiebench" / "traffic" / "bursty.json").write_text(
        json.dumps({"payload": "single_pin", "rate_rps": 9.0}))
    (tmp_path / "pixiebench" / "metrics" / "batch_fill.py").write_text(
        "def read(run):\n    return run.answer * 2\n")
    bench = {
        "configs": [{"name": "new-cfg",
                     "file": "pixiebench/configs/new-cfg.json"}],
        "workloads": [{"name": "new-cell", "config": "new-cfg",
                       "traffic": "bursty", "chips": 1}],
        "end_to_end": [{"name": "p50_ms"},
                       {"name": "tokens", "workloads": ["other"]}],
        "per_layer": [
            {"name": "batch_fill", "moves": "p50_ms"},
            {"name": "walk_steps", "moves": "tokens"},
            {"name": "only_there", "moves": "p50_ms",
             "workloads": ["other"]},
        ],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_added_files_are_found_by_name(tmp_path):
    root = _added(tmp_path)
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, "new-cell")
    assert registry.config(bench, cell["config"], root)["n_slots"] == 3
    mix = registry.traffic(cell["traffic"], root / "pixiebench")
    assert mix["rate_rps"] == 9.0
    read = registry.metric_reader("batch_fill", root / "pixiebench")

    class R:
        answer = 21

    assert read(R) == 42


def test_metric_selection_follows_workloads_and_moves(tmp_path):
    bench = registry.load_benchmark(_added(tmp_path))
    e2e = [m["name"] for m in registry.metrics_for(bench, "new-cell", False)]
    layer = [m["name"] for m in registry.metrics_for(bench, "new-cell", True)]
    assert e2e == ["p50_ms"]
    # walk_steps moves a metric this cell does not report; only_there is
    # listed for another cell
    assert layer == ["batch_fill"]


def test_unknown_names_are_errors(tmp_path):
    root = _added(tmp_path)
    bench = registry.load_benchmark(root)
    with pytest.raises(KeyError):
        registry.cell(bench, "nope")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("nope", root / "pixiebench")
