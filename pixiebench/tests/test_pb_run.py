"""A whole run on the CPU at a tiny size: refusal without a TPU, the check
passing on the program as it is and failing on each fault it must catch,
and the control (the reference with a broken guarantee) failing."""

import copy
import json
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pixiebench import check, graphgen, reference, registry, run
from repro.serving.server import PixieServer

METRICS = [{"name": n, "unit": "x"} for n in
           ("p50_ms", "latency_p95_ms", "completed_rps", "setup_s")]


def _tiny():
    bench = registry.load_benchmark()
    cfg = copy.deepcopy(registry.config(bench, "pixie-homefeed-10m"))
    cfg["graph"].update(n_pins=4000, n_boards=1714, edge_draws=40000,
                        max_pin_degree=128)
    cfg["walk"].update(n_steps=8192, n_walkers=128, top_k=32, n_p=64)
    cfg["check"].update(sample=8, widest=2, min_compared=8)
    mix = dict(registry.traffic("homefeed"), rate_rps=16.0)
    return cfg, mix


def test_main_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "homefeed-overload", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in out.err
    assert "{" not in out.out


def test_bare_benchmark_directory_fails(tmp_path):
    root = registry.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "pixiebench", tmp_path / "pixiebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "-m", "pixiebench.run", "--workload",
         "homefeed-overload", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)},
    )
    assert p.returncode != 0
    assert "{" not in p.stdout


def _altered(server):
    serve = server._serve

    def altered(*args):
        scores, ids = serve(*args)
        return scores, jnp.roll(ids, 1, axis=1)

    server._serve = altered


def _one_row_altered(server):
    """Shifts the answer of the first real request of each batch only."""
    serve = server._serve

    def one_row(*args):
        scores, ids = serve(*args)
        real = args[1][:, 0] >= 0
        first = real & (jnp.cumsum(real) == 1)
        return scores, jnp.where(first[:, None], jnp.roll(ids, 1, axis=1),
                                 ids)

    server._serve = one_row


def _half_left_out(server):
    """Serves only the first half (rounded down) of each batch's requests;
    the rest get empty answers."""
    serve = server._serve

    def half(*args):
        scores, ids = serve(*args)
        real = args[1][:, 0] >= 0
        rank = jnp.cumsum(real) - 1
        out = real & (rank >= jnp.sum(real) // 2)
        return (jnp.where(out[:, None], 0.0, scores),
                jnp.where(out[:, None], 0, ids))

    server._serve = half


@pytest.fixture
def on_cpu(monkeypatch):
    """Lets a run go on without a TPU and without the compile cache."""
    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks_for", lambda kind: {})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    return monkeypatch


@pytest.mark.parametrize("fault,least", [
    (None, None), (_altered, 0.5), (_one_row_altered, 1 / 16),
    (_half_left_out, 0.5),
], ids=["as_is", "answer_altered", "one_row_altered",
        "half_batch_left_out"])
def test_check_passes_as_is_and_catches_faults(on_cpu, fault, least):
    if fault is not None:
        build = PixieServer._build_serve

        def build_then_break(self):
            build(self)
            fault(self)

        on_cpu.setattr(PixieServer, "_build_serve", build_then_break)
    cfg, mix = _tiny()
    out = run.run_cell({"name": "tiny", "chips": 1}, cfg, mix, 2**33 + 9,
                       1.0, False, METRICS)
    assert out["correct"] is (least is None)
    assert list(out)[-1] == "check"
    assert out["failed"] == 0 and out["attempted"] == 16
    share = out["check"]["mismatch_share"]["value"]
    assert (share == 0.0) if least is None else (share >= least)
    json.dumps(out)


def test_control_breaking_the_language_bias_fails():
    cfg, _ = _tiny()
    spec = graphgen.spec_from_config(cfg["graph"])
    arrays, _ = graphgen.device_graph_fn(spec)(*graphgen.seed_words(5))
    hg = reference.host_graph(arrays, spec.n_pins, spec.max_pin_degree)
    walk = cfg["walk"]
    control = dict(walk, bias_beta=0.0)
    rng = np.random.default_rng(3)
    gaps = []
    for rid in range(8):
        pins = np.full(8, -1, np.int32)
        pins[:3] = rng.choice(np.flatnonzero(np.diff(hg.p2b_off)), 3,
                              replace=False)
        weights = np.where(pins >= 0, 1.0, 0.0).astype(np.float32)
        key = reference.request_key(11, rid)
        want = reference.recommend(hg, pins, weights, 1, key, walk, 8)
        got = reference.recommend(hg, pins, weights, 1, key, control, 8)
        order = np.argsort(-got.scores, kind="stable")[: walk["top_k"]]
        gaps.append(reference.answer_gap(got.scores[order], got.ids[order],
                                         want))
    ok, numbers = check.judge(np.asarray(gaps), 0, cfg["check"])
    assert not ok
    assert numbers["mismatch_share"]["value"] == 1.0


RANKER = {"d_model": 32, "n_neighbors": 8, "n_candidates": 64, "final_k": 16,
          "scenarios": ["related_pins", "homefeed"]}


def _ranked_root(tmp_path):
    """A benchmark tree holding a tiny ranked configuration and a mix that
    names its head, as files only."""
    cfg, mix = _tiny()
    cfg = dict(cfg, name="tiny-ranked", ranker=RANKER)
    cfg["check"] = dict(cfg["check"], rank_gap_tol=1e-4)
    for sub in ("configs", "traffic"):
        (tmp_path / "pixiebench" / sub).mkdir(parents=True)
    (tmp_path / "pixiebench" / "configs" / "tiny-ranked.json").write_text(
        json.dumps(cfg))
    (tmp_path / "pixiebench" / "traffic" / "tiny-ranked.json").write_text(
        json.dumps(dict(mix, scenario="homefeed")))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-ranked",
                     "file": "pixiebench/configs/tiny-ranked.json"}],
        "workloads": [{"name": "tiny-ranked", "config": "tiny-ranked",
                       "traffic": "tiny-ranked", "chips": 1}],
    }))
    return tmp_path


def test_ranked_configuration_runs_from_data_files(on_cpu, tmp_path):
    from pixiebench import loadgen

    root = _ranked_root(tmp_path)
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, "tiny-ranked")
    cfg = registry.config(bench, cell["config"], root)
    mix = registry.traffic(cell["traffic"], root / "pixiebench")
    seed = 2**33 + 11
    c = run.set_up(cell, cfg, mix, seed)
    assert c.server.ranker.cfg.scenarios == tuple(RANKER["scenarios"])
    assert c.scenario == 1
    reqs = loadgen.schedule(mix, seed, 1.0, c.offsets)
    w = run.measure(c, reqs, False)
    assert w.compiles == 0
    for _, ids in w.rec["answers"].values():
        assert ids.shape == (RANKER["final_k"],)
    ok, numbers = run.free_and_check(c, w.rec, reqs)
    assert ok, numbers
    assert numbers["mismatch_share"]["value"] == 0.0
    assert numbers["compared_at_least"]["value"] == cfg["check"]["sample"]


def test_retrieval_only_cell_builds_and_submits_as_before(on_cpu):
    from pixiebench import loadgen

    made, sent = [], []
    init, submit = PixieServer.__init__, PixieServer.submit

    def recording_init(self, *args, **kw):
        made.append((len(args), sorted(kw)))
        init(self, *args, **kw)

    def recording_submit(self, *args, **kw):
        sent.append((len(args), tuple(sorted(kw))))
        return submit(self, *args, **kw)

    on_cpu.setattr(PixieServer, "__init__", recording_init)
    on_cpu.setattr(PixieServer, "submit", recording_submit)
    cfg, mix = _tiny()
    c = run.set_up({"name": "tiny", "chips": 1}, cfg, mix, 3)
    assert c.scenario is None and c.server.ranker is None
    assert made == [(2, ["n_slots", "seed"])]
    warm = len(sent)
    reqs = loadgen.schedule(mix, 3, 1.0, c.offsets)
    run.measure(c, reqs, False)
    assert set(sent[:warm]) == {(2, ("user_feat",))}
    assert set(sent[warm:]) == {(2, ("now", "user_feat"))}
    assert len(sent) - warm == len(reqs)


@pytest.mark.parametrize("ranked,scenario,message", [
    (True, None, "names no scenario"),
    (False, "homefeed", "states no ranker"),
    (True, "home_feed", "unknown scenario"),
])
def test_configuration_and_mix_that_disagree_are_refused(
        on_cpu, ranked, scenario, message):
    cfg, mix = _tiny()
    if ranked:
        cfg = dict(cfg, ranker=RANKER)
    if scenario is not None:
        mix = dict(mix, scenario=scenario)
    with pytest.raises(ValueError, match=message):
        run.set_up({"name": "tiny", "chips": 1}, cfg, mix, 3)
