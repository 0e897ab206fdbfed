"""The stage split (``stages.py``) on hand-built events, its metric readers,
and the program's host spans in a CPU profile of a small server."""

import types

import jax
import pytest

from pixiebench import registry, stages, trace

MS = 1_000_000
WALK = "jit(serve)/vmap(pixie.walk)/while"
HOP = "jit(serve)/vmap(pixie.walk)/while/body/pixie.walk.hop/gather"


@pytest.mark.parametrize("name,stage", [
    (WALK, "pixie.walk"),
    (HOP, "pixie.walk.hop"),
    ("jit(serve)/pixie.walk/while/body/pixie.walk.count/jit(visit)/while",
     "pixie.walk.count"),
    ("jit(serve)/vmap(pixie.topk)/top_k", "pixie.topk"),
    ("jit(serve)/sort", None),
    ("", None),
])
def test_scope_of_takes_the_innermost_stage(name, stage):
    assert stages.scope_of(name) == stage


def _ops():
    """A loop op (no name stack, as on the chip) around its body: hops, a
    count, a copy without a stage and a small op of another stage; then a
    top-k, and an op of no stage."""
    return [
        ("%while.1 = loop", 10 * MS, 100 * MS, None),
        ("%fusion.2 = hop", 12 * MS, 30 * MS, "pixie.walk.hop"),
        ("%fusion.3 = count", 42 * MS, 20 * MS, "pixie.walk.count"),
        ("%fusion.2 = hop", 62 * MS, 30 * MS, "pixie.walk.hop"),
        ("%copy.4 = carry", 92 * MS, 5 * MS, None),
        ("%fusion.7 = hoisted", 97 * MS, 1 * MS, "pixie.query"),
        ("%fusion.5 = topk", 110 * MS, 25 * MS, "pixie.topk"),
        ("%fusion.6 = rest", 135 * MS, 5 * MS, None),
    ]


def _summary(ops, window=(0, 200 * MS), modules=((10 * MS, 130 * MS),)):
    devices = {"/device:TPU:0": {
        "ops": [o[:3] for o in ops],
        "modules": [("jit_serve", s, d) for s, d in modules]}}
    host = [("pb.window", window[0], window[1] - window[0])]
    return trace.reduce(devices, host)


def test_own_time_counts_a_loop_and_its_body_once():
    ns, op_stage = stages.own_time(_ops(), 0, 200 * MS)
    assert ns == {"pixie.walk": 19 * MS, "pixie.walk.hop": 60 * MS,
                  "pixie.walk.count": 20 * MS, "pixie.topk": 25 * MS,
                  "pixie.query": 1 * MS}
    # the loop takes the stage enclosing its body's, the copy the loop's;
    # the op outside any stage has none
    assert op_stage["%while.1 = loop"] == "pixie.walk"
    assert op_stage["%copy.4 = carry"] == "pixie.walk"
    assert "%fusion.6 = rest" not in op_stage
    # the stages cover exactly the device's busy time less the unstaged op
    busy = trace.span_ns(trace.merge([(s, s + d) for _, s, d, _ in _ops()]))
    assert sum(ns.values()) == busy - 5 * MS


def test_own_time_clips_to_the_window():
    ns, _ = stages.own_time(_ops(), 50 * MS, 120 * MS)
    assert ns == {"pixie.walk": 17 * MS, "pixie.walk.hop": 30 * MS,
                  "pixie.walk.count": 12 * MS, "pixie.topk": 10 * MS,
                  "pixie.query": 1 * MS}


def test_reduce_fills_unscoped_with_the_rest_of_module_time():
    ops = _ops()
    summary = _summary(ops)
    st = stages.reduce({"/device:TPU:0": ops}, summary, [], {})
    assert summary.module_ns == 130 * MS
    assert st.device_ns[stages.UNSCOPED] == 5 * MS
    assert sum(st.device_ns.values()) == summary.module_ns


def test_program_spans_and_stages_leave_the_summary_as_it_was():
    ops = _ops()
    plain = _summary(ops)
    host = [("pb.window", 0, 200 * MS), ("pb.harvest", 140 * MS, 20 * MS),
            ("pixie.harvest.wait", 141 * MS, 10 * MS),
            ("pixie.submit", 170 * MS, 1 * MS)]
    devices = {"/device:TPU:0": {
        "ops": [o[:3] for o in ops],
        "modules": [("jit_serve", 10 * MS, 130 * MS)]}}
    spanned = trace.reduce(devices, host)
    assert spanned.busy == plain.busy
    assert spanned.module_ns == plain.module_ns
    assert spanned.op_ns == plain.op_ns
    assert [s[0] for s in spanned.host_spans] == ["pb.harvest"]


@pytest.mark.parametrize("gap,want", [
    # pb.harvest around pixie.harvest.wait: the inner span names it
    ((142 * MS, 150 * MS), "pixie.harvest.wait"),
    # only the outer span covers any of it
    ((157 * MS, 165 * MS), "pb.harvest"),
    # split between inner spans: the one innermost over most of it
    ((150 * MS, 158 * MS), "pixie.harvest.fetch"),
    ((180 * MS, 190 * MS), "idle"),
])
def test_idle_gaps_take_the_innermost_covering_span(gap, want):
    spans = [("pb.harvest", 140 * MS, 20 * MS),
             ("pixie.harvest.wait", 141 * MS, 10 * MS, {"batch_seq": 3}),
             ("pixie.harvest.fetch", 152 * MS, 4 * MS, {"batch_seq": 3}),
             ("pixie.harvest.assemble", 156 * MS, 1 * MS, {"batch_seq": 3})]
    assert stages.name_gap(gap, spans) == want


def test_breakdown_names_stages_ops_and_gaps():
    ops = _ops()
    summary = _summary(ops)
    spans = [("pixie.harvest.wait", 0, 9 * MS, {"batch_seq": 0})]
    out = stages.breakdown(summary, stages.reduce(
        {"/device:TPU:0": ops}, summary, spans, {}))
    assert out["device_ops"][0] == ["%while.1 [pixie.walk]", 0.1]
    assert ["%fusion.6 [unscoped]", 0.005] in out["device_ops"]
    assert out["idle_gaps"] == [["idle", 0.06], ["pixie.harvest.wait", 0.01]]
    assert out["device_stages"][0] == ["pixie.walk.hop", 0.06]


def _run(st, batches=4):
    summary = _summary(_ops())
    return types.SimpleNamespace(stages=st, batches=batches, summary=summary)


def _staged(counters=None, spans=()):
    ops = _ops()
    return stages.reduce({"/device:TPU:0": ops}, _summary(ops), list(spans),
                         counters or {})


@pytest.mark.parametrize("metric,want", [
    ("walk_hop_ms", 60 / 4), ("count_ms", 20 / 4), ("topk_ms", 25 / 4),
])
def test_device_stage_readers(metric, want):
    read = registry.metric_reader(metric)
    assert read(_run(_staged())) == pytest.approx(want)
    # a run without stages (a program without scopes), or with no batch
    assert read(types.SimpleNamespace(batches=4, summary=None)) is None
    assert read(_run(_staged(), batches=0)) is None
    bare = stages.Stages({stages.UNSCOPED: 1.0}, {}, [], {})
    assert read(_run(bare)) is None


def test_dispatch_form_reader():
    read = registry.metric_reader("dispatch_form_ms")
    spans = [("pixie.dispatch.form", 10 * MS, 2 * MS, {}),
             ("pixie.dispatch.form", 199 * MS, 4 * MS, {}),  # half outside
             ("pixie.dispatch.enqueue", 20 * MS, 9 * MS, {})]
    assert read(_run(_staged(spans=spans), batches=2)) == pytest.approx(1.5)
    assert read(_run(_staged())) is None
    assert read(types.SimpleNamespace(batches=2, summary=None)) is None


@pytest.mark.parametrize("metric,counters,want", [
    ("batch_fill", {"lanes_filled": 30, "lanes_dispatched": 40}, 75.0),
    ("steps_taken_share", {"steps_taken": 150, "steps_budgeted": 600}, 25.0),
])
def test_counter_readers(metric, counters, want):
    read = registry.metric_reader(metric)
    assert read(_run(_staged(counters))) == pytest.approx(want)
    # a server without the counters reports nothing
    assert read(_run(_staged({}))) is None
    assert read(types.SimpleNamespace(batches=1, summary=None)) is None


def test_program_spans_in_a_cpu_profile(tmp_path):
    from repro.core import walk as walk_lib
    from repro.graphs.synthetic import small_test_graph, top_degree_pins
    from repro.serving.server import PixieServer

    sg = small_test_graph()
    cfg = walk_lib.WalkConfig(n_steps=2_000, n_walkers=64, top_k=10,
                              n_p=500, n_v=4)
    server = PixieServer(sg.graph, cfg, batch_size=4, n_slots=2)
    qs = top_degree_pins(sg, 6)
    server.submit([int(qs[0])], [1.0])
    server.flush()                       # compile outside the profile
    span = jax.profiler.TraceAnnotation
    trace.start(str(tmp_path))
    try:
        rids = [server.submit([int(q)], [1.0]) for q in qs]
        with span("pb.pump"):
            server.pump(now=float("inf"))
        with span("pb.harvest"):
            got = server.harvest()
    finally:
        jax.profiler.stop_trace()
    assert sorted(r.req_id for r in got) == rids
    path = trace.find_xplane(str(tmp_path))
    _, spans = stages.load(path)
    (_, hs, hd), = [s for s in trace.load(path)[1] if s[0] == "pb.harvest"]
    by = {}
    for name, s, d, args in spans:
        by.setdefault(name, []).append((s, s + d, args))
    assert sorted(a["req_id"] for _, _, a in by["pixie.submit"]) == rids
    dispatch = by["pixie.dispatch"]
    assert [(a["batch_seq"], a["n_real"], a["batch_size"], a["slots"])
            for *_, a in dispatch] == [(1, 4, 4, 2), (2, 2, 4, 2)]
    assert [a["queued"] for *_, a in dispatch] == [2, 0]
    for child in ("pixie.dispatch.form", "pixie.dispatch.enqueue"):
        assert len(by[child]) == 2
        for s, e, _ in by[child]:
            assert any(ps <= s and e <= pe for ps, pe, _ in dispatch)
    for child in ("pixie.harvest.wait", "pixie.harvest.fetch",
                  "pixie.harvest.assemble"):
        assert [a["batch_seq"] for *_, a in by[child]] == [1, 2]
        for s, e, _ in by[child]:
            assert hs <= s and e <= hs + hd


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 90000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 95000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.7 = (s32[]) while()"
    stats { metadata_id: 1 str_value: "jit(f)/vmap(pixie.walk)/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.8 = s32[8] fusion()"
    stats { metadata_id: 1 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.9 = s32[8] copy()" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.10 = f32[8] fusion()"
    stats { metadata_id: 1 str_value: "jit(f)/vmap(pixie.topk)/top_k" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_f(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9
    name: "jit(f)/vmap(pixie.walk)/while/body/pixie.walk.hop/gather" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 2000000
      stats { metadata_id: 1 int64_value: 0 }
      stats { metadata_id: 2 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "pb.window" } }
  event_metadata { key: 2 value { id: 2 name: "pixie.dispatch" } }
  stat_metadata { key: 1 value { id: 1 name: "batch_seq" } }
  stat_metadata { key: 2 value { id: 2 name: "batch_size" } }
}
"""


def test_device_stages_from_op_metadata_in_a_trace_file(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops, spans = stages.load(str(path))
    assert [(o[0].split(" = ")[0], o[3]) for o in ops["/device:TPU:0"]] == [
        ("%while.7", "pixie.walk"), ("%fusion.8", "pixie.walk.hop"),
        ("%copy.9", None), ("%fusion.10", "pixie.topk")]
    assert spans == [("pixie.dispatch", 500, 2000,
                      {"batch_seq": 0, "batch_size": 8})]
    # the same clock as trace.load, which the Summary is built from
    devices, host = trace.load(str(path))
    assert [o[:3] for o in ops["/device:TPU:0"]] == (
        devices["/device:TPU:0"]["ops"])
    summary = trace.reduce(devices, host)
    st = stages.reduce(ops, summary, spans, {})
    assert st.device_ns == {"pixie.walk": 60e3, "pixie.walk.hop": 30e3,
                            "pixie.topk": 4e3, stages.UNSCOPED: 6e3}
    assert st.op_stage["%copy.9 = s32[8] copy()"] == "pixie.walk"


def test_stages_command_refuses_without_a_tpu(capsys):
    rc = stages.main(["--workload", "related-overload", "--seed", "1",
                      "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3
    assert "needs a TPU" in out.err
    assert "{" not in out.out


def test_measure_fills_run_stages_from_the_trace(monkeypatch):
    """A traced window of a tiny CPU cell, its profile replaced by the
    recorded XSpace above: ``run.measure`` reduces it to the stage split
    with the server's counters over the window, and the readers read it."""
    import os

    from jax.profiler import ProfileData

    from pixiebench import loadgen, run
    from pixiebench.tests.test_pb_run import _tiny

    def recorded(log_dir):
        d = os.path.join(log_dir, "plugins", "profile", "1")
        os.makedirs(d)
        with open(os.path.join(d, "t.xplane.pb"), "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(XSPACE))

    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks_for", lambda kind: {})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(trace, "start", recorded)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cfg, mix = _tiny()
    c = run.set_up({"name": "tiny", "chips": 1}, cfg, mix, 7)
    reqs = loadgen.schedule(mix, 7, 1.0, c.offsets)
    w = run.measure(c, reqs, True)
    assert w.stages.device_ns == {"pixie.walk": 60e3, "pixie.walk.hop": 30e3,
                                  "pixie.topk": 4e3, stages.UNSCOPED: 6e3}
    n = len(reqs)
    assert w.stages.counters["lanes_filled"] == n
    assert w.stages.counters["lanes_dispatched"] == 8 * w.rec["batches"]
    assert w.stages.counters["batches_batch_native"] == w.rec["batches"]
    assert w.stages.counters["steps_budgeted"] == n * cfg["walk"]["n_steps"]
    r = run.window_run(reqs, w, 1.0, 0.0)
    assert r.stages is w.stages and r.summary is w.summary
    hop = registry.metric_reader("walk_hop_ms")(r)
    assert hop == pytest.approx(30e3 / w.rec["batches"] / 1e6)
    assert registry.metric_reader("batch_fill")(r) == pytest.approx(
        100.0 * n / (8 * w.rec["batches"]))
    # a retrieval-only run has no stage 2; a ranked one reads its scope
    rank_ms = registry.metric_reader("rank_ms")
    assert rank_ms(r) is None
    ranked = stages.Stages({"pixie.rank": 8 * MS}, {}, [], {})
    assert rank_ms(_run(ranked, batches=4)) == pytest.approx(2.0)
