"""The trace reduction and the metrics read from it, on a constructed
trace (CPU)."""

import numpy as np
import pytest

from pixiebench import registry, run, trace

MS = 1_000_000  # ns


def _trace():
    """A 100 ms window: two batches of device work, host spans between.

    Device ops run in [10, 40) and [60, 90) ms (two ops each, the second
    batch's ops overlap), programs in the same two stretches.
    """
    devices = {"/device:TPU:0": {
        "ops": [("fusion.1", 10 * MS, 20 * MS), ("sort.2", 30 * MS, 10 * MS),
                ("fusion.1", 60 * MS, 25 * MS), ("sort.2", 70 * MS, 20 * MS)],
        "modules": [("jit_serve", 10 * MS, 30 * MS),
                    ("jit_serve", 60 * MS, 30 * MS)],
    }}
    host = [("pb.window", 0, 100 * MS), ("pb.harvest", 10 * MS, 32 * MS),
            ("pb.pump", 42 * MS, 18 * MS), ("pb.wait", 90 * MS, 10 * MS),
            ("other", 0, 5 * MS)]
    return devices, host


def test_interval_helpers():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(2, 4), (6, 12)], 0, 10) == [(0, 2), (4, 6)]
    assert trace.intersect_ns([(0, 10)], [(2, 3), (5, 20)]) == 6
    assert trace.clip([(0, 5), (8, 9)], 3, 8) == [(3, 5)]


def test_reduce_busy_modules_and_ops():
    s = trace.reduce(*_trace())
    assert s.window == (0, 100 * MS) and s.n_devices == 1
    assert s.busy[0] == [(10 * MS, 40 * MS), (60 * MS, 90 * MS)]
    assert s.busy_s == pytest.approx(0.060)
    assert s.window_s == pytest.approx(0.100)
    assert s.module_ns == 60 * MS
    assert s.op_ns == {"fusion.1": 45 * MS, "sort.2": 30 * MS}
    assert [e[0] for e in s.host_spans] == ["pb.harvest", "pb.pump",
                                             "pb.wait"]


def test_breakdown_names_gaps_by_host_span():
    b = trace.breakdown(trace.reduce(*_trace()))
    assert b["device_ops"][0] == ["fusion.1", 0.045]
    gaps = {name: s for name, s in b["idle_gaps"]}
    # [40, 60): pump covers 18 of 20 ms; [90, 100): wait; [0, 10): none
    assert b["idle_gaps"][0] == ["pb.pump", 0.020]
    assert gaps["pb.wait"] == pytest.approx(0.010)
    assert gaps["idle"] == pytest.approx(0.010)


def test_breakdown_names_ops_by_instruction():
    devices, host = _trace()
    devices["/device:TPU:0"]["ops"][0] = (
        "%while.100 = (s32[8,8192]) while(s32[8,8192] %tuple.1)", 0, 5 * MS)
    names = [n for n, _ in trace.breakdown(trace.reduce(devices,
                                                        host))["device_ops"]]
    assert "%while.100" in names and not any(" = " in n for n in names)


def test_window_span_is_required():
    devices, host = _trace()
    with pytest.raises(ValueError, match="pb.window"):
        trace.reduce(devices, [h for h in host if h[0] != "pb.window"])


def _no_devices(devices):
    devices.clear()


def _modules_outside_window(devices):
    devices["/device:TPU:0"]["modules"] = [("jit_serve", 200 * MS, 30 * MS)]


@pytest.mark.parametrize("spoil,match", [
    (_no_devices, "no device plane"),
    (_modules_outside_window, "no XLA Modules event"),
])
def test_reduce_refuses_a_trace_read_wrongly(spoil, match):
    devices, host = _trace()
    spoil(devices)
    with pytest.raises(ValueError, match=match):
        trace.reduce(devices, host)


def _run(summary):
    """Two requests: due at 5 and 45 ms, answered at 40 and 90 ms."""
    return run.Run(
        seconds=0.1, setup_s=12.5, due=np.array([0.005, 0.045]),
        submitted=np.array([0.005, 0.046]), done=np.array([0.040, 0.090]),
        wait_ms=np.array([5.0, 15.0]), failed=np.zeros(2, bool),
        pumps=[(0.009, 0.0095, 1), (0.042, 0.050, 1), (0.05, 0.0501, 0)],
        batches=2, summary=summary, trace_t0_ns=0,
    )


def test_step_and_idle_with_work_readers():
    r = _run(trace.reduce(*_trace()))
    read = lambda name: registry.metric_reader(name)(r)
    assert read("step_ms") == pytest.approx(30.0)
    # work [5, 40) and [45, 90); idle [0, 10), [40, 60), [90, 100)
    # -> overlap [5, 10) and [45, 60): 20 ms of 100
    assert read("idle_with_work_share") == pytest.approx(20.0)
    assert read("dispatch_host_ms") == pytest.approx(4.25)
    assert read("queue_wait_ms") == pytest.approx(10.0)


def test_host_clock_readers_and_failures():
    r = _run(None)
    read = lambda name: registry.metric_reader(name)(r)
    assert read("p50_ms") == pytest.approx(35.0)  # nearest rank of 35, 45
    assert read("completed_rps") == pytest.approx(20.0)
    assert read("setup_s") == 12.5
    assert read("step_ms") is None
    assert read("idle_with_work_share") is None
    r.failed[1] = True
    assert read("latency_p95_ms") == np.inf
    assert read("completed_rps") == pytest.approx(10.0)
    # an answer after the window's end stretches the time it is taken over
    r.failed[1] = False
    r.done[1] = 0.125
    assert read("completed_rps") == pytest.approx(16.0)
