"""The trace reduction: busy and idle time, program times, gap labels."""
from pathlib import Path

import pytest

from harness import trace


def synthetic():
    ms = 1e6
    dev = "/device:TPU:0"
    modules = {dev: [("jit_process_segments_batched(3)", 10 * ms, 40 * ms),
                     ("jit_depth_maps_to_points(5)", 45 * ms, 50 * ms),
                     ("jit_process_segments_batched(3)", 70 * ms, 90 * ms)]}
    ops = {dev: [("fusion.1", 10 * ms, 25 * ms), ("convolution.2", 25 * ms, 40 * ms),
                 ("fusion.3", 45 * ms, 50 * ms), ("fusion.1", 70 * ms, 90 * ms)]}
    spans = [("bench.window", 0.0, 100 * ms),
             ("bench.push", 0.0, 8 * ms), ("bench.wait", 8 * ms, 10 * ms),
             ("bench.poll", 40 * ms, 44 * ms), ("bench.fetch", 50 * ms, 69 * ms),
             ("bench.push", 90 * ms, 100 * ms)]
    return trace.Trace(modules, ops, spans)


def test_reduce_busy_idle_and_programs():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["sweep_s"] == pytest.approx(0.050)
    assert r["other_s"] == pytest.approx(0.005)
    assert r["device_ops"][0] == ["jit_process_segments_batched",
                                  pytest.approx(0.050)]
    idle = r["idle_by_host"]
    # a whole gap goes to the host span that overlaps it most: [0, 10)
    # is mostly push (the 2 ms of wait lose), [90, 100) all push
    assert idle["push"] == pytest.approx(0.020)
    assert "generator wait" not in idle
    assert idle["poll"] == pytest.approx(0.005)  # [40, 45): poll 40-44 overlaps most
    assert idle["fetch"] == pytest.approx(0.020)  # [50, 70)
    assert sum(idle.values()) == pytest.approx(0.1 - 0.055)
    assert r["idle_gaps"][0] == ["fetch", pytest.approx(0.020)]


def test_reduce_needs_a_window_and_a_device():
    t = synthetic()
    assert trace.reduce(trace.Trace({}, {}, t.spans)) is None
    assert trace.reduce(trace.Trace(t.modules, t.ops, t.spans[1:])) is None


def test_sweep_programs_are_recognised_by_name():
    assert trace.is_sweep("jit_process_segments_batched(12)")
    assert not trace.is_sweep("jit_depth_maps_to_points")


RECORDED = Path(__file__).parent / "data" / "tiny_trace.xplane.pb.gz"


def test_reduce_a_trace_recorded_on_the_chip():
    """A 1 s window of the served path (2 cameras) traced on one TPU v5e."""
    t = trace.load(str(RECORDED))
    assert list(t.modules) == ["/device:TPU:0"]
    r = trace.reduce(t)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1.0, abs=0.05)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["sweep_s"] > 0 and r["other_s"] > 0
    assert r["sweep_s"] + r["other_s"] == pytest.approx(r["busy_s"], rel=1e-6)
    idle = r["idle_by_host"]
    assert set(idle) <= {"push", "poll", "fetch", "generator wait", "other"}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    names = [n for n, _ in r["device_ops"]]
    assert "jit_process_segments_batched" in names
    assert all(not n.endswith(")") for n in names)  # fingerprints stripped
