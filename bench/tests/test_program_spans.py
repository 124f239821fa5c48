"""The reduction of the program's own `emvs.*` spans: counts, time and
self time per span, idle gaps labelled by the innermost span, and the
per-layer numbers they give."""
from pathlib import Path

import pytest

from harness import program_spans, trace
from test_trace import synthetic

MS = 1e6
RECORDED = Path(__file__).parent / "data" / "tiny_trace.xplane.pb.gz"


def program():
    """Nested spans of one client thread, on `synthetic()`'s clock: its
    idle gaps are [0, 10) push, [40, 45) poll, [50, 70) fetch and
    [90, 100) push, and its window closes at 100 ms."""
    spans = [("emvs.push", 0, 9), ("emvs.hygiene", 0, 1),
             ("emvs.aggregate", 1, 7), ("emvs.pose_interp", 2, 7),
             ("emvs.pose_interp.sync", 5, 7), ("emvs.plan", 7, 8.5),
             ("emvs.poll", 40, 44), ("emvs.harvest", 41, 44),
             ("emvs.harvest.sync", 41, 43.5),
             # cut by the window's end
             ("emvs.push", 95, 105), ("emvs.plan", 96, 104)]
    # another thread, wholly before the window
    return ([(n, s * MS, e * MS, 1) for n, s, e in spans]
            + [("emvs.harvest", -5 * MS, -1 * MS, 2)])


def test_counts_time_and_self_time_per_span():
    stats, selves = program_spans.tree(program(), 0.0, 100 * MS)
    assert stats["emvs.push"]["count"] == 2
    assert stats["emvs.push"]["total_s"] == pytest.approx(0.014)  # 9 + 5
    # less hygiene, aggregate and plan; the cut span keeps [95, 96)
    assert stats["emvs.push"]["self_s"] == pytest.approx(0.0015)
    assert stats["emvs.aggregate"]["total_s"] == pytest.approx(0.006)
    assert stats["emvs.aggregate"]["self_s"] == pytest.approx(0.001)
    assert stats["emvs.pose_interp"]["self_s"] == pytest.approx(0.003)
    assert stats["emvs.pose_interp.sync"]["self_s"] == pytest.approx(0.002)
    assert stats["emvs.plan"] == {"count": 2, "total_s": pytest.approx(0.0055),
                                  "self_s": pytest.approx(0.0055)}
    assert stats["emvs.harvest"] == {"count": 1, "total_s": pytest.approx(0.003),
                                     "self_s": pytest.approx(0.0005)}
    # self intervals tile each thread's spans, innermost first
    own = selves[1]
    assert [n for n, _, _ in own[:7]] == [
        "emvs.hygiene", "emvs.aggregate", "emvs.pose_interp",
        "emvs.pose_interp.sync", "emvs.plan", "emvs.push", "emvs.poll"]
    assert all(a[2] <= b[1] for a, b in zip(own, own[1:]))
    total = sum(e - s for _, s, e in own if s >= 0)
    assert total == pytest.approx((9 + 4 + 10) * MS)


def test_idle_gaps_go_to_the_innermost_span():
    r = program_spans.reduce(synthetic(), program())
    idle = r["idle_by_span"]
    # [0, 10): pose_interp's own 3 ms beat sync's 2 and plan's 1.5
    assert idle["push:emvs.pose_interp"] == pytest.approx(0.010)
    assert idle["poll:emvs.harvest.sync"] == pytest.approx(0.005)
    assert idle["fetch"] == pytest.approx(0.020)  # no program span there
    assert idle["push:emvs.plan"] == pytest.approx(0.010)  # [90, 100)
    assert set(idle) == {"push:emvs.pose_interp", "poll:emvs.harvest.sync",
                         "fetch", "push:emvs.plan"}
    base = trace.reduce(synthetic())
    assert sum(idle.values()) == pytest.approx(sum(base["idle_by_host"].values()))
    assert r["idle_gaps"][0] == ["fetch", pytest.approx(0.020)]
    assert r["program_spans"]["emvs.push"]["count"] == 2


def test_without_program_spans_every_label_is_the_bench_label():
    t = trace.load(str(RECORDED))
    program = program_spans.load(str(RECORDED))
    assert program == []
    r = program_spans.reduce(t, program)
    base = trace.reduce(t)
    assert r["idle_by_span"] == base["idle_by_host"]
    assert r["idle_gaps"] == base["idle_gaps"]
    assert r["program_spans"] == {}
    assert program_spans.reduce(trace.Trace({}, {}, t.spans), []) is None


def test_per_layer_numbers():
    stats = {"emvs.push": {"count": 4, "total_s": 1.0, "self_s": 0.01},
             "emvs.pose_interp": {"count": 4, "total_s": 0.4, "self_s": 0.3},
             "emvs.hygiene": {"count": 4, "total_s": 0.02, "self_s": 0.02},
             "emvs.aggregate": {"count": 4, "total_s": 0.5, "self_s": 0.06},
             "emvs.plan": {"count": 4, "total_s": 0.9, "self_s": 0.3},
             "emvs.dispatch": {"count": 5, "total_s": 0.3, "self_s": 0.01},
             "emvs.stage": {"count": 5, "total_s": 0.1, "self_s": 0.1},
             "emvs.backpressure": {"count": 2, "total_s": 0.2, "self_s": 0.01},
             "emvs.harvest.sync": {"count": 5, "total_s": 0.05, "self_s": 0.05}}
    got = program_spans.per_layer(stats, 10)
    assert got == {"pose_interp_ms_per_push": pytest.approx(100.0),
                   "ingest_ms_per_push": pytest.approx(20.0),
                   "stage_ms_per_seg": pytest.approx(40.0),
                   "harvest_wait_ms_per_seg": pytest.approx(6.0)}
    assert set(program_spans.per_layer(stats, 0)) == {
        "pose_interp_ms_per_push", "ingest_ms_per_push"}
    assert program_spans.per_layer({}, 10) == {}
