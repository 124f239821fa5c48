"""The served path's profiler spans: `jax.profiler.TraceAnnotation`
blocks named `emvs.*` at the layer boundaries of push, poll, dispatch
and harvest, on the device trace's clock.

`jax.profiler.TraceAnnotation` is replaced by a recorder of enter and
exit events, and a two-session `MultiStreamEngine` at a small DSI is
driven through it. Pinned here:

  * the span tree of a push that closes a segment, and the harvest
    spans under whichever push or poll harvests;
  * a push opens as many spans for 1 frame as for 64 (none sits in a
    per-frame loop), and dispatch spans follow dispatches;
  * enters and exits balance when a push raises (hygiene,
    `PoseStallError`);
  * the `backpressure_harvests` counter matches its span;
  * a rig's `emvs.rig.assign` nests in push and plan, its
    `emvs.rig.fuse` in harvest, and its hold counters move;
  * results are bitwise-equal to an unrecorded run.
"""
from __future__ import annotations

import contextlib
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core.dsi import DSIConfig
from repro.core.pipeline import EMVSOptions
from repro.events.simulator import EventStream
from repro.events.trajectory_stream import PoseStallError
from repro.serving.emvs_stream import MultiStreamEngine, StreamConfig

EVENTS_PER_FRAME = 64
OPTS = EMVSOptions(formulation="matmul", voting="nearest", quantized=True,
                   keyframe_dist_frac=0.03)
DISPATCH = ("emvs.dispatch", "emvs.stage", "emvs.launch", "emvs.backpressure")
HARVEST = ("emvs.harvest", "emvs.harvest.sync")


class Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: logs each span's
    enter and exit, in order."""

    def __init__(self):
        self.log: list[tuple[str, str]] = []

    @contextlib.contextmanager
    def __call__(self, name: str, **metadata):
        assert not metadata, "spans carry no keyword metadata"
        self.log.append(("enter", name))
        try:
            yield
        finally:
            self.log.append(("exit", name))

    def mark(self) -> int:
        return len(self.log)

    def forest(self, start: int = 0) -> list:
        """Root spans since `start` as (name, children) trees; asserts
        that every exit closes the innermost open span."""
        roots, stack = [], []
        for kind, name in self.log[start:]:
            if kind == "enter":
                node = (name, [])
                (stack[-1][1] if stack else roots).append(node)
                stack.append(node)
            else:
                assert stack and stack[-1][0] == name, (name, stack)
                stack.pop()
        assert not stack, f"spans left open: {[n for n, _ in stack]}"
        return roots


def _walk(nodes, path=()):
    for name, kids in nodes:
        yield name, kids, path
        yield from _walk(kids, path + (name,))


def _names(nodes) -> list[str]:
    return [n for n, _ in nodes]


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


@pytest.fixture(scope="module")
def scene(small_scene, cam):
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=8, z_min=0.6, z_max=4.5)
    return small_scene["events"], small_scene["traj"], dsi_cfg


def _chunk(ev: EventStream, a: int, b: int) -> EventStream:
    return EventStream(xy=ev.xy[a:b], t=ev.t[a:b], polarity=ev.polarity[a:b],
                       valid=ev.valid[a:b])


def _engine(cam, dsi_cfg, **cfg) -> MultiStreamEngine:
    return MultiStreamEngine(cam, dsi_cfg, OPTS, StreamConfig(
        events_per_frame=EVENTS_PER_FRAME, dispatch_policy="latency", **cfg))


def _drain_by_polling(engine) -> None:
    """Poll until every dispatched sweep has been harvested."""
    for inf in list(engine.dispatcher._inflight):
        jax.block_until_ready(inf.dms.depth)
    engine.poll()
    assert not engine.dispatcher._inflight


def test_span_tree_of_a_push_that_closes_a_segment(cam, scene, recorder):
    ev, traj, dsi_cfg = scene
    engine = _engine(cam, dsi_cfg, max_inflight=1)
    a = engine.add_session("a", traj=traj)
    b = engine.add_session("b", traj=traj)
    half = 40 * EVENTS_PER_FRAME
    b.push(_chunk(ev, 0, half // 2))
    a.push(_chunk(ev, 0, half))
    _drain_by_polling(engine)
    roots = recorder.forest()
    assert _names(roots) == ["emvs.push", "emvs.push", "emvs.poll"]
    closing = [r for r in roots if any(
        n == "emvs.dispatch" for n, _, _ in _walk([r]))]
    assert closing, "no push closed a segment"
    name, kids = closing[0]
    assert name == "emvs.push"
    assert _names(kids) == ["emvs.hygiene", "emvs.aggregate", "emvs.plan",
                            "emvs.poll"]
    hygiene, aggregate, plan, _ = (k for _, k in kids)
    assert hygiene == []
    assert aggregate == [("emvs.pose_interp", [])]
    assert "emvs.dispatch" in _names(plan)
    assert set(_names(plan)) <= {"emvs.dispatch", "emvs.harvest"}
    for name, kids, path in _walk(roots):
        if name == "emvs.dispatch":
            assert path[:2] == ("emvs.push", "emvs.plan")
            assert _names(kids)[:2] == ["emvs.stage", "emvs.launch"]
            assert set(_names(kids)[2:]) <= {"emvs.backpressure"}
        elif name in ("emvs.stage", "emvs.launch", "emvs.harvest.sync"):
            assert kids == []
        elif name == "emvs.backpressure":
            assert _names(kids) == ["emvs.harvest"]
        elif name == "emvs.harvest":
            assert _names(kids) == ["emvs.harvest.sync"]
            assert path[0] in ("emvs.push", "emvs.poll")
    spans = Counter(n for n, _, _ in _walk(roots))
    stats = engine.dispatcher.stats
    assert spans["emvs.harvest"] == stats["dispatches"] > 1
    # one in-flight slot: every dispatch after the first waits for a slot
    assert spans["emvs.backpressure"] == stats["backpressure_harvests"] > 0


def test_span_count_per_push_does_not_grow_with_frames(cam, scene, recorder):
    ev, traj, dsi_cfg = scene
    engine = _engine(cam, dsi_cfg)
    a = engine.add_session("a", traj=traj)
    engine.add_session("b", traj=traj)
    counts, dispatches = [], []
    lo = 0
    for frames in (1, 64):
        hi = lo + frames * EVENTS_PER_FRAME
        mark, before = recorder.mark(), engine.dispatcher.stats["dispatches"]
        a.push(_chunk(ev, lo, hi))
        lo = hi
        spans = Counter(n for n, _, _ in _walk(recorder.forest(mark)))
        dispatches.append(engine.dispatcher.stats["dispatches"] - before)
        for name in ("emvs.dispatch", "emvs.stage", "emvs.launch"):
            assert spans[name] == dispatches[-1]
        counts.append(Counter({n: c for n, c in spans.items()
                               if n not in DISPATCH + HARVEST}))
    assert dispatches[1] > 0, "the 64-frame push closed no segment"
    assert counts[0] == counts[1] == Counter(
        {"emvs.push": 1, "emvs.hygiene": 1, "emvs.aggregate": 1,
         "emvs.pose_interp": 1, "emvs.plan": 1, "emvs.poll": 1})


@pytest.mark.parametrize("fault", ["hygiene", "pose_stall"])
def test_spans_close_when_a_push_raises(cam, scene, recorder, fault):
    ev, traj, dsi_cfg = scene
    if fault == "hygiene":
        engine = _engine(cam, dsi_cfg)
        sess = engine.add_session("a", traj=traj)
        bad = _chunk(ev, 0, 256)
        chunk, error = bad._replace(xy=bad.xy[:-1]), ValueError
    else:  # pose-gated, no poses yet: the frames overflow the stall bound
        engine = _engine(cam, dsi_cfg, max_stalled_frames=2)
        sess = engine.add_session("a", traj=None)
        chunk, error = _chunk(ev, 0, 4 * EVENTS_PER_FRAME), PoseStallError
    with pytest.raises(error):
        sess.push(chunk)
    roots = recorder.forest()  # asserts enters and exits balance
    assert _names(roots) == ["emvs.push"]
    inner = {"hygiene": ["emvs.hygiene"],
             "pose_stall": ["emvs.hygiene", "emvs.aggregate"]}[fault]
    assert _names(roots[0][1]) == inner


def _run(cam, traj, dsi_cfg, ev) -> dict:
    engine = _engine(cam, dsi_cfg, max_inflight=1)
    sessions = [engine.add_session(s, traj=traj) for s in ("a", "b")]
    n = int(ev.t.shape[0])
    step = 7 * EVENTS_PER_FRAME + 13
    for lo in range(0, n, step):
        for sess in sessions:
            sess.push(_chunk(ev, lo, min(n, lo + step)))
        engine.poll()
    return engine.flush()


def test_results_are_bitwise_equal_to_an_unrecorded_run(cam, scene,
                                                        monkeypatch):
    ev, traj, dsi_cfg = scene
    plain = _run(cam, traj, dsi_cfg, ev)
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    recorded = _run(cam, traj, dsi_cfg, ev)
    assert rec.log and rec.forest()
    for sid in ("a", "b"):
        got, want = recorded[sid].segments, plain[sid].segments
        assert len(got) == len(want) > 1
        for g, w in zip(got, want):
            assert g.frame_range == w.frame_range
            for x, y in ((g.dsi, w.dsi), (g.depth_map.depth, w.depth_map.depth),
                         (g.depth_map.mask, w.depth_map.mask)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_rig_spans_nest_in_push_and_harvest(cam, scene, recorder):
    """A rig's push that releases held maps assigns right frames in
    `emvs.rig.assign` under `emvs.push` / `emvs.plan`, before it
    dispatches; each harvest of fused maps routes them in
    `emvs.rig.fuse` under `emvs.harvest`. The hold counters move."""
    from repro.core.geometry import SE3
    from repro.events.simulator import Trajectory

    ev, traj, dsi_cfg = scene
    shift = np.asarray(traj.poses.t) + np.float32([0.1, 0.0, 0.0])
    right = Trajectory(traj.times, SE3(traj.poses.R, shift.astype(np.float32)))
    engine = _engine(cam, dsi_cfg, max_inflight=1)
    rig = engine.add_rig("rig", trajs=(traj, right))
    half = 40 * EVENTS_PER_FRAME
    rig.push(_chunk(ev, 0, half), camera=0)
    left_only = Counter(n for n, _, _ in _walk(recorder.forest()))
    assert left_only["emvs.rig.assign"] == left_only["emvs.dispatch"] == 0
    assert rig.stats["rig_holds"] == 0 and len(rig._closed) > 1
    mark = recorder.mark()
    rig.push(_chunk(ev, 0, half), camera=1)
    _drain_by_polling(engine)
    roots = recorder.forest(mark)
    assert _names(roots) == ["emvs.push", "emvs.poll"]
    plan = dict(roots[0][1])["emvs.plan"]
    assert _names(plan)[0] == "emvs.rig.assign"
    assert "emvs.dispatch" in _names(plan)
    for name, kids, path in _walk(roots):
        if name == "emvs.rig.assign":
            assert path == ("emvs.push", "emvs.plan") and kids == []
        elif name == "emvs.rig.fuse":
            assert path[-1] == "emvs.harvest" and kids == []
    spans = Counter(n for n, _, _ in _walk(roots))
    stats, d = rig.stats, engine.dispatcher.stats
    assert spans["emvs.rig.assign"] == 1
    assert spans["emvs.rig.fuse"] == spans["emvs.harvest"] == d["dispatches"] > 1
    assert stats["rig_holds"] == d["rig_holds"] == d["segments"] > 1
    assert stats["rig_hold_total_s"] == d["rig_hold_total_s"] > 0.0
