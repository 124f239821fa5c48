"""A two-camera rig fused at the left camera's key frames (Ghosh &
Gallego 2022) through the served path, against the float32 reference
`run_emvs_rig_looped`.

A 64x48 sensor with 16 depth planes; the right camera rides 0.1 m to
the left camera's x on the simulator's 6-DOF arc, so both see the
3-planes scene. Pinned here:

  * the served rig's fused DSI is bitwise the reference's, and so are
    its mask and depth, for both fusing formulations and for a right
    camera that lags the left by a push;
  * a closed segment waits for the right camera to pass its end, maps
    released together share a sweep, and right frames outside every
    kept segment are dropped and counted;
  * the fusion is 2ab / (a + b) rounded once to float32, bit for bit,
    and votes too wide for int32 fall back to a float32 divide;
  * one silent camera fuses to zero: no map from a single camera;
  * a one-camera session beside a rig keeps its offline result.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig, fuse_harmonic_mean
from repro.core.geometry import SE3
from repro.core.pipeline import (
    EMVSOptions,
    plan_segments,
    rig_right_ranges,
    run_emvs,
    run_emvs_rig_looped,
)
from repro.events.aggregation import aggregate
from repro.events.simulator import (
    EventStream,
    SceneConfig,
    Trajectory,
    make_scene,
    make_trajectory,
    simulate_events,
)
from repro.serving.emvs_stream import (
    MultiStreamEngine,
    StreamConfig,
    iter_event_chunks,
)

EVENTS_PER_FRAME = 128
CHUNK = 700  # events per push: not a multiple of a frame
BASELINE = np.array([0.1, 0.0, 0.0], np.float32)
CAM = CameraModel(width=64, height=48, fx=53.0, fy=53.0, cx=32.0, cy=24.0)
DSI = DSIConfig.for_camera(CAM, num_planes=16, z_min=0.6, z_max=4.5)


def _opts(formulation="matmul", **kw) -> EMVSOptions:
    return EMVSOptions(formulation=formulation, voting="nearest",
                       quantized=False, keyframe_dist_frac=0.04, **kw)


def _right_of(traj: Trajectory) -> Trajectory:
    R, t = np.asarray(traj.poses.R), np.asarray(traj.poses.t)
    return Trajectory(traj.times, SE3(jnp.asarray(R), jnp.asarray(
        (t + R @ BASELINE).astype(np.float32))))


@pytest.fixture(scope="module")
def rig():
    scene = make_scene(SceneConfig(points_per_plane=150))
    traj_l = make_trajectory("simulation_3planes", 32)
    traj_r = _right_of(traj_l)
    evs = (simulate_events(CAM, scene, traj_l, noise_fraction=0.0, seed=1),
           simulate_events(CAM, scene, traj_r, noise_fraction=0.0, seed=2))
    return evs, (traj_l, traj_r)


def _frames(evs, trajs):
    return [aggregate(CAM, ev, tr, events_per_frame=EVENTS_PER_FRAME)
            for ev, tr in zip(evs, trajs)]


def _engine(opts, **cfg) -> MultiStreamEngine:
    return MultiStreamEngine(CAM, DSI, opts, StreamConfig(
        events_per_frame=EVENTS_PER_FRAME, **cfg))


def _chunks(ev):
    return list(iter_event_chunks(ev, CHUNK))


def _serve(opts, evs, trajs, schedule="interleaved", **cfg):
    engine = _engine(opts, **cfg)
    rig = engine.add_rig("rig", trajs=trajs)
    left, right = (_chunks(ev) for ev in evs)
    lag = 1 if schedule == "right_lags" else 0
    for k in range(max(len(left), len(right)) + lag):
        if k < len(left):
            rig.push(left[k], camera=0)
        if 0 <= k - lag < len(right):
            rig.push(right[k - lag], camera=1)
    return rig, rig.flush()


def _assert_maps_equal(got, want):
    assert [r.frame_range for r in got.segments] == \
        [r.frame_range for r in want.segments]
    assert len(got.segments) >= 3
    for g, w in zip(got.segments, want.segments):
        assert np.asarray(g.dsi).dtype == np.float32
        np.testing.assert_array_equal(np.asarray(g.dsi), np.asarray(w.dsi))
        np.testing.assert_array_equal(np.asarray(g.depth_map.mask),
                                      np.asarray(w.depth_map.mask))
        np.testing.assert_array_equal(np.asarray(g.depth_map.depth),
                                      np.asarray(w.depth_map.depth))
        np.testing.assert_array_equal(np.asarray(g.T_w_ref.t),
                                      np.asarray(w.T_w_ref.t))


@pytest.mark.parametrize("formulation,schedule,policy", [
    ("matmul", "interleaved", "adaptive"), ("scatter", "interleaved",
                                            "adaptive"),
    ("matmul", "right_lags", "adaptive"), ("matmul", "interleaved",
                                           "throughput")])
def test_served_rig_equals_the_reference(rig, formulation, schedule, policy):
    evs, trajs = rig
    opts = _opts(formulation)
    served, got = _serve(opts, evs, trajs, schedule, dispatch_policy=policy)
    left, right = _frames(evs, trajs)
    want = run_emvs_rig_looped(CAM, DSI, left, right, opts)
    _assert_maps_equal(got, want)
    assert any(np.asarray(r.depth_map.mask).any() for r in got.segments)
    stats = served.stats
    assert stats["rig_holds"] == stats["segments"] == len(want.segments)
    assert stats["rig_hold_total_s"] > 0.0
    d = served.dispatcher.stats
    assert (d["rig_holds"], d["rig_frames_dropped"]) == (
        stats["rig_holds"], stats["rig_frames_dropped"])
    if policy == "throughput":  # maps of one shape share sweeps
        assert d["coalesced_dispatches"] > 0


def test_a_segment_waits_for_the_right_camera_to_pass_its_end(rig):
    evs, trajs = rig
    opts = _opts()
    # throughput: groups leave full or at the flush, whatever the
    # device's pace, so the released maps' coalescing is deterministic
    engine = _engine(opts, dispatch_policy="throughput")
    served = engine.add_rig("rig", trajs=trajs)
    for chunk in _chunks(evs[0]):
        served.push(chunk, camera=0)
    left, right = _frames(evs, trajs)
    segs = plan_segments(left, DSI, opts)
    closed = [s for s in segs if s[1] < left.xy.shape[0]]
    assert len(closed) >= 3
    d = engine.dispatcher.stats
    assert d["segments"] == d["pending_segments"] == 0
    assert served.stats["rig_holds"] == 0
    t_left = np.asarray(left.t_mid)
    released = []
    for chunk in _chunks(evs[1]):
        served.push(chunk, camera=1)
        newest = served._right_newest
        released.append((served.stats["rig_holds"],
                         sum(1 for a, b in closed if t_left[b] <= newest)))
    assert all(got == want for got, want in released)
    assert released[-1][0] == len(closed)
    got = served.flush()
    _assert_maps_equal(got, run_emvs_rig_looped(CAM, DSI, left, right, opts))
    # the released maps queued together and shared sweeps
    assert d["max_pending"] > 1
    assert d["segments"] == len(got.segments)
    assert d["coalesced_dispatches"] > 0


def _uniform_stream(n_frames: int, t0: float, seed: int) -> EventStream:
    """Frames of events at random pixels, frame k's in [t0 + k/n, ...)."""
    rng = np.random.default_rng(seed)
    n = n_frames * EVENTS_PER_FRAME
    t = (t0 + np.arange(n) / n).astype(np.float32)
    xy = np.round(rng.uniform(0, [CAM.width - 1, CAM.height - 1], (n, 2)))
    return EventStream(xy=xy.astype(np.float32), t=t,
                       polarity=np.ones(n, np.int8), valid=np.ones(n, bool))


def test_right_frames_outside_kept_segments_are_dropped():
    """The left camera jumps twice in a row, so the planner drops a
    one-frame segment; the right camera starts earlier than the left.
    Right frames before the first segment and in the dropped one are
    counted, and the maps still equal the reference."""
    opts = _opts()
    n = 24
    thr = 0.5 * (DSI.z_min + DSI.z_max) * opts.keyframe_dist_frac
    times = np.linspace(-0.2, 1.2, 141).astype(np.float32)
    # frame k's median time is (k + 0.5) / n: steps of 1.5 thresholds
    # between the medians of frames 11, 12 and 13
    x = np.interp(times, [0, 11.5 / n, 12.5 / n, 13.5 / n, 1.2],
                  [0, 0.05 * thr, 1.55 * thr, 3.05 * thr, 3.4 * thr])
    t = np.stack([x, np.zeros_like(x), np.zeros_like(x)], 1).astype(np.float32)
    eye = np.broadcast_to(np.eye(3, dtype=np.float32), (times.size, 3, 3))
    traj_l = Trajectory(jnp.asarray(times), SE3(jnp.asarray(eye.copy()),
                                                jnp.asarray(t)))
    trajs = (traj_l, _right_of(traj_l))
    evs = (_uniform_stream(n, 0.0, 1), _uniform_stream(n + 2, -1.5 / n, 2))
    left, right = _frames(evs, trajs)
    segs = plan_segments(left, DSI, opts)
    assert segs == [(0, 12), (13, n)]
    ranges = rig_right_ranges(left.t_mid, right.t_mid, segs)
    expect_dropped = right.xy.shape[0] - sum(b - a for a, b in ranges)
    assert expect_dropped == 2 + 1  # before frame 0, inside [12, 13)
    served, got = _serve(opts, evs, trajs)
    assert served.stats["rig_frames_dropped"] == expect_dropped
    want = run_emvs_rig_looped(CAM, DSI, left, right, opts)
    assert [r.frame_range for r in got.segments] == segs
    for g, w in zip(got.segments, want.segments):
        np.testing.assert_array_equal(np.asarray(g.dsi), np.asarray(w.dsi))
        np.testing.assert_array_equal(np.asarray(g.depth_map.mask),
                                      np.asarray(w.depth_map.mask))


@pytest.mark.parametrize("votes", ["grid", "random", "float", "wide"])
def test_harmonic_mean_is_rounded_once_to_float32(votes):
    """Integer votes below 2**15 fuse to 2ab / (a + b) divided in float64
    and rounded once to float32, bit for bit (0 where either is 0); float
    votes, and integer ones of 2**15 or more, by a float32 divide."""
    rng = np.random.default_rng(16)
    if votes == "grid":
        a, b = np.meshgrid(np.arange(300), np.arange(300))
    elif votes == "wide":  # a quarter of the voxels hold a wide vote
        a, b = rng.integers(0, 2**15, (2, 200_000))
        a[::4] = rng.integers(2**15, 2**20, a[::4].shape)
    else:
        a, b = rng.integers(0, 2**15, (2, 200_000))
    a, b = a.astype(np.int32), b.astype(np.int32)
    if votes == "float":
        a, b = a.astype(np.float32), b.astype(np.float32)
    got = np.asarray(fuse_harmonic_mean(jnp.asarray(a), jnp.asarray(b)))
    assert got.dtype == np.float32
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        want = np.where((a > 0) & (b > 0), 2 * a64 * b64 / (a64 + b64), 0)
    exact = (a < 2**15) & (b < 2**15) & (votes != "float")
    np.testing.assert_array_equal(got[exact], want[exact].astype(np.float32))
    np.testing.assert_allclose(got[~exact], want[~exact], rtol=2**-22)
    assert exact.all() == (votes in ("grid", "random"))
    assert not got[(a == 0) | (b == 0)].any()


def test_a_silent_camera_fuses_to_an_empty_map(rig):
    """The right camera's events are all invalid: every fused voxel is 0
    and no pixel is detected, although the left alone sees the scene.
    A sweep that skipped the right camera, or fused by a sum, would
    return the left's map here."""
    evs, trajs = rig
    ev_r = evs[1]
    silent = (evs[0], EventStream(xy=ev_r.xy, t=ev_r.t, polarity=ev_r.polarity,
                                  valid=np.zeros_like(np.asarray(ev_r.valid))))
    opts = _opts()
    _, got = _serve(opts, silent, trajs)
    assert len(got.segments) >= 3
    for r in got.segments:
        assert not np.asarray(r.dsi).any()
        assert not np.asarray(r.depth_map.mask).any()
    left, right = _frames(silent, trajs)
    alone = run_emvs(CAM, DSI, left, opts)
    assert any(np.asarray(r.depth_map.mask).any() for r in alone.segments)
    _assert_maps_equal(got, run_emvs_rig_looped(CAM, DSI, left, right, opts))


def test_a_session_beside_a_rig_keeps_its_offline_result(rig):
    evs, trajs = rig
    opts = _opts()
    engine = _engine(opts, dispatch_policy="throughput")
    served = engine.add_rig("rig", trajs=trajs)
    mono = engine.add_session("mono", traj=trajs[0])
    chunks = [_chunks(ev) for ev in evs]
    for k in range(max(map(len, chunks))):
        for cam, cs in enumerate(chunks):
            if k < len(cs):
                served.push(cs[k], camera=cam)
        if k < len(chunks[0]):
            mono.push(chunks[0][k])
    out = engine.flush()
    left, right = _frames(evs, trajs)
    offline = run_emvs(CAM, DSI, left, opts)
    assert [r.frame_range for r in out["mono"].segments] == \
        [r.frame_range for r in offline.segments]
    for g, w in zip(out["mono"].segments, offline.segments):
        assert np.asarray(g.dsi).dtype == np.int32
        np.testing.assert_array_equal(np.asarray(g.dsi), np.asarray(w.dsi))
        np.testing.assert_array_equal(np.asarray(g.depth_map.depth),
                                      np.asarray(w.depth_map.depth))
    _assert_maps_equal(out["rig"], run_emvs_rig_looped(CAM, DSI, left, right,
                                                       opts))
    # a rig's map and a camera's segment never share a sweep
    assert engine.dispatcher.stats["cross_stream_dispatches"] == 0


@pytest.mark.parametrize("case", ["kernel", "sharded", "budget", "Trajectory",
                                  "camera", "unnamed_camera",
                                  "named_camera_of_session"])
def test_what_a_rig_refuses(rig, case):
    _, trajs = rig
    if case == "Trajectory":  # a pose-gated camera
        with pytest.raises(ValueError, match=case):
            _engine(_opts()).add_rig("rig", trajs=(trajs[0], None))
        return
    if case in ("kernel", "sharded", "budget"):
        opts = _opts("kernel" if case == "kernel" else "matmul")
        cfg = {"sharded": {"sweep": "sharded"},
               "budget": {"frame_store_budget_bytes": 1 << 20}}.get(case, {})
        with pytest.raises(ValueError, match=case):
            _engine(opts, **cfg).add_rig("rig", trajs=trajs)
        return
    engine = _engine(_opts())
    served = engine.add_rig("rig", trajs=trajs)
    engine.add_session("mono", traj=trajs[0])
    chunk = _uniform_stream(1, 0.0, 0)
    with pytest.raises(ValueError):
        if case == "camera":
            served.push(chunk, camera=2)
        elif case == "unnamed_camera":
            engine.push("rig", chunk)
        else:
            engine.push("mono", chunk, camera=0)
