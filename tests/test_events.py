"""Event pipeline: simulator, streaming rectification, aggregation."""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.camera import CameraModel, in_bounds_mask, undistort_events, distort_normalized
from repro.events.aggregation import (
    PARKED_COORD,
    PoseExtrapolationWarning,
    StreamingAggregator,
    aggregate,
    empty_event_frames,
    pose_at_times,
)
from repro.events.simulator import EventStream
from repro.events.simulator import (
    SceneConfig,
    absrel,
    ground_truth_depth,
    make_scene,
    make_trajectory,
    simulate_events,
)


def test_event_stream_sorted_and_masked(cam, small_scene):
    ev = small_scene["events"]
    t = np.asarray(ev.t)
    assert (np.diff(t) >= 0).all()
    xy = np.asarray(ev.xy)
    v = np.asarray(ev.valid)
    assert (xy[~v] == -1e4).all()  # parked
    inb = (xy[v][:, 0] >= 0) & (xy[v][:, 0] <= cam.width - 1)
    assert inb.all()


def test_aggregation_shapes_and_poses(cam, small_scene):
    frames = small_scene["frames"]
    F, E, _ = frames.xy.shape
    assert E == 1024
    assert frames.poses.R.shape == (F, 3, 3)
    # frame mid-times increase
    assert (np.diff(np.asarray(frames.t_mid)) > 0).all()


def test_aggregate_keeps_tail(cam, small_scene):
    """The stream's tail must become a final padded frame, not be dropped."""
    ev, traj = small_scene["events"], small_scene["traj"]
    n = int(ev.t.shape[0])
    assert n % 1024 != 0, "fixture must leave a partial tail"
    frames = aggregate(cam, ev, traj, events_per_frame=1024)
    assert frames.xy.shape[0] == -(-n // 1024)  # ceil: tail kept
    dropped = aggregate(cam, ev, traj, events_per_frame=1024, keep_tail=False)
    assert dropped.xy.shape[0] == n // 1024  # the seed's behavior, opt-in
    # tail frame: real events first, then parked invalid padding
    r = n % 1024
    tail_xy = np.asarray(frames.xy[-1])
    tail_valid = np.asarray(frames.valid[-1])
    np.testing.assert_array_equal(tail_xy[:r], np.asarray(ev.xy[-r:]))
    assert (tail_xy[r:] == PARKED_COORD).all()
    assert not tail_valid[r:].any()
    # every frame before the tail is untouched by the fix
    np.testing.assert_array_equal(np.asarray(frames.xy[:-1]),
                                  np.asarray(dropped.xy))


def test_streaming_aggregator_carries_remainder(cam, small_scene):
    """Ragged pushes: remainder events cross chunk boundaries, none lost."""
    ev, traj = small_scene["events"], small_scene["traj"]
    n = int(ev.t.shape[0])
    agg = StreamingAggregator(cam, traj, events_per_frame=1024)
    sizes = [700, 1311, 257, 2048]
    parts, i, k = [], 0, 0
    while i < n:
        j = min(i + sizes[k % len(sizes)], n)
        parts.append(agg.push(EventStream(
            xy=ev.xy[i:j], t=ev.t[i:j],
            polarity=ev.polarity[i:j], valid=ev.valid[i:j])))
        i, k = j, k + 1
    assert agg.pending_events == n % 1024
    parts.append(agg.flush())
    assert agg.pending_events == 0
    got_xy = np.concatenate([np.asarray(p.xy) for p in parts])
    ref = small_scene["frames"]
    assert got_xy.shape[0] == -(-n // 1024)
    np.testing.assert_array_equal(got_xy, np.asarray(ref.xy))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.t_mid) for p in parts]),
        np.asarray(ref.t_mid))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.poses.t) for p in parts]),
        np.asarray(ref.poses.t))


def test_aggregator_max_stall_backpressure(cam, small_scene):
    """Pose-gated mode with `max_stalled`: a push that leaves more than
    the bound stalled raises PoseStallError AFTER buffering the frames,
    so nothing is lost and pushing the poses drains bit-identically."""
    from repro.events.aggregation import PoseStallError, TrajectoryBuffer

    ev, traj = small_scene["events"], small_scene["traj"]
    ref = small_scene["frames"]
    e, bound = 1024, 2
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=e,
                              max_stalled=bound)
    n = (bound + 2) * e  # enough events to overflow the bound in one push
    chunk = EventStream(xy=ev.xy[:n], t=ev.t[:n],
                        polarity=ev.polarity[:n], valid=ev.valid[:n])
    with pytest.raises(PoseStallError, match=f"max_stalled={bound}"):
        agg.push(chunk)
    # every completed frame was buffered before the raise: the late pose
    # chunk releases them all, posed bit-identically to the offline path
    assert agg.stalled_frames == bound + 2
    released = agg.push_poses(traj)
    assert agg.stalled_frames == 0
    np.testing.assert_array_equal(np.asarray(released.xy),
                                  np.asarray(ref.xy[:bound + 2]))
    np.testing.assert_array_equal(np.asarray(released.poses.t),
                                  np.asarray(ref.poses.t[:bound + 2]))
    # drained below the bound: the event stream may resume
    agg.push(EventStream(xy=ev.xy[n:n + e], t=ev.t[n:n + e],
                         polarity=ev.polarity[n:n + e],
                         valid=ev.valid[n:n + e]))
    with pytest.raises(ValueError, match="max_stalled"):
        StreamingAggregator(cam, TrajectoryBuffer(), max_stalled=0)

    # Only frames the current watermark CANNOT release count toward the
    # bound, and the check precedes the release: a push whose backlog
    # fits must return the releasable frames (not raise, not drop them).
    from repro.events.simulator import slice_trajectory

    agg2 = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=e,
                               max_stalled=bound)
    t_mid_all = np.asarray(ref.t_mid)
    times = np.asarray(traj.times)
    # poses covering the first 3 frame mid-times strictly
    hi = int(np.searchsorted(times, t_mid_all[2], side="right")) + 1
    agg2.push_poses(slice_trajectory(traj, 0, hi))
    wm = agg2.pose_watermark
    releasable = int((t_mid_all[:bound + 2] < wm).sum())
    assert releasable >= 3 and (bound + 2) - releasable <= bound, \
        "fixture: the backlog must fit the bound for this scenario"
    released = agg2.push(chunk)  # same 4 frames as above — no raise now
    assert released.xy.shape[0] == releasable
    np.testing.assert_array_equal(np.asarray(released.poses.t),
                                  np.asarray(ref.poses.t[:releasable]))
    assert agg2.stalled_frames == (bound + 2) - releasable


def test_aggregate_empty_stream(cam, small_scene):
    traj = small_scene["traj"]
    ev = EventStream(xy=jnp.zeros((0, 2)), t=jnp.zeros((0,)),
                     polarity=jnp.zeros((0,), jnp.int8),
                     valid=jnp.zeros((0,), bool))
    frames = aggregate(cam, ev, traj, events_per_frame=64)
    assert frames.xy.shape == (0, 64, 2)
    assert empty_event_frames(64).xy.shape == (0, 64, 2)


def test_pose_interpolation_monotone(small_scene):
    traj = small_scene["traj"]
    q = jnp.linspace(0.05, 0.95, 7)
    poses = pose_at_times(traj, q)
    # x-translation follows the trajectory's smooth arc: bounded by extremes
    tx = np.asarray(poses.t[:, 0])
    lo, hi = np.asarray(traj.poses.t[:, 0]).min(), np.asarray(traj.poses.t[:, 0]).max()
    assert (tx >= lo - 1e-5).all() and (tx <= hi + 1e-5).all()


@pytest.mark.parametrize("source", ["oracle", "buffer"])
def test_posing_frames_makes_no_device_transfer(cam, small_scene, source):
    """Frame poses are interpolated on the host: once the aggregator
    holds its pose source (here built from device arrays), no push,
    pose push, finalize or flush moves data to or from a device, which
    on a chip would wait behind the running sweep. The frames are the
    offline oracle's, bit for bit."""
    from repro.events.aggregation import TrajectoryBuffer, concat_event_frames
    from repro.events.simulator import slice_trajectory

    traj = jax.tree.map(jnp.asarray, small_scene["traj"])
    host_traj = jax.tree.map(np.asarray, traj)
    ev = jax.tree.map(np.asarray, small_scene["events"])
    n, k = int(ev.t.shape[0]), int(host_traj.times.shape[0]) // 2
    if source == "oracle":
        agg = StreamingAggregator(cam, traj, events_per_frame=1024)
    else:
        agg = StreamingAggregator(
            cam, TrajectoryBuffer(slice_trajectory(traj, 0, k)),
            events_per_frame=1024)

    def part(i, j):
        return jax.tree.map(lambda x: x[i:j], ev)

    parts = []
    with jax.transfer_guard("disallow"):
        parts.append(agg.push(part(0, n // 2)))
        if source == "buffer":
            parts.append(agg.push_poses(slice_trajectory(
                host_traj, k, int(host_traj.times.shape[0]))))
        parts.append(agg.push(part(n // 2, n)))
        parts.append(agg.flush())
        if source == "buffer":
            parts.append(agg.finalize_poses())
    got, ref = concat_event_frames(parts), small_scene["frames"]
    np.testing.assert_array_equal(got.t_mid, np.asarray(ref.t_mid))
    np.testing.assert_array_equal(got.poses.R, np.asarray(ref.poses.R))
    np.testing.assert_array_equal(got.poses.t, np.asarray(ref.poses.t))


def test_aggregate_pose_extrapolation_policies(cam, small_scene):
    """Offline aggregation no longer freezes out-of-span poses silently:
    the default warns (clamped numerics kept for equivalence), "raise"
    refuses, and the seed's silent clamp needs explicit opt-in."""
    from repro.events.aggregation import PoseExtrapolationError
    from repro.events.simulator import Trajectory

    ev = small_scene["events"]
    traj = small_scene["traj"]
    # truncate the trajectory so the stream's tail lies beyond the poses
    times = np.asarray(traj.times)
    cut = int(times.shape[0]) // 2
    short = Trajectory(times=traj.times[:cut],
                       poses=type(traj.poses)(traj.poses.R[:cut],
                                              traj.poses.t[:cut]))
    with pytest.warns(PoseExtrapolationWarning, match="outside the trajectory"):
        warned = aggregate(cam, ev, short, events_per_frame=1024)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # "clamp" must stay silent
        clamped = aggregate(cam, ev, short, events_per_frame=1024,
                            pose_extrapolation="clamp")
    # the warning changes visibility, never numerics (seed equivalence)
    np.testing.assert_array_equal(np.asarray(warned.poses.R),
                                  np.asarray(clamped.poses.R))
    np.testing.assert_array_equal(np.asarray(warned.poses.t),
                                  np.asarray(clamped.poses.t))
    with pytest.raises(PoseExtrapolationError, match="outside the trajectory"):
        aggregate(cam, ev, short, events_per_frame=1024,
                  pose_extrapolation="raise")
    with pytest.raises(ValueError, match="unknown pose_extrapolation"):
        aggregate(cam, ev, short, events_per_frame=1024,
                  pose_extrapolation="freeze")


def test_undistort_inverts_distortion():
    cam = CameraModel(k1=-0.35, k2=0.15, p1=0.001, p2=-0.0005)
    rng = np.random.default_rng(0)
    xy_true = jnp.asarray(rng.uniform((40, 40), (200, 140), (256, 2))
                          .astype(np.float32))
    xn = (xy_true[:, 0] - cam.cx) / cam.fx
    yn = (xy_true[:, 1] - cam.cy) / cam.fy
    xd, yd = distort_normalized(cam, xn, yn)
    xy_d = jnp.stack([xd * cam.fx + cam.cx, yd * cam.fy + cam.cy], axis=-1)
    xy_u = undistort_events(cam, xy_d)
    np.testing.assert_allclose(np.asarray(xy_u), np.asarray(xy_true), atol=0.05)


def test_ground_truth_depth_zbuffer(cam):
    # two points on the same pixel: nearer one wins
    pts = np.array([[0.0, 0.0, 2.0], [0.0, 0.0, 1.0]], np.float32)
    from repro.core.geometry import SE3

    d, m = ground_truth_depth(cam, pts, SE3.identity())
    yx = int(cam.cy), int(cam.cx)
    assert bool(m[yx])
    assert abs(float(d[yx]) - 1.0) < 1e-5


def test_absrel_metric():
    d = jnp.array([[1.0, 2.0]])
    gt = jnp.array([[2.0, 2.0]])
    m = jnp.array([[True, True]])
    assert abs(float(absrel(d, m, gt, m)) - 0.25) < 1e-6
    # masked-out pixels don't contribute
    m2 = jnp.array([[True, False]])
    assert abs(float(absrel(d, m2, gt, m2)) - 0.5) < 1e-6


def test_all_four_sequences_generate(cam):
    for name in ("simulation_3planes", "simulation_3walls", "slider_close",
                 "slider_far"):
        scene = make_scene(SceneConfig(name=name, points_per_plane=60))
        traj = make_trajectory(name, 8)
        ev = simulate_events(cam, scene, traj, noise_fraction=0.05, seed=1)
        assert bool(ev.valid.any()), name
        frac_valid = float(ev.valid.mean())
        assert frac_valid > 0.3, (name, frac_valid)


# --- ingest validation: the sorted/contiguous contract is enforced --------


def test_aggregator_push_rejects_non_monotone_naming_index(cam, small_scene):
    """A chunk with an intra-chunk timestamp regression must be rejected
    with a ValueError naming the first offending event index — not
    silently mis-binned into frames."""
    from repro.events.stream_hygiene import NonMonotoneEventError

    traj = small_scene["traj"]
    agg = StreamingAggregator(cam, traj, events_per_frame=64)
    t = np.float32([0.10, 0.11, 0.09, 0.12])
    bad = EventStream(xy=jnp.zeros((4, 2), jnp.float32), t=jnp.asarray(t),
                      polarity=jnp.ones((4,), jnp.int8),
                      valid=jnp.ones((4,), bool))
    with pytest.raises(NonMonotoneEventError, match=r"event 2 at"):
        agg.push(bad)
    assert isinstance(NonMonotoneEventError("x"), ValueError)


def test_aggregator_push_rejects_overlapping_chunks(cam, small_scene):
    """A chunk that regresses behind the previous push's last timestamp
    overlaps time already committed — a typed ValueError, state intact."""
    from repro.events.stream_hygiene import StreamOverlapError

    ev, traj = small_scene["events"], small_scene["traj"]
    agg = StreamingAggregator(cam, traj, events_per_frame=64)

    def part(i, j):
        return EventStream(xy=ev.xy[i:j], t=ev.t[i:j],
                           polarity=ev.polarity[i:j], valid=ev.valid[i:j])

    agg.push(part(0, 256))
    with pytest.raises(StreamOverlapError, match="watermark"):
        agg.push(part(128, 384))  # replays times 128..255
    agg.push(part(256, 512))  # the rejection did not poison the stream
    assert agg.pending_events == 512 % 64


def test_offline_aggregate_rejects_unsorted_stream(cam, small_scene):
    """aggregate() shares push()'s validation: an unsorted stream is a
    loud error, not a silently scrambled frame tensor."""
    ev, traj = small_scene["events"], small_scene["traj"]
    perm = np.arange(int(ev.t.shape[0]))
    perm[10], perm[20] = perm[20], perm[10]
    bad = EventStream(xy=ev.xy[perm], t=ev.t[perm],
                      polarity=ev.polarity[perm], valid=ev.valid[perm])
    with pytest.raises(ValueError, match="non-monotone"):
        aggregate(cam, bad, traj, events_per_frame=64)


# --- chunk iterators: edge cases + bitwise reassembly ---------------------


def test_iter_event_chunks_edge_cases(cam, small_scene):
    from repro.serving.emvs_stream import iter_event_chunks

    ev = small_scene["events"]
    n = int(ev.t.shape[0])
    # empty stream -> no chunks at all
    empty = EventStream(xy=ev.xy[:0], t=ev.t[:0],
                        polarity=ev.polarity[:0], valid=ev.valid[:0])
    assert list(iter_event_chunks(empty, 128)) == []
    # chunk larger than the stream -> exactly one chunk, the whole stream
    whole = list(iter_event_chunks(ev, n + 999))
    assert len(whole) == 1 and int(whole[0].t.shape[0]) == n
    # ragged tail: n % chunk != 0 -> last chunk carries the remainder
    chunk = 257
    assert n % chunk != 0, "fixture must leave a ragged tail"
    parts = list(iter_event_chunks(ev, chunk))
    assert [int(p.t.shape[0]) for p in parts[:-1]] == [chunk] * (len(parts) - 1)
    assert int(parts[-1].t.shape[0]) == n % chunk
    # bitwise reassembly: concatenating the chunks is the identity
    for field in ("xy", "t", "polarity", "valid"):
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(getattr(p, field)) for p in parts]),
            np.asarray(getattr(ev, field)))
    # invalid sizes are loud
    for sz in (0, -1, 1.5, True):
        with pytest.raises(ValueError):
            list(iter_event_chunks(ev, sz))


def test_iter_trajectory_chunks_edge_cases(small_scene):
    from repro.events.simulator import Trajectory, iter_trajectory_chunks
    from repro.core.geometry import SE3

    traj = small_scene["traj"]
    n = int(traj.times.shape[0])
    # empty trajectory -> no chunks
    empty = Trajectory(times=traj.times[:0],
                       poses=SE3(traj.poses.R[:0], traj.poses.t[:0]))
    assert list(iter_trajectory_chunks(empty, 4)) == []
    # chunk larger than the trajectory -> one chunk, everything
    whole = list(iter_trajectory_chunks(traj, n + 5))
    assert len(whole) == 1 and int(whole[0].times.shape[0]) == n
    # ragged tail + bitwise reassembly
    chunk = 5
    assert n % chunk != 0, "fixture must leave a ragged tail"
    parts = list(iter_trajectory_chunks(traj, chunk))
    assert int(parts[-1].times.shape[0]) == n % chunk
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.times) for p in parts]),
        np.asarray(traj.times))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.poses.R) for p in parts]),
        np.asarray(traj.poses.R))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.poses.t) for p in parts]),
        np.asarray(traj.poses.t))
    with pytest.raises(ValueError, match="chunk_poses"):
        list(iter_trajectory_chunks(traj, 0))
