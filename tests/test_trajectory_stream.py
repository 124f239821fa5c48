"""Streamed trajectory: TrajectoryBuffer watermark semantics, strict
pose interpolation, and the pose-gated StreamingAggregator stall/release
path. The core guarantee under test: no code path silently extrapolates
a pose beyond the received trajectory, and released frames are posed
bit-identically to the offline oracle for any event x pose interleaving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.geometry import SE3, so3_exp
from repro.events.aggregation import (
    StreamingAggregator,
    aggregate,
    concat_event_frames,
)
from repro.events.simulator import (
    EventStream,
    Trajectory,
    iter_trajectory_chunks,
    slice_trajectory,
)
from repro.events.trajectory_stream import (
    PoseExtrapolationError,
    PoseExtrapolationWarning,
    TrajectoryBuffer,
    enforce_pose_span,
    pose_at_times,
)


def _traj(n: int, t0: float = 0.0, t1: float = 1.0, seed: int = 0) -> Trajectory:
    rng = np.random.default_rng(seed)
    times = np.linspace(t0, t1, n).astype(np.float32)
    w = rng.uniform(-0.1, 0.1, (n, 3)).astype(np.float32)
    R = np.asarray(so3_exp(w), np.float32)
    t = np.cumsum(rng.uniform(-0.05, 0.05, (n, 3)), axis=0).astype(np.float32)
    return Trajectory(times=times, poses=SE3(R, t))


_slice = slice_trajectory


def _events(n: int, t0: float = 0.0, t1: float = 1.0, seed: int = 0) -> EventStream:
    rng = np.random.default_rng(seed)
    return EventStream(
        xy=rng.uniform(0, 200, (n, 2)).astype(np.float32),
        t=np.sort(rng.uniform(t0, t1, n).astype(np.float32)),
        polarity=rng.choice([-1, 1], n).astype(np.int8),
        valid=np.ones(n, bool),
    )


# --- pose_at_times: strict mode + degenerate trajectories -----------------


def test_pose_at_times_strict_raises_outside_span():
    traj = _traj(8)
    inside = np.asarray([0.2, 0.9], np.float32)
    p = pose_at_times(traj, inside, strict=True)
    assert p.R.shape == (2, 3, 3)
    with pytest.raises(PoseExtrapolationError, match="outside the trajectory"):
        pose_at_times(traj, np.asarray([0.2, 1.2], np.float32), strict=True)
    with pytest.raises(PoseExtrapolationError, match="outside the trajectory"):
        pose_at_times(traj, np.asarray([-0.1], np.float32), strict=True)
    # span endpoints are bracketed, not extrapolated
    pose_at_times(traj, np.asarray([0.0, 1.0], np.float32), strict=True)


def test_pose_at_times_single_sample_raises():
    """The seed clipped idx to [0, -1] and read times[idx + 1] out of
    range for a 1-pose trajectory; now it must refuse up front."""
    one = _slice(_traj(4), 0, 1)
    with pytest.raises(ValueError, match="at least 2 trajectory samples"):
        pose_at_times(one, np.asarray([0.0], np.float32))
    empty = _slice(_traj(4), 0, 0)
    with pytest.raises(ValueError, match="at least 2 trajectory samples"):
        pose_at_times(empty, np.asarray([0.0], np.float32))


@pytest.mark.parametrize("angle", [0.0, 1e-6, 0.05, 1.0, np.pi - 0.1])
def test_pose_at_times_host_float32_matches_float64(pose_f64, angle):
    """Host float32 interpolation against the same formula in float64,
    for samples `angle` apart in rotation: queries at the sample times,
    between samples and outside the span (clamped). A device-array
    trajectory gives the bits of its host copy."""
    rng = np.random.default_rng(7)
    n = 12
    times = np.cumsum(rng.uniform(0.5, 1.5, n)).astype(np.float32)
    axes = rng.normal(size=(n - 1, 3))
    axes /= np.linalg.norm(axes, axis=-1, keepdims=True)
    R = [pose_f64.so3_exp(rng.uniform(-2.0, 2.0, 3))]
    for axis in axes:
        R.append(pose_f64.so3_exp(axis * angle) @ R[-1])
    R = np.stack(R).astype(np.float32)
    t = rng.normal(size=(n, 3)).astype(np.float32)
    traj = Trajectory(times=times, poses=SE3(R, t))
    q = np.concatenate([times, rng.uniform(times[0], times[-1], 40),
                        [times[0] - 1.0, times[-1] + 1.0]]).astype(np.float32)
    got = pose_at_times(traj, q)
    for x in got:
        assert type(x) is np.ndarray and x.dtype == np.float32
    R_ref, t_ref = pose_f64.pose_at_times(times, R, t, q)
    # float32 rounding, amplified near pi by the log's 1 / (1 + cos)
    tol = 1e-6 + 4 * float(np.finfo(np.float32).eps) / (1.0 + np.cos(angle))
    np.testing.assert_allclose(got.R, R_ref, rtol=0, atol=tol)
    np.testing.assert_allclose(got.t, t_ref, rtol=0, atol=1e-5)
    # a sample time (but the last) and a query below the span read that
    # sample's pose bit for bit
    np.testing.assert_array_equal(got.R[:n - 1], R[:n - 1])
    np.testing.assert_array_equal(got.t[:n - 1], t[:n - 1])
    np.testing.assert_array_equal(got.R[-2], R[0])
    np.testing.assert_array_equal(got.t[-2], t[0])
    dev = pose_at_times(jax.tree.map(jnp.asarray, traj), q)
    np.testing.assert_array_equal(dev.R, got.R)
    np.testing.assert_array_equal(dev.t, got.t)


def test_enforce_pose_span_policies():
    times = np.asarray([0.0, 1.0], np.float32)
    enforce_pose_span(times, np.asarray([1.5]), "clamp")  # silent by request
    with pytest.warns(PoseExtrapolationWarning, match="outside the trajectory"):
        enforce_pose_span(times, np.asarray([1.5]), "warn")
    with pytest.raises(PoseExtrapolationError):
        enforce_pose_span(times, np.asarray([-1.0]), "raise")
    with pytest.raises(ValueError, match="unknown pose_extrapolation"):
        enforce_pose_span(times, np.asarray([0.5]), "never")


# --- TrajectoryBuffer ------------------------------------------------------


def test_buffer_watermark_advances_monotonically():
    traj = _traj(12)
    buf = TrajectoryBuffer()
    assert buf.watermark == float("-inf") and buf.num_samples == 0
    assert not buf.covers(0.0)
    seen = float("-inf")
    for chunk in iter_trajectory_chunks(traj, 5):
        wm = buf.push(chunk)
        assert wm >= seen, "watermark must only advance"
        seen = wm
    assert buf.num_samples == 12
    assert seen == float(np.asarray(traj.times)[-1])
    assert bool(buf.covers(0.5)) and not bool(buf.covers(1.5))


def test_buffer_single_sample_has_no_coverage():
    traj = _traj(6)
    buf = TrajectoryBuffer(_slice(traj, 0, 1))
    assert buf.num_samples == 1
    assert buf.watermark == float("-inf")
    assert not bool(buf.covers(float(np.asarray(traj.times)[0])))
    with pytest.raises(PoseExtrapolationError, match="needs at least 2"):
        buf.pose_at_times(np.asarray([0.0], np.float32))


def test_buffer_rejects_out_of_order_and_malformed_chunks():
    traj = _traj(10)
    buf = TrajectoryBuffer(_slice(traj, 0, 4))
    with pytest.raises(ValueError, match="time order"):
        buf.push(_slice(traj, 2, 6))  # overlaps what is already buffered
    with pytest.raises(ValueError, match="strictly increasing"):
        buf.push(Trajectory(times=np.asarray([2.0, 2.0], np.float32),
                            poses=SE3(np.zeros((2, 3, 3), np.float32),
                                      np.zeros((2, 3), np.float32))))
    with pytest.raises(ValueError, match="shape mismatch"):
        buf.push(Trajectory(times=np.asarray([3.0], np.float32),
                            poses=SE3(np.zeros((2, 3, 3), np.float32),
                                      np.zeros((2, 3), np.float32))))
    # rejected chunks must not corrupt the buffer
    assert buf.num_samples == 4
    buf.push(_slice(traj, 4, 10))
    assert buf.num_samples == 10
    # empty chunks are a tracker tick with no keyposes: allowed, no-op
    assert buf.push(_slice(traj, 10, 10)) == buf.watermark


def test_buffer_prefix_interpolation_is_bitwise_stable():
    """For queries strictly below the watermark, interpolating against
    the received prefix must equal interpolating against the eventual
    full trajectory — bitwise. (This is what lets the aggregator release
    stalled frames before the trajectory ends.)"""
    traj = _traj(16, seed=3)
    times = np.asarray(traj.times)
    q = np.asarray(
        np.sort(np.random.default_rng(1).uniform(0.0, times[9] - 1e-4, 13)),
        np.float32)
    full = pose_at_times(traj, q)
    buf = TrajectoryBuffer(_slice(traj, 0, 10))  # covers beyond every query
    got = buf.pose_at_times(q)
    np.testing.assert_array_equal(np.asarray(got.R), np.asarray(full.R))
    np.testing.assert_array_equal(np.asarray(got.t), np.asarray(full.t))


def test_buffer_query_past_watermark_raises_with_watermark_context():
    traj = _traj(8)
    buf = TrajectoryBuffer(_slice(traj, 0, 4))
    wm = buf.watermark
    with pytest.raises(PoseExtrapolationError, match="watermark"):
        buf.pose_at_times(np.asarray([wm + 0.05], np.float32))


# --- pose-gated StreamingAggregator ----------------------------------------


@pytest.fixture()
def gated_setup(cam):
    traj = _traj(12, seed=2)
    ev = _events(100, seed=2)
    ref = aggregate(cam, ev, traj, events_per_frame=16)
    return traj, ev, ref


def _collect(parts) -> list:
    return [p for p in parts if p.xy.shape[0] > 0]


def test_gated_aggregator_stalls_then_releases_bitwise(cam, gated_setup):
    traj, ev, ref = gated_setup
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=16)
    parts = [agg.push(ev)]
    assert parts[0].xy.shape[0] == 0, "no poses received -> everything stalls"
    assert agg.stalled_frames == 100 // 16
    released = 0
    for chunk in iter_trajectory_chunks(traj, 3):
        part = agg.push_poses(chunk)
        released += part.xy.shape[0]
        parts.append(part)
    parts.append(agg.flush())
    parts.append(agg.finalize_poses())
    assert agg.stalled_frames == 0
    got = concat_event_frames(_collect(parts))
    assert released >= 1, "interior pose chunks must release stalled frames"
    for name in ("xy", "valid", "t_mid"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(got.poses.R),
                                  np.asarray(ref.poses.R))
    np.testing.assert_array_equal(np.asarray(got.poses.t),
                                  np.asarray(ref.poses.t))


def test_gated_aggregator_one_pose_chunk_releases_many(cam, gated_setup):
    traj, ev, _ = gated_setup
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=16)
    agg.push(ev)
    n_stalled = agg.stalled_frames
    assert n_stalled >= 4
    part = agg.push_poses(traj)  # whole trajectory in one chunk
    assert part.xy.shape[0] >= n_stalled - 1, (
        "a single chunk advancing the watermark past many frames must "
        "release them all at once")
    assert agg.stalled_frames <= 1  # only a frame at/past the watermark may stall


def test_gated_aggregator_release_is_fifo(cam, gated_setup):
    traj, ev, _ = gated_setup
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=16)
    agg.push(ev)
    t_mids = []
    for chunk in iter_trajectory_chunks(traj, 2):
        t_mids.extend(np.asarray(agg.push_poses(chunk).t_mid).tolist())
    t_mids.extend(np.asarray(agg.finalize_poses().t_mid).tolist())
    assert t_mids == sorted(t_mids), "stalled frames must release in order"


def test_gated_finalize_applies_policy_to_beyond_end_frames(cam):
    """Events past the final pose sample: warn-clamp by default, raise on
    strict pipelines — never a silent freeze."""
    traj = _traj(6, t0=0.0, t1=0.5)
    ev = _events(32, t0=0.0, t1=1.0, seed=5)  # second half past the poses
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=8)
    agg.push(ev)
    agg.push_poses(traj)
    assert agg.stalled_frames > 0, "frames past the pose end must stall"
    with pytest.warns(PoseExtrapolationWarning, match="outside the trajectory"):
        released = agg.finalize_poses()
    assert agg.stalled_frames == 0
    # the clamped numerics equal the offline oracle's (warn != different values)
    ref = aggregate(cam, ev, traj, events_per_frame=8,
                    pose_extrapolation="clamp")
    np.testing.assert_array_equal(np.asarray(released.poses.t)[-1],
                                  np.asarray(ref.poses.t)[-1])

    strict = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=8,
                                 pose_extrapolation="raise")
    strict.push(ev)
    strict.push_poses(traj)
    with pytest.raises(PoseExtrapolationError):
        strict.finalize_poses()


def test_gated_finalize_without_enough_samples_raises(cam):
    ev = _events(16, seed=7)
    agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=8)
    agg.push(ev)
    with pytest.raises(PoseExtrapolationError, match="can never be posed"):
        agg.finalize_poses()


def test_oracle_aggregator_rejects_pose_stream_calls(cam):
    traj = _traj(4)
    agg = StreamingAggregator(cam, traj, events_per_frame=8)
    with pytest.raises(RuntimeError, match="TrajectoryBuffer"):
        agg.push_poses(traj)
    with pytest.raises(RuntimeError, match="TrajectoryBuffer"):
        agg.finalize_poses()


def test_interleaving_invariance_bitwise(cam):
    """Any interleaving of event chunks and pose chunks produces the same
    frames, bit-identical to the offline oracle aggregation."""
    traj = _traj(10, seed=4)
    ev = _events(120, seed=4)
    ref = aggregate(cam, ev, traj, events_per_frame=16)
    rng = np.random.default_rng(11)
    for trial in range(4):
        agg = StreamingAggregator(cam, TrajectoryBuffer(), events_per_frame=16)
        parts = []
        ev_cuts = np.sort(rng.integers(0, 121, size=3)).tolist()
        pose_cuts = np.sort(rng.integers(0, 11, size=2)).tolist()
        ev_slices = list(zip([0] + ev_cuts, ev_cuts + [120]))
        pose_slices = list(zip([0] + pose_cuts, pose_cuts + [10]))
        # alternate event and pose chunks (ragged: lists differ in length)
        while ev_slices or pose_slices:
            if ev_slices:
                lo, hi = ev_slices.pop(0)
                parts.append(agg.push(EventStream(
                    xy=ev.xy[lo:hi], t=ev.t[lo:hi],
                    polarity=ev.polarity[lo:hi], valid=ev.valid[lo:hi])))
            if pose_slices:
                lo, hi = pose_slices.pop(0)
                parts.append(agg.push_poses(_slice(traj, lo, hi)))
        parts.append(agg.flush())
        parts.append(agg.finalize_poses())
        got = concat_event_frames(_collect(parts))
        np.testing.assert_array_equal(np.asarray(got.xy), np.asarray(ref.xy),
                                      err_msg=f"trial {trial}")
        np.testing.assert_array_equal(np.asarray(got.poses.t),
                                      np.asarray(ref.poses.t),
                                      err_msg=f"trial {trial}")
        np.testing.assert_array_equal(np.asarray(got.poses.R),
                                      np.asarray(ref.poses.R),
                                      err_msg=f"trial {trial}")


# --- host/device contract ---------------------------------------------------


def test_emitted_frames_are_host_numpy_with_jnp_median_values(cam):
    """The aggregator's docstring promises frames stay on the host; t_mid
    must come out of np.median yet stay bit-identical to the previous
    jnp.median datapath."""
    import jax
    import jax.numpy as jnp

    n, e = 70, 16  # 4 full frames + a 6-event tail
    traj = _traj(6, seed=9)
    ev = _events(n, seed=9)
    agg = StreamingAggregator(cam, traj, events_per_frame=e)
    frames = agg.push(ev)
    tail = agg.flush()
    assert frames.xy.shape[0] == n // e and tail.xy.shape[0] == 1
    for f in (frames, tail):
        for field in (f.xy, f.valid, f.t_mid, f.poses.R, f.poses.t):
            assert isinstance(field, np.ndarray), type(field)
            assert not isinstance(field, jax.Array)
    # values: np.median == jnp.median bitwise on the same event times
    t_full = np.asarray(ev.t)[:n - n % e].reshape(-1, e)
    np.testing.assert_array_equal(
        frames.t_mid, np.asarray(jnp.median(jnp.asarray(t_full), axis=1)))
    np.testing.assert_array_equal(
        tail.t_mid,
        np.asarray(jnp.median(jnp.asarray(np.asarray(ev.t)[n - n % e:])))[None])
