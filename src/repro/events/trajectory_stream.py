"""Streamed trajectory: incremental pose ingestion with a safety watermark.

The paper's heterogeneous system assumes poses arrive from an external
tracker (a VIO/SLAM pipeline on the ARM side) while events stream in. In
real event-based pipelines (e.g. Event-based Stereo Visual Odometry,
Zhou et al. 2020) that tracker runs asynchronously and *behind* the
event front — so the pose source cannot be a fully-known `Trajectory`
oracle. `TrajectoryBuffer` is the streamed replacement: pose chunks are
pushed incrementally (in time order), and the buffer maintains a
monotonically advancing **pose-lag watermark** — the latest time at
which interpolation is safe, i.e. bracketed by received samples.
Queries outside the covered span raise `PoseExtrapolationError` instead
of silently clamping to a stale, frozen pose (the seed's latent bug:
`pose_at_times` clipped `frac` to [0, 1], so a frame past the pose
front got the last pose with no error and back-projected quietly
wrong).

`pose_at_times` (the interpolation core, host float32 NumPy,
re-exported by `repro.events.aggregation`) lives here too, with the
`strict=` mode and the single-sample validation; `enforce_pose_span`
is the shared out-of-span policy ("clamp" — the seed behavior, opt-in
only — / "warn" / "raise") used by the offline aggregation path and by
the streaming release path alike.
"""
from __future__ import annotations

import warnings

import numpy as np

from repro.core.geometry import SE3, interpolate_pose
from repro.events.simulator import Trajectory

# Out-of-span pose-query policies: "clamp" silently freezes the pose at
# the nearest trajectory endpoint (the seed behavior, kept only behind
# this explicit flag), "warn" clamps but emits PoseExtrapolationWarning,
# "raise" refuses with PoseExtrapolationError.
POSE_EXTRAPOLATION_POLICIES = ("clamp", "warn", "raise")


class PoseExtrapolationError(RuntimeError):
    """A pose query fell outside the span covered by trajectory samples."""


class PoseStallError(RuntimeError):
    """A streaming flush was asked to finish while frames still await poses."""


class PoseExtrapolationWarning(UserWarning):
    """A pose query outside the trajectory span was clamped to an endpoint."""


def enforce_pose_span(times: np.ndarray, t_query, policy: str,
                      context: str = "pose query") -> None:
    """Apply the out-of-span policy for queries against `times`.

    `times` must be a host (numpy) array of at least 2 sorted sample
    times; `t_query` may be scalar or vector (converted to host — strict
    checking is inherently a host-side decision).
    """
    if policy not in POSE_EXTRAPOLATION_POLICIES:
        raise ValueError(
            f"unknown pose_extrapolation policy {policy!r}: expected one of "
            f"{POSE_EXTRAPOLATION_POLICIES}")
    if policy == "clamp":
        return
    tq = np.atleast_1d(np.asarray(t_query))
    t0, t1 = float(times[0]), float(times[-1])
    below = tq < t0
    above = tq > t1
    n_out = int(below.sum() + above.sum())
    if n_out == 0:
        return
    worst = float(tq.max()) if above.any() else float(tq.min())
    msg = (f"{context}: {n_out} of {tq.shape[0]} query time(s) outside the "
           f"trajectory span [{t0:.6g}, {t1:.6g}] (worst t={worst:.6g}); "
           f"interpolation would freeze the pose at the span endpoint")
    if policy == "raise":
        raise PoseExtrapolationError(msg)
    warnings.warn(msg, PoseExtrapolationWarning, stacklevel=2)


def pose_at_times(traj: Trajectory, t_query, *,
                  strict: bool = False) -> SE3:
    """Interpolate trajectory poses at query times (vectorized).

    Host float32 NumPy end to end: the samples are read as host arrays
    and the poses come back as host `np.float32` arrays, so no device
    program runs. A device-array `Trajectory` costs a copy to the host
    on every call; callers on a hot path keep a host copy (the
    aggregator does).

    With `strict=True`, queries outside `[times[0], times[-1]]` raise
    `PoseExtrapolationError` (host-side check) instead of clamping to the
    span endpoint. The default keeps the clamping numerics (callers that
    want a warning instead route through `enforce_pose_span`).

    Trajectories with fewer than two samples are rejected: a single
    sample cannot bracket any query, and the seed's index clip
    (`clip(idx, 0, shape[0] - 2)`) would produce an inverted [0, -1]
    bound and read `times[idx + 1]` out of range.
    """
    n = int(traj.times.shape[0])
    if n < 2:
        raise ValueError(
            f"pose interpolation needs at least 2 trajectory samples, got "
            f"{n}: one sample cannot bracket any query time")
    times = np.asarray(traj.times, np.float32)
    t_query = np.asarray(t_query, np.float32)
    if strict:
        enforce_pose_span(times, t_query, "raise")
    R = np.asarray(traj.poses.R, np.float32)
    t = np.asarray(traj.poses.t, np.float32)
    # locate bracketing samples
    idx = np.clip(np.searchsorted(times, t_query, side="right") - 1, 0, n - 2)
    t0, t1 = times[idx], times[idx + 1]
    frac = np.clip((t_query - t0) / np.maximum(t1 - t0, 1e-9), 0.0, 1.0)
    return interpolate_pose(SE3(R[idx], t[idx]), SE3(R[idx + 1], t[idx + 1]),
                            frac)


class TrajectoryBuffer:
    """Incrementally received trajectory with a pose-lag watermark.

    Pose chunks are pushed in time order (each chunk strictly after the
    previous one; times strictly increasing within a chunk). The
    **watermark** is the latest time at which interpolation is bracketed
    by received samples — `times[-1]` once at least two samples exist,
    `-inf` before that — and it only ever advances. `pose_at_times`
    answers queries strictly within the covered span
    `[times[0], watermark]` and raises `PoseExtrapolationError` outside
    it: a streamed pose source never silently extrapolates.

    Note the bitwise subtlety the streaming release logic leans on: for
    a query `t < watermark` the bracketing interval can never change
    when later chunks arrive, so interpolating from a prefix of the
    trajectory is bit-identical to interpolating from the full one. At
    `t == watermark` the bracket still depends on whether another sample
    will arrive, so callers that need bitwise offline equivalence gate
    on strict inequality until the pose stream is finalized.
    """

    def __init__(self, chunk: Trajectory | None = None):
        self._times = np.zeros((0,), np.float32)
        self._R = np.zeros((0, 3, 3), np.float32)
        self._t = np.zeros((0, 3), np.float32)
        if chunk is not None:
            self.push(chunk)

    @property
    def num_samples(self) -> int:
        return int(self._times.shape[0])

    @property
    def watermark(self) -> float:
        """Latest safely interpolable time; -inf until 2 samples exist."""
        if self.num_samples < 2:
            return float("-inf")
        return float(self._times[-1])

    @property
    def start_time(self) -> float:
        """Earliest covered time; +inf until 2 samples exist."""
        if self.num_samples < 2:
            return float("inf")
        return float(self._times[0])

    def push(self, chunk: Trajectory) -> float:
        """Append one pose chunk; returns the (possibly advanced) watermark.

        Chunks must arrive in time order: strictly increasing times
        within the chunk, and strictly after everything already
        buffered. Empty chunks are allowed (a tracker tick with no new
        keyposes).
        """
        times = np.asarray(chunk.times, np.float32).reshape(-1)
        R = np.asarray(chunk.poses.R, np.float32)
        t = np.asarray(chunk.poses.t, np.float32)
        m = times.shape[0]
        if R.shape != (m, 3, 3) or t.shape != (m, 3):
            raise ValueError(
                f"pose chunk shape mismatch: {m} times vs R {R.shape}, "
                f"t {t.shape}")
        if m == 0:
            return self.watermark
        if np.any(np.diff(times) <= 0):
            raise ValueError("pose chunk times must be strictly increasing")
        if self.num_samples and times[0] <= self._times[-1]:
            raise ValueError(
                f"pose chunk starts at t={float(times[0]):.6g} but the "
                f"buffer already covers up to t={float(self._times[-1]):.6g}: "
                f"chunks must arrive in time order")
        self._times = np.concatenate([self._times, times])
        self._R = np.concatenate([self._R, R])
        self._t = np.concatenate([self._t, t])
        return self.watermark

    @property
    def times(self) -> np.ndarray:
        """Host-side view of the received sample times (do not mutate)."""
        return self._times

    def covers(self, t_query) -> np.ndarray:
        """Elementwise: is the query bracketed by received samples?"""
        tq = np.asarray(t_query)
        if self.num_samples < 2:
            return np.zeros(tq.shape, bool)
        return (tq >= self._times[0]) & (tq <= self._times[-1])

    def trajectory(self, lo: int = 0, hi: int | None = None) -> Trajectory:
        """Host-side view (no copy) of samples [lo, hi), everything by
        default."""
        sl = slice(lo, hi)
        return Trajectory(times=self._times[sl],
                          poses=SE3(self._R[sl], self._t[sl]))

    def pose_at_times(self, t_query) -> SE3:
        """Interpolate within the covered span only.

        Raises `PoseExtrapolationError` for any query outside
        `[start_time, watermark]` — including every query while fewer
        than two samples have been received.
        """
        if self.num_samples < 2:
            raise PoseExtrapolationError(
                f"trajectory buffer holds {self.num_samples} pose sample(s); "
                f"interpolation needs at least 2 (watermark {self.watermark})")
        enforce_pose_span(
            self._times, t_query, "raise",
            context=f"streamed trajectory (watermark t={self.watermark:.6g})")
        return pose_at_times(self.trajectory(), t_query)
