"""Event aggregation (A): stream -> fixed-size event frames.

The paper aggregates 1024 events per frame ("determined according to the
sensor's event rate and storage") and attaches one camera pose per frame
(interpolated at the frame's mid-timestamp).

Per the paper's rescheduling, distortion correction runs *before*
aggregation, per event, in streaming order.

Aggregation is incremental: `StreamingAggregator` accepts raw event
chunks of arbitrary size and carries the partial-frame remainder across
pushes, exactly as the device-side A stage holds a partial frame in its
buffer while waiting for more events. The offline `aggregate` is one big
push plus a flush, so the stream's tail is emitted as a final padded
frame instead of being silently dropped.

Poses come from either a fully-known `Trajectory` (the offline oracle)
or a `TrajectoryBuffer` receiving the tracker's pose stream in chunks
(`repro.events.trajectory_stream`). In the streamed (pose-gated) mode a
completed frame whose mid-time lies beyond the buffer's pose-lag
watermark is *stalled* — held unposed until the bracketing pose chunk
arrives — and then released bitwise-identically posed, so any
interleaving of event and pose chunks yields the same frames. Queries
outside the received span follow the `pose_extrapolation` policy
("warn" by default: clamp + `PoseExtrapolationWarning`; "raise";
"clamp" restores the seed's silent freeze and exists only for
compatibility).
"""
from __future__ import annotations

from collections import deque
from typing import NamedTuple

import jax
import numpy as np

from repro.core.camera import CameraModel, undistort_events
from repro.core.geometry import SE3
from repro.events.simulator import EventStream, Trajectory
from repro.events.stream_hygiene import check_chunk_monotone
from repro.events.trajectory_stream import (
    POSE_EXTRAPOLATION_POLICIES,
    PoseExtrapolationError,
    PoseExtrapolationWarning,
    PoseStallError,
    TrajectoryBuffer,
    enforce_pose_span,
    pose_at_times,
)

__all__ = [
    "EVENTS_PER_FRAME",
    "PARKED_COORD",
    "EventFrames",
    "PoseExtrapolationError",
    "PoseExtrapolationWarning",
    "PoseStallError",
    "StreamingAggregator",
    "TrajectoryBuffer",
    "aggregate",
    "concat_event_frames",
    "empty_event_frames",
    "pose_at_times",
]

Array = jax.Array

EVENTS_PER_FRAME = 1024  # paper §4.3

# Pad coordinate for events that exist only to fill out a frame: parked far
# outside the image (the simulator's convention for invalid events) so every
# downstream stage masks them even before the validity weight zeroes them.
PARKED_COORD = -1e4


class EventFrames(NamedTuple):
    """Aggregated frames. Fields produced by this module are host-side
    (numpy) arrays — staging into device programs happens downstream
    (`pad_segments`, the streaming engine's frame store) — but every
    consumer accepts jax arrays interchangeably."""

    xy: Array  # (F, E, 2) rectified coords
    valid: Array  # (F, E)
    t_mid: Array  # (F,)
    poses: SE3  # batched (F,3,3),(F,3): per-frame camera pose


def empty_event_frames(events_per_frame: int = EVENTS_PER_FRAME) -> EventFrames:
    """A zero-frame EventFrames with the usual field shapes/dtypes."""
    return EventFrames(
        xy=np.zeros((0, events_per_frame, 2), np.float32),
        valid=np.zeros((0, events_per_frame), bool),
        t_mid=np.zeros((0,), np.float32),
        poses=SE3(np.zeros((0, 3, 3), np.float32),
                  np.zeros((0, 3), np.float32)),
    )


def concat_event_frames(parts: list[EventFrames]) -> EventFrames:
    """Concatenate EventFrames along the frame axis (empties dropped)."""
    parts = [p for p in parts if p.xy.shape[0] > 0]
    if not parts:
        return empty_event_frames()
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: np.concatenate([np.asarray(x) for x in xs],
                                                   axis=0), *parts)


class _StalledFrame(NamedTuple):
    """A completed frame waiting for its bracketing pose samples."""

    xy: np.ndarray  # (E, 2)
    valid: np.ndarray  # (E,)
    t_mid: float


class StreamingAggregator:
    """Incremental A stage: push raw event chunks, receive completed frames.

    Each `push` applies streaming distortion correction to the chunk,
    prepends the remainder carried from the previous push, and emits every
    completed `events_per_frame`-sized frame (with its interpolated pose).
    The tail that does not fill a frame stays buffered for the next push;
    `flush` emits it as one final frame padded with parked, invalid events.

    Chunk boundaries never change the emitted frames: any chunking of the
    same stream produces bitwise-identical EventFrames (the streaming
    engine's offline-equivalence tests lean on exactly this).

    Pose source (`traj`):
      * a `Trajectory` — the offline oracle; every completed frame is
        posed immediately. Frame mid-times outside the trajectory span
        follow `pose_extrapolation` ("warn" clamps with
        `PoseExtrapolationWarning`; "raise" refuses; "clamp" is the
        seed's silent freeze, opt-in only).
      * a `TrajectoryBuffer` — the streamed tracker. Completed frames
        whose `t_mid` is not yet *strictly below* the buffer's watermark
        stall (see `stalled_frames`) and are released FIFO by
        `push_poses` / `finalize_poses` once the bracketing samples
        arrive; the strict inequality makes the released pose
        bit-identical to interpolating against the full trajectory, for
        any interleaving of event and pose chunks. `finalize_poses`
        declares the pose stream over: remaining frames release through
        the `pose_extrapolation` policy (they can only be beyond-span).

    `max_stalled` (pose-gated mode only) is the max-stall back-pressure
    bound: a push that leaves more than `max_stalled` frames stalled
    *past the current watermark* (i.e. frames the received poses cannot
    release — a tracker that keeps up never trips the bound) raises
    `PoseStallError`. The check runs after buffering the chunk's frames
    and before any release, so no event is lost: the caller recovers by
    pushing the missing pose chunks. Without a bound a tracker that
    silently dies would grow the stall queue (and every queue downstream
    of it) with the event rate, unboundedly.
    """

    def __init__(self, cam: CameraModel, traj: Trajectory | TrajectoryBuffer,
                 events_per_frame: int = EVENTS_PER_FRAME, *,
                 pose_extrapolation: str = "warn",
                 max_stalled: int | None = None):
        if events_per_frame < 1:
            raise ValueError(f"events_per_frame must be >= 1, got {events_per_frame}")
        if pose_extrapolation not in POSE_EXTRAPOLATION_POLICIES:
            raise ValueError(
                f"unknown pose_extrapolation policy {pose_extrapolation!r}: "
                f"expected one of {POSE_EXTRAPOLATION_POLICIES}")
        if max_stalled is not None and max_stalled < 1:
            raise ValueError(
                f"max_stalled must be >= 1 (or None for unbounded), got "
                f"{max_stalled}")
        self.cam = cam
        self.pose_extrapolation = pose_extrapolation
        self.max_stalled = max_stalled
        self._gated = isinstance(traj, TrajectoryBuffer)
        if max_stalled is not None and not self._gated:
            raise ValueError(
                "max_stalled requires a TrajectoryBuffer pose source: a "
                "fully-known Trajectory oracle never stalls frames, so "
                "the bound would silently do nothing")
        # the oracle as one host float32 copy: span checks and pose
        # interpolation read it on every push, and a device-array
        # Trajectory read there would wait behind the running sweep
        self.traj = traj if self._gated else Trajectory(
            np.asarray(traj.times, np.float32),
            SE3(np.asarray(traj.poses.R, np.float32),
                np.asarray(traj.poses.t, np.float32)))
        self.events_per_frame = int(events_per_frame)
        self._rem_xy = np.zeros((0, 2), np.float32)
        self._rem_t = np.zeros((0,), np.float32)
        self._rem_valid = np.zeros((0,), bool)
        self._last_t = float("-inf")
        self._stalled: deque[_StalledFrame] = deque()
        self._pose_final = False

    @property
    def pending_events(self) -> int:
        """Events buffered toward the next (incomplete) frame."""
        return self._rem_xy.shape[0]

    @property
    def pose_gated(self) -> bool:
        """True when the pose source is a streamed `TrajectoryBuffer`."""
        return self._gated

    @property
    def stalled_frames(self) -> int:
        """Completed frames held back waiting for pose chunks."""
        return len(self._stalled)

    @property
    def oldest_stalled_t(self) -> float:
        """Mid-time of the oldest stalled frame (+inf if none)."""
        return self._stalled[0].t_mid if self._stalled else float("inf")

    @property
    def pose_watermark(self) -> float:
        """Latest safely interpolable pose time received so far."""
        if self._gated:
            return self.traj.watermark
        return float(self.traj.times[-1])

    def push(self, chunk: EventStream) -> EventFrames:
        """Ingest a chunk (sorted, contiguous with prior pushes) of events.

        The sorted/contiguous contract is enforced, not assumed: a chunk
        with non-monotone timestamps, or one starting before the last
        pushed timestamp, raises a `ValueError`
        (`NonMonotoneEventError` / `StreamOverlapError`) naming the
        first offending index — a frame aggregated from misordered
        events would get a wrong mid-time and vote under a wrong pose,
        silently. Streams that need tolerance (drop, bounded reorder)
        should go through `events.stream_hygiene.StreamHygiene` (the
        streaming engine's `StreamConfig(hygiene=...)` does) before
        this backstop.
        """
        with jax.profiler.TraceAnnotation("emvs.aggregate"):
            t_chunk = np.asarray(chunk.t, np.float32)
            check_chunk_monotone(t_chunk, self._last_t,
                                 context="StreamingAggregator.push")
            if t_chunk.shape[0]:
                self._last_t = float(t_chunk[-1])
            xy = (undistort_events(self.cam, chunk.xy)
                  if self.cam.has_distortion() else chunk.xy)
            xy = np.concatenate([self._rem_xy, np.asarray(xy, np.float32)])
            t = np.concatenate([self._rem_t, np.asarray(chunk.t, np.float32)])
            valid = np.concatenate([self._rem_valid,
                                    np.asarray(chunk.valid, bool)])
            e = self.events_per_frame
            n_frames = xy.shape[0] // e
            n_keep = n_frames * e
            self._rem_xy, self._rem_t, self._rem_valid = (
                xy[n_keep:], t[n_keep:], valid[n_keep:])
            return self._emit(xy[:n_keep], t[:n_keep], valid[:n_keep],
                              n_frames)

    def push_poses(self, chunk: Trajectory) -> EventFrames:
        """Feed one pose chunk to the streamed trajectory; returns the
        stalled frames the advanced watermark releases (possibly none)."""
        if not self._gated:
            raise RuntimeError(
                "push_poses requires a TrajectoryBuffer pose source; this "
                "aggregator was built with a fully-known Trajectory oracle")
        with jax.profiler.TraceAnnotation("emvs.aggregate"):
            self.traj.push(chunk)
            return self._release()

    def finalize_poses(self) -> EventFrames:
        """Declare the pose stream complete and release every stalled frame.

        Frames at or beyond the final watermark can no longer gain a
        bracketing sample, so they release through the
        `pose_extrapolation` policy (warn-clamp or raise)."""
        if not self._gated:
            raise RuntimeError(
                "finalize_poses requires a TrajectoryBuffer pose source; "
                "a Trajectory oracle is always complete")
        with jax.profiler.TraceAnnotation("emvs.aggregate"):
            self._pose_final = True
            return self._release()

    def flush(self) -> EventFrames:
        """Emit the buffered tail as one padded frame (empty if no tail).

        In pose-gated mode the tail frame joins the stall queue like any
        other frame; the returned EventFrames contain only what the
        current watermark releases (check `stalled_frames` afterwards)."""
        with jax.profiler.TraceAnnotation("emvs.aggregate"):
            e = self.events_per_frame
            n_rem = self._rem_xy.shape[0]
            if n_rem == 0:
                if self._gated:
                    return self._release()
                return empty_event_frames(e)
            # t_mid from the REAL tail events only — the padding exists to
            # fill the frame shape and must not drag the pose toward the
            # last event
            t_mid = np.asarray(np.median(self._rem_t), np.float32).reshape(1)
            pad = e - n_rem
            xy = np.concatenate(
                [self._rem_xy, np.full((pad, 2), PARKED_COORD, np.float32)])
            t = np.concatenate(
                [self._rem_t, np.full((pad,), self._rem_t[-1], np.float32)])
            valid = np.concatenate([self._rem_valid, np.zeros((pad,), bool)])
            self._rem_xy = np.zeros((0, 2), np.float32)
            self._rem_t = np.zeros((0,), np.float32)
            self._rem_valid = np.zeros((0,), bool)
            return self._emit(xy, t, valid, 1, t_mid=t_mid)

    def _emit(self, xy: np.ndarray, t: np.ndarray, valid: np.ndarray,
              n_frames: int, t_mid: np.ndarray | None = None) -> EventFrames:
        e = self.events_per_frame
        if n_frames == 0:
            return self._release() if self._gated else empty_event_frames(e)
        t_f = t.reshape(n_frames, e)
        if t_mid is None:
            # host median: frames stay on the host (numpy) end to end — the
            # consumers (pad_segments, the streaming engine's frame store)
            # stage host-side, so a device round-trip per push would be
            # pure waste. np.median matches jnp.median bitwise on float32.
            t_mid = np.median(t_f, axis=1)
        t_mid = np.asarray(t_mid, np.float32)
        xy_f = xy.reshape(n_frames, e, 2)
        valid_f = valid.reshape(n_frames, e)
        if self._gated:
            for k in range(n_frames):
                self._stalled.append(
                    _StalledFrame(xy_f[k], valid_f[k], float(t_mid[k])))
            # Max-stall back-pressure: an event front running unboundedly
            # ahead of the pose tracker would grow the stall queue (and
            # everything downstream of it — the engine's coalescing queue
            # included) without limit. Only frames the CURRENT watermark
            # cannot release count toward the bound (a tracker that keeps
            # up never trips it), and the check runs after buffering the
            # chunk's frames but BEFORE the release — on overflow nothing
            # has been popped, so no frame is ever dropped and the caller
            # recovers by pushing the missing pose chunks (draining the
            # queue bit-identically) before feeding more events.
            if self.max_stalled is not None:
                wm = self.pose_watermark
                backlog = sum(1 for f in self._stalled if not f.t_mid < wm)
                if backlog > self.max_stalled:
                    raise PoseStallError(
                        f"pose tracker too far behind the event front: "
                        f"{backlog} frame(s) stalled past the watermark "
                        f"exceeds max_stalled={self.max_stalled} (watermark "
                        f"t={wm:.6g}, oldest stalled frame "
                        f"t_mid={self.oldest_stalled_t:.6g}); the frames "
                        f"are buffered — push the missing pose chunks to "
                        f"drain the stall queue before feeding more events")
            return self._release()
        with jax.profiler.TraceAnnotation("emvs.pose_interp"):
            enforce_pose_span(self.traj.times, t_mid,
                              self.pose_extrapolation,
                              context="frame mid-times")
            poses = pose_at_times(self.traj, t_mid)
        return EventFrames(xy=xy_f, valid=valid_f, t_mid=t_mid, poses=poses)

    def _release(self) -> EventFrames:
        """Pose and emit the FIFO prefix of stalled frames the watermark
        covers (everything, once the pose stream is finalized)."""
        e = self.events_per_frame
        if not self._stalled:
            return empty_event_frames(e)
        buf: TrajectoryBuffer = self.traj
        if buf.num_samples < 2:
            if self._pose_final:
                raise PoseExtrapolationError(
                    f"pose stream finalized with {buf.num_samples} sample(s) "
                    f"received; {len(self._stalled)} stalled frame(s) can "
                    f"never be posed")
            return empty_event_frames(e)
        if self._pose_final:
            take = len(self._stalled)
        else:
            # strictly below the watermark: the bracketing interval can no
            # longer change, so the interpolated pose is bit-identical to
            # the one the full trajectory will eventually give
            wm = buf.watermark
            take = 0
            while take < len(self._stalled) and self._stalled[take].t_mid < wm:
                take += 1
        if take == 0:
            return empty_event_frames(e)
        frames = [self._stalled.popleft() for _ in range(take)]
        t_mid = np.asarray([f.t_mid for f in frames], np.float32)
        with jax.profiler.TraceAnnotation("emvs.pose_interp"):
            times = buf.times
            n_s = times.shape[0]
            enforce_pose_span(times, t_mid, self.pose_extrapolation,
                              context="stalled frame mid-times")
            # interpolate over the bracketing slice of the pose history:
            # released t_mid are ascending (FIFO over a sorted event
            # stream), and searchsorted over a slice containing every
            # bracket returns the same intervals, so the pose is bitwise
            # the one the whole history gives
            lo = int(np.clip(np.searchsorted(times, t_mid[0],
                                             side="right") - 1, 0, n_s - 2))
            hi = max(min(n_s, int(np.searchsorted(times, t_mid[-1],
                                                  side="right")) + 1), lo + 2)
            poses = pose_at_times(buf.trajectory(lo, hi), t_mid)
        return EventFrames(
            xy=np.stack([f.xy for f in frames]),
            valid=np.stack([f.valid for f in frames]),
            t_mid=t_mid,
            poses=poses,
        )


def aggregate(cam: CameraModel, stream: EventStream, traj: Trajectory,
              events_per_frame: int = EVENTS_PER_FRAME,
              keep_tail: bool = True, *,
              pose_extrapolation: str = "warn") -> EventFrames:
    """Slice the (sorted) stream into frames of `events_per_frame`.

    One-big-chunk push through `StreamingAggregator`, so streaming and
    offline aggregation share one code path. With `keep_tail` (default)
    the trailing partial frame is flushed as a final padded frame; with
    `keep_tail=False` it is dropped (the seed's behavior — a device-side
    partial frame that never saw its remaining events).

    Frame mid-times outside the trajectory span no longer freeze the
    pose silently: the default `pose_extrapolation="warn"` keeps the
    clamped numerics but emits `PoseExtrapolationWarning`; "raise"
    refuses with `PoseExtrapolationError`; "clamp" restores the seed's
    silent behavior for callers that explicitly want it.
    """
    agg = StreamingAggregator(cam, traj, events_per_frame,
                              pose_extrapolation=pose_extrapolation)
    full = agg.push(stream)
    if not keep_tail:
        return full
    tail = agg.flush()
    if full.xy.shape[0] == 0 and tail.xy.shape[0] == 0:
        return empty_event_frames(events_per_frame)
    return concat_event_frames([full, tail])
