"""Per-camera session layer of the streaming EMVS engine.

`StreamSession` owns everything that belongs to ONE event camera's
stream and nothing that is shared with its neighbors:

  * the `StreamingAggregator` (partial-frame remainder, pose-stall queue,
    pose-lag watermark) and its `TrajectoryBuffer` / `Trajectory` oracle;
  * the `SegmentPlanner` applying the K criterion frame-by-frame;
  * the `_FrameStore` host retention window (with live/peak byte
    accounting — the hook for per-session memory caps);
  * per-session stats and the harvested-result store.

Everything shared — the tagged coalescing queue, dispatch policy, the
in-flight slots, the bounded compiled-variant cache, and the sweep
backends — lives in `repro.serving.sweep_dispatcher.SweepDispatcher`.
A session hands closed segments to its dispatcher tagged with itself and
gets `SegmentResult`s routed back into `_fresh` / `_done` when the
device finishes; `repro.serving.emvs_stream.EMVSStreamEngine` is the
N=1 composition of the two layers, `MultiStreamEngine` the N-camera one.

`RigSession` is a stereo rig's counterpart of a session: two cameras'
ingest (each with its own hygiene guard, aggregator and frame store)
behind one key-frame planner, queued on the same dispatcher as one map
per key frame of the left camera, whose two rows the sweep fuses.
"""
from __future__ import annotations

import math
from collections import deque
from time import perf_counter
from typing import NamedTuple

import jax
import numpy as np

from repro.core.geometry import SE3
from repro.core.pipeline import (
    EMVSResult,
    SegmentPlanner,
    SegmentResult,
    segment_shape,
)
from repro.core.pointcloud import PointCloud
from repro.events.aggregation import EventFrames, StreamingAggregator
from repro.events.simulator import EventStream, Trajectory
from repro.events.stream_hygiene import HygieneConfig, StreamHygiene
from repro.events.trajectory_stream import PoseStallError, TrajectoryBuffer

# How StreamConfig(frame_store_budget_bytes=...) responds when admitting
# the next aggregated frame would put _FrameStore.live_bytes over budget:
#   * "stall"  — back-pressure, like max_stalled_frames on the pose side:
#     the push blocks while the dispatcher makes room (dispatching this
#     session's queued segments raises its eviction floor; completed
#     sweeps are block-harvested to free dispatch slots). Only when no
#     progress is possible — the *open segment's* working set alone
#     exceeds the budget, and open-segment frames can never be evicted —
#     does the push raise `MemoryBudgetError` (a configuration error:
#     raise the budget or close segments sooner).
#   * "reject" — never block: the push raises `MemoryBudgetError` as soon
#     as non-blocking room-making (harvest-ready + evict + dispatch into
#     free slots) cannot fit the frame. The frames are buffered in the
#     admission backlog FIRST, so nothing is lost — a later `poll()`
#     retries admission quietly as results harvest, and `flush()` drains
#     everything (blocking is inherent to a drain).
BUDGET_POLICIES = ("stall", "reject")


class MemoryBudgetError(RuntimeError):
    """A session's frame-store byte budget cannot admit the next frame.

    Raised per `StreamConfig(budget_policy=...)` — see `BUDGET_POLICIES`.
    The offending frames are buffered in the session's admission backlog
    before the raise, so no events are lost: `poll()` retries admission
    non-blocking, `flush()` drains fully."""


class _FrameStore:
    """Host-side retention window of aggregated frames, globally indexed.

    Frames are appended as they are emitted and evicted once the planner's
    open segment has moved past them, so memory tracks the open-segment
    length, not the stream length. `live_bytes` / `peak_bytes` account the
    retained payload (event coords, validity, mid-times, poses) — the
    number a per-session memory cap would enforce against.
    """

    def __init__(self):
        self.base = 0  # global index of the oldest retained frame
        self.live_bytes = 0
        self.peak_bytes = 0
        self._xy: deque[np.ndarray] = deque()
        self._valid: deque[np.ndarray] = deque()
        self._t_mid: deque[np.float32] = deque()
        self._R: deque[np.ndarray] = deque()
        self._t: deque[np.ndarray] = deque()

    @property
    def end(self) -> int:
        """One past the newest retained global frame index."""
        return self.base + len(self._xy)

    @staticmethod
    def _frame_bytes(xy: np.ndarray, valid: np.ndarray, t_mid: np.ndarray,
                     r: np.ndarray, t: np.ndarray) -> int:
        return (xy.nbytes + valid.nbytes + t_mid.nbytes + r.nbytes + t.nbytes)

    def append_frame(self, xy: np.ndarray, valid: np.ndarray,
                     t_mid: np.ndarray, r: np.ndarray,
                     t: np.ndarray) -> None:
        self._xy.append(xy)
        self._valid.append(valid)
        self._t_mid.append(t_mid)
        self._R.append(r)
        self._t.append(t)
        self.live_bytes += self._frame_bytes(xy, valid, t_mid, r, t)
        self.peak_bytes = max(self.peak_bytes, self.live_bytes)

    def extend(self, frames: EventFrames) -> None:
        xy = np.asarray(frames.xy)
        valid = np.asarray(frames.valid)
        t_mid = np.asarray(frames.t_mid)
        r = np.asarray(frames.poses.R)
        t = np.asarray(frames.poses.t)
        for k in range(xy.shape[0]):
            self.append_frame(xy[k], valid[k], t_mid[k], r[k], t[k])

    def window(self, lo: int, hi: int) -> EventFrames:
        """Host EventFrames covering global frames [lo, hi)."""
        if not self.base <= lo < hi <= self.end:
            raise IndexError(
                f"window [{lo}, {hi}) outside retained [{self.base}, {self.end})")
        sel = range(lo - self.base, hi - self.base)
        return EventFrames(
            xy=np.stack([self._xy[k] for k in sel]),
            valid=np.stack([self._valid[k] for k in sel]),
            t_mid=np.asarray([self._t_mid[k] for k in sel], np.float32),
            poses=SE3(np.stack([self._R[k] for k in sel]),
                      np.stack([self._t[k] for k in sel])),
        )

    def evict_before(self, i: int) -> None:
        while self.base < i and self._xy:
            self.live_bytes -= self._frame_bytes(
                self._xy.popleft(), self._valid.popleft(),
                self._t_mid.popleft(), self._R.popleft(), self._t.popleft())
            self.base += 1


def _camera_ingest(dispatcher, traj: Trajectory | TrajectoryBuffer
                   ) -> tuple[StreamingAggregator, StreamHygiene]:
    """One camera's ingest per the dispatcher's `StreamConfig`: its
    aggregator over `traj`, and the hygiene guard every event chunk is
    scrubbed by before it reaches the aggregator (the camera model
    supplies the sensor bounds for the out-of-bounds check)."""
    cfg = dispatcher.stream_cfg
    aggregator = StreamingAggregator(
        dispatcher.cam, traj, cfg.events_per_frame,
        pose_extrapolation=cfg.pose_extrapolation,
        max_stalled=cfg.max_stalled_frames)
    hyg = cfg.hygiene
    if not isinstance(hyg, HygieneConfig):
        hyg = HygieneConfig(policy=hyg)
    return aggregator, StreamHygiene(hyg, width=dispatcher.cam.width,
                                     height=dispatcher.cam.height)


class _Session:
    """What a camera's session and a rig share: registration on the
    dispatcher, the key-frame planner, the harvested-result stores and
    the poll / flush / result end of the session contract. A subclass
    sets `stats` and registers itself."""

    def __init__(self, session_id: str, dispatcher):
        self.session_id = session_id
        self.dispatcher = dispatcher
        mean_depth = 0.5 * (dispatcher.dsi_cfg.z_min + dispatcher.dsi_cfg.z_max)
        # min_frames=2 is plan_segments' parallax filter, applied online.
        self.planner = SegmentPlanner(
            mean_depth * dispatcher.opts.keyframe_dist_frac, min_frames=2)
        self._fresh: list[SegmentResult] = []  # harvested, not yet polled
        self._done: dict[tuple[int, int], tuple[SegmentResult, PointCloud]] = {}
        self._flushed = False

    @staticmethod
    def _validate_chunk(chunk: EventStream) -> int:
        """Reject inconsistently shaped chunks before they corrupt the
        aggregator's remainder; returns the event count."""
        n = int(np.asarray(chunk.t).shape[0])
        fields = {"xy": np.asarray(chunk.xy).shape[0],
                  "polarity": np.asarray(chunk.polarity).shape[0],
                  "valid": np.asarray(chunk.valid).shape[0]}
        bad = {name: cnt for name, cnt in fields.items() if cnt != n}
        if bad:
            raise ValueError(
                f"inconsistent event chunk: t has {n} event(s) but "
                + ", ".join(f"{k} has {v}" for k, v in sorted(bad.items())))
        return n

    def _scrub(self, hygiene: StreamHygiene,
               chunk: EventStream) -> EventStream:
        """Validate one chunk and pass it through `hygiene` (the
        `emvs.hygiene` span), counting it."""
        with jax.profiler.TraceAnnotation("emvs.hygiene"):
            n = self._validate_chunk(chunk)
            chunk = hygiene.scrub(chunk)
        self.stats["chunks"] += 1
        if n == 0:
            # a legal no-op (e.g. a quiet sensor interval), but an easy
            # symptom of a broken feed — counted so callers can notice
            self.stats["empty_chunks"] += 1
        return chunk

    # --- harvest ----------------------------------------------------------

    def _deliver(self, res: SegmentResult, pc: PointCloud) -> None:
        """Store one harvested result (the dispatcher routes it here)."""
        self._done[res.frame_range] = (res, pc)
        self._fresh.append(res)

    def _take_fresh(self) -> list[SegmentResult]:
        out, self._fresh = self._fresh, []
        return out

    def _retry_admission(self) -> None:
        """Frames a push could not admit retry here (`poll`)."""

    def poll(self) -> list[SegmentResult]:
        """This session's results that became ready since the last poll:
        back-pressure harvests plus every in-flight sweep the device has
        finished. Freed in-flight slots let the shared coalescing queue
        drain, so a poll can also dispatch segments (of any session) the
        adaptive policy was holding. Under a memory budget, frames a
        rejected push left in the admission backlog retry admission here
        (non-blocking, never raising) as completed sweeps free bytes."""
        with jax.profiler.TraceAnnotation("emvs.poll"):
            self._retry_admission()
            self.dispatcher.pump()
            return self._take_fresh()

    def _drain(self) -> EMVSResult:
        """The end of `flush`: drain this session's share of the queue
        (under every policy) and its in-flight sweeps; the result covers
        everything, so nothing stays fresh."""
        self.dispatcher.drain_session(self)
        self._fresh.clear()
        return self.result()

    def result(self) -> EMVSResult:
        """Results harvested so far, in frame order (complete after flush)."""
        keys = sorted(self._done)
        return EMVSResult(segments=[self._done[k][0] for k in keys],
                          clouds=[self._done[k][1] for k in keys])


class StreamSession(_Session):
    """One camera's streaming state, multiplexed onto a shared dispatcher.

    Construct via `MultiStreamEngine.add_session` (or implicitly through
    the N=1 `EMVSStreamEngine`); the session registers itself with the
    dispatcher. The push/poll/flush lifecycle and error contract are the
    single-stream engine's, per session:

      * `push` / `push_poses` / `finalize_poses` feed this camera only;
        closed segments enter the dispatcher's shared tagged queue, where
        shape-compatible segments from OTHER sessions may share the same
        device sweep (cross-stream coalescing) — grouping never changes
        this session's numbers, so results stay bit-identical to a
        dedicated single-stream engine.
      * `poll` pumps the shared dispatcher (harvest + policy drain) and
        returns THIS session's newly ready results, in segment-close
        order.
      * `flush` drains this session only: its queued segments dispatch
        (same-capacity neighbors may ride along), its in-flight sweeps
        complete, other sessions keep streaming undisturbed.
    """

    def __init__(self, session_id: str,
                 dispatcher,
                 traj: Trajectory | TrajectoryBuffer | None = None):
        super().__init__(session_id, dispatcher)
        cfg = dispatcher.stream_cfg
        # traj=None: pose-gated mode with a fresh buffer the caller feeds
        # via push_poses; an existing TrajectoryBuffer (possibly pre-filled)
        # is used as-is; a Trajectory is the offline oracle.
        if traj is None:
            traj = TrajectoryBuffer()
        self.pose_gated = isinstance(traj, TrajectoryBuffer)
        if cfg.max_stalled_frames is not None and not self.pose_gated:
            raise ValueError(
                "max_stalled_frames is only meaningful in pose-gated mode "
                "(traj=None or a TrajectoryBuffer): a fully-known "
                "Trajectory oracle never stalls frames, so the bound "
                "would silently do nothing")
        self.aggregator, self.hygiene = _camera_ingest(dispatcher, traj)
        self._store = _FrameStore()
        # Memory budget: frames emitted by the aggregator pass through an
        # admission backlog before entering the frame store, so
        # live_bytes NEVER exceeds the budget (checked before append,
        # see _drain_backlog / BUDGET_POLICIES).
        self._budget = cfg.frame_store_budget_bytes
        self._budget_policy = cfg.budget_policy
        self._backlog: deque[tuple] = deque()  # per-frame (xy,valid,t_mid,R,t)
        self._tail_flushed = False  # aggregator tail emitted (flush began)
        # Ingestion-side counters; the dispatcher owns the shared dispatch
        # counters and attributes "segments" (dispatched, owned by this
        # session) back here. Same identities as the single-stream engine.
        # "hygiene" aliases the guard's live stats dict; "budget_stalls" /
        # "budget_rejects" / "backlog_frames" track the admission policy.
        # "dsi_saturation_peak" is the largest per-segment fraction of DSI
        # voxels at the int16 store limits seen on this stream (inclusive
        # boundary — see core.dsi.store_saturation_fraction): the live
        # monitor for the paper's "16 bits never saturate" claim. Updated
        # by the dispatcher on harvest; stays 0.0 on healthy streams.
        self.stats = {"chunks": 0, "empty_chunks": 0, "frames": 0,
                      "segments": 0, "pose_chunks": 0, "stalled_frames": 0,
                      "max_stalled": 0,
                      "pose_watermark": self.aggregator.pose_watermark,
                      "frame_store_bytes": 0, "frame_store_peak_bytes": 0,
                      "budget_stalls": 0, "budget_rejects": 0,
                      "backlog_frames": 0, "dsi_saturation_peak": 0.0,
                      "hygiene": self.hygiene.stats}
        dispatcher.register(self)

    # --- ingest -----------------------------------------------------------

    def push(self, chunk: EventStream) -> list[SegmentResult]:
        """Feed one event chunk; returns this session's segment results
        that became ready (without blocking — completed sweeps only). In
        pose-gated mode, frames whose mid-time lies past the pose
        watermark stall inside the aggregator and surface on a later
        `push_poses`.

        The chunk passes through this session's `StreamHygiene` guard
        first (`StreamConfig.hygiene`): an adversarial chunk raises a
        typed `StreamHygieneError` subclass / sheds offenders / waits in
        the reorder buffer per the policy, BEFORE any session state is
        touched — a hygiene raise leaves the session exactly as it was."""
        if self._flushed or self._tail_flushed:
            # once flush() has consumed the aggregator's tail remainder —
            # including a flush that then raised PoseStallError — more
            # events would land AFTER a padded mid-stream tail frame and
            # silently shift every later frame boundary
            raise RuntimeError(
                "push after flush: the event tail was already emitted "
                "(only push_poses / finalize_poses / flush may follow)")
        with jax.profiler.TraceAnnotation("emvs.push"):
            chunk = self._scrub(self.hygiene, chunk)
            try:
                self._ingest(self.aggregator.push(chunk))
            finally:
                # runs on the PoseStallError (max-stall bound) path too, so
                # max_stalled records the true peak, not the last quiet push
                self._track_stall()
            return self.poll()

    def push_poses(self, chunk: Trajectory) -> list[SegmentResult]:
        """Feed one pose chunk from the tracker; stalled frames the
        advanced watermark now covers are released (bitwise-identically
        posed), planned, and dispatched. Returns results that became
        ready, exactly like `push`."""
        if self._flushed:
            raise RuntimeError("push_poses after flush: the engine is drained")
        if not self.pose_gated:
            raise RuntimeError(
                "push_poses requires a pose-gated engine: construct with "
                "traj=None (or a TrajectoryBuffer), not a Trajectory oracle")
        self.stats["pose_chunks"] += 1
        self._ingest(self.aggregator.push_poses(chunk))
        self._track_stall()
        return self.poll()

    def finalize_poses(self) -> list[SegmentResult]:
        """Declare the pose stream complete: every still-stalled frame is
        released through `StreamConfig.pose_extrapolation` (its pose can
        no longer gain a bracketing sample). Call before `flush` when the
        tracker ends behind the event front."""
        if self._flushed:
            raise RuntimeError(
                "finalize_poses after flush: the engine is drained")
        if not self.pose_gated:
            raise RuntimeError(
                "finalize_poses requires a pose-gated engine: construct "
                "with traj=None (or a TrajectoryBuffer)")
        self._ingest(self.aggregator.finalize_poses())
        self._track_stall()
        return self.poll()

    def _track_stall(self) -> None:
        n = self.aggregator.stalled_frames
        self.stats["stalled_frames"] = n
        self.stats["max_stalled"] = max(self.stats["max_stalled"], n)
        self.stats["pose_watermark"] = self.aggregator.pose_watermark

    def _sync_store_stats(self) -> None:
        self.stats["frame_store_bytes"] = self._store.live_bytes
        self.stats["frame_store_peak_bytes"] = self._store.peak_bytes

    def _ingest(self, frames: EventFrames, *,
                blocking: bool | None = None) -> None:
        """Plan aggregated frames into segments and hand the closed ones
        to the dispatcher; the spans of the closing `pump()` nest in
        `emvs.plan`."""
        with jax.profiler.TraceAnnotation("emvs.plan"):
            n = int(frames.xy.shape[0])
            if n == 0:
                return
            self.stats["frames"] += n
            if self._budget is None:
                self._store.extend(frames)
                self._sync_store_stats()
                closed: list[tuple[int, int]] = []
                t_host = np.asarray(frames.poses.t)
                for k in range(n):
                    seg = self.planner.push(t_host[k])
                    if seg is not None:
                        closed.append(seg)
                if closed:
                    self.dispatcher.enqueue(self, closed)
                self.dispatcher.pump()
                return
            # budgeted admission: frames queue in the backlog and enter the
            # store one at a time, each admitted only once it fits under the
            # budget — live_bytes can never exceed it
            xy = np.asarray(frames.xy)
            valid = np.asarray(frames.valid)
            t_mid = np.asarray(frames.t_mid)
            r = np.asarray(frames.poses.R)
            t = np.asarray(frames.poses.t)
            for k in range(n):
                self._backlog.append((xy[k], valid[k], t_mid[k], r[k], t[k]))
            if blocking is None:
                blocking = self._budget_policy == "stall"
            self._drain_backlog(blocking=blocking, raise_on_full=True)

    def _drain_backlog(self, *, blocking: bool, raise_on_full: bool) -> None:
        """Admit backlogged frames into the store under the byte budget.

        Each frame is admitted only when `live_bytes + frame` fits; when
        it does not, the dispatcher is asked to make room (harvest
        completed sweeps, evict behind the retention floor, dispatch this
        session's queued segments to RAISE that floor — never below it:
        queued segments and the planner's open segment stay resident).
        With `blocking` the room-making may block on in-flight sweeps
        (the "stall" policy's back-pressure); without it the first
        no-progress answer stops the drain — raising `MemoryBudgetError`
        when `raise_on_full` (the "reject" policy's push path) or
        returning quietly (poll's retry path). Admitted frames run the
        planner and enqueue their closed segments immediately, so a
        closed segment can free its own frames for the next admission."""
        budget = self._budget
        while self._backlog:
            fb = self._backlog[0]
            nbytes = _FrameStore._frame_bytes(*fb)
            while self._store.live_bytes + nbytes > budget:
                if self.dispatcher.make_room(self, blocking=blocking):
                    self.stats["budget_stalls"] += 1
                    continue
                self.stats["backlog_frames"] = len(self._backlog)
                if not raise_on_full:
                    return
                live = self._store.live_bytes
                if not blocking:
                    self.stats["budget_rejects"] += 1
                    raise MemoryBudgetError(
                        f"session {self.session_id!r}: admitting the next "
                        f"{nbytes}-byte frame would put the frame store at "
                        f"{live + nbytes} bytes, over the "
                        f"{budget}-byte budget (policy 'reject'; "
                        f"{len(self._backlog)} frame(s) held in the "
                        f"admission backlog — nothing is lost: poll() "
                        f"retries as sweeps complete, flush() drains)")
                raise MemoryBudgetError(
                    f"session {self.session_id!r}: frame-store budget "
                    f"{budget} bytes cannot hold the open segment's "
                    f"working set — {live} bytes are pinned by frames "
                    f"that may not be evicted (the planner's open "
                    f"segment / queued dispatches) and the next frame "
                    f"needs {nbytes} more, with nothing left to dispatch "
                    f"or harvest; raise the budget or close segments "
                    f"sooner (larger keyframe_dist_frac means longer "
                    f"segments)")
            self._backlog.popleft()
            self._store.append_frame(*fb)
            self._sync_store_stats()
            seg = self.planner.push(fb[4])
            if seg is not None:
                self.dispatcher.enqueue(self, [seg])
        self.stats["backlog_frames"] = 0
        self.dispatcher.pump()

    # --- what the dispatcher asks of a queued segment ----------------------

    def map_shape(self, seg: tuple[int, int]) -> tuple[int, int]:
        """(rows, frames) the segment sweeps: one camera's row."""
        return segment_shape(self, seg)

    def stage(self, seg: tuple[int, int]) -> list:
        """The segment's row for `pad_segment_rows`."""
        start, end = seg
        return [(self._store.window(start, end), (0, end - start))]

    def evict_behind(self, oldest_queued: tuple[int, int] | None) -> None:
        """Release retained frames below the oldest queued segment, else
        below the planner's open segment."""
        floor = (self.planner.open_start if oldest_queued is None
                 else oldest_queued[0])
        self._store.evict_before(floor)
        self._sync_store_stats()

    def _retry_admission(self) -> None:
        if self._backlog:
            self._drain_backlog(blocking=False, raise_on_full=False)

    # --- end of stream ----------------------------------------------------

    def flush(self) -> EMVSResult:
        """End of this session's stream: flush the partial frame and the
        open segment, drain this session's queued and in-flight work, and
        return its accumulated result (same ordering and types as offline
        `run_emvs`). Other sessions on the shared dispatcher keep
        streaming — though their same-capacity segments may ride along in
        this session's final dispatches.

        In pose-gated mode, flushing while frames still await their pose
        chunks raises `PoseStallError` (naming the stalled frame count
        and the watermark) — either push the missing chunks or call
        `finalize_poses` first. The session stays usable after the error
        for the pose side only: frames released by later pose chunks are
        not lost, but `push` is rejected from the first flush attempt on
        (the event tail was already emitted as a padded frame)."""
        if not self._flushed:
            try:
                if not self._tail_flushed:
                    self._tail_flushed = True
                    # end of stream for the hygiene guard too: the
                    # reorder buffer's held events precede the tail
                    held = self.hygiene.flush()
                    if held.t.shape[0]:
                        self._ingest(self.aggregator.push(held),
                                     blocking=True)
                    self._ingest(self.aggregator.flush(), blocking=True)
                if self._backlog:
                    # frames a rejected push left behind: a drain is
                    # inherently blocking under either budget policy
                    self._drain_backlog(blocking=True, raise_on_full=True)
            finally:
                # runs when the tail frame trips the max-stall bound too,
                # so max_stalled records the true peak on the raise path
                self._track_stall()
            stalled = self.aggregator.stalled_frames
            if stalled:
                raise PoseStallError(
                    f"flush with {stalled} frame(s) stalled awaiting poses: "
                    f"pose watermark t={self.aggregator.pose_watermark:.6g}, "
                    f"oldest stalled frame t_mid="
                    f"{self.aggregator.oldest_stalled_t:.6g}; push the "
                    f"missing pose chunks or call finalize_poses() first")
            tail = self.planner.flush()
            if tail is not None:
                self.dispatcher.enqueue(self, [tail])
            self._flushed = True
        return self._drain()


class _RigCamera(NamedTuple):
    """One camera of a rig: its aggregator, hygiene guard and frames."""

    aggregator: StreamingAggregator
    hygiene: StreamHygiene
    store: _FrameStore

    @classmethod
    def build(cls, dispatcher, traj: Trajectory) -> _RigCamera:
        if not isinstance(traj, Trajectory):
            raise ValueError(
                f"a rig camera's poses are a known Trajectory, got "
                f"{type(traj).__name__}")
        return cls(*_camera_ingest(dispatcher, traj), _FrameStore())


class _Closed(NamedTuple):
    """A key-frame segment the left camera closed, waiting for the right
    camera to pass its end."""

    seg: tuple[int, int]  # left frames [a, b)
    t_lo: float  # median timestamp of left frame a
    t_hi: float  # ... of left frame b (inf: the stream's last segment)
    kept: bool  # False: shorter than the planner's min_frames, dropped
    t_close: float  # host clock at the close


class RigSession(_Session):
    """A rectified two-camera rig fused at the left camera's key frames
    (Ghosh & Gallego 2022, arXiv 2207.10494).

    Camera 0 (left) is the reference: its frames alone are cut into
    key-frame segments, by the same planner as a `StreamSession`'s. The
    right frame j belongs to the left segment [a, b) when
    t_L[a] <= t_R[j] < t_L[b], t a frame's median timestamp. A closed
    segment is held until the right camera's newest frame has
    t_R >= t_L[b], so every right frame of it has arrived; it is then
    enqueued as one map of two rows. The dispatcher groups a rig's maps
    of one shape as it groups segments (`map_shape`); in the sweep both
    rows are voted into the left key frame's view, fused by
    `dsi.fuse_harmonic_mean` and detected once
    (`pipeline.sweep_segment_batch`). Right frames before the first
    segment, or inside a segment the planner drops (`min_frames`), are
    dropped and counted. Results are one `SegmentResult` per map: the
    fused float32 DSI, the left key-frame pose and the left frame range,
    equal to `pipeline.run_emvs_rig_looped` on the same frames.

    Both cameras share the engine's intrinsics (a rectified rig) and
    replay known trajectories. The batched sweep's matmul and scatter
    formulations fuse; the kernel formulation, the sharded sweep and a
    frame-store budget are refused.

    Counters (`stats`, summed over rigs in the dispatcher's): `rig_holds`
    maps that left the hold for the queue, `rig_hold_total_s` their
    summed time from the left close to the enqueue, `rig_frames_dropped`
    right frames that fell in no map.
    """

    def __init__(self, session_id: str, dispatcher, trajs):
        cfg = dispatcher.stream_cfg
        if cfg.sweep != "batched":
            raise ValueError(f"a rig runs on the batched sweep, not "
                             f"{cfg.sweep!r}")
        if dispatcher.opts.formulation == "kernel":
            raise ValueError(
                "formulation='kernel' cannot fuse a rig: it votes and "
                "reduces one camera per segment in-kernel; use 'matmul' "
                "or 'scatter'")
        if (cfg.frame_store_budget_bytes is not None
                or cfg.max_stalled_frames is not None):
            raise ValueError("a rig has no frame-store budget and no stall "
                             "bound: set frame_store_budget_bytes and "
                             "max_stalled_frames to None")
        if len(trajs) != 2:
            raise ValueError(f"a rig has 2 cameras, got {len(trajs)} "
                             f"trajectories")
        super().__init__(session_id, dispatcher)
        self._cams = [_RigCamera.build(dispatcher, traj) for traj in trajs]
        self._open_t = math.nan  # t_mid of the left's open segment's start
        self._closed: deque[_Closed] = deque()
        self._right_t: deque[float] = deque()  # t_mid, unassigned right frames
        self._right_next = 0  # global index of the first of them
        self._right_newest = -math.inf  # t_mid of the newest right frame
        self._right_of: dict[tuple[int, int], tuple[int, int]] = {}
        self.stats = {"chunks": 0, "empty_chunks": 0, "frames": 0,
                      "segments": 0, "frame_store_bytes": 0,
                      "frame_store_peak_bytes": 0,
                      "dsi_saturation_peak": 0.0, "rig_holds": 0,
                      "rig_hold_total_s": 0.0, "rig_frames_dropped": 0,
                      "hygiene": [c.hygiene.stats for c in self._cams]}
        dispatcher.register(self)

    def _camera(self, camera: int) -> _RigCamera:
        if camera not in (0, 1):
            raise ValueError(f"a rig's cameras are 0 (left, the reference) "
                             f"and 1 (right), got {camera!r}")
        return self._cams[camera]

    # --- ingest -----------------------------------------------------------

    def push(self, chunk: EventStream, camera: int) -> list[SegmentResult]:
        """Feed one event chunk of `camera`; returns the rig's maps that
        became ready, as `StreamSession.push` does."""
        cam = self._camera(camera)
        if self._flushed:
            raise RuntimeError(
                "push after flush: the event tails were already emitted")
        with jax.profiler.TraceAnnotation("emvs.push"):
            chunk = self._scrub(cam.hygiene, chunk)
            self._ingest(camera, cam.aggregator.push(chunk))
            return self.poll()

    def _sync_store_stats(self) -> None:
        live = sum(c.store.live_bytes for c in self._cams)
        self.stats["frame_store_bytes"] = live
        self.stats["frame_store_peak_bytes"] = max(
            self.stats["frame_store_peak_bytes"], live)

    def _ingest(self, camera: int, frames: EventFrames) -> None:
        """Store a camera's new frames; cut the left's into segments;
        enqueue the segments the right camera has passed."""
        with jax.profiler.TraceAnnotation("emvs.plan"):
            n = int(frames.xy.shape[0])
            if n == 0:
                return
            self.stats["frames"] += n
            self._cams[camera].store.extend(frames)
            self._sync_store_stats()
            t_mid = np.asarray(frames.t_mid)
            if camera == 1:
                self._right_t.extend(float(t) for t in t_mid)
                self._right_newest = float(t_mid[-1])
            else:
                if self.planner.num_frames == 0:
                    self._open_t = float(t_mid[0])
                t_host = np.asarray(frames.poses.t)
                for k in range(n):
                    start = self.planner.open_start
                    seg = self.planner.push(t_host[k])
                    if self.planner.open_start != start:
                        self._close((start, self.planner.open_start),
                                    seg is not None, float(t_mid[k]))
            self._assign(final=False)
            self.dispatcher.pump()

    def _close(self, seg: tuple[int, int], kept: bool, t_hi: float) -> None:
        self._closed.append(_Closed(seg, self._open_t, t_hi, kept,
                                    perf_counter()))
        self._open_t = t_hi

    def _assign(self, final: bool) -> None:
        """Give each closed segment the right frames of its interval once
        the right camera has passed its end (every one, at `final`), and
        enqueue the kept ones as maps."""
        if not self._closed or not (
                final or self._right_newest >= self._closed[0].t_hi):
            return
        with jax.profiler.TraceAnnotation("emvs.rig.assign"):
            maps, dropped = [], 0
            while self._closed and (
                    final or self._right_newest >= self._closed[0].t_hi):
                c = self._closed.popleft()
                while self._right_t and self._right_t[0] < c.t_lo:
                    self._right_t.popleft()  # before the first segment
                    self._right_next += 1
                    dropped += 1
                lo = self._right_next
                while self._right_t and self._right_t[0] < c.t_hi:
                    self._right_t.popleft()
                    self._right_next += 1
                if not c.kept:
                    dropped += self._right_next - lo
                    continue
                self._right_of[c.seg] = (lo, self._right_next)
                hold = perf_counter() - c.t_close
                for stats in (self.stats, self.dispatcher.stats):
                    stats["rig_holds"] += 1
                    stats["rig_hold_total_s"] += hold
                maps.append(c.seg)
            for stats in (self.stats, self.dispatcher.stats):
                stats["rig_frames_dropped"] += dropped
            if maps:
                self.dispatcher.enqueue(self, maps)

    # --- what the dispatcher asks of a queued map --------------------------

    def map_shape(self, seg: tuple[int, int]) -> tuple[int, int]:
        """(rows, frames) the map sweeps: two rows, padded to the longer."""
        lo, hi = self._right_of[seg]
        return 2, max(seg[1] - seg[0], hi - lo)

    def stage(self, seg: tuple[int, int]) -> list:
        """The map's left and right rows for `pad_rig_rows`."""
        a, b = seg
        lo, hi = self._right_of[seg]
        left = self._cams[0].store.window(a, b)
        if hi > lo:
            right = (self._cams[1].store.window(lo, hi), (0, hi - lo))
        else:  # no right frame in the interval: a row that votes nothing
            right = (left._replace(valid=np.zeros_like(left.valid)),
                     (0, b - a))
        return [(left, (0, b - a)), right]

    def evict_behind(self, oldest_queued: tuple[int, int] | None) -> None:
        """Release frames no queued, held or open map needs."""
        if oldest_queued is not None:
            left_floor = oldest_queued[0]
            right_floor = self._right_of[oldest_queued][0]
        else:
            left_floor = (self._closed[0].seg[0] if self._closed
                          else self.planner.open_start)
            right_floor = self._right_next
        self._cams[0].store.evict_before(left_floor)
        self._cams[1].store.evict_before(right_floor)
        for seg in [s for s in self._right_of if s[0] < left_floor]:
            del self._right_of[seg]
        self._sync_store_stats()

    # --- harvest ----------------------------------------------------------

    def _deliver(self, res: SegmentResult, pc: PointCloud) -> None:
        with jax.profiler.TraceAnnotation("emvs.rig.fuse"):
            super()._deliver(res, pc)

    # --- end of stream ----------------------------------------------------

    def flush(self) -> EMVSResult:
        """End of both cameras' streams: emit their tails, close the
        left's last segment (it takes every right frame from its start
        on), release every held segment and drain the rig's maps."""
        if not self._flushed:
            self._flushed = True
            for k, cam in enumerate(self._cams):
                held = cam.hygiene.flush()
                if held.t.shape[0]:
                    self._ingest(k, cam.aggregator.push(held))
                self._ingest(k, cam.aggregator.flush())
            start = self.planner.open_start
            tail = self.planner.flush()
            if self.planner.open_start != start:
                self._close((start, self.planner.open_start),
                            tail is not None, math.inf)
            self._assign(final=True)
        return self._drain()
