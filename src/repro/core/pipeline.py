"""End-to-end EMVS pipeline: A -> P -> R -> (K) -> D -> M.

Key structural choice (mirrors the algorithm, DESIGN.md §2): key-frame
segmentation depends ONLY on the trajectory, not on event content, so the
segment boundaries are computed up front on the host (the ARM side in the
paper). Segments are then padded to a small set of fixed frame capacities
(multiple-of-four buckets) and processed by ONE jit'd device program per
bucket: a `lax.map` over segments whose body votes the segment's DSI
(scan over event frames, or the fused Pallas kernel), applies the int16
store semantics, runs detection and the median filter. Padded frames
repeat a real frame (finite geometry) and carry a validity weight of 0,
so they vote exactly nothing — the padded sweep matches the per-segment
path bitwise on the integer/nearest datapaths and to float tolerance on
the bilinear ones (tests enforce exactly that split).

This replaces the seed's host-side Python loop, which re-traced
`process_segment` for every distinct segment length and round-tripped
host<->device per segment — the "many small dispatches" pathology that
kills event-rate throughput. The looped path survives as
`run_emvs_looped` (a thin loop over `process_segment`, itself a
single-segment call into the batched sweep) for A/B benchmarking.

The voting hot loop supports three interchangeable formulations
(scatter / one-hot matmul / Pallas kernel) and the float vs Table-1
quantized datapaths; all are pairwise-validated by tests, batched and
looped alike.

Sweep backends: `run_emvs(sweep=...)` selects how each bucket runs.
`"batched"` (default) is the serial `lax.map` program above;
`"sharded"` hands the bucket to `repro.distributed.emvs
.process_segments_sharded`, which runs the SAME sweep body
(`sweep_segment_batch`) with the segment axis sharded across mesh
devices — the paper's key-frame-level parallelism. The backends agree
bitwise on the integer/nearest datapaths (tests/test_sharded_sweep.py).

Streaming entry point: `repro.serving.emvs_stream.EMVSStreamEngine`
drives this module online — `SegmentPlanner` (below) applies the K
criterion frame-by-frame as events arrive, closed segments are padded
into the same capacity buckets by `pad_segments`, and
`process_segments_batched` sweeps them with the segment axis padded to a
small fixed set of sizes so the jit cache stays bounded over an
unbounded stream. The engine's coalescing dispatcher groups queued
closed segments with `dispatch_group_head` / `plan_dispatch_groups`
(below): FIFO-order partitioning into same-capacity runs of at most one
S bucket each, so a dispatch policy can trade latency for batch size
without touching the numbers. The multi-tenant serving layer
(`repro.serving.sweep_dispatcher.SweepDispatcher`) generalizes both to
`(session, segment)`-tagged work via `dispatch_group_head_tagged` /
`plan_dispatch_groups_tagged`: per-stream FIFO is preserved while
shape-compatible segments from different sessions fill one S bucket
(`pad_segment_rows` gathers such cross-store groups), under a
FAIRNESS_POLICIES anchor rule (strict "fifo" vs starvation-bounded
"round_robin"). Per-segment outputs are bit-identical to `run_emvs` on
the integer/nearest datapaths for every chunking of the input, every
dispatch policy, and every session interleaving
(tests/test_streaming.py, tests/test_adaptive_dispatch.py,
tests/test_multi_stream.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dsi as dsi_lib
from repro.core.backproject import FrameGeometry, frame_geometry
from repro.core.camera import CameraModel
from repro.core.detection import DepthMap, detect_and_filter, detect_and_filter_from
from repro.core.dsi import DSIConfig
from repro.core.geometry import SE3, PlaneSweepCoeffs, apply_homography, propagate_to_planes
from repro.core.pointcloud import PointCloud, depth_map_to_points, depth_maps_to_points
from repro.core.voting import vote_onehot_chunk, vote_onehot_matmul, vote_scatter
from repro.events.aggregation import EventFrames
from repro.quant.policies import TABLE1, EMVSQuantPolicy

Array = jax.Array

# Smallest fixed segment capacity: keeping a floor bounds the number of
# distinct compiled bucket shapes for trajectories with many tiny segments.
SEGMENT_BUCKET_MIN = 4

# Frames the nearest matmul sweep votes per DSI update
# (`voting.vote_onehot_chunk`). Every bucket capacity is a multiple of
# it, so the served sweeps chunk without padding.
VOTE_CHUNK_FRAMES = 4

# Fairness policies for the TAGGED coalescing queue (multi-tenant serving):
#   * "fifo"        — every dispatch group anchors at the global queue head:
#     strict arrival order across streams. One stream's odd-capacity segment
#     can head-of-line delay the others (their shape-compatible segments
#     still ride along behind it, but a group never *anchors* past the head).
#   * "round_robin" — group anchors rotate over the streams in first-seen
#     order, skipping streams with nothing queued: a stream with queued work
#     is anchored again within at most (#streams) dispatches, so no stream
#     waits more than O(streams) dispatches behind a chatty neighbor.
#     Starvation-bounded; the property tests in tests/test_multi_stream.py
#     pin the bound.
FAIRNESS_POLICIES = ("fifo", "round_robin")


@dataclasses.dataclass(frozen=True)
class EMVSOptions:
    voting: str = "nearest"  # nearest | bilinear       (paper: nearest)
    formulation: str = "matmul"  # scatter | matmul | kernel (TPU-native: matmul)
    quantized: bool = False  # paper Table 1 hybrid quantization
    keyframe_dist_frac: float = 0.15  # threshold as fraction of mean scene depth
    detection_threshold_c: float = 6.0
    detection_min_votes: float = 3.0
    median_filter: bool = True
    policy: EMVSQuantPolicy = TABLE1
    # formulation="kernel" execution mode, resolved in ONE place
    # (repro.kernels.platform.resolve_interpret): None = compiled on
    # TPU/GPU with interpreter fallback elsewhere; True = force the
    # Pallas interpreter; False = require the compiled kernel
    # (ValueError on platforms without a Pallas compile path).
    kernel_interpret: bool | None = None


class SegmentResult(NamedTuple):
    depth_map: DepthMap
    dsi: Array
    T_w_ref: SE3
    frame_range: tuple[int, int]


class EMVSResult(NamedTuple):
    segments: list[SegmentResult]
    clouds: list[PointCloud]


class SegmentBatch(NamedTuple):
    """A bucket of key-frame segments padded to one fixed frame capacity C.

    Padded frame slots repeat the segment's last real frame so their
    geometry stays finite; `frame_valid` zeroes their vote weight.

    A rig batch (`pad_rig_rows`) carries K cameras per map: the frame
    fields gain a camera axis after S (xy (S, K, C, E, 2), ...), while
    `ref_R` / `ref_t` stay one reference view per map (the first
    camera's key frame). The sweep tells the two apart by `xy.ndim`.
    """

    xy: Array  # (S, C, E, 2) rectified event coords
    valid: Array  # (S, C, E) float32 per-event validity
    frame_valid: Array  # (S, C) float32 1 for real frames, 0 for padding
    poses_R: Array  # (S, C, 3, 3)
    poses_t: Array  # (S, C, 3)
    ref_R: Array  # (S, 3, 3) reference (key-frame) pose per segment
    ref_t: Array  # (S, 3)


# ---------------------------------------------------------------------------
# Key-frame segmentation (host-side, pose-only)
# ---------------------------------------------------------------------------


class SegmentPlanner:
    """Incremental key-frame segmentation: the K criterion, frame by frame.

    `push` one frame pose translation at a time; a segment closes the
    moment translation from the reference view exceeds the threshold, so
    a streaming caller can start voting a segment before the trajectory
    ends. `flush` closes the trailing segment at end of stream. The
    boundaries are exactly those of the offline `segment_keyframes`
    (which now routes through this planner), and segments shorter than
    `min_frames` are discarded on close — `plan_segments`' parallax
    filter, applied online.
    """

    def __init__(self, threshold: float, min_frames: int = 1):
        self.threshold = float(threshold)
        self.min_frames = int(min_frames)
        self._count = 0
        self._start = 0
        self._ref: np.ndarray | None = None

    @property
    def num_frames(self) -> int:
        """Frames pushed so far."""
        return self._count

    @property
    def open_start(self) -> int:
        """First frame index of the still-open segment (frames before it
        can be released by a streaming caller once dispatched)."""
        return self._start

    def _filtered(self, seg: tuple[int, int]) -> tuple[int, int] | None:
        return seg if seg[1] - seg[0] >= self.min_frames else None

    def push(self, t: np.ndarray) -> tuple[int, int] | None:
        """Feed the next frame's translation; returns a closed segment
        [start, end) the moment the K criterion trips, else None."""
        t = np.asarray(t)
        i = self._count
        self._count = i + 1
        if self._ref is None:
            self._ref = t
            return None
        if np.linalg.norm(t - self._ref) > self.threshold:
            closed = (self._start, i)
            self._start = i
            self._ref = t
            return self._filtered(closed)
        return None

    def flush(self) -> tuple[int, int] | None:
        """End of stream: close (and return) the trailing open segment."""
        if self._count == self._start:
            return None
        seg = (self._start, self._count)
        self._start = self._count
        self._ref = None
        return self._filtered(seg)


def segment_keyframes(poses: SE3, mean_depth: float, frac: float) -> list[tuple[int, int]]:
    """Split frame indices into key-frame segments [(start, end), ...).

    A segment's reference view is the pose of its first frame. A new
    segment begins when translation from the reference exceeds
    frac * mean_depth (the paper's K criterion). Implemented as one
    sweep of the incremental `SegmentPlanner`, so offline and streaming
    segmentation cannot drift apart. Zero frames -> no segments.
    """
    t = np.asarray(poses.t)
    planner = SegmentPlanner(mean_depth * frac, min_frames=1)
    bounds: list[tuple[int, int]] = []
    for i in range(t.shape[0]):
        closed = planner.push(t[i])
        if closed is not None:
            bounds.append(closed)
    tail = planner.flush()
    if tail is not None:
        bounds.append(tail)
    return bounds


def plan_segments(frames: EventFrames, dsi_cfg: DSIConfig,
                  opts: EMVSOptions) -> list[tuple[int, int]]:
    """Key-frame segments that carry enough parallax for a meaningful DSI."""
    mean_depth = 0.5 * (dsi_cfg.z_min + dsi_cfg.z_max)
    segs = segment_keyframes(frames.poses, mean_depth, opts.keyframe_dist_frac)
    return [(a, b) for a, b in segs if b - a >= 2]


def bucket_capacity(num_frames: int, minimum: int = SEGMENT_BUCKET_MIN) -> int:
    """Fixed per-bucket frame capacity: next multiple of `minimum`.

    Multiples of four bound the padding waste at 3 frames per segment
    (power-of-two buckets can waste ~50% of the vote work on long
    segments) while still collapsing the distinct compiled shapes to a
    handful per sequence.
    """
    if num_frames < 1:
        raise ValueError(f"segment must have at least one frame, got {num_frames}")
    return max(minimum, -(-num_frames // minimum) * minimum)


def dispatch_group_head(segs: Sequence[tuple[int, int]], max_group: int,
                        minimum: int = SEGMENT_BUCKET_MIN
                        ) -> tuple[int, int, bool]:
    """Head group of a FIFO queue of closed segments: `(n, capacity, sealed)`.

    The head group is the longest prefix of `segs` whose members share one
    `bucket_capacity`, capped at `max_group` segments (the largest S
    bucket a dispatch may carry). `sealed` means the group can never grow:
    either it already holds `max_group` segments, or the next queued
    segment needs a different frame capacity — a throughput-oriented
    coalescer may keep an unsealed group waiting for more segments, but a
    sealed one gains nothing by waiting.

    One SegmentBatch carries a single frame capacity, and a single
    stream's results must release in segment-close (FIFO) order, so only
    the head of the queue is ever eligible — a group never skips past a
    different-capacity segment queued ahead of it. (Implemented as the
    single-tag case of `dispatch_group_head_tagged`, where the group is
    always a queue prefix.)
    """
    indices, cap, sealed = dispatch_group_head_tagged(
        [(None, seg) for seg in segs], max_group, minimum)
    return len(indices), cap, sealed


def segment_shape(tag: Any, seg: tuple[int, int]) -> tuple[int, int]:
    """What a camera's segment sweeps, `(rows, frames)`: one row of its
    `end - start` frames."""
    return 1, seg[1] - seg[0]


def dispatch_group_head_tagged(queue: Sequence[tuple[Any, tuple[int, int]]],
                               max_group: int,
                               minimum: int = SEGMENT_BUCKET_MIN, *,
                               anchor: int = 0, shape=segment_shape
                               ) -> tuple[list[int], int, bool]:
    """Head group of a TAGGED coalescing queue: `(indices, capacity, sealed)`.

    `queue` holds `(tag, (start, end))` work items in arrival order — the
    tag names the stream/session that closed the segment, so one queue can
    multiplex N cameras onto shared device sweeps. The group is anchored
    at `queue[anchor]` (which must be its own tag's oldest queued segment)
    and collects up to `max_group` members of the anchor's
    `bucket_capacity` by walking the queue forward under the per-stream
    FIFO rule: skipping an item blocks every later item of the same tag.
    A stream's results therefore always release in its own close order,
    while OTHER streams' shape-compatible segments may overtake a blocked
    neighbor and fill the S bucket — the cross-stream coalescing the
    multi-tenant engine is built on.

    Returns queue indices (ascending, starting at `anchor`), the shared
    frame capacity, and `sealed` with its `dispatch_group_head` meaning:
    the group can never grow (it is full, or some queued segment was left
    behind). With one tag and `anchor=0` this reduces exactly to the
    untagged head group.

    `shape(tag, seg) -> (rows, frames)` says what one item sweeps: its
    rows per map (1 for a camera, 2 for a rig) and the frames its
    capacity must hold; members of a group share rows and
    `bucket_capacity(frames)`.
    """
    if not queue:
        raise ValueError("dispatch_group_head needs a non-empty queue")
    if max_group < 1:
        raise ValueError(f"max_group must be >= 1, got {max_group}")
    if not 0 <= anchor < len(queue):
        raise ValueError(
            f"anchor {anchor} outside queue of {len(queue)} item(s)")

    def key(tag, seg) -> tuple[int, int]:
        rows, frames = shape(tag, seg)
        return rows, bucket_capacity(frames, minimum)

    tag0, seg0 = queue[anchor]
    blocked = set()
    for j in range(anchor):
        tag, _ = queue[j]
        if tag == tag0:
            raise ValueError(
                "anchor must be its tag's oldest queued segment: anchoring "
                f"at index {anchor} would overtake an earlier segment of "
                "the same stream (per-stream FIFO)")
        blocked.add(tag)
    key0 = key(tag0, seg0)
    indices = [anchor]
    for i in range(anchor + 1, len(queue)):
        if len(indices) == max_group:
            break
        tag, seg = queue[i]
        if tag in blocked or key(tag, seg) != key0:
            blocked.add(tag)
            continue
        indices.append(i)
    sealed = len(indices) == max_group or len(indices) < len(queue)
    return indices, key0[1], sealed


class DispatchPlanner:
    """Dispatch-group planning, optionally cost-aware.

    The partition rules are the streaming coalescer's, unchanged: head
    groups via `dispatch_group_head_tagged`, fairness anchoring via
    `FAIRNESS_POLICIES`. What the class adds over the module-level
    functions (which now delegate here) is *prediction*: given a
    duck-typed cost model — anything with
    ``predict_sweep_s(key) -> float | None`` — and a ``variant_of``
    factory mapping a padded ``(s_bucket, capacity)`` dispatch shape to
    the model's key type, the planner predicts what a group costs and
    how long draining a queue would take. That is the signal the
    SLO-aware adaptive policy (`StreamConfig(target_latency_s=)`) and
    the deterministic replayer (`repro.serving.dispatch_replay`)
    schedule against.

    A cost model NEVER changes which groups form — only when a
    scheduler chooses to dispatch them. With ``cost_model=None`` (or
    one that predicts ``None``) every prediction is ``None`` and
    consumers fall back to the pre-cost-model heuristics, which is how
    the "latency"/"throughput" policies and the null-model adaptive
    policy keep bitwise-identical schedules
    (tests/test_adaptive_dispatch.py pins this). See
    docs/dispatch_planning.md for the full decision table.

    `s_buckets` are the fixed segment-axis pad sizes (ascending; the
    last is the planning `max_group`): predictions must account for the
    PADDED rows a dispatch sweeps, not just the real ones, or the model
    would reward under-filled buckets.
    """

    def __init__(self, s_buckets: Sequence[int],
                 minimum: int = SEGMENT_BUCKET_MIN, *,
                 cost_model=None, variant_of=None, shape=segment_shape):
        s_buckets = tuple(s_buckets)
        if not s_buckets:
            raise ValueError("s_buckets must be non-empty")
        if list(s_buckets) != sorted(set(s_buckets)) or s_buckets[0] < 1:
            raise ValueError(
                f"s_buckets must be strictly ascending positive ints, got "
                f"{s_buckets}")
        self.s_buckets = s_buckets
        self.max_group = s_buckets[-1]
        self.minimum = minimum
        self.cost_model = cost_model
        self.variant_of = variant_of
        self.shape = shape  # see dispatch_group_head_tagged

    # --- partitioning (the PR 5/6 rules, verbatim) ------------------------

    def head(self, segs: Sequence[tuple[int, int]]) -> tuple[int, int, bool]:
        return dispatch_group_head(segs, self.max_group, self.minimum)

    def head_tagged(self, queue: Sequence[tuple[Any, tuple[int, int]]], *,
                    anchor: int = 0) -> tuple[list[int], int, bool]:
        return dispatch_group_head_tagged(queue, self.max_group,
                                          self.minimum, anchor=anchor,
                                          shape=self.shape)

    def plan(self, segs: Sequence[tuple[int, int]]
             ) -> list[tuple[list[tuple[int, int]], int]]:
        groups: list[tuple[list[tuple[int, int]], int]] = []
        i = 0
        while i < len(segs):
            n, cap, _ = self.head(segs[i:])
            groups.append((list(segs[i:i + n]), cap))
            i += n
        return groups

    def plan_tagged(self, items: Sequence[tuple[Any, tuple[int, int]]], *,
                    fairness: str = "fifo"
                    ) -> list[tuple[list[tuple[Any, tuple[int, int]]], int]]:
        if fairness not in FAIRNESS_POLICIES:
            raise ValueError(f"unknown fairness {fairness!r}: expected one "
                             f"of {FAIRNESS_POLICIES}")
        queue = list(items)
        order: list[Any] = []
        for tag, _ in queue:
            if tag not in order:
                order.append(tag)
        cursor = 0
        groups: list[tuple[list[tuple[Any, tuple[int, int]]], int]] = []
        while queue:
            anchor = 0
            if fairness == "round_robin" and len(order) > 1:
                present = {tag for tag, _ in queue}
                for k in range(len(order)):
                    tag = order[(cursor + k) % len(order)]
                    if tag in present:
                        cursor = (cursor + k + 1) % len(order)
                        anchor = next(i for i, (t, _) in enumerate(queue)
                                      if t == tag)
                        break
            idx, cap, _ = self.head_tagged(queue, anchor=anchor)
            groups.append(([queue[i] for i in idx], cap))
            for i in reversed(idx):
                queue.pop(i)
        return groups

    # --- prediction -------------------------------------------------------

    def s_bucket(self, n: int) -> int:
        """Smallest fixed S bucket a group of `n` segments pads to."""
        for b in self.s_buckets:
            if b >= n:
                return b
        raise ValueError(f"group of {n} exceeds top segment bucket "
                         f"{self.s_buckets[-1]}")

    def predict_group_s(self, n_segments: int, capacity: int) -> float | None:
        """Predicted wall time of one dispatched group, or None when the
        model (or the variant factory) has nothing to say."""
        if self.cost_model is None or self.variant_of is None:
            return None
        key = self.variant_of(self.s_bucket(n_segments), capacity)
        return self.cost_model.predict_sweep_s(key)

    def predict_drain_s(self, items: Sequence[tuple[Any, tuple[int, int]]],
                        *, fairness: str = "fifo") -> float | None:
        """Predicted serial time to sweep an entire tagged queue, planned
        exactly as a full drain would partition it. None unless EVERY
        group gets a prediction — a partially predictable drain is not a
        deadline anyone should schedule against."""
        total = 0.0
        for group, cap in self.plan_tagged(items, fairness=fairness):
            cost = self.predict_group_s(len(group), cap)
            if cost is None:
                return None
            total += cost
        return total


def plan_dispatch_groups(segs: Sequence[tuple[int, int]], max_group: int,
                         minimum: int = SEGMENT_BUCKET_MIN
                         ) -> list[tuple[list[tuple[int, int]], int]]:
    """Partition a FIFO list of closed segments into dispatch groups.

    Repeated `dispatch_group_head`, so the partition is exactly what a
    streaming coalescer draining the whole queue would dispatch: each
    group is `(segments, frame_capacity)`, groups concatenate back to
    `segs` in order (nothing dropped, duplicated, or reordered), every
    group holds 1..max_group segments of one shared capacity. This is
    the bucket planning `run_emvs`'s capacity map performs offline,
    restated under the streaming FIFO-release constraint — the
    coalescing-planner property test pins these invariants for any
    segment sequence. (Delegates to a cost-model-free `DispatchPlanner`;
    the partition is identical by construction.)
    """
    return DispatchPlanner(_planner_buckets(max_group), minimum).plan(segs)


def _planner_buckets(max_group: int) -> tuple[int, ...]:
    # module-level planners know only the cap, not the full bucket set —
    # partitioning needs nothing else (prediction, which does, goes
    # through a DispatchPlanner constructed with the real buckets)
    if max_group < 1:
        raise ValueError(f"max_group must be >= 1, got {max_group}")
    return (max_group,)


def plan_dispatch_groups_tagged(
    items: Sequence[tuple[Any, tuple[int, int]]], max_group: int,
    minimum: int = SEGMENT_BUCKET_MIN, *, fairness: str = "fifo"
) -> list[tuple[list[tuple[Any, tuple[int, int]]], int]]:
    """Partition a TAGGED arrival order into dispatch groups.

    Repeated `dispatch_group_head_tagged` over a draining queue — exactly
    what the multi-tenant `SweepDispatcher` dispatches when it drains N
    sessions' closed segments, restated as a pure function for the
    property tests. Each group is `(tagged_segments, frame_capacity)`.
    (Delegates to a cost-model-free `DispatchPlanner`; the partition is
    identical by construction.)

    `fairness` picks how successive groups anchor (FAIRNESS_POLICIES):

      * "fifo" — every group anchors at the current queue head: strict
        global arrival order. A stream whose head-of-queue segment needs
        an odd frame capacity delays the anchors of everyone behind it
        (their shape-compatible segments still ride along as group
        members).
      * "round_robin" — anchors rotate over the tags in first-appearance
        order, skipping tags with nothing queued: a tag with queued work
        is anchored again after at most (#distinct tags) groups, so no
        stream waits more than O(streams) dispatches behind a chatty
        neighbor — at the cost of leaving the global arrival order.

    Invariants under BOTH policies (property-tested in
    tests/test_multi_stream.py): per tag, its segments appear in arrival
    order across the groups (per-stream FIFO); nothing is dropped,
    duplicated, or cross-tagged; every group holds 1..max_group segments
    sharing one `bucket_capacity`. With a single tag both policies
    reduce to `plan_dispatch_groups`.
    """
    return DispatchPlanner(_planner_buckets(max_group),
                           minimum).plan_tagged(items, fairness=fairness)


def _host_frames(frames: EventFrames) -> EventFrames:
    """One device-to-host transfer of the fields pad_segments gathers from."""
    return EventFrames(
        xy=np.asarray(frames.xy),
        valid=np.asarray(frames.valid),
        t_mid=frames.t_mid,
        poses=SE3(np.asarray(frames.poses.R), np.asarray(frames.poses.t)),
    )


def pad_segments(frames: EventFrames, segs: Sequence[tuple[int, int]],
                 capacity: int) -> SegmentBatch:
    """Gather a list of same-bucket segments into one padded SegmentBatch."""
    if not segs:
        raise ValueError(
            "pad_segments needs at least one segment: an empty segment "
            "list has no reference pose and nothing to sweep (callers "
            "must skip dispatch for empty buckets)")
    idx_rows, fv_rows = [], []
    for start, end in segs:
        n = end - start
        if not 0 < n <= capacity:
            raise ValueError(f"segment {(start, end)} does not fit capacity {capacity}")
        idx_rows.append(np.minimum(np.arange(start, start + capacity), end - 1))
        fv_rows.append((np.arange(capacity) < n).astype(np.float32))
    # Gather on the host with numpy: this is one-off ARM-side data staging,
    # and keeping it out of XLA avoids compiling a fleet of tiny gather
    # programs per bucket shape. Callers looping over buckets should pass
    # host-side frames (see _host_frames) so the device-to-host transfer
    # happens once per sequence, not once per bucket.
    idx = np.stack(idx_rows)  # (S, C) frame indices, clamped
    ref = np.array([s for s, _ in segs], dtype=np.int32)
    xy = np.asarray(frames.xy)
    valid = np.asarray(frames.valid)
    poses_R = np.asarray(frames.poses.R)
    poses_t = np.asarray(frames.poses.t)
    return SegmentBatch(
        xy=jnp.asarray(xy[idx]),
        valid=jnp.asarray(valid[idx].astype(np.float32)),
        frame_valid=jnp.asarray(np.stack(fv_rows)),
        poses_R=jnp.asarray(poses_R[idx]),
        poses_t=jnp.asarray(poses_t[idx]),
        ref_R=jnp.asarray(poses_R[ref]),
        ref_t=jnp.asarray(poses_t[ref]),
    )


def pad_segment_rows(rows: Sequence[tuple[EventFrames, tuple[int, int]]],
                     capacity: int) -> SegmentBatch:
    """`pad_segments` for segments that each bring their own frame window.

    The multi-tenant dispatcher coalesces shape-compatible segments from
    DIFFERENT sessions into one S bucket; their frames live in different
    per-session stores, so the batch is gathered row by row: `rows[k]` is
    `(frames_k, (start_k, end_k))` with indices relative to `frames_k`.
    Each row's gather is the same clamp-at-end indexing as
    `pad_segments`, so row k is bitwise what
    `pad_segments(frames_k, [seg_k], capacity)` would produce — grouping
    segments across sessions never changes a segment's numbers (the
    per-segment sweep body is independent).
    """
    if not rows:
        raise ValueError(
            "pad_segment_rows needs at least one segment row: an empty "
            "group has no reference pose and nothing to sweep (callers "
            "must skip dispatch for empty buckets)")
    gathered = [_gather_row(frames, seg, capacity) for frames, seg in rows]
    return SegmentBatch(*(jnp.asarray(np.stack(col))
                          for col in zip(*gathered)))


def pad_rig_rows(maps: Sequence[Sequence[tuple[EventFrames, tuple[int, int]]]],
                 capacity: int) -> SegmentBatch:
    """A rig batch: `maps[k]` holds one `(frames, (start, end))` row per
    camera, gathered as `pad_segment_rows` gathers a row, stacked on a
    camera axis after S. Every camera of map k votes into one reference
    view, the first camera's pose at its row's `start` (the key frame)."""
    if not maps:
        raise ValueError("pad_rig_rows needs at least one map")
    gathered = [[_gather_row(frames, seg, capacity) for frames, seg in m]
                for m in maps]

    def field(i: int) -> np.ndarray:  # (S, K, ...)
        return np.stack([np.stack([row[i] for row in rows])
                         for rows in gathered])

    return SegmentBatch(*(jnp.asarray(field(i)) for i in range(5)),
                        ref_R=jnp.asarray(field(5)[:, 0]),
                        ref_t=jnp.asarray(field(6)[:, 0]))


def _gather_row(frames: EventFrames, seg: tuple[int, int], capacity: int
                ) -> tuple[np.ndarray, ...]:
    """One padded row, in `SegmentBatch` field order: frames
    [start, end) clamped at the end to `capacity`, and the pose at
    `start` as the row's reference view."""
    start, end = seg
    n = end - start
    xy = np.asarray(frames.xy)
    if not 0 < n <= capacity:
        raise ValueError(
            f"segment {(start, end)} does not fit capacity {capacity}")
    if not 0 <= start < end <= xy.shape[0]:
        raise ValueError(f"segment {(start, end)} outside its window of "
                         f"{xy.shape[0]} frame(s)")
    idx = np.minimum(np.arange(start, start + capacity), end - 1)
    valid = np.asarray(frames.valid)
    poses_R = np.asarray(frames.poses.R)
    poses_t = np.asarray(frames.poses.t)
    return (xy[idx], valid[idx].astype(np.float32),
            (np.arange(capacity) < n).astype(np.float32), poses_R[idx],
            poses_t[idx], poses_R[start], poses_t[start])


# ---------------------------------------------------------------------------
# Per-frame projection (float + quantized datapaths)
# ---------------------------------------------------------------------------


def project_frame(
    cam: CameraModel,
    xy: Array,
    geom: FrameGeometry,
    opts: EMVSOptions,
) -> tuple[Array, Array]:
    """P for one frame: (E,2) -> per-plane coords ((Nz,E), (Nz,E))."""
    if opts.quantized:
        pol = opts.policy
        xy = pol.quantize_events(xy)
        H = pol.quantize_homography(geom.H)
        phi = pol.quantize_phi(geom.phi)
        xy0 = pol.quantize_canonical(apply_homography(H, xy))
        x_i, y_i = propagate_to_planes(cam, xy0, phi)
        if opts.voting == "nearest":
            # int8 plane-coord quantization (park-at-max for misses)
            x_i, y_i = pol.quantize_plane_coords(x_i, y_i)
        return x_i, y_i
    xy0 = apply_homography(geom.H, xy)
    return propagate_to_planes(cam, xy0, phi=geom.phi)


def vote_frame(
    dsi: Array,
    x_i: Array,
    y_i: Array,
    valid: Array,
    cam: CameraModel,
    opts: EMVSOptions,
) -> Array:
    """R for one frame. `valid` masks padded/invalid events (weight 0)."""
    w, h = cam.width, cam.height
    weights = jnp.broadcast_to(valid.astype(jnp.float32)[None, :], x_i.shape)
    if opts.formulation == "scatter":
        return vote_scatter(dsi, x_i, y_i, w=w, h=h, mode=opts.voting, weights=weights)
    if opts.formulation == "matmul":
        return vote_onehot_matmul(dsi, x_i, y_i, w=w, h=h, mode=opts.voting,
                                  weights=weights)
    if opts.formulation == "kernel":
        raise ValueError(
            "formulation='kernel' fuses projection and voting per segment; "
            "it is driven by process_segments_batched / process_segment, "
            "not per frame"
        )
    raise ValueError(f"unknown formulation {opts.formulation}")


# ---------------------------------------------------------------------------
# Segment processing: batched sweep (one compiled program per bucket)
# ---------------------------------------------------------------------------


def _accum_dtype(opts: EMVSOptions) -> Any:
    if opts.voting == "bilinear":
        return jnp.float32
    return dsi_lib.DSI_ACCUM_DTYPE


def precompute_batch_geometry(
    cam: CameraModel, poses_R: Array, poses_t: Array, T_w_ref: SE3,
    planes: Array, z0: Array
) -> FrameGeometry:
    """Vectorized H/phi for a stack of frame poses (ARM-side work)."""

    def per_frame(R, t):
        return frame_geometry(cam, T_w_ref, SE3(R, t), z0, planes)

    return jax.vmap(per_frame)(poses_R, poses_t)


def precompute_segment_geometry(
    cam: CameraModel, frames: EventFrames, T_w_ref: SE3, planes: Array, z0: Array
) -> FrameGeometry:
    """Vectorized H/phi for all frames of a segment (ARM-side work)."""
    return precompute_batch_geometry(cam, frames.poses.R, frames.poses.t,
                                     T_w_ref, planes, z0)


def _pad_frames(seg: SegmentBatch, multiple: int) -> SegmentBatch:
    """One row's frames padded to a multiple of `multiple` by the
    `SegmentBatch` rule: repeat the last frame, with `frame_valid` 0.
    Only a capacity no bucket builds (a direct `process_segment` call)
    needs it."""
    pad = -seg.xy.shape[0] % multiple
    if not pad:
        return seg

    def edge(a: Array) -> Array:
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), mode="edge")

    return seg._replace(xy=edge(seg.xy), valid=edge(seg.valid),
                        poses_R=edge(seg.poses_R), poses_t=edge(seg.poses_t),
                        frame_valid=jnp.pad(seg.frame_valid, (0, pad)))


def sweep_segment_batch(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    batch: SegmentBatch,
    opts: EMVSOptions,
) -> tuple[Array, DepthMap]:
    """Traceable body of the segment sweep: vote, quantize-store, detect
    and filter a whole `SegmentBatch`.

    Deliberately un-jitted: `process_segments_batched` wraps it in one
    jit per bucket shape, and `repro.distributed.emvs
    .process_segments_sharded` wraps it in a `shard_map` over the segment
    axis — every segment is independent (the DSI resets per key frame),
    so both wrappers run the exact same per-segment program and their
    outputs agree bitwise on the integer/nearest datapaths.

    A rig batch (`xy` of rank 5, see `SegmentBatch`) is swept map by
    map: each camera's row is voted into the map's reference view, the
    two stored DSIs are fused by `dsi.fuse_harmonic_mean`, and detection
    runs once, on the float32 fused volume, which is what the map
    returns as its DSI. A one-camera batch compiles to the same program
    as before rigs existed.
    """
    planes = dsi_cfg.planes()
    z0 = planes[dsi_cfg.num_planes // 2]

    def vote(seg: SegmentBatch, T_w_ref: SE3) -> Array:
        """One row's frames voted into a fresh DSI at `T_w_ref`, stored
        (XLA formulations)."""
        chunked = opts.formulation == "matmul" and opts.voting == "nearest"
        if chunked:
            seg = _pad_frames(seg, VOTE_CHUNK_FRAMES)
        geoms = precompute_batch_geometry(cam, seg.poses_R, seg.poses_t,
                                          T_w_ref, planes, z0)
        dsi0 = jnp.zeros(dsi_cfg.shape, dtype=_accum_dtype(opts))
        frames = (seg.xy, seg.valid, seg.frame_valid, geoms.H,
                  geoms.phi.alpha, geoms.phi.beta_x, geoms.phi.beta_y)

        def project(xy, H, alpha, beta_x, beta_y):
            geom = FrameGeometry(H, PlaneSweepCoeffs(alpha, beta_x, beta_y))
            return project_frame(cam, xy, geom, opts)

        if chunked:
            # VOTE_CHUNK_FRAMES frames a step, one int8 contraction each
            frames = tuple(a.reshape((-1, VOTE_CHUNK_FRAMES) + a.shape[1:])
                           for a in frames)

            def body(dsi, chunk):
                xy, valid, fv, *geom = chunk
                x_i, y_i = jax.vmap(project)(xy, *geom)
                return vote_onehot_chunk(dsi, x_i, y_i, valid * fv[:, None],
                                         w=cam.width, h=cam.height), None
        else:
            def body(dsi, frame):
                xy, valid, fv, *geom = frame
                x_i, y_i = project(xy, *geom)
                return vote_frame(dsi, x_i, y_i, valid * fv, cam, opts), None

        dsi, _ = jax.lax.scan(body, dsi0, frames)

        if opts.quantized:
            dsi = dsi_lib.storage_roundtrip(dsi)  # int16 store semantics
        return dsi

    def detect(dsi: Array) -> DepthMap:
        return detect_and_filter(
            dsi, planes,
            threshold_c=opts.detection_threshold_c,
            min_votes=opts.detection_min_votes,
            median_filter=opts.median_filter,
        )

    if batch.xy.ndim == 5:  # a rig: (S, K, C, E, 2)
        if opts.formulation == "kernel":
            raise ValueError(
                "formulation='kernel' votes and reduces one camera per "
                "segment in-kernel; a rig's fused sweep runs 'matmul' or "
                "'scatter'")
        if batch.xy.shape[1] != 2:
            raise ValueError(f"a rig fuses 2 cameras by the harmonic mean, "
                             f"got {batch.xy.shape[1]}")

        def one_map(m: SegmentBatch) -> tuple[Array, DepthMap]:
            T_w_ref = SE3(m.ref_R, m.ref_t)
            left, right = (vote(jax.tree.map(lambda a: a[k], m), T_w_ref)
                           for k in range(2))
            fused = dsi_lib.fuse_harmonic_mean(left, right)
            return fused, detect(fused)

        return jax.lax.map(one_map, batch)

    def one_segment(seg: SegmentBatch) -> tuple[Array, DepthMap]:
        T_w_ref = SE3(seg.ref_R, seg.ref_t)

        if opts.formulation == "kernel":
            from repro.kernels.backproject_vote import ops as bpv_ops

            geoms = precompute_batch_geometry(cam, seg.poses_R, seg.poses_t,
                                              T_w_ref, planes, z0)

            # Fused datapath: vote, int16 saturating store (when
            # quantized) and the depth max/argmax reduction all run
            # in-kernel against the VMEM-resident block — the stored DSI
            # makes exactly ONE HBM trip and is never read back for
            # detection (no post-kernel storage_roundtrip here).
            dsi, conf, zf = bpv_ops.backproject_vote_frames(
                seg.xy, seg.valid, geoms.H,
                jnp.stack([geoms.phi.alpha, geoms.phi.beta_x, geoms.phi.beta_y],
                          axis=-1),  # (C, Nz, 3)
                cam=cam, dsi_cfg=dsi_cfg, mode=opts.voting,
                quantized=opts.quantized, frame_valid=seg.frame_valid,
                interpret=opts.kernel_interpret,
            )
            if opts.quantized:
                # widen the int16 stored volume back to the accumulator
                # dtype so downstream consumers (saturation monitors,
                # point-cloud weights) see the same dtype as the XLA path
                dsi = dsi_lib.from_storage(dsi)
            dm = detect_and_filter_from(
                conf, zf, planes,
                threshold_c=opts.detection_threshold_c,
                min_votes=opts.detection_min_votes,
                median_filter=opts.median_filter,
            )
            return dsi, dm

        dsi = vote(seg, T_w_ref)
        return dsi, detect(dsi)

    return jax.lax.map(one_segment, batch)


@partial(jax.jit, static_argnames=("cam", "dsi_cfg", "opts"))
def process_segments_batched(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    batch: SegmentBatch,
    opts: EMVSOptions,
) -> tuple[Array, DepthMap]:
    """Vote, quantize-store, detect and filter a whole segment bucket.

    One compiled sweep: `lax.map` over the segment axis, so within a
    `run_emvs` call the trace happens once per bucket instead of once per
    segment, and no intermediate leaves the device. (The jit cache is
    keyed on the full batch shape — segment count S, capacity C, events E
    — so distinct sequences can still retrace; a streaming caller should
    pad S to stable sizes.) Returns stacked per-segment DSIs
    (S, Nz, h, w) and a DepthMap with (S, h, w) fields; for a rig batch,
    one fused float32 DSI and one depth map per map.

    This is the `sweep="batched"` backend of `run_emvs`; the
    `sweep="sharded"` backend (`process_segments_sharded`) runs the same
    body with the segment axis sharded across mesh devices.
    """
    return sweep_segment_batch(cam, dsi_cfg, batch, opts)


def process_segment(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    frames: EventFrames,
    T_w_ref: SE3,
    opts: EMVSOptions,
) -> tuple[Array, DepthMap]:
    """Vote all frames of one key-frame segment into a fresh DSI; detect.

    Thin wrapper over the batched sweep with a single unpadded segment, so
    per-segment and batched callers share one code path.
    """
    num_frames = frames.xy.shape[0]
    batch = SegmentBatch(
        xy=frames.xy[None],
        valid=frames.valid.astype(jnp.float32)[None],
        frame_valid=jnp.ones((1, num_frames), dtype=jnp.float32),
        poses_R=frames.poses.R[None],
        poses_t=frames.poses.t[None],
        ref_R=T_w_ref.R[None],
        ref_t=T_w_ref.t[None],
    )
    dsis, dms = process_segments_batched(cam, dsi_cfg, batch, opts)
    return dsis[0], DepthMap(dms.depth[0], dms.mask[0], dms.confidence[0])


# ---------------------------------------------------------------------------
# Full pipeline
# ---------------------------------------------------------------------------


def run_emvs(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    frames: EventFrames,
    opts: EMVSOptions = EMVSOptions(),
    *,
    sweep: str = "batched",
    mesh: Any | None = None,
) -> EMVSResult:
    """Process an aggregated event-frame sequence end to end.

    Segments are grouped into fixed frame-capacity buckets; each
    bucket is one sweep call plus one batched depth-map -> point-cloud
    conversion. Per-segment outputs are numerically identical to
    `run_emvs_looped` (padded frames vote with weight 0).

    sweep: which segment-sweep backend runs each bucket.
      * "batched" — `process_segments_batched`: one `lax.map` device
        program per bucket (serial over segments within the program).
      * "sharded" — `repro.distributed.emvs.process_segments_sharded`:
        the segment axis of each bucket is sharded across the devices of
        `mesh` (default: a 1-D mesh over all local devices), so
        concurrent segments vote on different devices — the paper's
        key-frame-level parallelism. The segment list is padded to a
        multiple of the mesh's segment-axis size by repeating the last
        segment; padded rows are discarded on harvest, and real rows are
        bit-identical to the batched backend on the integer/nearest
        datapaths (allclose on bilinear).
    """
    if sweep not in ("batched", "sharded"):
        raise ValueError(
            f"unknown sweep backend {sweep!r}: expected 'batched' or 'sharded'")
    if mesh is not None and sweep != "sharded":
        raise ValueError(
            "mesh= is only meaningful with sweep='sharded'; the batched "
            "sweep would silently ignore it")
    n_shard = 1
    if sweep == "sharded":
        from repro.distributed.emvs import (
            make_segment_mesh,
            process_segments_sharded,
            segment_axis_size,
        )

        if mesh is None:
            mesh = make_segment_mesh()
        n_shard = segment_axis_size(mesh)

    segs = plan_segments(frames, dsi_cfg, opts)
    if not segs:
        return EMVSResult(segments=[], clouds=[])

    by_cap: dict[int, list[tuple[int, int]]] = {}
    for seg in segs:
        by_cap.setdefault(bucket_capacity(seg[1] - seg[0]), []).append(seg)

    host = _host_frames(frames)
    out: dict[tuple[int, int], tuple[SegmentResult, PointCloud]] = {}
    for cap in sorted(by_cap):
        seg_list = by_cap[cap]
        # sharded sweeps need S divisible by the mesh's segment axis:
        # repeat the last segment (independent rows -> pure discarded work)
        run_list = seg_list + [seg_list[-1]] * (-len(seg_list) % n_shard)
        batch = pad_segments(host, run_list, cap)
        if sweep == "sharded":
            dsis, dms = process_segments_sharded(cam, dsi_cfg, batch, opts,
                                                 mesh=mesh)
        else:
            dsis, dms = process_segments_batched(cam, dsi_cfg, batch, opts)
        pcs = depth_maps_to_points(cam, dms, SE3(batch.ref_R, batch.ref_t))
        for k, (start, end) in enumerate(seg_list):
            dm = DepthMap(dms.depth[k], dms.mask[k], dms.confidence[k])
            T_w_ref = SE3(batch.ref_R[k], batch.ref_t[k])
            out[(start, end)] = (
                SegmentResult(dm, dsis[k], T_w_ref, (start, end)),
                PointCloud(pcs.points[k], pcs.weights[k], pcs.valid[k]),
            )

    ordered = [out[seg] for seg in segs]
    return EMVSResult(segments=[r for r, _ in ordered],
                      clouds=[c for _, c in ordered])


# ---------------------------------------------------------------------------
# Static-analysis entry points (repro.analysis)
# ---------------------------------------------------------------------------


class TensorContract(NamedTuple):
    """Worst-case input bounds the static analyzer may assume.

    `integral=True` asserts the tensor only holds integer *values*
    (whatever its storage dtype) — e.g. the 0/1 validity masks. These are
    semantic promises about what callers feed the sweep, not dtype facts;
    the linter's overflow proofs are conditional on them.
    """

    lo: float
    hi: float
    integral: bool = False


# per-field contracts for SegmentBatch inputs to the sweep programs:
# masks are exact 0/1, rotations are orthonormal (entries in [-1, 1]),
# coords/translations are bounded by any physically plausible rig
SWEEP_INPUT_CONTRACTS: dict[str, TensorContract] = {
    "xy": TensorContract(-4096.0, 4096.0),
    "valid": TensorContract(0.0, 1.0, integral=True),
    "frame_valid": TensorContract(0.0, 1.0, integral=True),
    "poses_R": TensorContract(-1.0, 1.0),
    "poses_t": TensorContract(-1e3, 1e3),
    "ref_R": TensorContract(-1.0, 1.0),
    "ref_t": TensorContract(-1e3, 1e3),
}


def sweep_trace_spec(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    opts: EMVSOptions,
    *,
    segments: int = 2,
    capacity: int = SEGMENT_BUCKET_MIN,
    events: int = 64,
    sweep: str = "batched",
    mesh=None,
):
    """Traceable sweep entry for `repro.analysis`: `(fn, args, contracts)`.

    `fn(*args)` stages the exact program the `sweep=` backend dispatches
    — `sweep_segment_batch` for "batched", `process_segments_sharded`
    (jit(shard_map(...))) for "sharded" — on `ShapeDtypeStruct` inputs,
    so `jax.make_jaxpr` can lint it without running anything. `contracts`
    maps `SegmentBatch` field names to `TensorContract`s seeding the
    analyzer's worst-case intervals.
    """
    s, c, e = segments, capacity, events
    f32 = jnp.float32
    batch = SegmentBatch(
        xy=jax.ShapeDtypeStruct((s, c, e, 2), f32),
        valid=jax.ShapeDtypeStruct((s, c, e), f32),
        frame_valid=jax.ShapeDtypeStruct((s, c), f32),
        poses_R=jax.ShapeDtypeStruct((s, c, 3, 3), f32),
        poses_t=jax.ShapeDtypeStruct((s, c, 3), f32),
        ref_R=jax.ShapeDtypeStruct((s, 3, 3), f32),
        ref_t=jax.ShapeDtypeStruct((s, 3), f32),
    )
    if sweep == "sharded":
        from repro.distributed.emvs import process_segments_sharded

        def fn(b: SegmentBatch):
            return process_segments_sharded(cam, dsi_cfg, b, opts, mesh=mesh)

    elif sweep == "batched":

        def fn(b: SegmentBatch):
            return sweep_segment_batch(cam, dsi_cfg, b, opts)

    else:
        raise ValueError(f"unknown sweep backend {sweep!r}")
    return fn, (batch,), dict(SWEEP_INPUT_CONTRACTS)


def run_emvs_looped(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    frames: EventFrames,
    opts: EMVSOptions = EMVSOptions(),
) -> EMVSResult:
    """Reference host-side per-segment loop (the seed's `run_emvs`).

    One device dispatch per segment and one retrace per distinct segment
    length — kept as the numerical baseline and the A/B counterpart for
    `benchmarks/segment_batching.py`.
    """
    results: list[SegmentResult] = []
    clouds: list[PointCloud] = []
    for start, end in plan_segments(frames, dsi_cfg, opts):
        sl = jax.tree.map(lambda a: a[start:end], frames)
        T_w_ref = SE3(frames.poses.R[start], frames.poses.t[start])
        dsi, dm = process_segment(cam, dsi_cfg, sl, T_w_ref, opts)
        results.append(SegmentResult(dm, dsi, T_w_ref, (start, end)))
        clouds.append(depth_map_to_points(cam, dm, T_w_ref))
    return EMVSResult(segments=results, clouds=clouds)


def rig_right_ranges(t_left, t_right,
                     segs: Sequence[tuple[int, int]]) -> list[tuple[int, int]]:
    """The right camera's frames of each of the left camera's key-frame
    segments [a, b): the frames j with t_L[a] <= t_R[j] < t_L[b], where t
    is a frame's median timestamp (`EventFrames.t_mid`, in time order). A
    segment that ends the left stream (b == len(t_left)) has no upper
    bound. Returns [ra, rb) per segment."""
    t_left = np.asarray(t_left)
    t_right = np.asarray(t_right)
    out = []
    for a, b in segs:
        lo = int(np.searchsorted(t_right, t_left[a], side="left"))
        hi = (t_right.shape[0] if b >= t_left.shape[0] else
              int(np.searchsorted(t_right, t_left[b], side="left")))
        out.append((lo, hi))
    return out


def run_emvs_rig_looped(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    left: EventFrames,
    right: EventFrames,
    opts: EMVSOptions = EMVSOptions(),
) -> EMVSResult:
    """Reference of a two-camera rig fused at the left camera's key
    frames (Ghosh & Gallego 2022, arXiv 2207.10494), a host loop in the
    style of `run_emvs_looped` on the scatter formulation.

    The left (reference) camera's frames are cut into key-frame segments
    as `plan_segments` cuts them; each segment takes the right frames
    `rig_right_ranges` assigns it. Both cameras' frames are voted into
    the left key frame's view, the two DSIs are fused by the harmonic
    mean in NumPy, divided in float64 and rounded once to float32 (the
    value `dsi.fuse_harmonic_mean` defines), and detection runs on the
    fused volume. Both cameras share the intrinsics `cam` (a rectified
    rig).
    """
    opts = dataclasses.replace(opts, formulation="scatter")
    # one compiled program that computes its planes, as the sweep's
    # does: op by op, or with the planes as an input, the depth
    # interpolation rounds differently in the last place
    detect = jax.jit(lambda dsi: detect_and_filter(
        dsi, dsi_cfg.planes(), threshold_c=opts.detection_threshold_c,
        min_votes=opts.detection_min_votes, median_filter=opts.median_filter))
    segs = plan_segments(left, dsi_cfg, opts)
    results: list[SegmentResult] = []
    clouds: list[PointCloud] = []
    for (a, b), (ra, rb) in zip(segs, rig_right_ranges(left.t_mid,
                                                       right.t_mid, segs)):
        T_w_ref = SE3(left.poses.R[a], left.poses.t[a])
        votes = []
        for frames, lo, hi in ((left, a, b), (right, ra, rb)):
            dsi = np.zeros(dsi_cfg.shape, np.float64)
            if hi > lo:
                sl = jax.tree.map(lambda x: x[lo:hi], frames)
                dsi = np.asarray(process_segment(cam, dsi_cfg, sl, T_w_ref,
                                                 opts)[0], np.float64)
            votes.append(dsi)
        va, vb = votes
        with np.errstate(divide="ignore", invalid="ignore"):
            fused = np.where((va > 0) & (vb > 0), 2 * va * vb / (va + vb),
                             0).astype(np.float32)
        dm = detect(jnp.asarray(fused))
        results.append(SegmentResult(dm, jnp.asarray(fused), T_w_ref, (a, b)))
        clouds.append(depth_map_to_points(cam, dm, T_w_ref))
    return EMVSResult(segments=results, clouds=clouds)
