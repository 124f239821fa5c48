"""Shared dispatch layer of the streaming EMVS engine.

`SweepDispatcher` owns everything N camera sessions share on one
accelerator: the `(session, segment)`-tagged coalescing queue, the
dispatch policy (latency / throughput / adaptive) and fairness anchor
rule (fifo / round_robin), the double-buffered in-flight slots, the
bounded compiled-variant cache (via fixed S buckets and frame-capacity
buckets), and the batched/sharded sweep backends.

Sessions (`repro.serving.stream_session.StreamSession`) `enqueue` their
closed segments tagged with themselves; the dispatcher forms head groups
with `repro.core.pipeline.dispatch_group_head_tagged`, so
`pad_segments`-compatible segments from DIFFERENT sessions fill one S
bucket — the cross-stream coalescing that keeps the device saturated
when any single stream goes quiet. Grouping never changes a segment's
numbers (rows are gathered per session store by `pad_segment_rows` and
the per-segment sweep body is independent), so every session's results
stay bit-identical to a dedicated single-stream engine, under any
interleaving, policy, and fairness setting. Harvested rows are routed
back to their owning session's result stores; one session's `flush`
drains only its share of the queue (same-capacity neighbors may ride
along — legal for the same independence reason).

A queue item asks its session what it sweeps (`map_shape`, `stage`):
a camera's segment is one row, a rig's map (`RigSession`) two rows that
the sweep fuses. A group holds items of one shape only, so rig maps
group with rig maps under the same policy as segments with segments.
"""
from __future__ import annotations

from collections import deque
from time import perf_counter
from typing import NamedTuple

import jax

from repro.core import dsi as dsi_lib
from repro.core.camera import CameraModel
from repro.core.detection import DepthMap
from repro.core.dsi import DSIConfig
from repro.core.geometry import SE3
from repro.core.pipeline import (
    DispatchPlanner,
    EMVSOptions,
    SegmentResult,
    pad_rig_rows,
    pad_segment_rows,
    process_segments_batched,
)
from repro.core.pointcloud import PointCloud, depth_maps_to_points
from repro.profiling.cost_table import VariantKey

Array = jax.Array

# Latency histogram bin edges (seconds): log-decade bins wide enough to
# cover a sub-millisecond warm CPU sweep and a multi-second cold compile.
_HIST_EDGES_S = (1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0)


class _LatencyHist:
    """Fixed-log-bin latency histogram over (t_in, t_out) sample pairs.

    Beyond the usual count/total/max, it keeps the raw timestamp sums so
    consumers can verify the reconciliation identity
    ``total_s == t_out_sum - t_in_sum`` — the sum of waits IS the sum of
    dispatch timestamps minus the sum of enqueue timestamps (resp.
    harvest minus dispatch for sweep times), so a histogram that lost or
    double-counted a sample cannot satisfy it
    (tests/test_adaptive_dispatch.py asserts this on live engines).
    """

    def __init__(self) -> None:
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.t_in_sum = 0.0
        self.t_out_sum = 0.0
        self.bins = [0] * (len(_HIST_EDGES_S) + 1)

    def observe(self, t_in: float, t_out: float) -> None:
        dt = t_out - t_in  # perf_counter is monotonic: never negative
        self.count += 1
        self.total_s += dt
        self.max_s = max(self.max_s, dt)
        self.t_in_sum += t_in
        self.t_out_sum += t_out
        i = 0
        while i < len(_HIST_EDGES_S) and dt >= _HIST_EDGES_S[i]:
            i += 1
        self.bins[i] += 1

    def snapshot(self) -> dict:
        return {"count": self.count, "total_s": self.total_s,
                "max_s": self.max_s, "t_in_sum": self.t_in_sum,
                "t_out_sum": self.t_out_sum,
                "bin_edges_s": list(_HIST_EDGES_S), "bins": list(self.bins)}


def enumerate_variant_space(stream_cfg, max_segment_frames: int, *,
                            mesh_segments: int = 1,
                            formulation: str = "matmul") -> dict:
    """Statically enumerate the dispatcher's compiled-variant space.

    Every sweep the dispatcher can stage has its entry shapes determined
    by exactly two numbers: the padded S bucket and the frame capacity.
    This reproduces the dispatcher's own bucket arithmetic (shard
    rounding for the sharded backend, `bucket_capacity` padding) as a
    pure function of config, so `repro.analysis`'s recompilation audit
    can verify the |S buckets| x |capacities| jit-cache bound without
    constructing an engine. Returns `{"s_buckets", "capacities",
    "variants", "backend"}` with `variants` the full (s_bucket, capacity)
    product and `backend` the cost-table backend axis value
    (`cost_table.backend_name`) the dispatcher would key these variants
    under — "batched+kernel" etc. for the non-default formulations.
    """
    from repro.profiling.cost_table import backend_name
    from repro.core.pipeline import bucket_capacity

    if max_segment_frames <= 0:
        raise ValueError("max_segment_frames must be positive")
    if stream_cfg.sweep == "sharded":
        n = max(1, int(mesh_segments))
        # must mirror SweepDispatcher.__init__'s shard rounding exactly
        s_buckets = tuple(sorted({-(-b // n) * n
                                  for b in stream_cfg.segment_buckets}))
    else:
        s_buckets = tuple(stream_cfg.segment_buckets)
    capacities = tuple(sorted({bucket_capacity(f)
                               for f in range(1, max_segment_frames + 1)}))
    variants = tuple((s, c) for s in s_buckets for c in capacities)
    return {"s_buckets": s_buckets, "capacities": capacities,
            "variants": variants,
            "backend": backend_name(stream_cfg.sweep, formulation)}


class _InFlight(NamedTuple):
    """One dispatched sweep: real segments + async device results.

    `owners[k]` is the session that owns `segs[k]` (rows of one sweep may
    belong to different sessions). `owners=None` — e.g. an entry staged
    by test stubs predating the session split — routes every row to the
    dispatcher's default (first-registered) session on harvest.
    """

    segs: list[tuple[int, int]]  # real (unpadded) segments, global indices
    ref_R: Array  # (S, 3, 3) including padded rows
    ref_t: Array  # (S, 3)
    dsis: Array
    dms: DepthMap
    pcs: PointCloud
    owners: tuple | None = None  # per-row owning sessions
    key: VariantKey | None = None  # compiled-variant identity of the sweep
    dispatched_t: float = 0.0  # host perf_counter at dispatch
    unshadowed: bool = False  # dispatched onto an otherwise idle device


class SweepDispatcher:
    """Shared segment-sweep scheduler for N streaming sessions.

    Construction mirrors the single-stream engine: the sharded backend
    rounds every S bucket up to a multiple of the mesh's segment-axis
    size so dispatch shapes stay shard-stable; the batched backend
    rejects a stray `mesh=`. `cam`, `dsi_cfg`, `opts` and `stream_cfg`
    are shared by every session on the dispatcher — one compiled sweep
    program per (S bucket, capacity) serves them all, which is exactly
    what makes cross-stream coalescing possible.
    """

    def __init__(self, cam: CameraModel, dsi_cfg: DSIConfig,
                 opts: EMVSOptions = EMVSOptions(),
                 stream_cfg=None, *, mesh=None, cost_model=None,
                 profiler=None):
        if stream_cfg is None:
            from repro.serving.emvs_stream import StreamConfig

            stream_cfg = StreamConfig()
        self.cam = cam
        self.dsi_cfg = dsi_cfg
        if getattr(stream_cfg, "kernel_interpret", None) is not None:
            # serving-level interpret/compiled override for the fused
            # kernel formulation; EMVSOptions stays the single source the
            # sweep body reads (and jit keys on — both are static/hashable)
            import dataclasses as _dc

            opts = _dc.replace(opts, kernel_interpret=stream_cfg.kernel_interpret)
        self.opts = opts
        self.stream_cfg = stream_cfg
        if stream_cfg.sweep == "sharded":
            from repro.distributed.emvs import (
                make_segment_mesh,
                segment_axis_size,
            )

            self.mesh = mesh if mesh is not None else make_segment_mesh()
            n = segment_axis_size(self.mesh)
            # shard-stable S buckets: every dispatch's segment axis must
            # divide the mesh, so round each bucket up to a multiple of n
            # (deduplicated, still ascending — the compiled-variant bound
            # only shrinks).
            self._segment_buckets = tuple(sorted(
                {-(-b // n) * n for b in stream_cfg.segment_buckets}))
        else:
            if mesh is not None:
                raise ValueError(
                    "mesh= is only meaningful with "
                    "StreamConfig(sweep='sharded'); the batched sweep "
                    "would silently ignore it")
            self.mesh = None
            self._segment_buckets = stream_cfg.segment_buckets
        # Cost-aware planning (docs/dispatch_planning.md): the planner
        # owns the partition rules; `cost_model` (duck-typed:
        # predict_sweep_s(key) -> float | None) lets the SLO-aware
        # adaptive policy predict queue-drain time, `profiler` (a
        # repro.profiling.SweepProfiler) opts into online cost-table
        # recording + dispatch-trace capture. Both default off — the
        # scheduler is then bitwise-identical to the pre-cost-model
        # engine.
        self.cost_model = cost_model
        self.profiler = profiler
        self.planner = DispatchPlanner(
            self._segment_buckets, cost_model=cost_model,
            variant_of=self._variant_key,
            shape=lambda sess, seg: sess.map_shape(seg))
        self._sessions: list = []  # registration = round-robin order
        self._rr_cursor = 0
        self.default_owner = None  # harvest target for untagged in-flight
        # tagged coalescing queue: (session, (start, end)) in arrival order
        self._pending: list = []
        self._inflight: deque[_InFlight] = deque()
        # Counter invariants (asserted by tests/test_adaptive_dispatch.py
        # via the N=1 engine): segments == sum of dispatched group sizes;
        # coalesced_segments counts segments that left in a group of >= 2,
        # so segments == coalesced_segments + (dispatches -
        # coalesced_dispatches); pending_segments is the live tagged-queue
        # depth (0 after all sessions flush), max_pending its high-water
        # mark; cross_stream_dispatches counts groups whose rows span more
        # than one session — the coalescing the multi-tenant benchmark
        # gates on.
        # slo_dispatches / slo_holds count the SLO-aware adaptive
        # policy's decisions (0 unless target_latency_s + a cost model
        # are both active); queue_wait_s / sweep_time_s are _LatencyHist
        # snapshots (enqueue->dispatch per segment, dispatch->harvest
        # per sweep) refreshed on every observation. sweep_time_s is
        # wall time on the host clock: it includes queueing behind older
        # sweeps and the lag until a poll harvests; the sweep's device
        # time comes from a profiler trace. backpressure_harvests counts
        # the harvests that blocked because every in-flight slot was
        # full (in _dispatch and make_room), i.e. dispatches that stalled
        # the pushing client. rig_holds / rig_hold_total_s /
        # rig_frames_dropped sum the rigs' own counters (RigSession).
        self._queue_wait_hist = _LatencyHist()
        self._sweep_time_hist = _LatencyHist()
        self._session_wait_hists: dict[int, _LatencyHist] = {}
        self._enqueued_t: dict[tuple[int, tuple[int, int]], float] = {}
        self.stats = {"segments": 0, "dispatches": 0, "padded_segments": 0,
                      "pending_segments": 0, "max_pending": 0,
                      "coalesced_dispatches": 0, "coalesced_segments": 0,
                      "cross_stream_dispatches": 0,
                      "slo_dispatches": 0, "slo_holds": 0,
                      "backpressure_harvests": 0,
                      "rig_holds": 0, "rig_hold_total_s": 0.0,
                      "rig_frames_dropped": 0,
                      "queue_wait_s": self._queue_wait_hist.snapshot(),
                      "sweep_time_s": self._sweep_time_hist.snapshot()}

    def _variant_key(self, s_bucket: int, capacity: int) -> VariantKey:
        """The compiled-variant identity of a padded dispatch shape —
        the cost table's key axes (repro.profiling.cost_table).

        The backend axis folds in the voting formulation
        (`backend_name`): "batched" is the default matmul program,
        "batched+kernel" the fused Pallas sweep, etc. — distinct compiled
        programs with very different costs, so the DispatchPlanner must
        price them separately."""
        from repro.profiling.cost_table import backend_name

        return VariantKey(
            s_bucket=s_bucket, capacity=capacity,
            backend=backend_name(self.stream_cfg.sweep,
                                 self.opts.formulation),
            interpolation=self.opts.voting,
            quantized=self.opts.quantized)

    # --- session plumbing -------------------------------------------------

    def register(self, session) -> None:
        self._sessions.append(session)
        if self.default_owner is None:
            self.default_owner = session
        # per-session queue-wait histogram, mirrored into session stats
        hist = _LatencyHist()
        self._session_wait_hists[id(session)] = hist
        session.stats["queue_wait_s"] = hist.snapshot()

    def enqueue(self, session, closed: list[tuple[int, int]]) -> None:
        """Append one session's newly closed segments to the tagged queue
        (arrival order; they dispatch on the next pump/drain)."""
        t = perf_counter()
        for seg in closed:
            self._enqueued_t[(id(session), seg)] = t
            if self.profiler is not None:
                self.profiler.note_enqueue(t, session, seg)
        self._pending.extend((session, seg) for seg in closed)
        self._note_queue_depth()

    def _note_queue_depth(self) -> None:
        d = len(self._pending)
        self.stats["pending_segments"] = d
        self.stats["max_pending"] = max(self.stats["max_pending"], d)

    def _oldest_pending(self, session) -> tuple[int, int] | None:
        # per-session FIFO holds in the tagged queue, so a session's first
        # occurrence is its oldest queued segment
        for sess, seg in self._pending:
            if sess is session:
                return seg
        return None

    def _evict_all(self) -> None:
        # each session's retention window must cover its segments still
        # waiting in the shared queue, not just its planner's open
        # segment: a queued group references frames the planner already
        # moved past
        for sess in self._sessions:
            sess.evict_behind(self._oldest_pending(sess))

    def make_room(self, session, blocking: bool) -> bool:
        """Free retained frame-store bytes for `session`'s budget admission.

        Returns True when progress was made (bytes freed, or queued work
        dispatched so the next eviction can free them), False when no
        more room can be made — without blocking when `blocking` is
        False, or at all when True (everything dispatchable is
        dispatched and the store already sits at its retention floor:
        the planner's open segment, which may never be evicted — the
        PR 5 bug class this floor exists to prevent).

        Order of escalation: harvest device-completed sweeps and evict
        behind the floor (free); then dispatch the session's queued
        segments — dispatch stages its rows immediately, so each
        dispatched group RAISES the session's eviction floor past its
        segments; when the in-flight queue is full, dispatching means
        block-harvesting the oldest sweep first, which only the "stall"
        policy (blocking=True) may do."""
        before = session._store.live_bytes
        self._harvest_ready()
        self._evict_all()
        if session._store.live_bytes < before:
            return True
        while True:
            if len(self._inflight) >= self.stream_cfg.max_inflight:
                self._harvest_ready()  # a sweep may have completed by now
            if len(self._inflight) >= self.stream_cfg.max_inflight:
                # dispatching now would hit _dispatch's blocking
                # back-pressure on the oldest in-flight sweep
                if not blocking:
                    return False
                self._block_for_slot()
            group = self._pop_group(final=True, only=session)
            if group is None:
                return False
            self._dispatch(*group)
            self._note_queue_depth()
            self._evict_all()
            if session._store.live_bytes < before:
                return True
            # dispatched but nothing freed yet (the floor is still
            # pinned by further queued segments): keep dispatching

    # --- dispatch (double-buffered, policy- and fairness-scheduled) -------

    def pump(self) -> None:
        """One scheduler turn: harvest device-completed sweeps (routing
        results to their owning sessions), drain the tagged queue per the
        dispatch policy and fairness anchor rule, harvest again, evict."""
        self._harvest_ready()
        self._drain(final=False)
        self._harvest_ready()
        self._evict_all()

    def drain_session(self, session) -> None:
        """End of one session's stream: dispatch every queued segment of
        `session` (same-capacity segments of other sessions ride along),
        then block until all sweeps carrying its rows have harvested.
        Other sessions' queued work stays put."""
        while True:
            group = self._pop_group(final=True, only=session)
            if group is None:
                break
            self._dispatch(*group)
            self._note_queue_depth()
        self._evict_all()
        while any(inf.owners is None or session in inf.owners
                  for inf in self._inflight):
            self._harvest(self._inflight.popleft(), block=True)

    def _drain(self, final: bool) -> None:
        """Dispatch groups while the policy allows. With `final` every
        policy drains the whole queue — back-pressure blocking in
        `_dispatch` paces the device."""
        while self._pending:
            if not final:
                # harvest completed sweeps first: results surface sooner
                # and the freed slots un-deepen the in-flight queue the
                # adaptive policy reads
                self._harvest_ready()
            group = self._pop_group(final)
            if group is None:
                break
            self._dispatch(*group)
            self._note_queue_depth()
        self._evict_all()

    def _anchor_candidates(self, only) -> list:
        """Sessions eligible to anchor the next group, in try order."""
        if only is not None:
            return [only]
        if self.stream_cfg.fairness == "fifo" or len(self._sessions) == 1:
            # strict arrival order: only the global queue head ever anchors
            return [self._pending[0][0]]
        # round_robin: rotate over registered sessions, skipping those
        # with nothing queued; trying each once per turn means a session
        # whose anchored group is policy-held (unsealed throughput group)
        # does not head-of-line block a neighbor with a dispatchable one
        present = {id(sess) for sess, _ in self._pending}
        n = len(self._sessions)
        return [self._sessions[(self._rr_cursor + k) % n] for k in range(n)
                if id(self._sessions[(self._rr_cursor + k) % n]) in present]

    def _pop_group(self, final: bool, only=None):
        """Pop the next dispatchable group off the tagged queue, or None
        when the policy says to keep coalescing. Anchors follow the
        fairness rule; each anchored group obeys per-stream FIFO, so a
        session's results release in its segment-close order under every
        policy and fairness setting."""
        if not self._pending:
            return None
        policy = self.stream_cfg.dispatch_policy
        # SLO mode (docs/dispatch_planning.md): with a deadline AND a
        # cost model that can price the whole queue, the adaptive policy
        # schedules against predicted drain time instead of in-flight
        # depth — dispatch now iff draining everything (in-flight sweeps
        # + the planned partition of the pending queue) is predicted to
        # blow the deadline, else keep coalescing. `slo_urgent is None`
        # means SLO inactive (no deadline, null model, or an
        # out-of-distribution variant): fall back to the depth rule, so
        # the schedule is bitwise-identical to the pre-SLO engine.
        slo_urgent = None
        if policy == "adaptive" and not final:
            if self.stream_cfg.target_latency_s is not None:
                drain = self.predict_drain_s()
                if drain is not None:
                    slo_urgent = drain > self.stream_cfg.target_latency_s
            if (slo_urgent is None
                    and len(self._inflight) >= self.stream_cfg.max_inflight):
                return None  # device saturated: coalesce until a slot frees
        for sess in self._anchor_candidates(only):
            if only is not None and self._oldest_pending(sess) is None:
                return None  # the drained session has nothing queued
            anchor = next(i for i, (s, _) in enumerate(self._pending)
                          if s is sess)
            idx, cap, sealed = self.planner.head_tagged(
                self._pending, anchor=anchor)
            if policy == "latency":
                idx = idx[:1]  # one sweep per segment — the baseline
            elif policy == "throughput" and not (final or sealed):
                continue  # this anchor's group can still grow: try the next
            elif slo_urgent is not None and not (slo_urgent or sealed):
                # SLO slack and the group can still grow: hold it (a
                # sealed group gains nothing by waiting, so it goes)
                continue
            group = [self._pending[i] for i in idx]
            for i in reversed(idx):
                self._pending.pop(i)
            if self._sessions:
                # fairness bookkeeping: the dispatched session goes to the
                # back of the rotation
                try:
                    self._rr_cursor = ((self._sessions.index(sess) + 1)
                                       % len(self._sessions))
                except ValueError:
                    pass
            if slo_urgent:
                self.stats["slo_dispatches"] += 1
            return group, cap
        if slo_urgent is False:
            self.stats["slo_holds"] += 1
        return None

    def predict_drain_s(self) -> float | None:
        """Predicted serial time to complete every in-flight sweep and
        drain the whole pending queue under the cost model. In-flight
        sweeps count at full predicted cost (their progress is not
        observable without a device sync — the estimate is deliberately
        conservative). None when any component is unpredictable."""
        if self.cost_model is None:
            return None
        total = 0.0
        for inf in self._inflight:
            if inf.key is None:
                return None
            cost = self.cost_model.predict_sweep_s(inf.key)
            if cost is None:
                return None
            total += cost
        pending = self.planner.predict_drain_s(
            self._pending, fairness=self.stream_cfg.fairness)
        if pending is None:
            return None
        return total + pending

    def _s_bucket(self, n: int) -> int:
        for b in self._segment_buckets:
            if b >= n:
                return b
        raise AssertionError(f"group of {n} exceeds top segment bucket")

    def variant_space(self, max_segment_frames: int) -> dict:
        """The live dispatcher's compiled-variant space (see
        `enumerate_variant_space`), using the actual mesh segment-axis
        size when the sharded backend is active."""
        if self.mesh is not None:
            from repro.distributed.emvs import segment_axis_size
            mesh_segments = segment_axis_size(self.mesh)
        else:
            mesh_segments = 1
        return enumerate_variant_space(self.stream_cfg, max_segment_frames,
                                       mesh_segments=mesh_segments)

    def _sweep(self, batch) -> tuple[Array, DepthMap]:
        if self.stream_cfg.sweep == "sharded":
            from repro.distributed.emvs import process_segments_sharded

            return process_segments_sharded(self.cam, self.dsi_cfg, batch,
                                            self.opts, mesh=self.mesh)
        return process_segments_batched(self.cam, self.dsi_cfg, batch,
                                        self.opts)

    def _dispatch(self, group, cap: int) -> None:
        """Stage and asynchronously dispatch one tagged group: gather each
        row from its owning session's frame store, pad the segment axis to
        the smallest fitting S bucket, enqueue the sweep."""
        # groups are only formed from non-empty closed-segment runs, so an
        # empty dispatch is a planner/grouping bug, not a stream condition
        # — and pad_segment_rows would reject it anyway.
        assert group, "_dispatch requires at least one closed segment"
        with jax.profiler.TraceAnnotation("emvs.dispatch"):
            s_pad = self._s_bucket(len(group))
            # padded rows repeat the last real segment: the sweep body is
            # per-segment independent, so they are pure discarded work
            padded = list(group) + [group[-1]] * (s_pad - len(group))
            with jax.profiler.TraceAnnotation("emvs.stage"):
                # one row per camera of each map; a group shares its rows
                # per map (DispatchPlanner's shape)
                maps = [sess.stage(seg) for sess, seg in padded]
                batch = (pad_rig_rows(maps, cap) if len(maps[0]) > 1 else
                         pad_segment_rows([rows[0] for rows in maps], cap))
            # async dispatch: both calls below return with the sweep
            # enqueued, so the caller stages the next batch while this
            # one votes
            unshadowed = not self._inflight  # nothing older on the device
            t_disp = perf_counter()
            key = self._variant_key(s_pad, cap)
            for sess, seg in group:
                t_enq = self._enqueued_t.pop((id(sess), seg), None)
                if t_enq is not None:
                    self._queue_wait_hist.observe(t_enq, t_disp)
                    sess_hist = self._session_wait_hists.get(id(sess))
                    if sess_hist is not None:
                        sess_hist.observe(t_enq, t_disp)
                        sess.stats["queue_wait_s"] = sess_hist.snapshot()
            self.stats["queue_wait_s"] = self._queue_wait_hist.snapshot()
            if self.profiler is not None:
                self.profiler.note_dispatch(t_disp, group, key)
            with jax.profiler.TraceAnnotation("emvs.launch"):
                dsis, dms = self._sweep(batch)
                pcs = depth_maps_to_points(self.cam, dms,
                                           SE3(batch.ref_R, batch.ref_t))
            self._inflight.append(_InFlight(
                [seg for _, seg in group], batch.ref_R, batch.ref_t, dsis,
                dms, pcs, owners=tuple(sess for sess, _ in group), key=key,
                dispatched_t=t_disp, unshadowed=unshadowed))
            self.stats["segments"] += len(group)
            self.stats["dispatches"] += 1
            self.stats["padded_segments"] += s_pad - len(group)
            if len(group) > 1:
                self.stats["coalesced_dispatches"] += 1
                self.stats["coalesced_segments"] += len(group)
            if len({id(sess) for sess, _ in group}) > 1:
                self.stats["cross_stream_dispatches"] += 1
            for sess, _ in group:
                sess.stats["segments"] += 1
            while len(self._inflight) > self.stream_cfg.max_inflight:
                # back-pressure: block on the oldest sweep; its results
                # are routed for the owning sessions' next poll
                self._block_for_slot()

    def _block_for_slot(self) -> None:
        """Back-pressure: every in-flight slot is taken, so block on the
        oldest sweep and harvest it."""
        with jax.profiler.TraceAnnotation("emvs.backpressure"):
            self.stats["backpressure_harvests"] += 1
            self._harvest(self._inflight.popleft(), block=True)

    # --- harvest ----------------------------------------------------------

    def _harvest_ready(self) -> None:
        """Pop and harvest every device-completed sweep at the head of the
        in-flight queue (non-blocking, dispatch order)."""
        while self._inflight and self._inflight[0].dms.depth.is_ready():
            self._harvest(self._inflight.popleft(), block=False)

    def _harvest(self, inf: _InFlight, block: bool) -> None:
        with jax.profiler.TraceAnnotation("emvs.harvest"):
            with jax.profiler.TraceAnnotation("emvs.harvest.sync"):
                if block:
                    inf.dms.depth.block_until_ready()
                t_harv = perf_counter()
                # per-segment fraction of DSI voxels at the int16 store
                # limits, feeding the owning session's
                # "dsi_saturation_peak" monitor (the live check of the
                # paper's "16 bits never saturate" claim). Computed on
                # results that are already device-complete, so this adds
                # one tiny reduction, not a per-chunk round-trip.
                sats = [float(dsi_lib.store_saturation_fraction(inf.dsis[k]))
                        for k in range(len(inf.segs))]
            if inf.key is not None:
                self._sweep_time_hist.observe(inf.dispatched_t, t_harv)
                self.stats["sweep_time_s"] = self._sweep_time_hist.snapshot()
                if self.profiler is not None:
                    self.profiler.note_harvest(
                        inf.key, inf.dispatched_t, t_harv,
                        unshadowed=inf.unshadowed)
            owners = inf.owners
            if owners is None:
                owners = (self.default_owner,) * len(inf.segs)
            for k, ((start, end), sess) in enumerate(zip(inf.segs, owners)):
                sess.stats["dsi_saturation_peak"] = max(
                    sess.stats.get("dsi_saturation_peak", 0.0), sats[k])
                dm = DepthMap(inf.dms.depth[k], inf.dms.mask[k],
                              inf.dms.confidence[k])
                res = SegmentResult(dm, inf.dsis[k],
                                    SE3(inf.ref_R[k], inf.ref_t[k]),
                                    (start, end))
                pc = PointCloud(inf.pcs.points[k], inf.pcs.weights[k],
                                inf.pcs.valid[k])
                sess._deliver(res, pc)
