"""Drives one cell: the served EMVS path under open-loop camera traffic.

One `MultiStreamEngine` serves every camera of the cell. A single
client thread pushes each camera's packets when they are due
(`StreamSession.push`) and polls the engine (`MultiStreamEngine.poll`)
while it waits; a depth map counts as emitted once its `SegmentResult`
has been returned and its depth and mask are on the host. Set-up
(traffic, the reference's segmentation, every program the cell's traffic
uses, and a warm-up stretch of the same traffic) ends when the window
opens; the window then runs for `seconds` of wall time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

from harness import reference as ref
from harness.traffic import Camera, Packetizer, make_cameras

POLL_IDLE_S = 0.002  # poll the engine this often while waiting for packets
POLL_BUSY_S = 0.005  # ... and at least this often while pushing late ones
SLEEP_S = 0.0005
# recorded around every compile-or-load-from-cache of a program
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, with times."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t < b)


@dataclasses.dataclass
class Emitted:
    cam: int
    frames: tuple[int, int]
    t_emit: float  # stream time on the client's clock
    latency: float
    depth: np.ndarray
    mask: np.ndarray
    result: object  # the SegmentResult (its DSI stays on the device)


@dataclasses.dataclass
class Spans:
    """The client thread's own spans: what it was doing, and when."""

    annotate: bool
    rows: list[tuple[str, float, float]] = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.rows.append((name, t0, time.perf_counter()))


def build_program(config: dict):
    """The system under test, configured as the cell's file states."""
    from repro.core.camera import CameraModel
    from repro.core.dsi import DSIConfig
    from repro.core.pipeline import EMVSOptions
    from repro.serving.emvs_stream import MultiStreamEngine, StreamConfig

    s, d, e, st = (config["sensor"], config["dsi"], config["emvs"],
                   config["stream"])
    cam = CameraModel(width=s["width"], height=s["height"], fx=s["fx"],
                      fy=s["fy"], cx=s["cx"], cy=s["cy"])
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=d["num_planes"],
                                   z_min=d["z_min"], z_max=d["z_max"],
                                   inverse_depth=d["inverse_depth"])
    opts = EMVSOptions(voting=e["voting"], formulation=e["formulation"],
                       quantized=e["quantized"],
                       keyframe_dist_frac=e["keyframe_dist_frac"],
                       detection_threshold_c=e["detection_threshold_c"],
                       detection_min_votes=e["detection_min_votes"],
                       median_filter=e["median_filter"])
    if st["sweep"] != "batched":
        raise ValueError(f"the benchmark drives the batched sweep, not "
                         f"{st['sweep']!r}")
    stream_cfg = StreamConfig(events_per_frame=st["events_per_frame"],
                              segment_buckets=tuple(st["segment_buckets"]),
                              max_inflight=st["max_inflight"],
                              dispatch_policy=st["dispatch_policy"],
                              hygiene=st["hygiene"], sweep=st["sweep"])
    engine = MultiStreamEngine(cam, dsi_cfg, opts, stream_cfg)
    return cam, dsi_cfg, opts, engine


@dataclasses.dataclass
class Plan:
    """The reference's view of each camera's stream over the run."""

    positions: list[np.ndarray]  # per camera: frame centres (F, 3) float32
    segments: list[list[tuple[int, int]]]  # per camera: closed segments
    last_due: list[np.ndarray]  # per camera: client time of each segment's last event


def plan(cameras: list[Camera], mix: dict, setup: ref.Setup,
         until_s: float) -> Plan:
    e = setup.events_per_frame
    positions, segments, last_due = [], [], []
    for cam in cameras:
        first = np.arange(cam.index_at(until_s) // e) * e
        # a frame's events are in time order, so its median timestamp is
        # the mean of its two middle ones
        mid = ref.middle_mean(cam.times_at(first + e // 2 - 1),
                              cam.times_at(first + e // 2))
        times, _, pos = cam.pose_table(mix, until_s + 1.0)
        p = ref.interpolate_positions(times, pos, mid)
        segs = ref.key_frame_segments(p, setup)
        positions.append(p)
        segments.append(segs)
        ends = np.array([b for _, b in segs], np.int64)
        last_due.append(cam.start + cam.times_at(ends * e - 1).astype(np.float64))
    return Plan(positions, segments, last_due)


def capacity(frames: int) -> int:
    """The served path's frame-capacity bucket (multiples of 4)."""
    return max(4, -(-frames // 4) * 4)


def frames_per_push(cameras: list[Camera], mix: dict, events_per_frame: int,
                    until_s: float) -> set[int]:
    """How many frames the pushes before `until_s` complete, as a set."""
    out = set()
    for i, cam in enumerate(cameras):
        pk = Packetizer(i, cam, mix)
        p = pk.next()
        while p.due < until_s:
            out.add(p.g1 // events_per_frame - p.g0 // events_per_frame)
            p = pk.next()
    return out - {0}


def warm_programs(cam, dsi_cfg, opts, s_buckets, caps, events_per_frame,
                  traj_len: int, push_frames) -> None:
    """Compile (or load) every program the window's traffic can reach:
    each (S bucket, capacity) sweep with its point cloud and harvest
    slices, and the pose interpolation for each number of frames a push
    completes."""
    import jax
    import jax.numpy as jnp

    from repro.core import dsi as dsi_lib
    from repro.core.geometry import SE3
    from repro.core.pipeline import SegmentBatch, process_segments_batched
    from repro.core.pointcloud import depth_maps_to_points
    from repro.events.simulator import Trajectory
    from repro.events.trajectory_stream import pose_at_times

    for s in s_buckets:
        for c in sorted(caps):
            f32 = np.float32
            eye = np.broadcast_to(np.eye(3, dtype=f32), (s, c, 3, 3))
            batch = SegmentBatch(
                xy=jnp.asarray(np.zeros((s, c, events_per_frame, 2), f32)),
                valid=jnp.asarray(np.zeros((s, c, events_per_frame), f32)),
                frame_valid=jnp.asarray(np.zeros((s, c), f32)),
                poses_R=jnp.asarray(np.ascontiguousarray(eye)),
                poses_t=jnp.asarray(np.zeros((s, c, 3), f32)),
                ref_R=jnp.asarray(np.ascontiguousarray(eye[:, 0])),
                ref_t=jnp.asarray(np.zeros((s, 3), f32)))
            dsis, dms = process_segments_batched(cam, dsi_cfg, batch, opts)
            pcs = depth_maps_to_points(cam, dms, SE3(batch.ref_R, batch.ref_t))
            dms.depth.is_ready()
            for k in range(s):
                float(dsi_lib.store_saturation_fraction(dsis[k]))
                jax.block_until_ready((dms.depth[k], dms.mask[k],
                                       dms.confidence[k], dsis[k],
                                       batch.ref_R[k], batch.ref_t[k],
                                       pcs.points[k], pcs.weights[k],
                                       pcs.valid[k]))
            del dsis, dms, pcs, batch
    times = jnp.asarray(np.arange(traj_len, dtype=np.float32))
    traj = Trajectory(times, SE3(jnp.asarray(np.broadcast_to(
        np.eye(3, dtype=np.float32), (traj_len, 3, 3)).copy()),
        jnp.zeros((traj_len, 3), jnp.float32)))
    for n in sorted(push_frames):
        jax.block_until_ready(pose_at_times(traj, np.linspace(
            0.5, 1.5, n).astype(np.float32)))


def drive(config: dict, mix: dict, seed: int, seconds: float, *,
          trace_dir: str | None, devices, t_process0: float,
          log=print) -> dict:
    """Run the cell once; returns everything the result line is made of."""
    import jax

    from repro.core.geometry import SE3
    from repro.events.simulator import EventStream, Trajectory

    compiles = CompileCounter()
    setup = ref.Setup.from_config(config)
    e = setup.events_per_frame
    warm, window = float(mix["warmup_s"]), float(seconds)
    t_close = warm + window
    cameras = make_cameras(config, mix, seed)
    the_plan = plan(cameras, mix, setup, t_close + 2.0)
    caps = {capacity(b - a) for segs, due in zip(the_plan.segments,
                                                 the_plan.last_due)
            for (a, b), d in zip(segs, due) if d < t_close + 1.0}
    cam, dsi_cfg, opts, engine = build_program(config)
    sessions, trajs = [], []
    for i, c in enumerate(cameras):
        times, rot, pos = c.pose_table(mix, t_close + 5.0)
        traj = Trajectory(jax.numpy.asarray(times),
                          SE3(jax.numpy.asarray(rot), jax.numpy.asarray(pos)))
        trajs.append(traj)
        sessions.append(engine.add_session(f"cam{i}", traj=traj))
    warm_programs(cam, dsi_cfg, opts, config["stream"]["segment_buckets"],
                  caps, e, int(trajs[0].times.shape[0]),
                  frames_per_push(cameras, mix, e, t_close))
    log(f"set-up: {len(cameras)} cameras, lap {cameras[0].period:.3f} s of "
        f"{cameras[0].lap_events} events each; capacities {sorted(caps)}; "
        f"{compiles.between(0, math.inf)} programs compiled or loaded")

    spans = Spans(annotate=trace_dir is not None)
    emitted: list[Emitted] = []
    lags: list[tuple[float, float]] = []  # (due, lag) per pushed packet
    failed = 0
    packetizers = [Packetizer(i, c, mix) for i, c in enumerate(cameras)]
    nxt = [p.next() for p in packetizers]
    stats_open = None

    def take(results_by_cam):
        with spans.span("bench.fetch"):
            for i, results in results_by_cam:
                for res in results:
                    depth, mask = jax.device_get((res.depth_map.depth,
                                                  res.depth_map.mask))
                    t_emit = time.perf_counter() - wall0
                    a, b = res.frame_range
                    due = cameras[i].start + float(
                        cameras[i].times(b * e - 1, b * e)[0])
                    emitted.append(Emitted(i, (a, b), t_emit, t_emit - due,
                                           depth, mask, res))

    wall0 = time.perf_counter()
    window_open = window_close = win_ctx = None
    last_poll = -1.0
    while True:
        now = time.perf_counter() - wall0
        if window_open is None and now >= warm:
            if trace_dir is not None:
                opts_ = jax.profiler.ProfileOptions()
                opts_.python_tracer_level = 0  # the client's spans suffice
                opts_.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts_)
                win_ctx = jax.profiler.TraceAnnotation("bench.window")
                win_ctx.__enter__()
            window_open = time.perf_counter()
            stats_open = _dispatcher_stats(engine)
        if now >= t_close:
            window_close = time.perf_counter()
            break
        i = min(range(len(nxt)), key=lambda k: nxt[k].due)
        pkt = nxt[i]
        if pkt.due <= now:
            xy, t, pol, valid = cameras[i].events(pkt.g0, pkt.g1)
            with spans.span("bench.push"):
                try:
                    out = sessions[i].push(EventStream(xy=xy, t=t,
                                                       polarity=pol,
                                                       valid=valid))
                except Exception as exc:  # the engine refused the packet
                    failed += 1
                    log(f"push refused: {type(exc).__name__}: {exc}")
                    out = []
            lags.append((pkt.due, now - pkt.due))
            nxt[i] = packetizers[i].next()
            if out:
                take([(i, out)])
            if now - last_poll < POLL_BUSY_S:
                continue
        if now - last_poll >= POLL_IDLE_S or pkt.due <= now:
            with spans.span("bench.poll"):
                polled = engine.poll()
            last_poll = now
            ready = [(int(sid[3:]), r) for sid, r in polled.items() if r]
            if ready:
                take(ready)
        wait = min(nxt[i].due, t_close if window_open else warm) - (
            time.perf_counter() - wall0)
        if wait > 0:
            with spans.span("bench.wait"):
                time.sleep(min(wait, SLEEP_S))
    if win_ctx is not None:
        win_ctx.__exit__(None, None, None)
    stats_close = _dispatcher_stats(engine)
    memory = _memory_peak(devices)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {
        "cameras": cameras, "plan": the_plan, "setup": setup, "engine": engine,
        "emitted": emitted, "lags": lags, "failed": failed, "spans": spans.rows,
        "window": (warm, t_close),
        "setup_s": window_open - t_process0,
        "window_wall": (window_open, window_close),
        "stats": (stats_open, stats_close), "memory_peak_bytes": memory,
        "compiles_in_window": compiles.between(window_open, window_close),
        "compiles_total": len(compiles.times),
    }


def _dispatcher_stats(engine) -> dict:
    d = engine.dispatcher.stats
    return {"segments": d["segments"], "dispatches": d["dispatches"],
            "padded_segments": d["padded_segments"],
            "pending_segments": d["pending_segments"],
            "queue_wait_count": d["queue_wait_s"]["count"],
            "queue_wait_total_s": d["queue_wait_s"]["total_s"]}


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
