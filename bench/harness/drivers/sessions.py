"""The `sessions` driver: one `StreamSession` per camera on one
`MultiStreamEngine`. Stream `i` is camera `i`; its frames are cut into
key-frame segments of its own, and each map is voted into that camera's
view at its segment's first frame.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import reference as ref
from harness import work
from harness.traffic import Camera, Packetizer


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


class Served:
    """The engine with one session per camera (`cam<i>`)."""

    def __init__(self, engine, sessions, cameras: list[Camera],
                 events_per_frame: int):
        import jax

        from repro.events.simulator import EventStream

        self.engine, self.sessions, self.cameras = engine, sessions, cameras
        self.events_per_frame = events_per_frame
        self._device_get, self._event_stream = jax.device_get, EventStream

    def push(self, cam: int, xy, t, polarity, valid) -> list:
        out = self.sessions[cam].push(self._event_stream(
            xy=xy, t=t, polarity=polarity, valid=valid))
        return [(cam, r) for r in out]

    def poll(self) -> list:
        return [(int(sid[3:]), r) for sid, rs in self.engine.poll().items()
                for r in rs]

    def fetch(self, stream: int, result):
        depth, mask = self._device_get((result.depth_map.depth,
                                        result.depth_map.mask))
        a, b = result.frame_range
        e, cam = self.events_per_frame, self.cameras[stream]
        due = cam.start + float(cam.times(b * e - 1, b * e)[0])
        return (a, b), (b - a) * e, due, depth, mask

    def dsi(self, result) -> np.ndarray:
        return np.asarray(result.dsi)

    def stats(self) -> dict:
        d = self.engine.dispatcher.stats
        return {"segments": d["segments"], "dispatches": d["dispatches"],
                "padded_segments": d["padded_segments"],
                "pending_segments": d["pending_segments"],
                "queue_wait_count": d["queue_wait_s"]["count"],
                "queue_wait_total_s": d["queue_wait_s"]["total_s"]}


def serve(config: dict, mix: dict, cameras: list[Camera], the_plan: Plan,
          until_s: float, log) -> Served:
    import jax

    from repro.core.geometry import SE3
    from repro.events.simulator import Trajectory

    e = config["stream"]["events_per_frame"]
    caps = {capacity(b - a) for segs, due in zip(the_plan.segments,
                                                 the_plan.last_due)
            for (a, b), d in zip(segs, due) if d < until_s + 1.0}
    cam, dsi_cfg, opts, engine = build_program(config)
    sessions, trajs = [], []
    for i, c in enumerate(cameras):
        times, rot, pos = c.pose_table(mix, until_s + 5.0)
        traj = Trajectory(jax.numpy.asarray(times),
                          SE3(jax.numpy.asarray(rot), jax.numpy.asarray(pos)))
        trajs.append(traj)
        sessions.append(engine.add_session(f"cam{i}", traj=traj))
    warm_programs(cam, dsi_cfg, opts, config["stream"]["segment_buckets"],
                  caps, e, int(trajs[0].times.shape[0]),
                  frames_per_push(cameras, mix, e, until_s))
    log(f"sessions: one per camera; capacities {sorted(caps)}")
    return Served(engine, sessions, cameras, e)


def reference_inputs(setup: ref.Setup, cameras: list[Camera], the_plan: Plan,
                     m) -> tuple[np.ndarray, np.ndarray]:
    """The map's frames of events (F, E, 2) and their centres (F, 3),
    from its camera's raw traffic."""
    a, b = m.frames
    e = setup.events_per_frame
    xy = cameras[m.stream].events(a * e, b * e)[0]
    return xy.reshape(b - a, e, 2), the_plan.positions[m.stream][a:b]


def reference(setup: ref.Setup, xy_frames, pos_frames, *, lowp=False):
    dsi = ref.segment_dsi(setup, xy_frames, pos_frames, lowp=lowp)
    depth, mask = ref.detect(setup, dsi)
    return dsi, depth, mask


def map_work(config: dict, m) -> tuple[float, float]:
    d = config["dsi"]
    return work.segment_work(m.frames[1] - m.frames[0],
                             config["stream"]["events_per_frame"],
                             d["num_planes"], config["sensor"]["height"],
                             config["sensor"]["width"],
                             config["emvs"]["quantized"])
