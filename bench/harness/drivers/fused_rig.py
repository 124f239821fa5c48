"""The `fused_rig` driver: a two-camera rig fused at the left camera's
key frames (Ghosh & Gallego, arXiv 2207.10494), one `RigSession` on a
`MultiStreamEngine`. There is one stream, the rig: camera 0 (left) is
cut into key-frame segments; the right frames whose median timestamps
fall in [t_L[a], t_L[b]) belong to the map of segment [a, b). Both
cameras' frames are voted into the left key frame's view, the two DSIs
are fused by the harmonic mean F = 2ab / (a + b) (0 where a * b = 0)
and detection runs once, on F. A map's events are both cameras' frames
in it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import reference as ref
from harness import work
from harness.serve import DRIVERS, load_driver
from harness.traffic import Camera

_sessions = load_driver(DRIVERS / "sessions.py")
capacity = _sessions.capacity
FUSE_OPS_PER_VOXEL = 4  # multiply, add, divide, select


@dataclasses.dataclass
class Plan:
    """The reference's view of the rig's stream over the run."""

    positions: list[np.ndarray]  # per camera: frame centres (F, 3) float32
    mids: list[np.ndarray]  # per camera: frame median timestamps (F,) float32
    segments: list[list[tuple[int, int]]]  # [the left's closed segments]
    last_due: list[np.ndarray]  # [per map: the later camera's last event]


def right_range(plan: Plan, a: int, b: int) -> tuple[int, int]:
    """The right frames [ra, rb) of the left segment [a, b): those with
    t_L[a] <= t_R < t_L[b] (no upper bound past the planned frames)."""
    t_l, t_r = plan.mids
    return (int(np.searchsorted(t_r, t_l[a], side="left")),
            int(np.searchsorted(t_r, t_l[b], side="left"))
            if b < t_l.shape[0] else t_r.shape[0])


def _last_due(cameras: list[Camera], e: int, a: int, b: int, ra: int,
              rb: int) -> float:
    left, right = cameras
    due = left.start + float(left.times(b * e - 1, b * e)[0])
    if rb > ra:
        due = max(due, right.start + float(right.times(rb * e - 1, rb * e)[0]))
    return due


def plan(cameras: list[Camera], mix: dict, setup: ref.Setup,
         until_s: float) -> Plan:
    if len(cameras) != 2:
        raise ValueError(f"a fused rig has 2 cameras, the mix has "
                         f"{len(cameras)}")
    e = setup.events_per_frame
    positions, mids = [], []
    for cam in cameras:
        first = np.arange(cam.index_at(until_s) // e) * e
        mid = ref.middle_mean(cam.times_at(first + e // 2 - 1),
                              cam.times_at(first + e // 2))
        times, _, pos = cam.pose_table(mix, until_s + 1.0)
        positions.append(ref.interpolate_positions(times, pos, mid))
        mids.append(mid)
    segs = ref.key_frame_segments(positions[0], setup)
    the_plan = Plan(positions, mids, [segs], [])
    the_plan.last_due.append(np.array(
        [_last_due(cameras, e, a, b, *right_range(the_plan, a, b))
         for a, b in segs], np.float64))
    return the_plan


def warm_programs(cam, dsi_cfg, opts, s_buckets, caps, events_per_frame,
                  traj_len: int, push_frames) -> None:
    """Compile (or load) every rig sweep the window's traffic can reach:
    each (S bucket of maps, capacity), with its point cloud, saturation
    reduction and harvest slices, and the sessions driver's pose
    interpolation."""
    import jax
    import jax.numpy as jnp

    from repro.core import dsi as dsi_lib
    from repro.core.geometry import SE3
    from repro.core.pipeline import SegmentBatch, process_segments_batched
    from repro.core.pointcloud import depth_maps_to_points

    f32 = np.float32
    for s in s_buckets:
        for c in sorted(caps):
            eye = np.broadcast_to(np.eye(3, dtype=f32), (s, 2, c, 3, 3))
            batch = SegmentBatch(
                xy=jnp.asarray(np.zeros((s, 2, c, events_per_frame, 2), f32)),
                valid=jnp.asarray(np.zeros((s, 2, c, events_per_frame), f32)),
                frame_valid=jnp.asarray(np.zeros((s, 2, c), f32)),
                poses_R=jnp.asarray(np.ascontiguousarray(eye)),
                poses_t=jnp.asarray(np.zeros((s, 2, c, 3), f32)),
                ref_R=jnp.asarray(np.ascontiguousarray(eye[:, 0, 0])),
                ref_t=jnp.asarray(np.zeros((s, 3), f32)))
            dsis, dms = process_segments_batched(cam, dsi_cfg, batch, opts)
            pcs = depth_maps_to_points(cam, dms,
                                       SE3(batch.ref_R, batch.ref_t))
            dms.depth.is_ready()
            for k in range(s):
                float(dsi_lib.store_saturation_fraction(dsis[k]))
                jax.block_until_ready((dms.depth[k], dms.mask[k],
                                       dms.confidence[k], dsis[k],
                                       batch.ref_R[k], batch.ref_t[k],
                                       pcs.points[k], pcs.weights[k],
                                       pcs.valid[k]))
            del dsis, dms, pcs, batch
    _sessions.warm_programs(cam, dsi_cfg, opts, (), (), events_per_frame,
                            traj_len, push_frames)


class Served:
    """The engine with one rig (`rig`): camera 0 left, camera 1 right."""

    def __init__(self, engine, rig, cameras: list[Camera], the_plan: Plan,
                 events_per_frame: int):
        import jax

        from repro.events.simulator import EventStream

        self.engine, self.rig, self.cameras = engine, rig, cameras
        self.plan, self.events_per_frame = the_plan, events_per_frame
        self._device_get, self._event_stream = jax.device_get, EventStream

    def push(self, cam: int, xy, t, polarity, valid) -> list:
        out = self.rig.push(self._event_stream(
            xy=xy, t=t, polarity=polarity, valid=valid), camera=cam)
        return [(0, r) for r in out]

    def poll(self) -> list:
        return [(0, r) for rs in self.engine.poll().values() for r in rs]

    def fetch(self, stream: int, result):
        depth, mask = self._device_get((result.depth_map.depth,
                                        result.depth_map.mask))
        a, b = result.frame_range
        ra, rb = right_range(self.plan, a, b)
        e = self.events_per_frame
        due = _last_due(self.cameras, e, a, b, ra, rb)
        return (a, b), (b - a + rb - ra) * e, due, depth, mask

    def dsi(self, result) -> np.ndarray:
        """The fused float32 DSI."""
        return np.asarray(result.dsi)

    def stats(self) -> dict:
        d = self.engine.dispatcher.stats
        return {"segments": d["segments"], "dispatches": d["dispatches"],
                "padded_segments": d["padded_segments"],
                "pending_segments": d["pending_segments"],
                "queue_wait_count": d["queue_wait_s"]["count"],
                "queue_wait_total_s": d["queue_wait_s"]["total_s"],
                "rig_holds": d["rig_holds"],
                "rig_hold_total_s": d["rig_hold_total_s"],
                "rig_frames_dropped": d["rig_frames_dropped"]}


def serve(config: dict, mix: dict, cameras: list[Camera], the_plan: Plan,
          until_s: float, log) -> Served:
    import jax

    from repro.core.geometry import SE3
    from repro.events.simulator import Trajectory

    if config.get("fusion") != {"function": "harmonic_mean",
                                "reference_camera": 0}:
        raise ValueError(f"the fused_rig driver fuses by the harmonic mean "
                         f"at camera 0, not {config.get('fusion')!r}")
    e = config["stream"]["events_per_frame"]
    caps = {capacity(max(b - a, rb - ra))
            for (a, b), d in zip(the_plan.segments[0], the_plan.last_due[0])
            if d < until_s + 1.0
            for ra, rb in [right_range(the_plan, a, b)]}
    cam, dsi_cfg, opts, engine = _sessions.build_program(config)
    trajs = []
    for c in cameras:
        times, rot, pos = c.pose_table(mix, until_s + 5.0)
        trajs.append(Trajectory(jax.numpy.asarray(times),
                                SE3(jax.numpy.asarray(rot),
                                    jax.numpy.asarray(pos))))
    rig = engine.add_rig("rig", trajs=trajs)
    warm_programs(cam, dsi_cfg, opts, config["stream"]["segment_buckets"],
                  caps, e, int(trajs[0].times.shape[0]),
                  _sessions.frames_per_push(cameras, mix, e, until_s))
    log(f"fused rig: one map per left key frame; capacities {sorted(caps)}")
    return Served(engine, rig, cameras, the_plan, e)


def reference_inputs(setup: ref.Setup, cameras: list[Camera], the_plan: Plan,
                     m) -> tuple[np.ndarray, ...]:
    """Both cameras' frames of events (F, E, 2) and centres (F, 3) in the
    map, from the raw traffic: left, then right."""
    a, b = m.frames
    ra, rb = right_range(the_plan, a, b)
    e = setup.events_per_frame
    out = []
    for k, (lo, hi) in enumerate(((a, b), (ra, rb))):
        xy = cameras[k].events(lo * e, hi * e)[0]
        out += [xy.reshape(hi - lo, e, 2), the_plan.positions[k][lo:hi]]
    return tuple(out)


def fuse(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The harmonic mean of two vote counts, 2ab / (a + b), divided in
    float64 and rounded once to float32; 0 where either is 0."""
    a, b = a.astype(np.float64), b.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where((a > 0) & (b > 0), 2 * a * b / (a + b),
                        0).astype(ref.F32)


def reference(setup: ref.Setup, xy_left, pos_left, xy_right, pos_right, *,
              lowp=False):
    """Both cameras voted into the left key frame's view, fused, detected."""
    ref_pos = pos_left[0]
    left = ref.segment_dsi(setup, xy_left, pos_left, ref_pos=ref_pos,
                           lowp=lowp)
    right = ref.segment_dsi(setup, xy_right, pos_right, ref_pos=ref_pos,
                            lowp=lowp)
    fused = fuse(left, right)
    depth, mask = ref.detect(setup, fused)
    return fused, depth, mask


def map_work(config: dict, m) -> tuple[float, float]:
    """Both cameras' events, frames and planes, the fusion's per-voxel
    operations, and one float32 fused DSI and one set of maps written."""
    e = config["stream"]["events_per_frame"]
    n_left = m.frames[1] - m.frames[0]
    n_right = m.events // e - n_left
    d, s = config["dsi"], config["sensor"]
    shape = (d["num_planes"], s["height"], s["width"])
    (ops_l, bytes_l), (ops_r, bytes_r), (ops_0, bytes_0) = (
        work.segment_work(n, e, *shape, quantized=False)
        for n in (n_left, n_right, 0))
    voxels = shape[0] * shape[1] * shape[2]
    return (ops_l + ops_r - ops_0 + FUSE_OPS_PER_VOXEL * voxels,
            bytes_l + bytes_r - bytes_0)
