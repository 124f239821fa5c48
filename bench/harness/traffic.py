"""The one traffic generator: event cameras on a closed path, packetised.

A traffic mix is a data file (`bench/traffic/<mix>.json`) of parameters;
this module turns it, with the configuration's sensor and scene and the
run's seed, into per-camera event streams, pose tables and packets.

Event model (a copy of the system's simulator logic, kept here so that a
change to the program cannot move the yardstick): the scene is 3-D points
sampled along edge segments on planes; at each time step visible points
emit one event each at their rounded pixel, with a timestamp jittered
inside the step; a fraction of events is replaced by uniform noise
pixels. Unlike the simulator, every step emits the same number of events
(drawn from the points visible then), so a camera's rate is constant and
every key-frame segment of a mix holds nearly the same number of frames.

Each camera moves at constant speed on a circle parallel to the image
plane, facing the scene (+z); a fleet's cameras are spread evenly along
the circle. The path is periodic, so one lap of events is generated and
replayed with the lap period added to its timestamps. Events are
delivered in time-sliced packets of at most `packet_s` seconds or
`packet_events` events, whichever comes first; a packet is due when it
closes. Every seed gives every camera the same rate, path, phase and
packet times; only which points fire, jitter, polarity and noise change
with the seed.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

POSE_RATE_HZ = 200.0  # motion-capture rate of the Event Camera Dataset


def make_scene(scene: dict) -> np.ndarray:
    """(P, 3) world points: edge segments drawn on three fronto-parallel
    planes (the `simulation_3planes` scene of EMVS / the paper)."""
    if scene["name"] != "simulation_3planes":
        raise ValueError(f"unknown scene {scene['name']!r}")
    rng = np.random.default_rng(scene["seed"])
    n, k = scene["points_per_plane"], scene["edge_segments_per_plane"]
    planes = []
    for depth, extent in ((1.0, 0.5), (2.0, 0.9), (3.5, 1.4)):
        ends = rng.uniform(-extent, extent, size=(k, 2, 2))
        per = max(n // k, 2)
        s = np.linspace(0.0, 1.0, per)[:, None]
        uv = np.concatenate([a[None] * (1 - s) + b[None] * s for a, b in ends])[:n]
        if uv.shape[0] < n:
            uv = np.tile(uv, (int(np.ceil(n / uv.shape[0])), 1))[:n]
        planes.append(np.stack([uv[:, 0], uv[:, 1], np.full(n, depth)], axis=1))
    return np.concatenate(planes).astype(np.float32)


def circle_position(mix: dict, phase: float, s, offset: float = 0.0
                    ) -> np.ndarray:
    """Camera centre (world frame) at stream time(s) `s`, float64; a rig
    camera sits `offset` metres along x from the rig's centre."""
    r = mix["radius_m"]
    period = lap_period(mix)
    theta = 2 * np.pi * (phase + np.asarray(s, np.float64) / period)
    return np.stack([r * np.cos(theta) + offset, r * np.sin(theta),
                     np.zeros_like(theta)], axis=-1)


def lap_period(mix: dict) -> float:
    return 2 * math.pi * mix["radius_m"] / mix["speed_m_s"]


@dataclasses.dataclass
class Camera:
    """One camera's periodic event stream: a lap of events, replayed."""

    phase: float
    offset: float  # along x from the rig's centre (0 for a lone camera)
    period: float
    lap_t: np.ndarray  # (n,) float64 stream times in [0, period), sorted
    lap_xy: np.ndarray  # (n, 2) float32 pixel coordinates
    lap_pol: np.ndarray  # (n,) int8
    start: float = 0.0  # client time at which the camera's stream time is 0

    @property
    def lap_events(self) -> int:
        return int(self.lap_t.shape[0])

    def times(self, g0: int, g1: int) -> np.ndarray:
        """float32 timestamps of global events [g0, g1)."""
        return self.times_at(np.arange(g0, g1))

    def times_at(self, g: np.ndarray) -> np.ndarray:
        """float32 timestamps of the global events `g`."""
        lap, i = np.divmod(np.asarray(g), self.lap_events)
        return (self.lap_t[i] + lap * self.period).astype(np.float32)

    def events(self, g0: int, g1: int) -> tuple[np.ndarray, ...]:
        """(xy, t, polarity, valid) of global events [g0, g1)."""
        i = np.arange(g0, g1) % self.lap_events
        return (self.lap_xy[i], self.times(g0, g1), self.lap_pol[i],
                np.ones(g1 - g0, bool))

    def index_at(self, s: float) -> int:
        """Number of events with stream time < s."""
        lap = math.floor(s / self.period)
        off = s - lap * self.period
        return lap * self.lap_events + int(np.searchsorted(self.lap_t, off))

    def pose_table(self, mix: dict, until_s: float) -> tuple[np.ndarray, ...]:
        """Pose samples at POSE_RATE_HZ covering [0, until_s]: float32
        times (n,), rotations (n, 3, 3) (identity: the camera faces +z)
        and translations (n, 3)."""
        n = int(math.ceil(until_s * POSE_RATE_HZ)) + 2
        times = (np.arange(n) / POSE_RATE_HZ).astype(np.float32)
        pos = circle_position(mix, self.phase, times.astype(np.float64),
                              self.offset)
        rot = np.broadcast_to(np.eye(3, dtype=np.float32), (n, 3, 3)).copy()
        return times, rot, pos.astype(np.float32)


def make_lap(sensor: dict, scene_pts: np.ndarray, mix: dict,
             rng: np.random.Generator, offset: float = 0.0) -> Camera:
    """One lap of events of a camera that starts at phase 0."""
    w, h = sensor["width"], sensor["height"]
    fx, fy, cx, cy = sensor["fx"], sensor["fy"], sensor["cx"], sensor["cy"]
    period = lap_period(mix)
    # a constant rate: every step emits the same number m of events, from
    # m of the points visible then, m a little under the fewest visible
    # anywhere on the lap (so m, and every size, is the same for all seeds)
    probe = circle_position(mix, 0.0, np.linspace(0, period, 256,
                                                  endpoint=False), offset)
    m = int(0.95 * min(_visible(scene_pts - p, fx, fy, cx, cy, w, h)[2].sum()
                       for p in probe))
    steps = max(1, round(mix["rate_ev_s"] * period / m))
    dt = period / steps
    s = np.arange(steps) * dt
    pos = circle_position(mix, 0.0, s, offset)  # (steps, 3)
    pc = scene_pts[None, :, :] - pos[:, None, :].astype(np.float32)
    x, y, vis = _visible(pc, fx, fy, cx, cy, w, h)
    key = np.where(vis, rng.random(vis.shape, np.float32), np.float32(2))
    pick = np.argpartition(key, m - 1, axis=1)[:, :m]  # (steps, m)
    rows = np.arange(steps)[:, None]
    xy = np.stack([np.round(x[rows, pick]), np.round(y[rows, pick])], axis=-1)
    t = s[:, None] + rng.uniform(0, 0.45 * dt, size=(steps, m))
    pol = rng.choice(np.array([-1, 1], np.int8), size=(steps, m))
    xy, t, pol = xy.reshape(-1, 2), t.reshape(-1), pol.reshape(-1)
    n_noise = int(mix["noise_fraction"] * t.size)
    if n_noise:
        idx = rng.choice(t.size, size=n_noise, replace=False)
        xy[idx] = np.round(np.stack([rng.uniform(0, w - 1, n_noise),
                                     rng.uniform(0, h - 1, n_noise)], axis=1))
    order = np.argsort(t, kind="stable")
    return Camera(phase=0.0, offset=offset, period=period, lap_t=t[order],
                  lap_xy=xy[order].astype(np.float32), lap_pol=pol[order])


def rotate(lap: Camera, phase: float) -> Camera:
    """The same lap for a camera that starts `phase` laps further on."""
    shift = phase * lap.period
    k = int(np.searchsorted(lap.lap_t, shift))
    t = np.concatenate([lap.lap_t[k:] - shift, lap.lap_t[:k] + lap.period - shift])
    roll = lambda a: np.concatenate([a[k:], a[:k]])  # noqa: E731
    return Camera(phase=phase, offset=lap.offset, period=lap.period, lap_t=t,
                  lap_xy=roll(lap.lap_xy), lap_pol=roll(lap.lap_pol))


def _visible(pc: np.ndarray, fx, fy, cx, cy, w, h):
    z = pc[..., 2]
    with np.errstate(divide="ignore", invalid="ignore"):
        x = fx * pc[..., 0] / z + cx
        y = fy * pc[..., 1] / z + cy
    ok = (z > 0.05) & (x >= 0) & (x <= w - 1) & (y >= 0) & (y <= h - 1)
    # rounding must stay on the sensor too: the served path refuses
    # events outside it
    ok &= (np.round(x) <= w - 1) & (np.round(y) <= h - 1)
    return x, y, ok


def make_cameras(config: dict, mix: dict, seed: int) -> list[Camera]:
    """Every camera of the mix. A fleet's n cameras fly the same lap at
    phases k/n, so every seed offers the same arrivals; the cameras of a
    rig (`rig_baseline_m`) move as one, side by side along x, each with a
    lap of its own. The seed draws the lap's events (which points fire,
    jitter, polarity, noise)."""
    scene_pts = make_scene(config["scene"])
    n = mix["cameras"]
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    baseline = mix.get("rig_baseline_m")
    if baseline is not None:
        return [make_lap(config["sensor"], scene_pts, mix, rng,
                         (k - (n - 1) / 2) * baseline) for k in range(n)]
    lap = make_lap(config["sensor"], scene_pts, mix, rng)
    # cameras come online 1/n of a packet apart, so packets do not all
    # fall due at once
    interval = min(mix["packet_s"], mix["packet_events"] / mix["rate_ev_s"])
    return [dataclasses.replace(rotate(lap, k / n), start=k / n * interval)
            for k in range(n)]


@dataclasses.dataclass
class Packet:
    cam: int
    g0: int  # first global event index
    g1: int  # one past the last
    due: float  # client time at which it is delivered


class Packetizer:
    """Packets of one camera, in order, on demand; `due` is on the
    client's clock (stream time + the camera's start)."""

    def __init__(self, cam_index: int, camera: Camera, mix: dict):
        self.cam = cam_index
        self.camera = camera
        self.slice_s = mix["packet_s"]
        self.max_events = mix["packet_events"]
        self.g = 0
        self.slice_end = self.slice_s

    def next(self) -> Packet:
        while True:
            g_end = self.camera.index_at(self.slice_end)
            if g_end - self.g >= self.max_events:
                g1 = self.g + self.max_events
                pkt = Packet(self.cam, self.g, g1, self.camera.start
                             + float(self.camera.times(g1 - 1, g1)[0]))
                self.g = g1
                return pkt
            if g_end > self.g:
                pkt = Packet(self.cam, self.g, g_end,
                             self.camera.start + self.slice_end)
                self.g = g_end
                self.slice_end += self.slice_s
                return pkt
            self.slice_end += self.slice_s  # an empty slice sends nothing
