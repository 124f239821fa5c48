"""Plain EMVS reference, written from the published description.

EMVS (Rebecq et al., IJCV 2018) with the Eventor datapath (arXiv
2203.15439, Table 1): events are aggregated into fixed-size frames, each
frame posed at its median timestamp; a key-frame segment closes when the
camera has moved more than `keyframe_dist_frac` x the mean scene depth
from the segment's reference view; every event of every frame of a
segment is back-projected through the canonical plane Z0 and propagated
to each depth plane, and votes into the nearest voxel of the segment's
disparity space image (DSI); detection takes the per-pixel maximum over
depth, keeps pixels above an adaptive Gaussian threshold, refines depth
by a parabola around the maximum, and applies a 3x3 median filter.

Table 1 (quantized=True): event and canonical coordinates Q9.7, the
homography and the plane coefficients Q11.21, plane coordinates 8-bit
pixel indices (out-of-range parked at 255, so a sensor narrower than 256
pixels drops them), DSI scores stored as int16 with saturation. Rounding
is half away from zero for the fixed-point formats and half up for the
nearest voxel, as in the RTL.

NumPy on the host, float32 arithmetic; `lowp=True` computes every float
intermediate of the geometry and projection in bfloat16 instead (the
control). It imports nothing of the system under test.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

F32 = np.float32
_BF16 = ml_dtypes.bfloat16


@dataclasses.dataclass(frozen=True)
class Setup:
    """What the reference needs of a configuration."""

    width: int
    height: int
    fx: float
    fy: float
    cx: float
    cy: float
    num_planes: int
    z_min: float
    z_max: float
    quantized: bool
    keyframe_dist_frac: float
    threshold_c: float
    min_votes: float
    median_filter: bool
    events_per_frame: int

    @staticmethod
    def from_config(config: dict) -> "Setup":
        s, d, e = config["sensor"], config["dsi"], config["emvs"]
        if e["voting"] != "nearest" or not d["inverse_depth"]:
            raise ValueError("the reference covers nearest voting over "
                             "inverse-depth planes")
        return Setup(s["width"], s["height"], s["fx"], s["fy"], s["cx"],
                     s["cy"], d["num_planes"], d["z_min"], d["z_max"],
                     e["quantized"], e["keyframe_dist_frac"],
                     e["detection_threshold_c"], e["detection_min_votes"],
                     e["median_filter"], config["stream"]["events_per_frame"])

    def planes(self) -> np.ndarray:
        inv = np.linspace(1.0 / self.z_max, 1.0 / self.z_min,
                          self.num_planes).astype(F32)
        return (F32(1.0) / inv)[::-1].copy()


# --- aggregation, poses, key-frame segments --------------------------------


def middle_mean(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The median timestamp of even-sized frames of time-ordered events,
    from their two middle timestamps, rounded as a float32 median is."""
    return ((a.astype(F32) + b.astype(F32)) / F32(2)).astype(F32)


def interpolate_positions(times: np.ndarray, pos: np.ndarray,
                          t_query: np.ndarray) -> np.ndarray:
    """Linear interpolation of sampled camera centres (float32)."""
    n = times.shape[0]
    idx = np.clip(np.searchsorted(times, t_query, side="right") - 1, 0, n - 2)
    t0, t1 = times[idx], times[idx + 1]
    frac = np.clip((t_query - t0) / np.maximum(t1 - t0, F32(1e-9)),
                   F32(0), F32(1)).astype(F32)
    return (pos[idx] + frac[:, None] * (pos[idx + 1] - pos[idx])).astype(F32)


def key_frame_segments(positions: np.ndarray, setup: Setup
                       ) -> list[tuple[int, int]]:
    """Closed segments [start, end) of frames, in close order. A segment
    closes at the first frame farther than the threshold from its
    reference (first) frame; segments of fewer than 2 frames are dropped.
    The trailing open segment is not returned."""
    threshold = 0.5 * (setup.z_min + setup.z_max) * setup.keyframe_dist_frac
    out, start = [], 0
    for i in range(1, positions.shape[0]):
        if np.linalg.norm(positions[i] - positions[start]) > threshold:
            if i - start >= 2:
                out.append((start, i))
            start = i
    return out


# --- geometry ---------------------------------------------------------------


class _Prec:
    """float32 arithmetic, or bfloat16 after every operation (lowp)."""

    def __init__(self, lowp: bool):
        self.lowp = lowp

    def __call__(self, x):
        x = np.asarray(x, F32)
        return x.astype(_BF16).astype(F32) if self.lowp else x


def _q(x: np.ndarray, total: int, frac: int, signed: bool = True) -> np.ndarray:
    """Fixed-point round trip: round half away, saturate, back to float."""
    scale = F32(2.0 ** frac)
    lo = -(2 ** (total - 1)) if signed else 0
    hi = 2 ** (total - 1) - 1 if signed else 2 ** total - 1
    v = np.asarray(x, F32) * scale
    r = np.sign(v) * np.floor(np.abs(v) + F32(0.5))
    return (np.clip(r, F32(lo), F32(hi)) / scale).astype(F32)


def _plane_coord(c: np.ndarray) -> np.ndarray:
    """8-bit pixel index; out-of-range parked at 255."""
    park = (c < F32(-0.5)) | (c > F32(255.5))
    return np.where(park, F32(255), _q(c, 8, 0, signed=False)).astype(F32)


def frame_geometry(setup: Setup, ref_t: np.ndarray, cam_t: np.ndarray,
                   z0, planes: np.ndarray, p: _Prec):
    """Canonical homography H (3x3) and plane coefficients (alpha, bx, by)
    of one frame, for camera centres given in the world frame with the
    world orientation (translation-only motion)."""
    t_rc = p(cam_t - ref_t)  # current centre in the reference frame
    d_c = p(z0 - t_rc[2])
    K = np.array([[setup.fx, 0, setup.cx], [0, setup.fy, setup.cy], [0, 0, 1]], F32)
    K_inv = np.array([[1 / setup.fx, 0, -setup.cx / setup.fx],
                      [0, 1 / setup.fy, -setup.cy / setup.fy], [0, 0, 1]], F32)
    H_metric = np.eye(3, dtype=F32)
    H_metric[:, 2] = p(H_metric[:, 2] + p(t_rc / d_c))  # R + t n^T / d, n = e_z
    H = p(p(K @ H_metric) @ K_inv)
    H = p(H / H[2, 2])
    cz = t_rc[2]
    s = p(p(planes - cz) / p(z0 - cz))
    alpha = p(p(s * z0) / planes)
    one_minus = p(F32(1) - s)
    bx = p(p(p(F32(setup.fx) * t_rc[0]) * one_minus) / planes)
    by = p(p(p(F32(setup.fy) * t_rc[1]) * one_minus) / planes)
    return H, alpha, bx, by


def project(setup: Setup, xy: np.ndarray, H, alpha, bx, by, p: _Prec):
    """Per-plane voxel indices (F, Nz, E) of F frames' events (F, E, 2),
    given each frame's H (F, 3, 3) and coefficients (F, Nz); float32
    integer values, out of bounds for events that miss the sensor."""
    if setup.quantized:
        xy = _q(xy, 16, 7)
        H = _q(H, 32, 21)
        alpha, bx, by = (_q(v, 32, 21) for v in (alpha, bx, by))
    x, y = xy[..., 0], xy[..., 1]

    def h(i, j):
        return H[:, i, j][:, None]

    den = p(p(p(h(2, 0) * x) + p(h(2, 1) * y)) + h(2, 2))
    u = p(p(p(p(h(0, 0) * x) + p(h(0, 1) * y)) + h(0, 2)) / den)
    v = p(p(p(p(h(1, 0) * x) + p(h(1, 1) * y)) + h(1, 2)) / den)
    if setup.quantized:
        u, v = _q(u, 16, 7), _q(v, 16, 7)
    cx, cy = F32(setup.cx), F32(setup.cy)
    xi = p(p(p(alpha[:, :, None] * p(u - cx)[:, None, :]) + bx[:, :, None]) + cx)
    yi = p(p(p(alpha[:, :, None] * p(v - cy)[:, None, :]) + by[:, :, None]) + cy)
    if setup.quantized:
        xi, yi = _plane_coord(xi), _plane_coord(yi)
    xi = np.where(np.isfinite(xi), xi, F32(-1e6))
    yi = np.where(np.isfinite(yi), yi, F32(-1e6))
    return np.floor(xi + F32(0.5)), np.floor(yi + F32(0.5))


# --- one segment -----------------------------------------------------------

FRAMES_PER_BLOCK = 16
BLOCKS_PER_COUNT = 8


def segment_dsi(setup: Setup, xy_frames: np.ndarray, pos_frames: np.ndarray,
                *, ref_pos: np.ndarray | None = None,
                lowp: bool = False) -> np.ndarray:
    """DSI (Nz, h, w) int32 of one segment: frames (F, E, 2) of events,
    camera centres (F, 3), voted into the reference view centred at
    `ref_pos` (3,), the first frame's centre where it is None. Frames are
    projected in blocks and their votes counted per block."""
    p = _Prec(lowp)
    ref_t = pos_frames[0] if ref_pos is None else np.asarray(ref_pos, F32)
    planes = setup.planes()
    z0 = planes[setup.num_planes // 2]
    nz, h, w = setup.num_planes, setup.height, setup.width
    plane_off = (np.arange(nz, dtype=np.int64) * h * w)[None, :, None]
    counts = np.zeros(nz * h * w, np.int64)
    votes = []
    for f0 in range(0, xy_frames.shape[0], FRAMES_PER_BLOCK):
        f1 = min(f0 + FRAMES_PER_BLOCK, xy_frames.shape[0])
        geo = [frame_geometry(setup, ref_t, pos_frames[f], z0, planes, p)
               for f in range(f0, f1)]
        H, alpha, bx, by = (np.stack(g) for g in zip(*geo))
        xr, yr = project(setup, xy_frames[f0:f1], H, alpha, bx, by, p)
        ok = (xr >= 0) & (xr <= w - 1) & (yr >= 0) & (yr <= h - 1)
        lin = plane_off + yr.astype(np.int64) * w + xr.astype(np.int64)
        votes.append(lin[ok])
        if len(votes) == BLOCKS_PER_COUNT or f1 == xy_frames.shape[0]:
            counts += np.bincount(np.concatenate(votes), minlength=counts.size)
            votes = []
    dsi = counts.reshape(nz, h, w)
    if setup.quantized:
        dsi = np.clip(dsi, -32768, 32767)
    return dsi.astype(np.int32)


def _blur(img: np.ndarray, sigma: float = 2.5, radius: int = 5) -> np.ndarray:
    x = np.arange(-radius, radius + 1, dtype=F32)
    k = np.exp(F32(-0.5) * (x / F32(sigma)) ** 2).astype(F32)
    k = k / k.sum()
    for axis in (0, 1):
        pad = [(0, 0), (0, 0)]
        pad[axis] = (radius, radius)
        src = np.pad(img, pad, mode="edge")
        n = img.shape[axis]
        out = np.zeros_like(img)
        for j in range(2 * radius + 1):
            sl = [slice(None), slice(None)]
            sl[axis] = slice(j, j + n)
            out = out + k[j] * src[tuple(sl)]
        img = out.astype(F32)
    return img


def detect(setup: Setup, dsi: np.ndarray):
    """Semi-dense depth map of a DSI: (depth (h, w) float32, mask bool)."""
    planes = setup.planes()
    d = dsi.astype(F32)
    nz = d.shape[0]
    conf = d.max(axis=0)
    z = d.argmax(axis=0)
    hh, ww = np.indices(conf.shape)
    cm = d[np.clip(z - 1, 0, nz - 1), hh, ww]
    c0 = d[z, hh, ww]
    cp = d[np.clip(z + 1, 0, nz - 1), hh, ww]
    den = cm - F32(2) * c0 + cp
    with np.errstate(divide="ignore", invalid="ignore"):
        off = np.where(np.abs(den) > F32(1e-6), F32(0.5) * (cm - cp) / den, F32(0))
    zf = z.astype(F32) + np.clip(off, F32(-0.5), F32(0.5))
    mask = (conf > _blur(conf) + F32(setup.threshold_c)) & (conf >= F32(setup.min_votes))
    lo = np.clip(np.floor(zf).astype(np.int64), 0, nz - 1)
    hi = np.clip(lo + 1, 0, nz - 1)
    frac = zf - lo.astype(F32)
    depth = (planes[lo] * (F32(1) - frac) + planes[hi] * frac).astype(F32)
    if setup.median_filter:
        depth = _median3(depth, mask)
    return depth, mask


def _median3(depth: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """3x3 median over masked neighbours (borders wrap around)."""
    stack = []
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            dd = np.roll(np.roll(depth, dy, axis=0), dx, axis=1)
            mm = np.roll(np.roll(mask, dy, axis=0), dx, axis=1)
            stack.append(np.where(mm, dd, np.inf).astype(F32))
    stack = np.sort(np.stack(stack), axis=0)
    cnt = np.isfinite(stack).sum(axis=0)
    mid = np.maximum((cnt - 1) // 2, 0)
    med = np.take_along_axis(stack, mid[None], axis=0)[0]
    return np.where(mask & (cnt > 0), med, depth).astype(F32)
