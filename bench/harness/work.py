"""The work the EMVS algorithm needs per key-frame segment, whatever
implements it: the least a sweep can do, for its roofline share.

Bytes: the segment's events read once (x, y as float32 and a validity
byte), each frame's pose once, the DSI written once at its store width
(int16 under Table 1, int32 otherwise), and the depth, mask and
confidence maps written once. This assumes the result carries the DSI,
as the served path's `SegmentResult` does; a result contract without it
is a change for a benchmark PR to make.

Operations: per event the canonical homography (6 multiplies, 6 adds,
2 divides); per event and depth plane the propagation (2 multiply-adds,
2 centring adds) and the vote (1 add); per frame the geometry (3x3
products and 3 coefficients per plane); per voxel the depth maximum and
its index (2 compares).

No formulation's own work is counted (the one-hot matmul's h*w MACs per
vote, padding, carries), so a faster formulation raises the share and
none can push it past 100%.
"""
from __future__ import annotations

EVENT_BYTES = 9  # x, y float32 + validity
POSE_BYTES = 48  # R (3x3) + t (3) float32
MAP_BYTES = 9  # depth f32 + mask u8 + confidence f32, per pixel
OPS_PER_EVENT = 14
OPS_PER_EVENT_PLANE = 7
OPS_PER_FRAME = 54  # two 3x3 products, inversion, normalisation
OPS_PER_FRAME_PLANE = 10
OPS_PER_VOXEL = 2


def segment_work(frames: int, events_per_frame: int, planes: int,
                 height: int, width: int, quantized: bool) -> tuple[float, float]:
    """(operations, bytes) the algorithm needs for one segment."""
    events = frames * events_per_frame
    voxels = planes * height * width
    ops = (events * (OPS_PER_EVENT + OPS_PER_EVENT_PLANE * planes)
           + frames * (OPS_PER_FRAME + OPS_PER_FRAME_PLANE * planes)
           + voxels * OPS_PER_VOXEL)
    nbytes = (events * EVENT_BYTES + frames * POSE_BYTES
              + voxels * (2 if quantized else 4) + height * width * MAP_BYTES)
    return float(ops), float(nbytes)


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Roofline time and which bound sets it."""
    t_ops = ops / peak["ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
