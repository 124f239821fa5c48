"""What decides `correct`: the served results against the plain reference.

Every depth map emitted must close a segment where the reference closes
one (its frame range is one of the reference's segments of its stream),
and a sample of the maps emitted in the window, drawn from the seed with
the longest among them, is recomputed by the reference from the raw
traffic (the inputs and the reference that the cell's driver gives)
and compared: DSI voxel by voxel, the semi-dense mask pixel by pixel,
and depth where both masks hold. The limits live in the
configuration's file (`limits`), with the readings they were set from in
PERF.md.
"""
from __future__ import annotations

import numpy as np

from harness import reference as ref

NUMBERS = ("segments_unmatched", "dsi_voxels", "mask_pixels", "depth_gap")


def sample(emitted: list, k: int, seed: int) -> list:
    """k of the emitted maps, drawn from the seed, the longest among them."""
    if not emitted:
        return []
    longest = max(range(len(emitted)),
                  key=lambda i: emitted[i].frames[1] - emitted[i].frames[0])
    rest = [i for i in range(len(emitted)) if i != longest]
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [emitted[longest]] + [emitted[rest[j]] for j in sorted(pick)]


def compare(setup: ref.Setup, dsi: np.ndarray, depth: np.ndarray,
            mask: np.ndarray, ref_dsi: np.ndarray, ref_depth: np.ndarray,
            ref_mask: np.ndarray) -> dict:
    """Gaps of one segment: share of DSI voxels that differ, mask pixels
    that differ per reference pixel, mean relative depth gap where both
    masks hold."""
    both = mask & ref_mask
    gap = (float(np.mean(np.abs(depth[both] - ref_depth[both]) / ref_depth[both]))
           if both.any() else (0.0 if not ref_mask.any() else 1.0))
    return {"dsi_voxels": float(np.mean(dsi != ref_dsi)),
            "mask_pixels": float(np.sum(mask != ref_mask) / max(1, ref_mask.sum())),
            "depth_gap": gap}


def judge(values: dict, limits: dict) -> bool:
    return all(values[n] <= limits[n] for n in NUMBERS)
