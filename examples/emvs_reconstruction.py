"""Full EMVS reconstruction demo: every pipeline stage, all datapaths.

Walks A -> P -> R -> K -> D -> M on a synthetic sequence, compares the
three voting formulations and the quantized datapath, and writes the
reconstruction (depth maps + merged point cloud) to an .npz.

    PYTHONPATH=src python examples/emvs_reconstruction.py \
        [--scene simulation_3walls] [--out /tmp/emvs_recon.npz]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig
from repro.core.pipeline import EMVSOptions, run_emvs
from repro.core.pointcloud import concatenate, radius_outlier_filter
from repro.events.aggregation import aggregate
from repro.events.simulator import (
    SceneConfig, absrel, ground_truth_depth, make_scene, make_trajectory,
    simulate_events,
)
from repro.kernels.platform import compiled_kernels_supported


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scene", default="simulation_3planes",
                    choices=["simulation_3planes", "simulation_3walls",
                             "slider_close", "slider_far"])
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--points", type=int, default=400)
    ap.add_argument("--planes", type=int, default=64)
    ap.add_argument("--out", default="/tmp/emvs_recon.npz")
    args = ap.parse_args()

    cam = CameraModel()
    scene = make_scene(SceneConfig(name=args.scene, points_per_plane=args.points))
    traj = make_trajectory(args.scene, args.steps)
    events = simulate_events(cam, scene, traj, noise_fraction=0.02)
    frames = aggregate(cam, events, traj)
    z = (0.5, 1.8) if args.scene == "slider_close" else (0.6, 4.5)
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=args.planes,
                                   z_min=z[0], z_max=z[1])
    print(f"scene={args.scene}: {int(events.valid.sum())} events, "
          f"{frames.xy.shape[0]} frames, DSI {dsi_cfg.shape}")

    # say which kernel mode runs: compiled where the platform can, the
    # Pallas interpreter elsewhere
    kernel_mode = ("compiled" if compiled_kernels_supported()
                   else "interpreted")
    variants = {
        "scatter/float (original EMVS)": EMVSOptions(
            voting="bilinear", formulation="scatter"),
        "matmul/nearest (Eventor reformulation)": EMVSOptions(
            voting="nearest", formulation="matmul"),
        "matmul/nearest + Table-1 quantization": EMVSOptions(
            voting="nearest", formulation="matmul", quantized=True),
        f"Pallas kernel ({kernel_mode}) + quantization": EMVSOptions(
            voting="nearest", formulation="kernel", quantized=True,
            kernel_interpret=kernel_mode == "interpreted"),
    }
    results = {}
    for name, opts in variants.items():
        t0 = time.time()
        res = run_emvs(cam, dsi_cfg, frames, opts)
        dt = time.time() - t0
        errs, px = [], 0
        for seg in res.segments:
            gt, gtm = ground_truth_depth(cam, scene, seg.T_w_ref)
            errs.append(float(absrel(seg.depth_map.depth, seg.depth_map.mask,
                                     gt, gtm)))
            px += int(seg.depth_map.mask.sum())
        results[name] = res
        print(f"{name:44s} AbsRel {np.mean(errs):.4f}  "
              f"{px:6d} px  {dt:6.1f}s  ({len(res.segments)} keyframes)")

    # merge + filter the map of the reformulated variant (stage M)
    res = results["matmul/nearest + Table-1 quantization"]
    cloud = concatenate(res.clouds)
    cloud = radius_outlier_filter(cloud, radius=0.08, min_neighbors=2)
    n = int(np.asarray(cloud.valid).sum())
    print(f"merged global map: {n} points after outlier filtering")

    np.savez(
        args.out,
        points=np.asarray(cloud.points)[np.asarray(cloud.valid)],
        weights=np.asarray(cloud.weights)[np.asarray(cloud.valid)],
        depth0=np.asarray(res.segments[0].depth_map.depth),
        mask0=np.asarray(res.segments[0].depth_map.mask),
    )
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
