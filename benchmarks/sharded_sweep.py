"""A/B: batched (lax.map) segment sweep vs the device-sharded sweep.

`run_emvs(sweep="batched")` runs every segment of a bucket serially
inside one `lax.map` program; `run_emvs(sweep="sharded")` shards the
segment axis across mesh devices (`process_segments_sharded`), so
concurrent key-frame segments vote on different devices — the paper's
key-frame-level parallelism, the axis a serial sweep cannot exploit.

On a real multi-chip backend the sharded path buys near-linear
cross-segment speedup over the devices JAX finds; on a CPU host with
forced host devices (`--devices N`, XLA's host-platform partitioning,
the only case that touches XLA_FLAGS) the devices share the same cores, so the interesting outputs here are (a) the bitwise
nearest-datapath equality check between the two backends and (b) the
machine-readable segments/s trajectory in BENCH_emvs.json. Both paths
are measured cold (fresh jit caches) and warm.

    PYTHONPATH=src python benchmarks/sharded_sweep.py [--dry-run]
        [--devices N] [--json-out BENCH_emvs.json]
"""
from __future__ import annotations

import argparse
import os
import time


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sequence for CI smoke (same code path)")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host (CPU) devices; without it the "
                         "sweep shards over the devices JAX finds")
    ap.add_argument("--json-out", default=None,
                    help="BENCH_emvs.json path (default: repo cwd)")
    return ap.parse_args()


ARGS = _parse_args()
if ARGS.devices is not None:
    # must precede any jax import: jax locks the device count on first init
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ARGS.devices} "
        + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402

try:  # script invocation (python benchmarks/sharded_sweep.py)
    from _emvs_common import update_bench_json
except ImportError:  # module invocation
    from benchmarks._emvs_common import update_bench_json

from repro.core.camera import CameraModel  # noqa: E402
from repro.core.dsi import DSIConfig  # noqa: E402
from repro.core.pipeline import (  # noqa: E402
    EMVSOptions,
    plan_segments,
    run_emvs,
)
from repro.distributed.emvs import SEGMENT_AXIS, make_segment_mesh  # noqa: E402
from repro.events.aggregation import aggregate  # noqa: E402
from repro.events.simulator import (  # noqa: E402
    SceneConfig,
    make_scene,
    make_trajectory,
    simulate_events,
)

WARM_REPEATS = 2


def build_sequence(dry_run: bool):
    cam = CameraModel()
    steps, points, e_frame, planes = (
        (64, 80, 256, 8) if dry_run else (144, 200, 512, 16))
    scene = make_scene(SceneConfig(name="simulation_3planes",
                                   points_per_plane=points))
    traj = make_trajectory("simulation_3planes", steps)
    ev = simulate_events(cam, scene, traj, noise_fraction=0.02, seed=0)
    frames = aggregate(cam, ev, traj, events_per_frame=e_frame)
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=planes, z_min=0.6, z_max=4.5)
    return cam, frames, dsi_cfg


def _block(res):
    for seg in res.segments:
        seg.depth_map.depth.block_until_ready()
    return res


def _measure(fn):
    jax.clear_caches()
    t0 = time.perf_counter()
    res = _block(fn())
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(WARM_REPEATS):
        t0 = time.perf_counter()
        _block(fn())
        warm = min(warm, time.perf_counter() - t0)
    return res, cold, warm


def main() -> None:
    mesh = make_segment_mesh()
    n_dev = mesh.shape[SEGMENT_AXIS]
    cam, frames, dsi_cfg = build_sequence(ARGS.dry_run)
    opts = EMVSOptions(keyframe_dist_frac=0.02)
    segs = plan_segments(frames, dsi_cfg, opts)
    n_seg = len(segs)
    n_ev = sum(b - a for a, b in segs) * frames.xy.shape[1]
    print(f"sequence: {frames.xy.shape[0]} frames x {frames.xy.shape[1]} "
          f"events, {n_seg} segments; mesh: {n_dev} device(s) on the "
          f"'{SEGMENT_AXIS}' axis")

    res_b, cold_b, warm_b = _measure(
        lambda: run_emvs(cam, dsi_cfg, frames, opts))
    res_s, cold_s, warm_s = _measure(
        lambda: run_emvs(cam, dsi_cfg, frames, opts, sweep="sharded",
                         mesh=mesh))

    # default opts vote nearest: the backends must agree bitwise
    assert len(res_b.segments) == len(res_s.segments) == n_seg
    worst = 0.0
    for sb, ss in zip(res_b.segments, res_s.segments):
        assert sb.frame_range == ss.frame_range
        worst = max(worst, float(np.abs(
            np.asarray(sb.dsi, np.float32)
            - np.asarray(ss.dsi, np.float32)).max()))
        assert (np.asarray(sb.depth_map.mask)
                == np.asarray(ss.depth_map.mask)).all()
    assert worst == 0.0, f"nearest-voting DSIs must match bitwise, got {worst}"
    print(f"numerical match: max |DSI_batched - DSI_sharded| = {worst:g}, "
          f"masks equal")

    print(f"\n{'path':<10}{'cold s':>10}{'cold seg/s':>12}"
          f"{'warm s':>10}{'warm seg/s':>12}")
    for name, cold, warm in (("batched", cold_b, warm_b),
                             ("sharded", cold_s, warm_s)):
        print(f"{name:<10}{cold:>10.2f}{n_seg / cold:>12.2f}"
              f"{warm:>10.2f}{n_seg / warm:>12.2f}")
    print(f"\nsharded/batched warm ratio: {warm_b / warm_s:.2f}x "
          f"(host devices share cores; expect ~1x on CPU, ~{n_dev}x on a "
          f"real {n_dev}-chip mesh)")

    path = update_bench_json("sharded_sweep", {
        "dry_run": bool(ARGS.dry_run),
        "devices": n_dev,
        "segments": n_seg,
        "events": n_ev,
        "batched": {"cold_s": round(cold_b, 3), "warm_s": round(warm_b, 3),
                    "cold_segments_per_s": round(n_seg / cold_b, 3),
                    "warm_segments_per_s": round(n_seg / warm_b, 3)},
        "sharded": {"cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
                    "cold_segments_per_s": round(n_seg / cold_s, 3),
                    "warm_segments_per_s": round(n_seg / warm_s, 3)},
        "bitwise_match": True,
    }, path=ARGS.json_out)
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
