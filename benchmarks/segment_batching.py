"""A/B: host-looped per-segment EMVS vs the padded batched segment sweep.

The seed's `run_emvs` processed key-frame segments in a host-side Python
loop: one device dispatch per segment and one retrace/compile per
distinct segment length — the "many small dispatches" pathology for
event-rate processing. The batched sweep pads segments into
multiple-of-four frame-capacity buckets and runs ONE compiled program
per bucket.

Reported per path:
  * cold: fresh jit caches, one full run (includes tracing/compilation —
    this is what a new sequence costs, and where per-length retraces hurt);
  * warm: best of WARM_REPEATS steady-state runs.
Headline metric is cold segments/s; Mev/s counts real (unpadded) events.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

try:  # script invocation (python benchmarks/segment_batching.py)
    from _emvs_common import update_bench_json
except ImportError:  # module invocation (python -m benchmarks.segment_batching)
    from benchmarks._emvs_common import update_bench_json

from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig
from repro.core.pipeline import (
    EMVSOptions,
    plan_segments,
    run_emvs,
    run_emvs_looped,
)
from repro.events.aggregation import aggregate
from repro.events.simulator import (
    SceneConfig,
    make_scene,
    make_trajectory,
    simulate_events,
)

WARM_REPEATS = 3


def build_sequence():
    cam = CameraModel()
    scene = make_scene(SceneConfig(name="simulation_3planes", points_per_plane=200))
    traj = make_trajectory("simulation_3planes", 144)
    ev = simulate_events(cam, scene, traj, noise_fraction=0.02, seed=0)
    frames = aggregate(cam, ev, traj, events_per_frame=512)
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=16, z_min=0.6, z_max=4.5)
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


def _check_match(a, b):
    assert len(a.segments) == len(b.segments), "segment count mismatch"
    worst = 0.0
    for sa, sb in zip(a.segments, b.segments):
        assert sa.frame_range == sb.frame_range
        worst = max(worst, float(np.abs(
            np.asarray(sa.dsi, np.float32) - np.asarray(sb.dsi, np.float32)).max()))
        assert (np.asarray(sa.depth_map.mask) == np.asarray(sb.depth_map.mask)).all()
    # default opts vote nearest: integral counts, so the match must be exact
    assert worst == 0.0, f"nearest-voting DSIs must match bitwise, got {worst}"
    return worst


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default=None,
                    help="BENCH_emvs.json path (default: repo cwd)")
    # parse_known_args: benchmarks.run invokes this main() with the
    # driver's own flags (e.g. --skip-slow) still on sys.argv
    args, _ = ap.parse_known_args()

    cam, frames, dsi_cfg = build_sequence()
    opts = EMVSOptions(keyframe_dist_frac=0.02)
    segs = plan_segments(frames, dsi_cfg, opts)
    lengths = sorted({b - a for a, b in segs})
    n_seg = len(segs)
    n_ev = sum(b - a for a, b in segs) * frames.xy.shape[1]
    print(f"sequence: {frames.xy.shape[0]} frames x {frames.xy.shape[1]} events, "
          f"{n_seg} segments, lengths {lengths} "
          f"({len(lengths)} distinct -> {len(lengths)} looped retraces)")

    res_l, cold_l, warm_l = _measure(lambda: run_emvs_looped(cam, dsi_cfg, frames, opts))
    res_b, cold_b, warm_b = _measure(lambda: run_emvs(cam, dsi_cfg, frames, opts))
    worst = _check_match(res_l, res_b)
    print(f"numerical match: max |DSI_looped - DSI_batched| = {worst:g}, masks equal")

    print(f"\n{'path':<10}{'cold s':>10}{'cold seg/s':>12}{'cold Mev/s':>12}"
          f"{'warm s':>10}{'warm seg/s':>12}{'warm Mev/s':>12}")
    for name, cold, warm in (("looped", cold_l, warm_l), ("batched", cold_b, warm_b)):
        print(f"{name:<10}{cold:>10.2f}{n_seg / cold:>12.2f}{n_ev / cold / 1e6:>12.3f}"
              f"{warm:>10.2f}{n_seg / warm:>12.2f}{n_ev / warm / 1e6:>12.3f}")

    cold_speedup = cold_l / cold_b
    warm_speedup = warm_l / warm_b
    print(f"\nbatched sweep speedup: {cold_speedup:.2f}x cold (segments/s), "
          f"{warm_speedup:.2f}x warm")
    if cold_speedup < 1.5:
        print("WARNING: cold speedup below the 1.5x acceptance threshold")

    path = update_bench_json("segment_batching", {
        "segments": n_seg,
        "events": n_ev,
        "looped": {"cold_s": round(cold_l, 3), "warm_s": round(warm_l, 3),
                   "cold_segments_per_s": round(n_seg / cold_l, 3),
                   "warm_segments_per_s": round(n_seg / warm_l, 3)},
        "batched": {"cold_s": round(cold_b, 3), "warm_s": round(warm_b, 3),
                    "cold_segments_per_s": round(n_seg / cold_b, 3),
                    "warm_segments_per_s": round(n_seg / warm_b, 3)},
        "cold_speedup": round(cold_speedup, 3),
        "warm_speedup": round(warm_speedup, 3),
    }, path=args.json_out)
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
