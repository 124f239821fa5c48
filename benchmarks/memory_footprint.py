"""Paper §2.3: "our hybrid data quantization strategy can save up to 50%
of the memory requirement and data transferring bandwidth".

Second table: the STREAMING HOST WINDOW. Each streaming session keeps
its aggregated frames in a host-side `_FrameStore` until the planner's
open segment no longer needs them; the store counts its live and peak
resident bytes exactly (`frame_store_bytes` / `frame_store_peak_bytes`
in the engine stats). A tiny end-to-end streaming run here shows the two
invariants that make the window a *window* rather than a leak: the peak
stays below the whole sequence's resident footprint (eviction works
mid-stream), and the live count returns to exactly zero after `flush`
(nothing survives the stream).
"""
from __future__ import annotations

from repro.core.camera import CameraModel
from repro.quant.policies import memory_report


def run() -> dict:
    cam = CameraModel()
    rep = memory_report(cam, num_planes=128, events_per_frame=1024)
    f32 = sum(rep["float32"].values())
    q = sum(rep["table1"].values())
    return {"float32_bytes_per_frame": f32, "table1_bytes_per_frame": q,
            "saving": 1 - q / f32, "detail": rep,
            "claim_ok": bool(q <= 0.55 * f32)}


def run_streaming_window() -> dict:
    """Stream a tiny sequence and report the host frame-window footprint:
    peak resident bytes vs the un-evicted whole-sequence cost (measured
    by filling a reference `_FrameStore` with every frame), and the
    post-flush live count (must be exactly 0)."""
    from repro.core.dsi import DSIConfig
    from repro.core.pipeline import EMVSOptions
    from repro.events.aggregation import aggregate
    from repro.events.simulator import (
        SceneConfig,
        make_scene,
        make_trajectory,
        simulate_events,
    )
    from repro.serving.emvs_stream import (
        EMVSStreamEngine,
        StreamConfig,
        _FrameStore,
        iter_event_chunks,
    )

    cam = CameraModel()
    e_frame = 256
    scene = make_scene(SceneConfig(name="simulation_3planes",
                                   points_per_plane=80))
    traj = make_trajectory("simulation_3planes", 64)
    ev = simulate_events(cam, scene, traj, noise_fraction=0.02, seed=0)
    dsi_cfg = DSIConfig.for_camera(cam, num_planes=8, z_min=0.6, z_max=4.5)
    opts = EMVSOptions(keyframe_dist_frac=0.02)

    engine = EMVSStreamEngine(cam, dsi_cfg, traj, opts,
                              StreamConfig(events_per_frame=e_frame))
    for chunk in iter_event_chunks(ev, e_frame):
        engine.push(chunk)
    res = engine.flush()
    stats = engine.stats

    # the counterfactual: every aggregated frame resident at once, counted
    # by the same accounting the engine uses
    whole_store = _FrameStore()
    whole_store.extend(aggregate(cam, ev, traj, events_per_frame=e_frame))
    whole = whole_store.live_bytes

    return {
        "frames": int(stats["frames"]),
        "segments": len(res.segments),
        "live_bytes_after_flush": int(stats["frame_store_bytes"]),
        "peak_bytes": int(stats["frame_store_peak_bytes"]),
        "whole_sequence_bytes": int(whole),
        "peak_fraction_of_sequence": round(
            stats["frame_store_peak_bytes"] / whole, 4) if whole else 0.0,
        "window_ok": bool(stats["frame_store_bytes"] == 0
                          and 0 < stats["frame_store_peak_bytes"] <= whole),
    }


def main() -> None:
    out = run()
    print("== §2.3 memory footprint (bytes per 1024-event frame + DSI) ==")
    print(f"{'item':14s} {'float32':>12s} {'table1':>12s}")
    for k in out["detail"]["float32"]:
        print(f"{k:14s} {out['detail']['float32'][k]:12d} "
              f"{out['detail']['table1'][k]:12d}")
    print(f"total: {out['float32_bytes_per_frame']} -> "
          f"{out['table1_bytes_per_frame']} bytes "
          f"({out['saving']*100:.1f}% saved; paper: 'up to 50%'; "
          f"{'OK' if out['claim_ok'] else 'VIOLATED'})")

    win = run_streaming_window()
    print("\n== streaming host frame-window (live/peak byte accounting) ==")
    print(f"frames aggregated:       {win['frames']}")
    print(f"segments swept:          {win['segments']}")
    print(f"whole sequence resident: {win['whole_sequence_bytes']} bytes")
    print(f"peak window resident:    {win['peak_bytes']} bytes "
          f"({win['peak_fraction_of_sequence']*100:.1f}% of sequence)")
    print(f"live after flush:        {win['live_bytes_after_flush']} bytes")
    print("OK: eviction bounds the window and flush drains it"
          if win["window_ok"] else "VIOLATED: window accounting broken")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
