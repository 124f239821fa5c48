#!/usr/bin/env python3
"""Bring-up smoke: the EMVS main path on a TPU, at the paper's size.

    python chip_smoke.py               # one chip: phases 1-4
    python chip_smoke.py --four-chips  # four chips: the sharded sweep only

One process holds the chip(s) and starts no children. Without a TPU the
script exits non-zero and prints no result. Every input comes from fixed
seeds: the DAVIS240 camera (240x180), 128 depth planes, 1024-event
frames, the simulated 3-planes scene.

One chip:
  1. offline   `run_emvs` (batched matmul sweep, nearest), int16 store
               off and on
  2. served    `MultiStreamEngine`, two sessions with their own noise
               seeds, push -> poll -> flush; each session bitwise-equal
               to its own offline `run_emvs`
  3. kernel    `formulation="kernel"` compiled (`kernel_interpret=False`),
               bitwise-equal to phase 1, store off and on
  4. accuracy  AbsRel against ground truth on the chip and on the host
               CPU (float32 scatter reference, whose depth maps the
               chip's must equal); max |matmul - scatter| on bilinear

Four chips (`--four-chips`): `run_emvs(sweep="sharded")` over a 4-device
`make_segment_mesh()` and a two-session engine with
`StreamConfig(sweep="sharded")`, both bitwise-equal to the one-chip
batched sweep.

Printed wall times are smoke timings with compilation included, not
metrics. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core.camera import CameraModel  # noqa: E402
from repro.core.dsi import DSIConfig  # noqa: E402
from repro.core.pipeline import (  # noqa: E402
    EMVSOptions, bucket_capacity, pad_segments, plan_segments, run_emvs,
)
from repro.events.aggregation import aggregate  # noqa: E402
from repro.events.simulator import (  # noqa: E402
    SceneConfig, absrel, ground_truth_depth, make_scene, make_trajectory,
    simulate_events,
)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.emvs_stream import (  # noqa: E402
    MultiStreamEngine, StreamConfig, iter_event_chunks,
)

TRAJECTORY_STEPS = 48  # ~85 frames -> 6 key-frame segments, 3 buckets
EVENTS_PER_FRAME = 1024
# the arc travels ~80 cm over a scene ~2.2 m deep: 0.05 x mean depth opens
# a key frame ~11 cm from the last one (the default 0.15 gives too few)
KEYFRAME_DIST_FRAC = 0.05
SESSION_SEEDS = (1, 2)  # noise seed per session; the first is the offline run
CHUNK_FRAMES = 3  # events pushed per session turn, in frames
KERNEL_INTERPRET = False  # phase 3 must run the compiled kernel
MIN_SEGMENTS, MIN_BUCKETS = 6, 2
# chip vs host CPU (phase 4). Unpinned bf16 geometry moved ~10% of the
# DSI voxels, ~40% of the mask pixels and AbsRel by 0.009 (PERF.md)
MAX_VOXEL_FRAC = 1e-4
MAX_MASK_FRAC = 0.01
MAX_DEPTH_GAP = 1e-3  # mean |depth_chip - depth_cpu| / depth_cpu
MAX_ABSREL_GAP = 1e-3
MAX_BILINEAR_GAP = 1e-3  # the CPU tests' matmul-vs-scatter DSI tolerance


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(count: int) -> dict:
    """Device info of the TPU(s); exits non-zero on anything else."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) != count:
        sys.exit(f"chip_smoke: this run needs {count} TPU chip(s), JAX "
                 f"sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def config() -> tuple[CameraModel, DSIConfig]:
    cam = CameraModel()
    return cam, DSIConfig.for_camera(cam)


def options(**kw) -> EMVSOptions:
    return EMVSOptions(keyframe_dist_frac=KEYFRAME_DIST_FRAC, **kw)


def phase(name: str, fn):
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    print(f"[{name}] {time.perf_counter() - t:.2f} s wall "
          "(smoke timing, compilation included; not a metric)", flush=True)
    return out


class CacheCounter:
    """Counts persistent compile-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def make_inputs(cam: CameraModel, dsi_cfg: DSIConfig):
    """Scene, trajectory and each session's aggregated event stream."""
    scene = make_scene(SceneConfig(name="simulation_3planes"))
    traj = make_trajectory("simulation_3planes", TRAJECTORY_STEPS)
    streams, frames = {}, {}
    for seed in SESSION_SEEDS:
        ev = simulate_events(cam, scene, traj, noise_fraction=0.02, seed=seed)
        sid = f"seed{seed}"
        streams[sid] = ev
        frames[sid] = aggregate(cam, ev, traj, events_per_frame=EVENTS_PER_FRAME)
    segs = plan_segments(frames[f"seed{SESSION_SEEDS[0]}"], dsi_cfg, options())
    caps = sorted({bucket_capacity(b - a) for a, b in segs})
    print(f"inputs: {len(SESSION_SEEDS)} sessions, "
          + ", ".join(f"{sid} {int(ev.valid.sum())} events -> "
                      f"{frames[sid].xy.shape[0]} frames"
                      for sid, ev in streams.items()), flush=True)
    print(f"plan: {len(segs)} key-frame segments of {[b - a for a, b in segs]} "
          f"frames in {len(caps)} capacity buckets {caps}; DSI "
          f"{dsi_cfg.shape} float32 = {4 * np.prod(dsi_cfg.shape) / 1e6:.1f} "
          f"MB per segment", flush=True)
    check(len(segs) >= MIN_SEGMENTS and len(caps) >= MIN_BUCKETS,
          f"need >= {MIN_SEGMENTS} segments in >= {MIN_BUCKETS} buckets")
    return scene, traj, streams, frames


def check_sane(res, dsi_cfg: DSIConfig, what: str) -> None:
    check(len(res.segments) >= MIN_SEGMENTS, f"{what}: {len(res.segments)} segments")
    for k, seg in enumerate(res.segments):
        depth, mask = np.asarray(seg.depth_map.depth), np.asarray(seg.depth_map.mask)
        check(tuple(seg.dsi.shape) == dsi_cfg.shape,
              f"{what}: segment {k} DSI shape {seg.dsi.shape}")
        check(mask.shape == dsi_cfg.shape[1:] and mask.any(),
              f"{what}: segment {k} has no semi-dense pixels")
        check(bool(np.isfinite(depth[mask]).all()),
              f"{what}: segment {k} non-finite depth")


def diff_results(a, b) -> list[str]:
    """Every way two EMVS results differ bitwise (empty when equal)."""
    if [s.frame_range for s in a.segments] != [s.frame_range for s in b.segments]:
        return [f"segment ranges {[s.frame_range for s in a.segments]} vs "
                f"{[s.frame_range for s in b.segments]}"]
    out = []
    for k, (sa, sb) in enumerate(zip(a.segments, b.segments)):
        dsi_a, dsi_b = np.asarray(sa.dsi), np.asarray(sb.dsi)
        if not np.array_equal(dsi_a, dsi_b):
            out.append(f"segment {k}: {int((dsi_a != dsi_b).sum())} DSI voxels")
        ma, mb = np.asarray(sa.depth_map.mask), np.asarray(sb.depth_map.mask)
        if not np.array_equal(ma, mb):
            out.append(f"segment {k}: {int((ma != mb).sum())} mask pixels")
        elif not np.array_equal(np.asarray(sa.depth_map.depth)[ma],
                                np.asarray(sb.depth_map.depth)[mb]):
            out.append(f"segment {k}: depth values")
    return out


def require_same(a, b, what: str) -> None:
    diffs = diff_results(a, b)
    check(not diffs, f"{what} differ: {'; '.join(diffs)}")
    print(f"  {what}: bitwise-equal over {len(a.segments)} segments", flush=True)


def serve(cam, dsi_cfg, opts, streams, traj, stream_cfg):
    """Sessions interleaved chunk by chunk: push -> poll, then flush."""
    engine = MultiStreamEngine(cam, dsi_cfg, opts, stream_cfg)
    sessions = {sid: engine.add_session(sid, traj=traj) for sid in streams}
    feeds = {sid: list(iter_event_chunks(ev, CHUNK_FRAMES * EVENTS_PER_FRAME))
             for sid, ev in streams.items()}
    for k in range(max(len(f) for f in feeds.values())):
        for sid, chunks in feeds.items():
            if k < len(chunks):
                sessions[sid].push(chunks[k])
        engine.poll()
    results = engine.flush()
    d = engine.stats["dispatcher"]
    print(f"  engine: {d['segments']} segments in {d['dispatches']} dispatches "
          f"({d['cross_stream_dispatches']} cross-stream)", flush=True)
    return results


def on_host():
    """Run what follows on the host CPU; inputs must be host arrays."""
    return jax.default_device(jax.devices("cpu")[0])


def host_ground_truth(cam, scene, res) -> list:
    """Each segment's ground-truth depth, z-buffered on the host CPU."""
    with on_host():
        return [jax.device_get(ground_truth_depth(
                    cam, scene, jax.device_get(seg.T_w_ref)))
                for seg in res.segments]


def mean_absrel(res, gts) -> float:
    with on_host():
        return float(np.mean([
            float(absrel(*jax.device_get((s.depth_map.depth, s.depth_map.mask)),
                         gt, gt_mask))
            for s, (gt, gt_mask) in zip(res.segments, gts)]))


def require_close(chip, cpu, gts, what: str) -> None:
    """The chip's depth maps against the host CPU's float32 reference.

    Bitwise equality across platforms is not expected (the TPU's float32
    division and fused multiply-adds round differently, so an event on a
    voxel boundary can land one pixel over), but the maps must agree far
    inside the gap that unpinned bf16 geometry opened (see PERF.md).
    """
    check([s.frame_range for s in chip.segments]
          == [s.frame_range for s in cpu.segments], f"{what}: segment ranges")
    voxels = flips = cpu_px = 0
    rel = []
    for a, b in zip(chip.segments, cpu.segments):
        voxels += int((np.asarray(a.dsi) != np.asarray(b.dsi)).sum())
        ma, mb = np.asarray(a.depth_map.mask), np.asarray(b.depth_map.mask)
        flips += int((ma != mb).sum())
        cpu_px += int(mb.sum())
        both = ma & mb
        da, db = (np.asarray(s.depth_map.depth)[both] for s in (a, b))
        rel.extend(np.abs(da - db) / db)
    total = np.prod(chip.segments[0].dsi.shape) * len(chip.segments)
    err_chip, err_cpu = mean_absrel(chip, gts), mean_absrel(cpu, gts)
    print(f"  {what}: {voxels} of {total} DSI voxels differ, {flips} of "
          f"{cpu_px} mask pixels, mean relative depth gap "
          f"{float(np.mean(rel))!r}; AbsRel chip {err_chip!r}, host CPU "
          f"{err_cpu!r}", flush=True)
    check(voxels <= MAX_VOXEL_FRAC * total, f"{what}: {voxels} DSI voxels differ")
    check(flips <= MAX_MASK_FRAC * cpu_px, f"{what}: {flips} mask pixels differ")
    check(float(np.mean(rel)) <= MAX_DEPTH_GAP, f"{what}: depth gap")
    check(abs(err_chip - err_cpu) <= MAX_ABSREL_GAP, f"{what}: AbsRel gap")


def one_chip(cam, dsi_cfg) -> None:
    scene, traj, streams, frames = phase(
        "inputs", lambda: make_inputs(cam, dsi_cfg))
    first = f"seed{SESSION_SEEDS[0]}"

    offline = {}
    for q in (False, True):
        offline[q] = phase(f"phase 1 offline quantized={q}", lambda: run_emvs(
            cam, dsi_cfg, frames[first], options(quantized=q)))
        check_sane(offline[q], dsi_cfg, f"offline quantized={q}")

    served_opts = options(quantized=True)
    refs = {first: offline[True]}
    for sid in streams:
        if sid not in refs:
            refs[sid] = phase(f"phase 2 offline reference {sid}", lambda: run_emvs(
                cam, dsi_cfg, frames[sid], served_opts))
    served = phase("phase 2 served", lambda: serve(
        cam, dsi_cfg, served_opts, streams, traj,
        StreamConfig(events_per_frame=EVENTS_PER_FRAME)))
    for sid in streams:
        require_same(served[sid], refs[sid], f"served {sid} vs offline")

    for q in (False, True):
        kern = phase(f"phase 3 kernel quantized={q}", lambda: run_emvs(
            cam, dsi_cfg, frames[first], options(
                quantized=q, formulation="kernel",
                kernel_interpret=KERNEL_INTERPRET)))
        require_same(kern, offline[q], f"compiled kernel vs matmul quantized={q}")

    gts = host_ground_truth(cam, scene, offline[False])
    host_frames = jax.device_get(frames[first])
    for q in (False, True):
        with on_host():
            cpu = phase(f"phase 4 host-CPU scatter reference quantized={q}",
                        lambda: run_emvs(cam, dsi_cfg, host_frames,
                                         options(quantized=q,
                                                 formulation="scatter")))
        check(cpu.segments[0].dsi.devices() == {jax.devices("cpu")[0]},
              "the host reference did not run on the host")
        require_close(offline[q], cpu, gts, f"chip vs host CPU quantized={q}")
    bil = {f: phase(f"phase 4 bilinear {f}", lambda: run_emvs(
        cam, dsi_cfg, frames[first], options(voting="bilinear", formulation=f)))
        for f in ("matmul", "scatter")}
    gap = max(float(np.max(np.abs(np.asarray(a.dsi) - np.asarray(b.dsi))))
              for a, b in zip(bil["matmul"].segments, bil["scatter"].segments))
    print(f"  bilinear max |matmul - scatter| DSI: {gap!r}", flush=True)
    check(gap <= MAX_BILINEAR_GAP, f"bilinear matmul vs scatter gap {gap!r}")


def four_chips(cam, dsi_cfg) -> None:
    from repro.distributed.emvs import make_segment_mesh, process_segments_sharded

    _, traj, streams, frames = phase(
        "inputs", lambda: make_inputs(cam, dsi_cfg))
    first = f"seed{SESSION_SEEDS[0]}"
    opts = options(quantized=True)
    mesh = make_segment_mesh()
    print(f"  mesh: {mesh}", flush=True)

    refs = {sid: phase(f"one-chip batched {sid}", lambda: run_emvs(
        cam, dsi_cfg, frames[sid], opts)) for sid in streams}
    sharded = phase("run_emvs sharded", lambda: run_emvs(
        cam, dsi_cfg, frames[first], opts, sweep="sharded", mesh=mesh))
    require_same(sharded, refs[first], "run_emvs sharded vs batched")

    segs = plan_segments(frames[first], dsi_cfg, opts)
    cap = bucket_capacity(max(b - a for a, b in segs))
    group = [s for s in segs if bucket_capacity(s[1] - s[0]) == cap]
    group = (group * 4)[:4]  # one segment row per device
    dsis, _ = phase("sweep outputs", lambda: process_segments_sharded(
        cam, dsi_cfg, pad_segments(frames[first], group, cap), opts, mesh=mesh))
    print(f"  sharded sweep DSI {dsis.shape}: {dsis.sharding}; shards on "
          f"{[str(s.device) for s in dsis.addressable_shards]}", flush=True)
    check(len({s.device for s in dsis.addressable_shards}) == 4,
          "sharded sweep outputs do not span 4 devices")

    served = phase("served sharded", lambda: serve(
        cam, dsi_cfg, opts, streams, traj,
        StreamConfig(events_per_frame=EVENTS_PER_FRAME, sweep="sharded")))
    for sid in streams:
        require_same(served[sid], refs[sid], f"served sharded {sid} vs batched")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep over four chips")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']}); compile cache {cache_dir}", flush=True)
    cam, dsi_cfg = config()
    (four_chips if args.four_chips else one_chip)(cam, dsi_cfg)
    print(f"compile cache: {cache.hits} hits, {cache.misses} misses", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
