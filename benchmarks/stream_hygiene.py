"""Ingest-hygiene benchmark: what the guard costs, and what it survives.

Three parts, one `"stream_hygiene"` section in `BENCH_emvs.json`:

  * CLEAN-STREAM OVERHEAD — the same trickle stream (per-frame event
    chunks, the worst case for per-chunk guard overhead) through the
    streaming engine with `hygiene="off"` vs the default
    `hygiene="raise"` (watermark + monotonicity + duplicate digest +
    out-of-bounds checks on every chunk), measured WARM (every sweep
    variant precompiled) as best-of-N wall time. The gate: the guard
    may cost at most `max_overhead` of end-to-end time — 5% full-size
    per the acceptance criteria, a loose crash barrier on the
    sub-second `--dry-run` smoke whose timings jitter ~10% even idle.
    A scrub-only microbenchmark (Mevents/s through `StreamHygiene.scrub`
    alone) rides along as a timing-noise-resistant secondary.

  * ADVERSARIAL GRID — every `simulator.corrupt_stream` mode through
    the full engine under `hygiene="raise"` and `"reorder"`: each run
    must either be REJECTED LOUDLY (the expected typed
    `StreamHygieneError` subclass) or produce results bitwise-equal to
    the clean stream's (reorder absorbing the misordering inside its
    slack). Structural — no timing — so CI noise cannot flip it.

  * HOT-PIXEL STORM SURVIVAL — a `corrupt_stream("hot_pixel")` burst
    under `hygiene="drop"` with a per-pixel rate limit: the engine must
    SURVIVE (flush cleanly, produce segments) while shedding the storm
    (dropped hot-pixel events counted in stats), the
    degrade-gracefully mode a production rig with a damaged sensel
    needs.

Sections persist to BENCH_emvs.json BEFORE the gates assert (the repo's
artifact-first contract: a failing gate still ships the numbers that
explain it); `ci.yml` re-checks the gates from the artifact.

    PYTHONPATH=src python benchmarks/stream_hygiene.py [--dry-run]
"""
from __future__ import annotations

import argparse
import time
import warnings

import numpy as np

try:  # script invocation (python benchmarks/stream_hygiene.py)
    from _emvs_common import update_bench_json
    from streaming_latency import (
        _assert_bitwise,
        _precompile_variants,
        build_sequence,
    )
except ImportError:  # module invocation
    from benchmarks._emvs_common import update_bench_json
    from benchmarks.streaming_latency import (
        _assert_bitwise,
        _precompile_variants,
        build_sequence,
    )

from repro.core.pipeline import EMVSOptions, plan_segments, run_emvs
from repro.events.aggregation import aggregate
from repro.events.simulator import EVENT_CORRUPTIONS, corrupt_stream
from repro.events.stream_hygiene import (
    DuplicateChunkError,
    HotPixelError,
    HygieneConfig,
    NonMonotoneEventError,
    OutOfBoundsEventError,
    StreamHygiene,
    StreamHygieneError,
    StreamHygieneWarning,
    StreamOverlapError,
)
from repro.serving.emvs_stream import (
    EMVSStreamEngine,
    StreamConfig,
    iter_event_chunks,
)

# expected response per (corruption mode, hygiene policy): an error type
# (must raise exactly it) or "bitwise" (must reproduce the clean result)
GRID_EXPECT = {
    ("shuffle_events", "raise"): NonMonotoneEventError,
    ("swap_chunks", "raise"): StreamOverlapError,
    ("duplicate_chunk", "raise"): DuplicateChunkError,
    ("out_of_bounds", "raise"): OutOfBoundsEventError,
    ("hot_pixel", "raise"): HotPixelError,
    ("shuffle_events", "reorder"): "bitwise",
    ("swap_chunks", "reorder"): "bitwise",
    ("duplicate_chunk", "reorder"): DuplicateChunkError,
    ("out_of_bounds", "reorder"): OutOfBoundsEventError,
    ("hot_pixel", "reorder"): HotPixelError,
}
HOT_PIXEL_LIMIT = 24
HOT_PIXEL_BURST = 96


def _stream_once(cam, dsi_cfg, traj, opts, scfg, chunks):
    engine = EMVSStreamEngine(cam, dsi_cfg, traj, opts, scfg)
    t0 = time.perf_counter()
    for c in chunks:
        engine.push(c)
    res = engine.flush()
    return res, time.perf_counter() - t0, engine.stats


def clean_overhead(cam, dsi_cfg, traj, ev, opts, e_frame, frames,
                   ref, repeats: int) -> dict:
    """Warm best-of-N trickle runs, hygiene off vs raise (both bitwise)."""
    segs = plan_segments(frames, dsi_cfg, opts)
    chunks = list(iter_event_chunks(ev, e_frame))
    cfgs = {p: StreamConfig(events_per_frame=e_frame, hygiene=p)
            for p in ("off", "raise")}
    _precompile_variants(cam, dsi_cfg, frames, segs, opts,
                         next(iter(cfgs.values())))
    best = {p: float("inf") for p in cfgs}
    for _ in range(repeats):  # round-robin so machine noise spreads evenly
        for p, scfg in cfgs.items():
            res, dt, _ = _stream_once(cam, dsi_cfg, traj, opts, scfg, chunks)
            _assert_bitwise(res, ref, f"hygiene={p} trickle")
            best[p] = min(best[p], dt)
    # scrub-only microbenchmark: the guard's own per-event cost, no engine
    hyg = StreamHygiene(HygieneConfig(policy="raise"),
                        width=cam.width, height=cam.height)
    n_events = int(ev.t.shape[0])
    t0 = time.perf_counter()
    for c in chunks:
        hyg.scrub(c)
    scrub_s = time.perf_counter() - t0
    return {
        "off_best_s": round(best["off"], 4),
        "raise_best_s": round(best["raise"], 4),
        "overhead_ratio": round(best["raise"] / best["off"] - 1.0, 4),
        "scrub_mevents_per_s": round(n_events / scrub_s / 1e6, 3),
        "chunks": len(chunks),
        "events": n_events,
    }


def adversarial_grid(cam, dsi_cfg, traj, ev, opts, e_frame, ref) -> list[dict]:
    """Every corruption x {raise, reorder} through the full engine:
    rejected loudly with the expected type, or bitwise-equal to clean."""
    rows = []
    for mode in EVENT_CORRUPTIONS:
        bad = corrupt_stream(ev, mode, e_frame, seed=7,
                             width=cam.width, height=cam.height,
                             burst=HOT_PIXEL_BURST)
        spans = [float(np.asarray(c.t).max() - np.asarray(c.t).min())
                 for c in bad if c.t.shape[0]]
        slack = 2.0 * max(spans)
        for policy in ("raise", "reorder"):
            hyg = HygieneConfig(policy=policy, reorder_slack=slack,
                                hot_pixel_limit=HOT_PIXEL_LIMIT)
            scfg = StreamConfig(events_per_frame=e_frame, hygiene=hyg)
            want = GRID_EXPECT[(mode, policy)]
            outcome = None
            try:
                res, _, _ = _stream_once(cam, dsi_cfg, traj, opts, scfg, bad)
                _assert_bitwise(res, ref, f"{mode}/{policy}")
                outcome = "bitwise"
            except StreamHygieneError as e:
                outcome = f"raised:{type(e).__name__}"
            expected = (want if isinstance(want, str)
                        else f"raised:{want.__name__}")
            rows.append({"mode": mode, "policy": policy,
                         "outcome": outcome, "expected": expected,
                         "ok": outcome == expected})
            print(f"  {mode:<16}{policy:<9}{outcome:<28}"
                  f"{'OK' if outcome == expected else 'UNEXPECTED'}")
    return rows


def storm_survival(cam, dsi_cfg, traj, ev, opts, e_frame, ref) -> dict:
    """A hot-pixel storm under hygiene="drop": the engine must survive,
    shed the storm, and keep producing depth maps."""
    bad = corrupt_stream(ev, "hot_pixel", e_frame, seed=7,
                         width=cam.width, height=cam.height,
                         burst=HOT_PIXEL_BURST)
    hyg = HygieneConfig(policy="drop", hot_pixel_limit=HOT_PIXEL_LIMIT)
    scfg = StreamConfig(events_per_frame=e_frame, hygiene=hyg)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", StreamHygieneWarning)
        res, dt, stats = _stream_once(cam, dsi_cfg, traj, opts, scfg, bad)
    h = stats["hygiene"]
    return {
        "burst_events": HOT_PIXEL_BURST,
        "hot_pixel_limit": HOT_PIXEL_LIMIT,
        "dropped_hot_pixel": int(h["dropped_hot_pixel"]),
        "segments": len(res.segments),
        "clean_segments": len(ref.segments),
        "end_to_end_s": round(dt, 3),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--dry-run", action="store_true",
                        help="CI-sized smoke (same asserts, looser gate)")
    parser.add_argument("--json-out", default=None,
                        help="BENCH json path (default: repo BENCH_emvs.json)")
    args = parser.parse_args()

    cam, traj, ev, e_frame, dsi_cfg = build_sequence(args.dry_run)
    opts = EMVSOptions()
    frames = aggregate(cam, ev, traj, events_per_frame=e_frame)
    ref = run_emvs(cam, dsi_cfg, frames, opts)
    print(f"sequence: {int(ev.t.shape[0])} events, "
          f"{int(frames.xy.shape[0])} frames, {len(ref.segments)} segments")

    repeats = 3 if args.dry_run else 5
    overhead = clean_overhead(cam, dsi_cfg, traj, ev, opts, e_frame,
                              frames, ref, repeats)
    print(f"\nclean-stream overhead (warm, best of {repeats}): "
          f"off={overhead['off_best_s']}s raise={overhead['raise_best_s']}s "
          f"-> {100 * overhead['overhead_ratio']:.1f}% "
          f"(scrub alone: {overhead['scrub_mevents_per_s']} Mevents/s)")

    print("\nadversarial grid (full engine):")
    grid = adversarial_grid(cam, dsi_cfg, traj, ev, opts, e_frame, ref)

    storm = storm_survival(cam, dsi_cfg, traj, ev, opts, e_frame, ref)
    print(f"\nhot-pixel storm under drop: {storm['segments']} segments "
          f"(clean: {storm['clean_segments']}), "
          f"{storm['dropped_hot_pixel']} storm events shed")

    # the acceptance gate is 5% on full-size runs; the sub-second smoke
    # jitters ~10% even on an idle machine, so its timing gate is only a
    # crash barrier — the structural grid/storm gates stay strict there
    max_overhead = 0.5 if args.dry_run else 0.05
    gate = {
        "max_overhead": max_overhead,
        "overhead_ratio": overhead["overhead_ratio"],
        "grid_ok": all(r["ok"] for r in grid),
        "storm_survived": storm["segments"] == storm["clean_segments"]
        and storm["dropped_hot_pixel"] > 0,
    }
    path = update_bench_json("stream_hygiene", {
        "dry_run": bool(args.dry_run),
        "overhead": overhead,
        "adversarial_grid": grid,
        "hot_pixel_storm": storm,
        "gate": gate,
    }, path=args.json_out)
    print(f"\nwrote {path}")

    # gate LAST, after every section is persisted
    assert gate["grid_ok"], (
        "adversarial grid: unexpected outcome(s): "
        + str([r for r in grid if not r["ok"]]))
    assert gate["storm_survived"], f"hot-pixel storm not survived: {storm}"
    assert overhead["overhead_ratio"] <= max_overhead, (
        f"hygiene overhead {100 * overhead['overhead_ratio']:.1f}% exceeds "
        f"the {100 * max_overhead:.0f}% gate")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
