#!/usr/bin/env python3
"""The control of `correct`: the plain reference in bfloat16, put in the
served path's place, compared with the float32 reference exactly as a
run compares the served maps.

    python3 bench/control.py --workload davis240.fleet8.overload \\
        --seeds 11 12 13 [--seconds 30]

For each seed it draws the cell's traffic, takes the segments whose last
event falls in the window, samples them as a run does (the longest and
seeded others) and prints the compared numbers beside the configuration's
limits. The control must fail at least one of them. It runs on the host
CPU; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import check, reference, serve  # noqa: E402
from harness.traffic import make_cameras  # noqa: E402
from run import load_cell  # noqa: E402


def control_numbers(config: dict, mix: dict, seed: int, seconds: float) -> dict:
    """Compared numbers of the bfloat16 reference against the float32 one
    on the segments a run of `seed` would check, as the configuration's
    driver cuts and votes them."""
    driver = serve.driver(config)
    setup = reference.Setup.from_config(config)
    cameras = make_cameras(config, mix, seed)
    w0 = float(mix["warmup_s"])
    plan = driver.plan(cameras, mix, setup, w0 + seconds + 2.0)
    due = [SimpleNamespace(stream=i, frames=seg)
           for i, (segs, last) in enumerate(zip(plan.segments, plan.last_due))
           for seg, d in zip(segs, last) if w0 <= d < w0 + seconds]
    gaps = {n: 0.0 for n in check.NUMBERS}
    for m in check.sample(due, int(mix["check_segments"]), seed):
        inputs = driver.reference_inputs(setup, cameras, plan, m)
        good = driver.reference(setup, *inputs)
        low = driver.reference(setup, *inputs, lowp=True)
        for k, v in check.compare(setup, *low, *good).items():
            gaps[k] = max(gaps[k], v)
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    seconds = args.seconds or bench["run_seconds"]
    failed_all = True
    for seed in args.seeds:
        gaps = control_numbers(config, mix, seed, seconds)
        failed = not check.judge(gaps, config["limits"])
        failed_all &= failed
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": failed, "numbers": gaps,
                          "limits": config["limits"]}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    raise SystemExit(main())
