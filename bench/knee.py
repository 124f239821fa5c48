#!/usr/bin/env python3
"""Knee sweep: one cell's traffic at several per-camera event rates, to
find the highest rate the served path sustains.

    python3 bench/knee.py --workload davis240.fleet8.overload \\
        --rates 300000 400000 500000 --seconds 16 --seed 5

One process on the chip runs each rate in turn (a fresh engine each) and
prints one JSON line per rate: the offered Mev/s, depth maps emitted
against segments due, how late packets were pushed in the first and the
second half of the window, the segments still queued at the close, and
the cells' own metric readers (`bench/metrics/`) on the same run. A rate
is sustained when the lateness does not grow and the queue does not
build. The cells' rates are set from these sweeps (PERF.md); the
benchmark's runs never run it.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from run import BENCH, ROOT, accelerators, enable_compile_cache, load_cell, log  # noqa: E402

READ = ("mev_s", "host_busy_pct", "latency_p50_ms", "latency_p95_ms",
        "ingest_lag_ms_p95", "queue_wait_ms_mean", "bucket_fill_pct")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    devices = accelerators(cell["chips"])
    enable_compile_cache()
    from harness import cell as cell_lib
    from harness import serve

    for rate in args.rates:
        m = dict(mix, rate_ev_s=rate)
        run = serve.drive(config, m, args.seed, args.seconds, trace_dir=None,
                          devices=devices, t_process0=time.perf_counter(),
                          log=log)
        view = cell_lib.make_view(run)
        w0, w1 = run["window"]
        mid = 0.5 * (w0 + w1)
        lags = [(d, lag) for d, lag in run["lags"] if w0 <= d < w1]
        first = [lag for d, lag in lags if d < mid]
        second = [lag for d, lag in lags if d >= mid]
        print(json.dumps({
            "workload": args.workload, "rate_ev_s": rate,
            "offered_mev_s": mix["cameras"] * rate / 1e6,
            "maps": len(view.emitted), "due": cell_lib.attempted(run),
            "lag_p95_first_s": float(np.percentile(first, 95)) if first else None,
            "lag_p95_second_s": float(np.percentile(second, 95)) if second else None,
            "pending_at_close": view.stats_close["pending_segments"],
            **{name: cell_lib.reader(name)(view) for name in READ},
            "compiles_in_window": run["compiles_in_window"],
            "setup_s": run["setup_s"]}), flush=True)
        del run, view
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
