#!/usr/bin/env python3
"""Where the served path's time goes: one traced run of a cell, reduced
by the program's own spans.

    python3 bench/spans.py --workload davis240.fleet8.overload --seed 7 \\
        --seconds 30

Drives the cell once as `bench/run.py --trace 1` does (same traffic,
warm-up and profiler options) and prints one JSON line: the completed
Mev/s and client busy share of the traced window, `harness.trace`'s
device busy, sweep and idle times, and `harness.program_spans`' view of
the `emvs.*` spans: per span its count, time and self time, the idle
gaps labelled by host activity and innermost span (`push:emvs.plan`),
and the per-layer numbers those spans give. It skips the check of
`correct`; the benchmark's runs never run it.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from run import BENCH, ROOT, WORK, accelerators, enable_compile_cache, load_cell, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    devices = accelerators(cell["chips"])
    enable_compile_cache()
    from harness import cell as cell_lib
    from harness import program_spans, serve
    from harness import trace as trace_lib

    trace_dir = WORK / "spans"
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = serve.drive(config, mix, args.seed, args.seconds,
                      trace_dir=str(trace_dir), devices=devices,
                      t_process0=T_PROCESS0, log=log)
    view = cell_lib.make_view(run)
    pb = str(sorted(trace_dir.rglob("*.xplane.pb"))[-1])
    trace = trace_lib.load(pb)
    reduced = trace_lib.reduce(trace)
    spans = program_spans.reduce(trace, program_spans.load(pb))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None or spans is None:
        sys.exit("the trace holds no measured window or no device")
    stats = spans["program_spans"]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind,
        "mev_s": cell_lib.reader("mev_s")(view),
        "host_busy_pct": cell_lib.reader("host_busy_pct")(view),
        "segments": view.delta("segments"),
        **{k: reduced[k] for k in ("window_s", "busy_s", "sweep_s", "other_s",
                                   "idle_by_host")},
        "idle_by_span": spans["idle_by_span"],
        "idle_gaps": spans["idle_gaps"],
        "per_layer": program_spans.per_layer(stats, view.delta("segments")),
        "program_spans": stats}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
