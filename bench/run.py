#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload davis240.fleet8.overload --seed 7 \\
        --seconds 30 --trace 0

The cell (BENCHMARK.json `workloads`) names a configuration
(`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`); the configuration's driver
(`bench/harness/drivers/<driver>.py`, `sessions` where it names none)
serves it; each metric is read by `bench/metrics/<name>.py`. Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result. The last
line of stdout is the result as one JSON object; the numbers compared
for `correct` are also the last lines of stderr.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = BENCH / ".work"  # traces while they are reduced (gitignored)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"unknown workload {name!r}: expected one of {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, mix


def accelerators(chips: int):
    """The first `chips` TPU devices; exits non-zero on anything else."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU found (JAX backend is {devs[0].platform!r}); "
                 "nothing was run")
    if len(devs) < chips:
        sys.exit(f"this cell needs {chips} TPU chip(s), JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> Path:
    """JAX's persistent compilation cache at one fixed path: the one
    JAX_COMPILATION_CACHE_DIR names, else `<checkout>/.jax_cache`."""
    import jax

    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    devices = accelerators(cell["chips"])
    cache = enable_compile_cache()
    log(f"cell {cell['name']}: {devices[0].device_kind} x{len(devices)} "
        f"({devices[0].platform}); seed {args.seed}; compile cache {cache}")
    from harness.cell import run_cell

    out = run_cell(bench, cell["name"], config, mix, args.seed, args.seconds,
                   bool(args.trace), devices, T_PROCESS0, WORK, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
