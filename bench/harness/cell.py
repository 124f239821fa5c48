"""One run of one cell: drive the served path, check it, read the metrics,
and build the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import multiprocessing
import shutil
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

from harness import check, serve, work
from harness import trace as trace_lib

BENCH = Path(__file__).resolve().parents[1]
METRICS = BENCH / "metrics"
PEAKS = BENCH / "harness" / "peaks.json"
REFERENCE_WORKERS = 4


@dataclasses.dataclass
class RunView:
    """What a metric reader may read of one run (times in seconds)."""

    window_s: float
    emitted: list  # maps emitted in the window (serve.Emitted)
    lags: list[float]  # lateness of each packet due in the window
    spans: list[tuple[str, float, float]]  # client spans inside the window
    stats_open: dict  # dispatcher counters when the window opened
    stats_close: dict  # ... and when it closed
    memory_peak_bytes: int | None
    setup_s: float
    trace: dict | None  # harness.trace.reduce of the window, traced runs only
    seg_least_s: list[float]  # roofline time of each emitted segment

    @property
    def latencies_s(self) -> list[float]:
        return [m.latency for m in self.emitted]

    def delta(self, key: str) -> float:
        return self.stats_close[key] - self.stats_open[key]


def make_view(run: dict) -> RunView:
    """What the readers see of a `serve.drive` result: the maps emitted,
    the packets due and the client's spans inside the window. The trace
    and the roofline times are added by the caller of a traced run."""
    w0, w1 = run["window"]
    wall = run["window_wall"]
    stats_open, stats_close = run["stats"]
    return RunView(
        window_s=w1 - w0,
        emitted=[m for m in run["emitted"] if w0 <= m.t_emit < w1],
        lags=[lag for due, lag in run["lags"] if w0 <= due < w1],
        spans=[(n, max(a, wall[0]), min(b, wall[1])) for n, a, b in run["spans"]
               if b > wall[0] and a < wall[1]],
        stats_open=stats_open, stats_close=stats_close,
        memory_peak_bytes=run["memory_peak_bytes"], setup_s=run["setup_s"],
        trace=None, seg_least_s=[])


def attempted(run: dict) -> int:
    """Segments whose last event was due in the window."""
    w0, w1 = run["window"]
    return sum(int(np.sum((d >= w0) & (d < w1))) for d in run["plan"].last_due)


def reader(name: str):
    """The reader of metric `name`: `metrics/<name>.py`, else the file of
    the name up to its first dot (`sweep_roofline.lat` ->
    `metrics/sweep_roofline.py`)."""
    for stem in (name, name.split(".")[0]):
        path = METRICS / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under {METRICS}")


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of `cell` reports: with --trace 0 its end-to-end
    metrics, with --trace 1 its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved else [])]


def _reference(driver_file: str, setup, inputs: tuple):
    """The reference of one map, in a worker that loads the driver anew."""
    return serve.load_driver(driver_file).reference(setup, *inputs)


def run_cell(bench: dict, cell: str, config: dict, mix: dict, seed: int,
             seconds: float, traced: bool, devices, t_process0: float,
             work_dir: Path, log=print, peaks: dict | None = None,
             drivers: Path = serve.DRIVERS) -> dict:
    """One run; returns the result line's object. `peaks` replaces the
    table of chip peaks (tests on the host CPU); `drivers` is where the
    configuration's driver is found."""
    trace_dir = None
    if traced:
        trace_dir = work_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = serve.drive(config, mix, seed, seconds,
                      trace_dir=None if trace_dir is None else str(trace_dir),
                      devices=devices, t_process0=t_process0, log=log,
                      drivers=drivers)
    view = make_view(run)
    emitted = view.emitted
    setup, driver = run["setup"], run["driver"]

    # correctness: boundaries of every map, and a seeded sample recomputed
    known = [set(s) for s in run["plan"].segments]
    unmatched = sum(1 for m in run["emitted"]
                    if m.frames not in known[m.stream])
    picked = check.sample(emitted, int(mix["check_segments"]), seed)
    program = [(m, run["served"].dsi(m.result)) for m in picked]
    n_attempted = attempted(run)
    del run["served"], run["emitted"]
    for m in emitted:
        m.result = None

    reduced = None
    if traced:
        pb = sorted(trace_dir.rglob("*.xplane.pb"))
        if pb:
            t = time.perf_counter()
            reduced = trace_lib.reduce(trace_lib.load(str(pb[-1])))
            log(f"trace reduced in {time.perf_counter() - t:.1f} s "
                f"({pb[-1].stat().st_size / 1e6:.1f} MB)")
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    gaps = {n: 0.0 for n in check.NUMBERS}
    gaps["segments_unmatched"] = float(unmatched)
    inputs = [driver.reference_inputs(setup, run["cameras"], run["plan"], m)
              for m, _ in program]
    # one process per sampled segment: the reference is NumPy on the host
    # and imports nothing that would reach for the chip
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max(1, min(len(inputs), REFERENCE_WORKERS)),
                             mp_context=ctx) as pool:
        refs = list(pool.map(_reference, [driver.__file__] * len(inputs),
                             [setup] * len(inputs), inputs))
    for (m, dsi), r in zip(program, refs):
        got = check.compare(setup, dsi, m.depth, m.mask, *r)
        for k, v in got.items():
            gaps[k] = max(gaps[k], v)
    log(f"reference: {len(program)} segments in {time.perf_counter() - t:.1f} s")
    limits = config["limits"]
    correct = (bool(program) and run["failed"] == 0
               and check.judge(gaps, limits))

    peak = None
    kind = devices[0].device_kind
    if peaks is None:
        peaks = json.loads(PEAKS.read_text())
    if traced:
        if kind not in peaks:
            raise KeyError(f"device kind {kind!r} is not in {PEAKS}")
        peak = peaks[kind]
    least = []
    if peak is not None:
        for m in emitted:
            ops, nbytes = driver.map_work(config, m)
            least.append(work.least_time_s(ops, nbytes, peak)[0])
    view.trace, view.seg_least_s = reduced, least

    metrics = {}
    for m in cell_metrics(bench, cell, traced):
        value = reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    side = {}
    for m in bench["end_to_end"] + (bench["per_layer"] if traced else []):
        if m["name"] not in metrics:
            value = reader(m["name"])(view)
            if value is not None:
                side[m["name"]] = value
    log(f"not judged in this cell: {json.dumps(side)}")
    log(f"window: {len(emitted)} depth maps emitted, {n_attempted} segments "
        f"due, {run['failed']} pushes refused, {run['compiles_in_window']} "
        f"programs compiled or loaded inside the window "
        f"({run['compiles_total']} in all), pending segments at close "
        f"{view.stats_close['pending_segments']}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": n_attempted,
           "failed": run["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        totals = sorted(reduced["idle_by_host"].items(), key=lambda x: -x[1])
        out["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": ([[f"all gaps during {k}", v] for k, v in totals]
                          + [[f"one gap during {k}", v]
                             for k, v in reduced["idle_gaps"]])[:10]}
    out["checks"] = {n: {"value": gaps[n], "limit": limits[n]}
                     for n in check.NUMBERS}
    return out
