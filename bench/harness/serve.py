"""Drives one cell: the served EMVS path under open-loop camera traffic.

A driver (`harness/drivers/<name>.py`, named by the configuration's key
`driver`, `sessions` where it names none) builds the program and binds
the cell's cameras to it; this module is the part every driver shares.
A single client thread pushes each camera's packets when they are due
and polls the program while it waits; a depth map counts as emitted once
the driver has returned it and its depth and mask are on the host.
Set-up (traffic, the reference's segmentation, every program the cell's
traffic uses, and a warm-up stretch of the same traffic) ends when the
window opens; the window then runs for `seconds` of wall time.

A driver is a module with these functions (`stream` numbers what one
map is cut from: a camera, or a rig of them):

- `plan(cameras, mix, setup, until_s)`: the reference's view of the
  traffic before `until_s`, with `segments` (per stream, the closed
  segments `(first, end)` of frames, in close order) and `last_due` (per
  stream, the client time of each segment's last event).
- `serve(config, mix, cameras, plan, until_s, log)`: the program, built,
  bound to the cameras, and with every program compiled or loaded that
  the traffic before `until_s` can reach. It has `push(cam, xy, t,
  polarity, valid)` and `poll()`, each returning `(stream, result)`
  pairs; `fetch(stream, result)`, the map's `(frames, events, due,
  depth, mask)`: depth and mask on the host, `events` the events it was
  built from, `due` the client time of the last of them; `dsi(result)`,
  its DSI on the host; and `stats()`, the dispatcher's counters (the
  keys `RunView.delta` reads).
- `reference_inputs(setup, cameras, plan, m)`: the reference's inputs
  for the emitted map `m`, from the raw traffic; `reference(setup,
  *inputs, lowp=False)`: its `(dsi, depth, mask)`, NumPy on the host,
  with nothing of the program.
- `map_work(config, m)`: the `(operations, bytes)` the algorithm needs
  for the map `m` (`harness.work`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import math
import sys
import time
from pathlib import Path

import numpy as np

from harness import reference as ref
from harness.traffic import Packetizer, make_cameras

DRIVERS = Path(__file__).resolve().parent / "drivers"
DEFAULT_DRIVER = "sessions"
POLL_IDLE_S = 0.002  # poll the engine this often while waiting for packets
POLL_BUSY_S = 0.005  # ... and at least this often while pushing late ones
SLEEP_S = 0.0005
# recorded around every compile-or-load-from-cache of a program
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, with times."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t < b)


@dataclasses.dataclass
class Emitted:
    stream: int
    frames: tuple[int, int]
    events: int  # events the map was built from
    t_emit: float  # stream time on the client's clock
    latency: float
    depth: np.ndarray
    mask: np.ndarray
    result: object  # the driver's result (its DSI stays on the device)


@dataclasses.dataclass
class Spans:
    """The client thread's own spans: what it was doing, and when."""

    annotate: bool
    rows: list[tuple[str, float, float]] = dataclasses.field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.rows.append((name, t0, time.perf_counter()))


def driver(config: dict, drivers: Path = DRIVERS):
    """The driver the configuration names: `<drivers>/<name>.py`."""
    name = config.get("driver", DEFAULT_DRIVER)
    path = Path(drivers) / f"{name}.py"
    if not (name.isidentifier() and path.is_file()):
        have = sorted(f.name for f in Path(drivers).glob("*.py"))
        sys.exit(f"unknown driver {name!r}: {drivers} holds {have}")
    return load_driver(path)


def load_driver(path):
    """The driver module in the file `path`, loaded afresh."""
    path = Path(path)
    name = f"harness_driver_{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def drive(config: dict, mix: dict, seed: int, seconds: float, *,
          trace_dir: str | None, devices, t_process0: float,
          log=print, drivers: Path = DRIVERS) -> dict:
    """Run the cell once; returns everything the result line is made of."""
    import jax

    compiles = CompileCounter()
    drv = driver(config, drivers)
    setup = ref.Setup.from_config(config)
    warm, window = float(mix["warmup_s"]), float(seconds)
    t_close = warm + window
    cameras = make_cameras(config, mix, seed)
    the_plan = drv.plan(cameras, mix, setup, t_close + 2.0)
    served = drv.serve(config, mix, cameras, the_plan, t_close, log)
    log(f"set-up: {len(cameras)} cameras, lap {cameras[0].period:.3f} s of "
        f"{cameras[0].lap_events} events each; "
        f"{compiles.between(0, math.inf)} programs compiled or loaded")

    spans = Spans(annotate=trace_dir is not None)
    emitted: list[Emitted] = []
    lags: list[tuple[float, float]] = []  # (due, lag) per pushed packet
    failed = 0
    packetizers = [Packetizer(i, c, mix) for i, c in enumerate(cameras)]
    nxt = [p.next() for p in packetizers]
    stats_open = None

    def take(ready):
        with spans.span("bench.fetch"):
            for stream, res in ready:
                frames, events, due, depth, mask = served.fetch(stream, res)
                t_emit = time.perf_counter() - wall0
                emitted.append(Emitted(stream, frames, events, t_emit,
                                       t_emit - due, depth, mask, res))

    wall0 = time.perf_counter()
    window_open = window_close = win_ctx = None
    last_poll = -1.0
    while True:
        now = time.perf_counter() - wall0
        if window_open is None and now >= warm:
            if trace_dir is not None:
                opts_ = jax.profiler.ProfileOptions()
                opts_.python_tracer_level = 0  # the client's spans suffice
                opts_.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts_)
                win_ctx = jax.profiler.TraceAnnotation("bench.window")
                win_ctx.__enter__()
            window_open = time.perf_counter()
            stats_open = served.stats()
        if now >= t_close:
            window_close = time.perf_counter()
            break
        i = min(range(len(nxt)), key=lambda k: nxt[k].due)
        pkt = nxt[i]
        if pkt.due <= now:
            xy, t, pol, valid = cameras[i].events(pkt.g0, pkt.g1)
            with spans.span("bench.push"):
                try:
                    out = served.push(i, xy, t, pol, valid)
                except Exception as exc:  # the engine refused the packet
                    failed += 1
                    log(f"push refused: {type(exc).__name__}: {exc}")
                    out = []
            lags.append((pkt.due, now - pkt.due))
            nxt[i] = packetizers[i].next()
            if out:
                take(out)
            if now - last_poll < POLL_BUSY_S:
                continue
        if now - last_poll >= POLL_IDLE_S or pkt.due <= now:
            with spans.span("bench.poll"):
                ready = served.poll()
            last_poll = now
            if ready:
                take(ready)
        wait = min(nxt[i].due, t_close if window_open else warm) - (
            time.perf_counter() - wall0)
        if wait > 0:
            with spans.span("bench.wait"):
                time.sleep(min(wait, SLEEP_S))
    if win_ctx is not None:
        win_ctx.__exit__(None, None, None)
    stats_close = served.stats()
    memory = _memory_peak(devices)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return {
        "driver": drv, "cameras": cameras, "plan": the_plan, "setup": setup,
        "served": served,
        "emitted": emitted, "lags": lags, "failed": failed, "spans": spans.rows,
        "window": (warm, t_close),
        "setup_s": window_open - t_process0,
        "window_wall": (window_open, window_close),
        "stats": (stats_open, stats_close), "memory_peak_bytes": memory,
        "compiles_in_window": compiles.between(window_open, window_close),
        "compiles_total": len(compiles.times),
    }


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
