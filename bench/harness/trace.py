"""Reduction of a profiler trace to device busy time, program times and
idle gaps attributed to what the host was doing.

The benchmark writes its own host spans into the trace
(`jax.profiler.TraceAnnotation`, names starting with `bench.`), so they
share the device's clock: `bench.window` marks the measured window and
`bench.push`, `bench.poll`, `bench.fetch`, `bench.wait` what the client
thread was in. A device is every plane `/device:<platform>:<n>`; its
"XLA Modules" line holds one event per program execution and its
"XLA Ops" line one per operation.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

# Programs that are the sweep (vote, store, detect, median filter): the
# served path's batched jit.
SWEEP_PROGRAMS = (r"process_segments_batched",)
GAP_LABELS = {"bench.push": "push", "bench.poll": "poll",
              "bench.fetch": "fetch", "bench.wait": "generator wait"}
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_FINGERPRINT = re.compile(r"\(\d+\)$")  # "jit_add(5131...)" -> "jit_add"


@dataclasses.dataclass
class Trace:
    """Events in ns on one clock: per device its module and op events,
    and the benchmark's host spans; each event is (name, start, end)."""

    modules: dict[str, list[tuple[str, float, float]]]
    ops: dict[str, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]


def load(path: str) -> Trace:
    """Read an `.xplane.pb` file (or a gzipped one, `.xplane.pb.gz`)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Modules":
                    modules[plane.name] = evs
                elif line.name == "XLA Ops":
                    ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name.startswith("bench."))
    return Trace(modules, ops, spans)


def _clip(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]


def _union(evs) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def is_sweep(name: str) -> bool:
    return any(re.search(p, name) for p in SWEEP_PROGRAMS)


def reduce(trace: Trace, top: int = 10) -> dict | None:
    """Seconds of the measured window: its length, device busy time
    (mean over devices), sweep-program and other device time (summed
    over devices), the programs that took most device time and the idle
    gaps by host activity. None when the trace holds no window or no
    device."""
    win = [(s, e) for n, s, e in trace.spans if n == "bench.window"]
    devices = sorted(set(trace.modules) | set(trace.ops))
    if not win or not devices:
        return None
    lo, hi = win[0]
    host = sorted(((GAP_LABELS[n], s, e) for n, s, e in trace.spans
                   if n in GAP_LABELS), key=lambda x: x[1])
    starts = [s for _, s, _ in host]
    busy, sweep, other = [], 0.0, 0.0
    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    longest: list[tuple[str, float]] = []
    for dev in devices:
        ops = _clip(trace.ops.get(dev) or trace.modules.get(dev, []), lo, hi)
        mods = _clip(trace.modules.get(dev, []), lo, hi)
        occupied = _union(ops + mods)
        busy.append(sum(e - s for s, e in occupied))
        sweep_iv = _union([m for m in mods if is_sweep(m[0])])
        sweep_dev = sum(e - s for s, e in sweep_iv)
        sweep += sweep_dev
        other += busy[-1] - sweep_dev
        for name, s, e in mods or ops:
            name = _FINGERPRINT.sub("", name)
            op_time[name] = op_time.get(name, 0.0) + (e - s)
        prev = lo
        for s, e in occupied + [(hi, hi)]:
            if s > prev:
                label = _label(host, starts, prev, s)
                gap_time[label] = gap_time.get(label, 0.0) + (s - prev)
                longest.append((label, s - prev))
            prev = max(prev, e)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "devices": len(devices),
        "busy_s": sum(busy) / len(busy) * ns,
        "sweep_s": sweep * ns,
        "other_s": other * ns,
        "device_ops": [[n, t * ns] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_by_host": {k: v * ns for k, v in gap_time.items()},
        "idle_gaps": [[n, t * ns] for n, t in
                      sorted(longest, key=lambda x: -x[1])[:top]],
    }


def _label(host, starts, a: float, b: float) -> str:
    """The host activity that overlaps [a, b) most ("other" if none).
    Host spans come from one thread, so they do not overlap: walk back
    from the last span that starts before b."""
    best, label = 0.0, "other"
    i = bisect.bisect_left(starts, b) - 1
    while i >= 0 and host[i][2] > a:
        name, s, e = host[i]
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, label = ov, name
        i -= 1
    return label
