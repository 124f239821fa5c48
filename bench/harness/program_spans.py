"""The served path's own spans in a profiler trace, reduced beside
`harness.trace`.

The program writes nested host spans (`jax.profiler.TraceAnnotation`,
names starting with `emvs.`: `emvs.push` down to `emvs.harvest.sync`)
on the device trace's clock. This module reads them and gives, for the
window that `bench.window` marks:

- per span name, the spans that start in the window (`count`), their
  time clipped to it (`total_s`) and their self time (`self_s`: time no
  child span on the same thread covers);
- each device idle gap labelled `<bench label>:<span>` by the innermost
  program span that covers most of it, or by the plain `harness.trace`
  label where none overlaps it (`idle_by_span`, and the longest gaps);
- the per-layer numbers that read those spans.

`harness.trace.reduce` does not read these spans; `bench/spans.py`
runs a traced cell and prints both reductions.
"""
from __future__ import annotations

import bisect

from harness import trace as trace_lib

PREFIX = "emvs."


def load(path: str) -> list[tuple[str, float, float, int]]:
    """The program's spans of an `.xplane.pb` file (or a gzipped one,
    `.xplane.pb.gz`), as (name, start, end, thread) in ns; `thread`
    numbers the host lines."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out, thread = [], 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns, thread)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def tree(program, lo: float, hi: float):
    """Per span name its count, time and self time in [lo, hi) (seconds),
    and per thread its self intervals: sorted, non-overlapping (name,
    start, end), each instant under the innermost span covering it.
    Spans on one thread nest, as `with` blocks do."""
    stats: dict[str, dict] = {}
    selves: dict[int, list[tuple[str, float, float]]] = {}
    by_thread: dict[int, list] = {}
    for name, s, e, th in program:
        by_thread.setdefault(th, []).append((name, s, e))
    for th, evs in by_thread.items():
        evs.sort(key=lambda x: (x[1], -x[2]))
        own: list[tuple[str, float, float]] = []
        stack: list[list] = []  # [name, end, cursor]: self time resumes at cursor
        for name, s, e in evs:
            while stack and stack[-1][1] <= s:
                top = stack.pop()
                own.append((top[0], top[2], top[1]))
            if stack:  # the parent's self time pauses for this child
                parent = stack[-1]
                if s > parent[2]:
                    own.append((parent[0], parent[2], s))
                parent[2] = min(max(parent[2], e), parent[1])
            stack.append([name, e, s])
        while stack:
            top = stack.pop()
            own.append((top[0], top[2], top[1]))
        own = sorted((x for x in own if x[2] > x[1]), key=lambda x: x[1])
        selves[th] = own
        for name, s, e in evs:
            st = stats.setdefault(name, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
            st["count"] += int(lo <= s < hi)
            st["total_s"] += _clipped(s, e, lo, hi)
        for name, s, e in own:
            stats[name]["self_s"] += _clipped(s, e, lo, hi)
    for st in stats.values():
        st["total_s"] *= 1e-9
        st["self_s"] *= 1e-9
    return stats, selves


def _clipped(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def innermost(threads, a: float, b: float) -> str | None:
    """The span whose self time covers most of [a, b), over every
    thread's (self intervals, their starts); None when no program span
    overlaps it."""
    cover: dict[str, float] = {}
    for own, starts in threads:
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and own[i][2] > a:
            name, s, e = own[i]
            cover[name] = cover.get(name, 0.0) + min(b, e) - max(a, s)
            i -= 1
    return max(cover, key=cover.get) if cover else None


def reduce(trace: trace_lib.Trace, program, top: int = 10) -> dict | None:
    """`program_spans`, `idle_by_span` and the `top` longest idle gaps
    with the finer labels, for the window and the devices that
    `harness.trace.reduce` reads (its gaps, walked the same way). None
    when the trace holds no window or no device."""
    win = [(s, e) for n, s, e in trace.spans if n == "bench.window"]
    devices = sorted(set(trace.modules) | set(trace.ops))
    if not win or not devices:
        return None
    lo, hi = win[0]
    host = sorted(((trace_lib.GAP_LABELS[n], s, e) for n, s, e in trace.spans
                   if n in trace_lib.GAP_LABELS), key=lambda x: x[1])
    starts = [s for _, s, _ in host]
    stats, selves = tree(program, lo, hi)
    threads = [(own, [s for _, s, _ in own]) for own in selves.values()]
    idle: dict[str, float] = {}
    gaps: list[tuple[str, float]] = []
    for dev in devices:
        ops = trace_lib._clip(trace.ops.get(dev) or trace.modules.get(dev, []),
                              lo, hi)
        mods = trace_lib._clip(trace.modules.get(dev, []), lo, hi)
        prev = lo
        for s, e in trace_lib._union(ops + mods) + [(hi, hi)]:
            if s > prev:
                label = trace_lib._label(host, starts, prev, s)
                span = innermost(threads, prev, s)
                if span is not None:
                    label = f"{label}:{span}"
                idle[label] = idle.get(label, 0.0) + (s - prev)
                gaps.append((label, s - prev))
            prev = max(prev, e)
    return {"program_spans": stats,
            "idle_by_span": {k: v * 1e-9 for k, v in idle.items()},
            "idle_gaps": [[n, t * 1e-9] for n, t in
                          sorted(gaps, key=lambda x: -x[1])[:top]]}


def per_layer(stats: dict, segments: float) -> dict:
    """The per-layer numbers the spans give, in ms, from `tree`'s stats
    and the real segments dispatched in the window; a number whose spans
    or divisor are missing is left out.

    - `pose_interp_ms_per_push`: `emvs.pose_interp` time per `emvs.push`;
    - `ingest_ms_per_push`: self time of `emvs.hygiene` and
      `emvs.aggregate` per `emvs.push`;
    - `stage_ms_per_seg`: self time of `emvs.plan` (frame store and
      planner loops) and `emvs.stage` (gathers, padding, copies to the
      device) per segment;
    - `harvest_wait_ms_per_seg`: `emvs.harvest.sync` time plus the self
      time of `emvs.backpressure` per segment. A back-pressure harvest's
      wait lies in its `emvs.harvest.sync` child, so it counts once.
    """
    def total(name):
        return stats.get(name, {}).get("total_s", 0.0)

    def own(name):
        return stats.get(name, {}).get("self_s", 0.0)

    out = {}
    pushes = stats.get("emvs.push", {}).get("count", 0)
    if pushes:
        out["pose_interp_ms_per_push"] = 1e3 * total("emvs.pose_interp") / pushes
        out["ingest_ms_per_push"] = 1e3 * (
            own("emvs.hygiene") + own("emvs.aggregate")) / pushes
    if segments and "emvs.dispatch" in stats:
        out["stage_ms_per_seg"] = 1e3 * (
            own("emvs.plan") + own("emvs.stage")) / segments
        out["harvest_wait_ms_per_seg"] = 1e3 * (
            total("emvs.harvest.sync") + own("emvs.backpressure")) / segments
    return out
