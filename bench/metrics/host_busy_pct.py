"""Share of the window the client thread spent inside engine calls
(push, poll) and result fetches, from the benchmark's own spans."""


def read(run):
    busy = sum(e - s for name, s, e in run.spans
               if name in ("bench.push", "bench.poll", "bench.fetch"))
    return 100.0 * busy / run.window_s
