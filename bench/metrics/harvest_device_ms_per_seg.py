"""Device time of every operation that is not a sweep program (point
cloud, per-segment slices, saturation reduction, pose interpolation) in
the window, per real segment dispatched in it, from the device trace."""


def read(run):
    n = run.delta("segments")
    if run.trace is None or not n:
        return None
    return 1e3 * run.trace["other_s"] / n
