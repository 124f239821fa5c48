"""Device time of the sweep programs in the window per real segment
dispatched in it, from the device trace."""


def read(run):
    n = run.delta("segments")
    if run.trace is None or not n or not run.trace["sweep_s"]:
        return None
    return 1e3 * run.trace["sweep_s"] / n
