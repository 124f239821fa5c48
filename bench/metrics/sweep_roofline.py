"""Share of the sweep's device time that the algorithm's own work needs
at the chip's peaks (harness/work.py): the mean roofline time of the
segments emitted in the window, times the segments dispatched in it,
over the sweep programs' device time in the window."""


def read(run):
    n = run.delta("segments")
    if run.trace is None or not run.seg_least_s or not n or not run.trace["sweep_s"]:
        return None
    least = sum(run.seg_least_s) / len(run.seg_least_s) * n
    return 100.0 * least / run.trace["sweep_s"]
