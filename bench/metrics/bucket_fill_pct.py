"""Real segments over swept rows (real + padding) of the dispatches made
in the window, from the dispatcher's counters."""


def read(run):
    real, pad = run.delta("segments"), run.delta("padded_segments")
    return 100.0 * real / (real + pad) if real else None
