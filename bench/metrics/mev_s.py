"""Millions of events per second completed: the events of every map
emitted in the window, as its driver counts them, over the window's
length."""


def read(run):
    if not run.emitted:
        return None
    return sum(m.events for m in run.emitted) / run.window_s / 1e6
