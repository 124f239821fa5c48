"""Millions of events per second completed: the events of every segment
whose depth map was emitted in the window, over the window's length."""


def read(run):
    if not run.emitted:
        return None
    frames = sum(b - a for a, b in (m.frames for m in run.emitted))
    return frames * run.events_per_frame / run.window_s / 1e6
