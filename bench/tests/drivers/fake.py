"""A driver that only the tests use: the `sessions` driver, but its
reference drops the votes of the second half of each map's frames."""
from harness.serve import DRIVERS, load_driver

_sessions = load_driver(DRIVERS / "sessions.py")
plan, serve = _sessions.plan, _sessions.serve
reference_inputs, map_work = _sessions.reference_inputs, _sessions.map_work


def reference(setup, xy_frames, pos_frames, *, lowp=False):
    half = xy_frames.shape[0] // 2
    return _sessions.reference(setup, xy_frames[:half], pos_frames[:half],
                               lowp=lowp)
