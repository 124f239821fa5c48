"""A driver that only the tests use: the `fused_rig` driver, but its
reference leaves out the right camera and detects on the left's votes."""
from harness import reference as ref
from harness.serve import DRIVERS, load_driver

_rig = load_driver(DRIVERS / "fused_rig.py")
plan, serve = _rig.plan, _rig.serve
reference_inputs, map_work = _rig.reference_inputs, _rig.map_work


def reference(setup, xy_left, pos_left, xy_right, pos_right, *, lowp=False):
    dsi = ref.segment_dsi(setup, xy_left, pos_left, lowp=lowp)
    return (dsi, *ref.detect(setup, dsi))
