"""The reference and its control at a size a test run holds."""
import copy

import numpy as np
import pytest

from conftest import load
from control import control_numbers
from harness import check, reference, serve
from harness.traffic import make_cameras


def small_setup(config_name="davis240", planes=16):
    config = copy.deepcopy(load("configs", config_name))
    config["dsi"]["num_planes"] = planes
    return config


def test_fixed_point_rounds_half_away_and_saturates():
    q = reference._q(np.array([0.5, -0.5, 1.49, 300.0, -300.0], np.float32), 8, 0)
    np.testing.assert_array_equal(q, [1, -1, 1, 127, -128])
    pc = reference._plane_coord(np.array([-0.6, -0.4, 255.4, 255.6, 10.5], np.float32))
    np.testing.assert_array_equal(pc, [255, 0, 255, 255, 11])


@pytest.mark.parametrize("config_name", ["davis240", "vga640"])
def test_control_fails_where_the_reference_passes(config_name):
    """bfloat16 geometry moves far more votes than any limit allows; the
    float32 reference against itself moves none."""
    config = small_setup(config_name)
    mix = dict(load("traffic", "fleet8.overload"), cameras=1, rate_ev_s=60000,
               speed_m_s=0.5,
               check_segments=2)
    gaps = control_numbers(config, mix, 424242, 3.0)
    assert not check.judge(gaps, config["limits"])
    assert gaps["dsi_voxels"] > 2 * config["limits"]["dsi_voxels"]

    setup = reference.Setup.from_config(config)
    cams = make_cameras(config, mix, 424242)
    p = serve.plan(cams, mix, setup, 4.0)
    xy, pos = check.segment_inputs(cams[0], p.positions[0], p.segments[0][0], 1024)
    a = check.reference_segment(setup, xy, pos)
    b = check.reference_segment(setup, xy, pos)
    same = check.compare(setup, *b, *a)
    assert same == {"dsi_voxels": 0.0, "mask_pixels": 0.0, "depth_gap": 0.0}
    assert a[2].sum() > 30  # a real semi-dense map, not an empty one


def test_detection_on_a_single_plane_of_votes():
    config = small_setup(planes=8)
    setup = reference.Setup.from_config(config)
    dsi = np.zeros((8, 180, 240), np.int32)
    dsi[3, 50:60, 100] = 40  # a vertical edge on plane 3
    depth, mask = reference.detect(setup, dsi)
    assert mask[50:60, 100].all() and mask.sum() == 10
    np.testing.assert_allclose(depth[55, 100], setup.planes()[3], rtol=1e-6)


def test_frame_median_from_the_middle_pair():
    """The segmentation poses each frame at its median timestamp; for a
    time-ordered stream that is the mean of the two middle events."""
    config, mix = load("configs", "davis240"), load("traffic", "fleet8.overload")
    cam = make_cameras(config, dict(mix, cameras=1), 5)[0]
    t = cam.times(0, 300 * 1024)
    first = np.arange(300) * 1024
    np.testing.assert_array_equal(
        np.median(t.reshape(300, 1024), axis=1).astype(np.float32),
        reference.middle_mean(cam.times_at(first + 511), cam.times_at(first + 512)))
