"""The reference and its control at a size a test run holds."""
import copy
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import load
from control import control_numbers
from harness import check, reference, serve
from harness.traffic import make_cameras, make_scene

sessions = serve.driver({})


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
    p = sessions.plan(cams, mix, setup, 4.0)
    xy, pos = sessions.reference_inputs(
        setup, cams, p, SimpleNamespace(stream=0, frames=p.segments[0][0]))
    a = sessions.reference(setup, xy, pos)
    b = sessions.reference(setup, xy, pos)
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


def rig_segment(planes):
    """The first segment of both cameras of the VGA rig (0.6 m apart
    along x, moving as one), at a rate a test run holds."""
    config = small_setup("vga640", planes)
    mix = dict(load("traffic", "stereo.overload"), rate_ev_s=60000)
    setup = reference.Setup.from_config(config)
    cams = make_cameras(config, mix, 2**31 + 4242)
    p = sessions.plan(cams, mix, setup, 3.0)
    seg = p.segments[0][0]
    inputs = [sessions.reference_inputs(setup, cams, p,
                                        SimpleNamespace(stream=k, frames=seg))
              for k in (0, 1)]
    return config, setup, inputs


def test_reference_view_defaults_to_the_first_frame():
    _, setup, [(xy, pos), _] = rig_segment(planes=32)
    np.testing.assert_array_equal(
        reference.segment_dsi(setup, xy, pos, ref_pos=pos[0]),
        reference.segment_dsi(setup, xy, pos))


def test_rig_camera_votes_into_the_other_cameras_view():
    """The right camera's events, voted into the left camera's view at
    its key frame, detect the scene's planes (1, 2 and 3.5 m) within one
    plane step, where the left camera sees the scene's points. (Without
    `ref_pos`, in the right camera's own view, 17% of the map's pixels
    lie near those points; with the view 20 cm off in depth, 25% of its
    depths lie within a step.)"""
    config, setup, [(_, pos_left), (xy_right, pos_right)] = rig_segment(64)
    np.testing.assert_allclose(pos_right[0] - pos_left[0], [0.6, 0, 0],
                               atol=1e-4)
    dsi = reference.segment_dsi(setup, xy_right, pos_right,
                                ref_pos=pos_left[0])
    depth, mask = reference.detect(setup, dsi)
    assert mask.sum() > 100
    step = (1 / setup.z_min - 1 / setup.z_max) / (setup.num_planes - 1)
    off = np.min(np.abs(1 / depth[mask][:, None]
                        - 1 / np.array([1.0, 2.0, 3.5])[None]), axis=1)
    assert np.mean(off <= step) > 0.8
    # the scene's points as the left camera sees them, 2 px around
    pc = make_scene(config["scene"]) - pos_left[0]
    x = np.round(setup.fx * pc[:, 0] / pc[:, 2] + setup.cx).astype(int)
    y = np.round(setup.fy * pc[:, 1] / pc[:, 2] + setup.cy).astype(int)
    ok = (x >= 0) & (x < setup.width) & (y >= 0) & (y < setup.height)
    seen = np.zeros((setup.height + 4, setup.width + 4), bool)
    for dy in range(5):
        for dx in range(5):
            seen[y[ok] + dy, x[ok] + dx] = True
    assert np.mean(seen[2:-2, 2:-2][mask]) > 0.5
