"""The `fused_rig` driver on the host CPU: its reference against a fusion
by hand, its control, a tiny served run, a reference that leaves out the
right camera, and its work count."""
import copy
import time
from types import SimpleNamespace

import numpy as np

from conftest import BENCH, CPU_PEAKS, load, tiny
from control import control_numbers
from harness import cell as cell_lib
from harness import check, reference, serve
from harness.traffic import make_cameras

RIG_CELL = "dsec_rig_fused.stereo.overload"
SEED = 2**31 + 91

rig = serve.driver({"driver": "fused_rig"})


def small_setup(config_name="dsec_rig_fused", planes=16):
    config = copy.deepcopy(load("configs", config_name))
    config["dsi"]["num_planes"] = planes
    return config


def tiny_rig():
    """The fused rig cut to the host CPU: a 160x120 sensor of the same
    field of view, 8 planes, the stereo mix at a low rate."""
    config, mix = tiny("dsec_rig_fused", "stereo.overload")
    config["sensor"] = dict(width=160, height=120, fx=140.0, fy=140.0,
                            cx=80.0, cy=60.0)
    return config, dict(mix, rate_ev_s=60000)


def run_tiny_rig(bench_spec, tmp_path, config, monkeypatch, **kw):
    """One tiny run of the rig cell, with the view its readers saw."""
    import jax

    views = []
    make_view = cell_lib.make_view
    monkeypatch.setattr(cell_lib, "make_view",
                        lambda run: views.append(make_view(run)) or views[-1])
    _, mix = tiny_rig()
    out = cell_lib.run_cell(bench_spec, RIG_CELL, config, mix, SEED, 3.0,
                            False, jax.devices()[:1], time.perf_counter(),
                            tmp_path, log=lambda m: None, peaks=CPU_PEAKS,
                            **kw)
    return out, views[0]


def test_rig_reference_fuses_by_the_harmonic_mean_by_hand():
    """Two cameras' votes at the left key frame, fused voxel by voxel by
    2ab / (a + b) written out per voxel (a Python float, rounded once to
    float32), then detected: the `fused_rig` reference gives the same
    volume and map."""
    config = small_setup(planes=8)
    mix = dict(load("traffic", "stereo.overload"), rate_ev_s=200000)
    setup = reference.Setup.from_config(config)
    cams = make_cameras(config, mix, 31337)
    p = rig.plan(cams, mix, setup, 4.0)
    m = SimpleNamespace(stream=0, frames=p.segments[0][0])
    xy_l, pos_l, xy_r, pos_r = rig.reference_inputs(setup, cams, p, m)
    assert xy_r.shape[0] > 0 and pos_r.shape[0] == xy_r.shape[0]
    fused, depth, mask = rig.reference(setup, xy_l, pos_l, xy_r, pos_r)
    a = reference.segment_dsi(setup, xy_l, pos_l, ref_pos=pos_l[0])
    b = reference.segment_dsi(setup, xy_r, pos_r, ref_pos=pos_l[0])
    want = np.zeros(a.shape, np.float32)
    both = np.argwhere((a > 0) & (b > 0))
    assert both.shape[0] > 100
    for z, y, x in both:
        va, vb = int(a[z, y, x]), int(b[z, y, x])
        want[z, y, x] = np.float32(2 * va * vb / (va + vb))
    np.testing.assert_array_equal(fused, want)
    # every voxel one camera missed is 0, though the left voted there
    assert ((a > 0) & (b == 0)).any() and not fused[(a > 0) & (b == 0)].any()
    want_depth, want_mask = reference.detect(setup, want)
    np.testing.assert_array_equal(mask, want_mask)
    np.testing.assert_array_equal(depth, want_depth)
    assert mask.sum() > 30


def test_rig_control_fails_where_the_reference_passes():
    config = small_setup()
    mix = dict(load("traffic", "stereo.overload"), rate_ev_s=200000,
               check_segments=2)
    gaps = control_numbers(config, mix, 424243, 3.0)
    assert not check.judge(gaps, config["limits"])
    assert gaps["mask_pixels"] > 2 * config["limits"]["mask_pixels"]


def test_the_fused_rig_is_served_and_checked(bench_spec, tmp_path,
                                             monkeypatch):
    """One stream, the rig: every map is a left segment of the plan,
    counts both cameras' frames' events, equals the reference, and the
    rig's counters reach the readers."""
    config, _ = tiny_rig()
    out, view = run_tiny_rig(bench_spec, tmp_path, config, monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and view.emitted
    e = config["stream"]["events_per_frame"]
    for m in view.emitted:
        n_left = m.frames[1] - m.frames[0]
        assert m.stream == 0 and m.events % e == 0
        # the cameras run at one rate: a map holds as many right frames
        assert abs(m.events // e - 2 * n_left) <= 2
    assert out["metrics"]["mev_s"]["value"] == (
        sum(m.events for m in view.emitted) / view.window_s / 1e6)
    assert view.delta("rig_holds") > 0
    assert cell_lib.reader("rig_hold_ms_mean.tput")(view) >= 0.0


def test_a_rig_reference_without_the_right_camera_is_caught(
        bench_spec, tmp_path, monkeypatch):
    config, _ = tiny_rig()
    out, view = run_tiny_rig(bench_spec, tmp_path,
                             dict(config, driver="rig_left_only"), monkeypatch,
                             drivers=BENCH / "tests" / "drivers")
    assert view.emitted and out["attempted"] > 0
    assert out["correct"] is False
    assert out["checks"]["dsi_voxels"]["value"] > out["checks"]["dsi_voxels"]["limit"]


def test_rig_work_counts_each_camera_once_and_one_fused_dsi():
    config, _ = tiny_rig()
    e, nz, h, w = 1024, 8, 120, 160
    n_left, n_right = 12, 13
    m = SimpleNamespace(frames=(40, 40 + n_left), events=(n_left + n_right) * e)
    ops, nbytes = rig.map_work(config, m)
    frames, voxels = n_left + n_right, nz * h * w
    assert ops == (frames * e * (14 + 7 * nz) + frames * (54 + 10 * nz)
                   + voxels * (2 + 4))
    assert nbytes == frames * e * 9 + frames * 48 + voxels * 4 + h * w * 9
