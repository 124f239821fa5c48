"""The traffic generator: seeded, the same sizes for every seed, and
packets cut by time or by count."""
import numpy as np
import pytest

from conftest import load
from harness import reference, serve
from harness.traffic import Packetizer, make_cameras

sessions = serve.driver({})

CELLS = [("davis240", "fleet8.overload"),
         ("vga640", "stereo.overload")]


def small(mix_name):
    return dict(load("traffic", mix_name), cameras=2)


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_same_seed_same_events(config_name, mix_name):
    config, mix = load("configs", config_name), small(mix_name)
    a = make_cameras(config, mix, 2**31 + 12345)
    b = make_cameras(config, mix, 2**31 + 12345)
    for ca, cb in zip(a, b):
        assert ca.phase == cb.phase
        np.testing.assert_array_equal(ca.lap_t, cb.lap_t)
        np.testing.assert_array_equal(ca.lap_xy, cb.lap_xy)


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_every_seed_has_the_same_sizes(config_name, mix_name):
    """Seeds change phases, jitter and noise, never the amount of work:
    events per lap, frames per segment and so the compiled capacities."""
    config, mix = load("configs", config_name), small(mix_name)
    setup = reference.Setup.from_config(config)
    laps, caps = set(), set()
    for seed in (1, 2, 3 + 2**32):
        cams = make_cameras(config, mix, seed)
        laps.add(tuple(c.lap_events for c in cams))
        p = sessions.plan(cams, mix, setup, 12.0)
        caps |= {sessions.capacity(b - a) for segs in p.segments for a, b in segs}
    assert len(laps) == 1
    assert len(caps) == 1, caps
    for n in laps.pop():
        assert n / cams[0].period == pytest.approx(mix["rate_ev_s"], rel=1e-3)


def test_segment_count_follows_the_path():
    """On a circle of radius r a segment closes every chord of 0.15 x
    the mean depth: 2 asin(0.4125 / 2r) r / speed seconds."""
    config, mix = load("configs", "davis240"), small("fleet8.overload")
    setup = reference.Setup.from_config(config)
    cams = make_cameras(config, mix, 7)
    p = sessions.plan(cams, mix, setup, 20.0)
    t_seg = 2 * np.arcsin(0.4125 / (2 * mix["radius_m"])) * mix["radius_m"] / mix["speed_m_s"]
    for segs in p.segments:
        assert len(segs) == pytest.approx(20.0 / t_seg, abs=1.5)
        frames = [b - a for a, b in segs]
        assert max(frames) - min(frames) <= 1


def test_rig_cameras_move_as_one():
    config, mix = load("configs", "vga640"), load("traffic", "stereo.overload")
    a, b = make_cameras(config, mix, 5)
    assert a.phase == b.phase and b.offset - a.offset == pytest.approx(mix["rig_baseline_m"])


@pytest.mark.parametrize("packet_s,packet_events",
                         [(0.01, 8192), (30.0, 65536)])
def test_packets_are_sliced_by_time_or_count(packet_s, packet_events):
    config = load("configs", "davis240")
    mix = dict(small("fleet8.overload"), packet_s=packet_s,
               packet_events=packet_events)
    cams = make_cameras(config, mix, 9)
    assert cams[1].start > cams[0].start == 0.0  # staggered starts
    cam = cams[1]
    pk = Packetizer(1, cam, mix)
    g, due = 0, 0.0
    for _ in range(200):
        p = pk.next()
        assert p.g0 == g and 0 < p.g1 - p.g0 <= packet_events
        t = cam.times(p.g0, p.g1)
        assert cam.start + t[-1] <= p.due + 1e-6 and p.due >= due
        if p.g1 - p.g0 < packet_events:  # cut by time: one slice at most
            assert t[-1] - t[0] <= packet_s
        g, due = p.g1, p.due
    if packet_s > 1.0:  # count-sliced: every push completes the same frames
        fpp = sessions.frames_per_push(cams, mix, 1024, 20.0)
        assert fpp == {packet_events // 1024}
    # replayed laps keep time moving forward
    t = cam.times(0, 3 * cam.lap_events)
    assert np.all(np.diff(t) >= 0)
