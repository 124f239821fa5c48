"""The driver seam on the host CPU: the default driver, an unknown one,
and a driver added as one new file."""
import time

import pytest

from conftest import BENCH, CPU_PEAKS, tiny
from harness import cell as cell_lib
from harness import serve

CELL = "davis240.fleet8.overload"
SEED = 2**31 + 91


def run_tiny(bench_spec, tmp_path, config, monkeypatch, **kw):
    """One tiny run, with the view its readers saw."""
    import jax

    views = []
    make_view = cell_lib.make_view
    monkeypatch.setattr(cell_lib, "make_view",
                        lambda run: views.append(make_view(run)) or views[-1])
    _, mix = tiny()
    out = cell_lib.run_cell(bench_spec, CELL, config, mix, SEED, 3.0, False,
                            jax.devices()[:1], time.perf_counter(), tmp_path,
                            log=lambda m: None, peaks=CPU_PEAKS, **kw)
    return out, views[0]


def test_no_driver_key_means_sessions(bench_spec, tmp_path, monkeypatch):
    config, _ = tiny()
    assert "driver" not in config
    named = dict(config, driver="sessions")
    assert serve.driver(config).__file__ == serve.driver(named).__file__
    runs = [run_tiny(bench_spec, tmp_path / str(k), c, monkeypatch)
            for k, c in enumerate((config, named))]
    (a, va), (b, vb) = runs
    assert a["correct"] is True and b["correct"] is True
    assert a["attempted"] == b["attempted"] > 0
    assert a["checks"] == b["checks"]
    # the maps due early enough to be out by the close on any host
    w1 = 1.5 + 3.0
    early = [sorted((m.stream, m.frames) for m in v.emitted
                    if m.t_emit - m.latency < w1 - 1.0) for v in (va, vb)]
    assert early[0] and early[0] == early[1]


def test_events_per_map_are_frames_times_events_per_frame(
        bench_spec, tmp_path, monkeypatch):
    """`mev_s` sums each map's events; for the sessions driver that is
    the frames' events, the same integer sum as frames x 1024."""
    config, _ = tiny()
    out, view = run_tiny(bench_spec, tmp_path, config, monkeypatch)
    e = config["stream"]["events_per_frame"]
    assert view.emitted
    for m in view.emitted:
        assert m.events == (m.frames[1] - m.frames[0]) * e
    frames = sum(b - a for a, b in (m.frames for m in view.emitted))
    assert cell_lib.reader("mev_s")(view) == frames * e / view.window_s / 1e6
    assert out["metrics"]["mev_s"]["value"] == frames * e / view.window_s / 1e6


def test_an_unknown_driver_names_the_ones_there():
    config, _ = tiny()
    with pytest.raises(SystemExit) as exc:
        serve.driver(dict(config, driver="no_such_driver"))
    assert "no_such_driver" in str(exc.value)
    assert "sessions.py" in str(exc.value)


def test_a_driver_in_a_new_file_is_picked_up(bench_spec, tmp_path,
                                             monkeypatch):
    """A driver that votes half of each map's frames in its reference:
    one new file, found by its name, and `correct` turns false."""
    config, _ = tiny()
    out, view = run_tiny(bench_spec, tmp_path, dict(config, driver="fake"),
                         monkeypatch, drivers=BENCH / "tests" / "drivers")
    assert view.emitted and out["attempted"] > 0
    assert out["correct"] is False
    assert out["checks"]["dsi_voxels"]["value"] > out["checks"]["dsi_voxels"]["limit"]
