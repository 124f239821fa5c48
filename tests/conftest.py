"""Shared fixtures. Tests run on ONE (real) device — the 512-device flag
lives only in launch/dryrun.py; distributed tests spawn subprocesses.

`hypothesis` is an optional test dependency: when it is missing we install
a minimal stub into `sys.modules` *before* collection so `@given`-based
tests are collected and skipped instead of crashing every test file that
imports it.
"""
from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    from hypothesis import HealthCheck, settings

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised by the no-hypothesis CI job
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    settings.register_profile(
        "repro",
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.load_profile("repro")
else:
    class _AnyStrategy:
        """Permissive stand-in for `hypothesis.strategies`: any attribute is
        callable and returns another _AnyStrategy, so strategy-construction
        expressions at module import time never fail."""

        def __getattr__(self, name):
            return self

        def __call__(self, *args, **kwargs):
            return self

    class _StubSettings:
        """Accepts both `@settings(...)` decoration and profile management."""

        def __init__(self, *args, **kwargs):
            pass

        def __call__(self, fn):
            return fn

        @staticmethod
        def register_profile(*args, **kwargs):
            pass

        @staticmethod
        def load_profile(*args, **kwargs):
            pass

    def _stub_given(*_args, **_kwargs):
        def decorate(fn):
            def skipped(*args, **kwargs):
                pytest.skip("hypothesis is not installed")

            skipped.__name__ = getattr(fn, "__name__", "hypothesis_test")
            skipped.__doc__ = getattr(fn, "__doc__", None)
            return skipped

        return decorate

    def _stub_assume(condition):
        return bool(condition)

    _strategies = _AnyStrategy()
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _stub_given
    _hyp.settings = _StubSettings
    _hyp.assume = _stub_assume
    _hyp.HealthCheck = _AnyStrategy()
    _hyp.strategies = _strategies
    _st_mod = types.ModuleType("hypothesis.strategies")
    _st_mod.__getattr__ = lambda name: getattr(_strategies, name)
    sys.modules.setdefault("hypothesis", _hyp)
    sys.modules.setdefault("hypothesis.strategies", _st_mod)


@pytest.fixture(scope="session")
def cam():
    from repro.core.camera import CameraModel

    return CameraModel()


@pytest.fixture(scope="session")
def small_scene(cam):
    """Small 3-planes scene + trajectory + event frames (shared, ~seconds)."""
    from repro.events.aggregation import aggregate
    from repro.events.simulator import (
        SceneConfig,
        make_scene,
        make_trajectory,
        simulate_events,
    )

    scene = make_scene(SceneConfig(name="simulation_3planes", points_per_plane=150))
    traj = make_trajectory("simulation_3planes", 24)
    ev = simulate_events(cam, scene, traj, noise_fraction=0.0)
    frames = aggregate(cam, ev, traj, events_per_frame=1024)
    return {"scene": scene, "traj": traj, "events": ev, "frames": frames}


def rng(seed: int = 0) -> np.random.Generator:
    return np.random.default_rng(seed)


@pytest.fixture(scope="session")
def pose_f64():
    """Frame-pose interpolation in float64 NumPy, written out apart from
    `repro.core.geometry`: the reference the float32 host path is
    compared against (`so3_log`, `so3_exp`, `pose_at_times`)."""

    def so3_log(R):
        R = np.asarray(R, np.float64)
        cos = np.clip((np.trace(R, axis1=-2, axis2=-1) - 1.0) / 2.0, -1.0, 1.0)
        theta = np.arccos(cos)
        sin = np.sin(theta)
        v = np.stack([R[..., 2, 1] - R[..., 1, 2], R[..., 0, 2] - R[..., 2, 0],
                      R[..., 1, 0] - R[..., 0, 1]], axis=-1)
        scale = np.where(np.abs(sin) < 1e-8, 0.5, theta / (2.0 * sin + 1e-30))
        return v * scale[..., None]

    def so3_exp(w):
        w = np.asarray(w, np.float64)
        theta = np.linalg.norm(w, axis=-1)[..., None, None]
        zero = np.zeros_like(w[..., 0])
        K = np.stack([np.stack([zero, -w[..., 2], w[..., 1]], axis=-1),
                      np.stack([w[..., 2], zero, -w[..., 0]], axis=-1),
                      np.stack([-w[..., 1], w[..., 0], zero], axis=-1)],
                     axis=-2) / np.where(theta < 1e-8, 1.0, theta)
        eye = np.broadcast_to(np.eye(3), K.shape)
        R = eye + np.sin(theta) * K + (1.0 - np.cos(theta)) * (K @ K)
        return np.where(theta < 1e-8, eye, R)

    def pose_at_times(times, R, t, t_query):
        times, R, t, tq = (np.asarray(a, np.float64)
                           for a in (times, R, t, t_query))
        i = np.clip(np.searchsorted(times, tq, side="right") - 1,
                    0, times.shape[0] - 2)
        frac = np.clip((tq - times[i])
                       / np.maximum(times[i + 1] - times[i], 1e-9), 0.0, 1.0)
        w = so3_log(R[i + 1] @ np.swapaxes(R[i], -1, -2))
        return (so3_exp(w * frac[..., None]) @ R[i],
                t[i] + frac[..., None] * (t[i + 1] - t[i]))

    return types.SimpleNamespace(so3_log=so3_log, so3_exp=so3_exp,
                                 pose_at_times=pose_at_times)
