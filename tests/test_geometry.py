"""Plane-sweep geometry: the H_Z0 + phi factorization must agree with
direct 3D reprojection — the correctness core of the paper's P stage."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.camera import CameraModel, project, unproject
from repro.core.geometry import (
    SE3,
    apply_homography,
    canonical_homography,
    depth_planes,
    interpolate_pose,
    pose_distance,
    proportional_coeffs,
    propagate_to_planes,
    relative_pose_ref_from_cam,
    so3_exp,
    so3_log,
)


def _random_pose(rng, t_scale=0.1, r_scale=0.1) -> SE3:
    w = jnp.asarray(rng.uniform(-r_scale, r_scale, 3).astype(np.float32))
    t = jnp.asarray(rng.uniform(-t_scale, t_scale, 3).astype(np.float32))
    return SE3(so3_exp(w), t)


def test_se3_compose_inverse():
    rng = np.random.default_rng(0)
    a, b = _random_pose(rng), _random_pose(rng)
    ident = a.compose(a.inverse())
    assert np.allclose(ident.R, np.eye(3), atol=1e-5)
    assert np.allclose(ident.t, 0, atol=1e-5)
    pts = jnp.asarray(rng.normal(size=(1, 10, 3)).astype(np.float32))
    ab = a.compose(b)
    assert np.allclose(ab.apply(pts), a.apply(b.apply(pts)), atol=1e-4)


@given(seed=st.integers(0, 10_000))
def test_so3_log_exp_roundtrip(seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.uniform(-1.0, 1.0, 3).astype(np.float32))
    R = so3_exp(w)
    w2 = so3_log(R)
    assert type(w2) is np.ndarray and w2.dtype == np.float32
    assert np.allclose(np.asarray(w), w2, atol=1e-4)


# Rotation angles of the host float32 so3_log / interpolate_pose tests:
# the small-angle branches (0, below and above the 1e-8 cut), ordinary
# angles, and the ill-conditioned end near pi.
ANGLES = [0.0, 1e-9, 1e-6, 1e-3, 0.5, 2.0, np.pi - 0.1, np.pi - 0.02]


def _f32_tol(angle: float) -> float:
    """float32 rounding, amplified near pi by the log's 1 / (1 + cos)
    conditioning (arccos and the axis's sin(theta) both lose digits)."""
    return 1e-6 + 4 * float(np.finfo(np.float32).eps) / (1.0 + np.cos(angle))


def _rotations(pose_f64, angle: float, n: int = 16, seed: int = 0):
    """n float32 rotations by `angle` about random axes."""
    axes = np.random.default_rng(seed).normal(size=(n, 3))
    axes /= np.linalg.norm(axes, axis=-1, keepdims=True)
    return pose_f64.so3_exp(axes * angle).astype(np.float32)


@pytest.mark.parametrize("angle", ANGLES)
def test_so3_log_host_float32_matches_float64(pose_f64, angle):
    R = _rotations(pose_f64, angle)
    w = so3_log(R)
    assert type(w) is np.ndarray and w.dtype == np.float32
    np.testing.assert_allclose(w, pose_f64.so3_log(R), rtol=0,
                               atol=_f32_tol(angle))


@pytest.mark.parametrize("angle", ANGLES)
def test_interpolate_pose_host_float32_matches_float64(pose_f64, angle):
    rng = np.random.default_rng(2)
    R0 = pose_f64.so3_exp(rng.uniform(-2.0, 2.0, (16, 3)))
    # R1 turns R0 by `angle`, both rounded to float32
    R1 = (_rotations(pose_f64, angle, seed=1) @ R0).astype(np.float32)
    R0 = R0.astype(np.float32)
    t0, t1 = (rng.normal(size=(16, 3)).astype(np.float32) for _ in range(2))
    frac = np.concatenate([[0.0, 1.0], rng.uniform(0, 1, 14)]).astype(np.float32)
    got = interpolate_pose(SE3(R0, t0), SE3(R1, t1), frac)
    for x in got:
        assert type(x) is np.ndarray and x.dtype == np.float32
    R0d, R1d, fd = (np.asarray(a, np.float64) for a in (R0, R1, frac))
    w = pose_f64.so3_log(R1d @ np.swapaxes(R0d, -1, -2))
    R_ref = pose_f64.so3_exp(w * fd[:, None]) @ R0d
    t_ref = t0 + fd[:, None] * (np.float64(t1) - t0)
    np.testing.assert_allclose(got.R, R_ref, rtol=0, atol=_f32_tol(angle))
    np.testing.assert_allclose(got.t, t_ref, rtol=0, atol=1e-6)
    # frac 0 is the first pose, bit for bit
    np.testing.assert_array_equal(got.R[0], R0[0])
    np.testing.assert_array_equal(got.t[0], t0[0])


def test_homography_plus_phi_equals_direct_reprojection(cam):
    """Back-project pixels from the current camera onto plane Zi in the
    reference frame two ways: (a) H_Z0 then the phi multiply-add (the
    paper's P(Z0) + P(Z0->Zi)), (b) full 3D ray-plane intersection."""
    rng = np.random.default_rng(1)
    T_w_ref = SE3.identity()
    T_w_cam = _random_pose(rng, t_scale=0.15, r_scale=0.08)
    T_ref_cam = relative_pose_ref_from_cam(T_w_ref, T_w_cam)

    planes = depth_planes(0.8, 4.0, 8)
    z0 = planes[4]
    H = canonical_homography(cam, T_ref_cam, z0)
    phi = proportional_coeffs(cam, T_ref_cam, z0, planes)

    xy = jnp.asarray(rng.uniform((20, 20), (220, 160), (64, 2)).astype(np.float32))
    xy0 = apply_homography(H, xy)
    x_i, y_i = propagate_to_planes(cam, xy0, phi)  # (Nz, E)

    # direct: ray through current camera centre and the pixel, intersected
    # with plane z = Zi in the reference frame, projected by the reference
    C = T_ref_cam.t  # camera centre in ref frame
    dirs_cam = unproject(cam, xy, jnp.float32(1.0))  # (E, 3) in current frame
    dirs_ref = jnp.einsum("ij,ej->ei", T_ref_cam.R, dirs_cam)  # direction
    for i, zi in enumerate(np.asarray(planes)):
        s = (zi - C[2]) / dirs_ref[:, 2]
        pts = C[None, :] + s[:, None] * dirs_ref  # (E, 3), z == zi
        uv = project(cam, pts)
        assert np.allclose(np.asarray(x_i[i]), np.asarray(uv[:, 0]), atol=2e-2), i
        assert np.allclose(np.asarray(y_i[i]), np.asarray(uv[:, 1]), atol=2e-2), i


def test_interpolate_pose_endpoints():
    rng = np.random.default_rng(2)
    p0, p1 = _random_pose(rng), _random_pose(rng)
    a = interpolate_pose(p0, p1, jnp.float32(0.0))
    b = interpolate_pose(p0, p1, jnp.float32(1.0))
    assert np.allclose(a.R, p0.R, atol=1e-5) and np.allclose(a.t, p0.t, atol=1e-6)
    assert np.allclose(b.R, p1.R, atol=1e-4) and np.allclose(b.t, p1.t, atol=1e-6)
    mid = interpolate_pose(p0, p1, jnp.float32(0.5))
    assert np.allclose(mid.t, (p0.t + p1.t) / 2, atol=1e-6)
    for x in (*a, *b, *mid):
        assert type(x) is np.ndarray and x.dtype == np.float32


def test_pose_distance_is_keyframe_criterion():
    p0 = SE3.identity()
    p1 = SE3(jnp.eye(3), jnp.array([0.3, 0.4, 0.0]))
    assert abs(float(pose_distance(p0, p1)) - 0.5) < 1e-6
