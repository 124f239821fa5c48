"""SE(3) poses and plane-sweep geometry for event-based space-sweep.

Implements the two geometric objects the paper's FPGA computes on the ARM
side once per event frame:

  * the canonical-plane homography  H_Z0  (current camera image -> reference
    camera image via the plane z = Z0 in the reference frame), consumed by
    PE_Z0 for P(Z0);
  * the proportional back-projection coefficients  phi = {alpha_i, beta_i}
    consumed by the PE_Zi scalar MACs for P(Z0 -> Zi).

Derivation of phi (matches the paper's 2-MAC-per-plane structure):
  Let C = (Cx, Cy, Cz) be the current camera's optical centre expressed in
  the *reference* camera frame, and let a point on the canonical plane
  z = Z0 project to reference pixel (x0, y0). The viewing ray through C and
  that point intersects plane z = Zi at

      s_i = (Zi - Cz) / (Z0 - Cz),
      X_i = C + s_i (X_0 - C),            X_i.z = Zi  (exact).

  Projecting X_i with the reference pinhole gives

      x_i = alpha_i * (x0 - cx) + beta_x_i + cx,
      y_i = alpha_i * (y0 - cy) + beta_y_i + cy,

      alpha_i  = s_i * Z0 / Zi,
      beta_x_i = fx * Cx * (1 - s_i) / Zi,
      beta_y_i = fy * Cy * (1 - s_i) / Zi.

  i.e. one multiply-add per coordinate per plane — exactly the workload the
  paper assigns to the Scalar MAC Units inside each PE_Zi.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.camera import CameraModel

Array = jax.Array

# The 3x3 products below run in full float32. At default precision a TPU
# multiplies f32 matrices in one bfloat16 pass, which moves the
# homography K @ H @ K^-1 (fx ~ 200, cx ~ 120) by up to half a pixel and
# flips nearest-voxel votes; these products are tiny, so exactness is free.
HIGHEST = jax.lax.Precision.HIGHEST
_mm = partial(jnp.matmul, precision=HIGHEST)


class SE3(NamedTuple):
    """Rigid transform X_out = R @ X_in + t. Batched via leading dims."""

    R: Array  # (..., 3, 3)
    t: Array  # (..., 3)

    @staticmethod
    def identity(batch: tuple[int, ...] = ()) -> "SE3":
        R = jnp.broadcast_to(jnp.eye(3, dtype=jnp.float32), batch + (3, 3))
        t = jnp.zeros(batch + (3,), dtype=jnp.float32)
        return SE3(R, t)

    def compose(self, other: "SE3") -> "SE3":
        """self ∘ other: apply `other` first, then `self`."""
        return SE3(_mm(self.R, other.R), _mm(self.R, other.t[..., None])[..., 0] + self.t)

    def inverse(self) -> "SE3":
        Rt = jnp.swapaxes(self.R, -1, -2)
        return SE3(Rt, -_mm(Rt, self.t[..., None])[..., 0])

    def apply(self, points: Array) -> Array:
        """points: (..., 3) -> transformed (..., 3)."""
        return (jnp.einsum("...ij,...nj->...ni", self.R, points, precision=HIGHEST)
                + self.t[..., None, :])


def so3_exp(w: Array) -> Array:
    """Rodrigues: axis-angle (..., 3) -> rotation matrix (..., 3, 3)."""
    theta = jnp.linalg.norm(w, axis=-1, keepdims=True)[..., None]  # (...,1,1)
    safe = jnp.where(theta < 1e-8, 1.0, theta)
    # build K (normalized cross-product matrix) explicitly for clarity
    wx, wy, wz = w[..., 0], w[..., 1], w[..., 2]
    zeros = jnp.zeros_like(wx)
    K = jnp.stack(
        [
            jnp.stack([zeros, -wz, wy], axis=-1),
            jnp.stack([wz, zeros, -wx], axis=-1),
            jnp.stack([-wy, wx, zeros], axis=-1),
        ],
        axis=-2,
    )
    K = K / safe[..., 0, 0][..., None, None]
    eye = jnp.broadcast_to(jnp.eye(3, dtype=w.dtype), K.shape)
    sin_t, cos_t = jnp.sin(theta[..., 0, 0]), jnp.cos(theta[..., 0, 0])
    R = eye + sin_t[..., None, None] * K + (1.0 - cos_t)[..., None, None] * _mm(K, K)
    return jnp.where(theta < 1e-8, eye, R)


def interpolate_pose(p0: SE3, p1: SE3, frac) -> SE3:
    """Linear pose interpolation (translation lerp; rotation via axis-angle).

    Used to assign a camera pose to each event timestamp between two
    trajectory samples (events are asynchronous; poses are sampled).
    For the small inter-sample motions of event cameras this matches the
    first-order interpolation used by the EMVS reference implementation.

    Host float32 NumPy, batched over leading dims (`frac` has the poses'
    batch shape): the inputs are host pose samples and the results go to
    the host, so a device round trip per frame batch would be pure
    dispatch cost. The 3x3 products are written out elementwise, so a
    pose is bit-identical whatever batch it is interpolated in.
    """
    f32 = np.float32
    R0, R1 = np.asarray(p0.R, f32), np.asarray(p1.R, f32)
    t0, t1 = np.asarray(p0.t, f32), np.asarray(p1.t, f32)
    frac = np.asarray(frac, f32)[..., None]
    t = t0 + frac * (t1 - t0)
    # relative rotation
    dR = _mm3(R1, np.swapaxes(R0, -1, -2))
    w = so3_log(dR)
    R = _mm3(_so3_exp_host(w * frac), R0)
    return SE3(R, t)


def so3_log(R) -> np.ndarray:
    """Rotation matrix -> axis-angle (..., 3), host float32."""
    R = np.asarray(R, np.float32)
    trace = R[..., 0, 0] + R[..., 1, 1] + R[..., 2, 2]
    cos_theta = np.clip((trace - 1.0) / 2.0, -1.0, 1.0)
    theta = np.arccos(cos_theta)
    sin_theta = np.sin(theta)
    v = np.stack(
        [
            R[..., 2, 1] - R[..., 1, 2],
            R[..., 0, 2] - R[..., 2, 0],
            R[..., 1, 0] - R[..., 0, 1],
        ],
        axis=-1,
    )
    scale = np.where(np.abs(sin_theta) < 1e-8, np.float32(0.5),
                     theta / (2.0 * sin_theta + 1e-30))
    return v * scale[..., None]


def _so3_exp_host(w: np.ndarray) -> np.ndarray:
    """Rodrigues on the host: `so3_exp`'s formula in float32 NumPy."""
    wx, wy, wz = w[..., 0], w[..., 1], w[..., 2]
    theta = np.sqrt(wx * wx + wy * wy + wz * wz)[..., None, None]
    safe = np.where(theta < 1e-8, np.float32(1.0), theta)
    zeros = np.zeros_like(wx)
    K = np.stack(
        [
            np.stack([zeros, -wz, wy], axis=-1),
            np.stack([wz, zeros, -wx], axis=-1),
            np.stack([-wy, wx, zeros], axis=-1),
        ],
        axis=-2,
    ) / safe
    eye = np.broadcast_to(np.eye(3, dtype=np.float32), K.shape)
    R = eye + np.sin(theta) * K + (1.0 - np.cos(theta)) * _mm3(K, K)
    return np.where(theta < 1e-8, eye, R)


def _mm3(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Batched 3x3 product as three rank-1 terms added in a fixed order
    (no BLAS), so each product is the same bits in any batch."""
    return (A[..., :, 0, None] * B[..., None, 0, :]
            + A[..., :, 1, None] * B[..., None, 1, :]
            + A[..., :, 2, None] * B[..., None, 2, :])


# ---------------------------------------------------------------------------
# Plane sweep: depth planes, canonical homography, proportional coefficients
# ---------------------------------------------------------------------------


def depth_planes(z_min: float, z_max: float, num: int, inverse_depth: bool = True) -> Array:
    """Depth plane placement. EMVS samples uniformly in inverse depth."""
    if inverse_depth:
        inv = jnp.linspace(1.0 / z_max, 1.0 / z_min, num, dtype=jnp.float32)
        return (1.0 / inv)[::-1]  # ascending depth
    return jnp.linspace(z_min, z_max, num, dtype=jnp.float32)


def relative_pose_ref_from_cam(T_w_ref: SE3, T_w_cam: SE3) -> SE3:
    """T_ref_cam: maps points in current-camera frame -> reference frame."""
    return T_w_ref.inverse().compose(T_w_cam)


def canonical_homography(cam: CameraModel, T_ref_cam: SE3, z0: Array) -> Array:
    """H_Z0 (3x3): current-camera pixels -> reference pixels via plane z=Z0.

    The plane z = Z0 in the *reference* frame, expressed in the current
    frame, has normal n_c = R_cr^T e_z and offset d_c = Z0 - e_z . t_rc
    (with T_ref_cam = (R_rc, t_rc) mapping cur -> ref). The induced
    homography cur -> ref is

        H = K (R_rc + t_rc n_c^T / d_c) K^{-1}

    computed once per event frame (ARM-side work in the paper).
    """
    R_rc, t_rc = T_ref_cam.R, T_ref_cam.t
    e_z = jnp.array([0.0, 0.0, 1.0], dtype=jnp.float32)
    n_c = _mm(R_rc.T, e_z)  # plane normal in current frame
    d_c = z0 - _mm(e_z, t_rc)  # plane offset along ray in current frame
    H_metric = R_rc + jnp.outer(t_rc, n_c) / d_c
    H = _mm(_mm(cam.K, H_metric), cam.K_inv)
    return (H / H[2, 2]).astype(jnp.float32)


class PlaneSweepCoeffs(NamedTuple):
    """phi: the proportional back-projection coefficients (paper sub-task 3).

    alpha:  (Nz,)  scale of centred canonical coords
    beta_x: (Nz,)  per-plane x offset
    beta_y: (Nz,)  per-plane y offset
    """

    alpha: Array
    beta_x: Array
    beta_y: Array


def proportional_coeffs(
    cam: CameraModel, T_ref_cam: SE3, z0: Array, planes: Array
) -> PlaneSweepCoeffs:
    """Compute phi = {alpha_i, beta_i} for all depth planes (once per frame)."""
    c_ref = T_ref_cam.t  # current camera centre in reference frame
    cz = c_ref[2]
    s = (planes - cz) / (z0 - cz)  # (Nz,)
    alpha = s * z0 / planes
    beta_x = cam.fx * c_ref[0] * (1.0 - s) / planes
    beta_y = cam.fy * c_ref[1] * (1.0 - s) / planes
    return PlaneSweepCoeffs(
        alpha.astype(jnp.float32), beta_x.astype(jnp.float32), beta_y.astype(jnp.float32)
    )


def apply_homography(H: Array, xy: Array) -> Array:
    """Apply 3x3 homography to pixel coords (..., 2) with normalization.

    This is P(Z0): the PE_Z0 matrix-vector MAC + normalization unit.
    """
    x, y = xy[..., 0], xy[..., 1]
    denom = H[2, 0] * x + H[2, 1] * y + H[2, 2]
    u = (H[0, 0] * x + H[0, 1] * y + H[0, 2]) / denom
    v = (H[1, 0] * x + H[1, 1] * y + H[1, 2]) / denom
    return jnp.stack([u, v], axis=-1)


def propagate_to_planes(
    cam: CameraModel, xy0: Array, phi: PlaneSweepCoeffs
) -> tuple[Array, Array]:
    """P(Z0 -> Zi): centred multiply-add per plane (PE_Zi Scalar MACs).

    xy0: (E, 2) canonical-plane coords. Returns (x_i, y_i): each (Nz, E).
    """
    xc = xy0[..., 0] - cam.cx  # (E,)
    yc = xy0[..., 1] - cam.cy
    x_i = phi.alpha[:, None] * xc[None, :] + phi.beta_x[:, None] + cam.cx
    y_i = phi.alpha[:, None] * yc[None, :] + phi.beta_y[:, None] + cam.cy
    return x_i, y_i


def pose_distance(a: SE3, b: SE3) -> Array:
    """Translation distance between two poses (keyframe criterion)."""
    return jnp.linalg.norm(a.t - b.t)
