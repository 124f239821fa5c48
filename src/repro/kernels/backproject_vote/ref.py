"""Pure-jnp oracle for the fused backproject+vote kernel.

Semantics: given canonical-plane event coords xy0 (F, E, 2), validity
(F, E), and per-frame plane-sweep coefficients phi (F, Nz, 3) =
(alpha, beta_x, beta_y), produce the DSI (Nz, h, w):

    x_i = alpha[z] * (x0 - cx) + beta_x[z] + cx
    y_i = alpha[z] * (y0 - cy) + beta_y[z] + cy
    DSI[z] += sum_e onehot(y_i[e]) ⊗ onehot(x_i[e])     (nearest)
    DSI[z] += sum_e twohot(y_i[e]) ⊗ twohot(x_i[e])     (bilinear)

with out-of-bounds projections dropped (bounds are the *logical* w, h,
not the padded kernel tile).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array


@partial(jax.jit, static_argnames=("w", "h", "mode", "quantize_plane_coords"))
def backproject_vote_ref(
    xy0: Array,  # (F, E, 2) float32 canonical coords
    valid: Array,  # (F, E) bool or float
    phi: Array,  # (F, Nz, 3) float32: alpha, beta_x, beta_y
    *,
    cx: float,
    cy: float,
    w: int,
    h: int,
    mode: str = "nearest",
    quantize_plane_coords: bool = False,
) -> Array:
    """`quantize_plane_coords` applies the Table-1 int8 plane-coord
    contract (via the policy object itself, NOT the kernel's in-body
    replica — so kernel-vs-ref tests cross-check the two
    implementations) before the vote sanitize, mirroring the quantized
    nearest datapath of `pipeline.project_frame`."""
    F, E, _ = xy0.shape
    nz = phi.shape[1]
    # bilinear weights contract in full f32, as in the kernel
    precision = jax.lax.Precision.HIGHEST if mode == "bilinear" else None

    def frame(dsi, inputs):
        xy, v, ph = inputs
        alpha, beta_x, beta_y = ph[:, 0], ph[:, 1], ph[:, 2]
        x_i = alpha[:, None] * (xy[None, :, 0] - cx) + beta_x[:, None] + cx
        y_i = alpha[:, None] * (xy[None, :, 1] - cy) + beta_y[:, None] + cy
        if quantize_plane_coords:
            from repro.quant.policies import TABLE1

            x_i, y_i = TABLE1.quantize_plane_coords(x_i, y_i)
        x_i = jnp.clip(jnp.where(jnp.isfinite(x_i), x_i, -1e6), -1e6, 1e6)
        y_i = jnp.clip(jnp.where(jnp.isfinite(y_i), y_i, -1e6), -1e6, 1e6)
        vf = v.astype(jnp.float32)
        if mode == "nearest":
            # RTL convention: round half up (floor(x+0.5)), as in the kernel
            xr, yr = jnp.floor(x_i + 0.5), jnp.floor(y_i + 0.5)
            ok = (xr >= 0) & (xr <= w - 1) & (yr >= 0) & (yr <= h - 1)
            wt = vf[None, :] * ok.astype(jnp.float32)
            ox = (xr[..., None] == jnp.arange(w)).astype(jnp.float32)
            oy = (yr[..., None] == jnp.arange(h)).astype(jnp.float32)
            ox = ox * wt[..., None]
        else:
            x0f, y0f = jnp.floor(x_i), jnp.floor(y_i)
            ok = (x0f >= 0) & (x0f + 1 <= w - 1) & (y0f >= 0) & (y0f + 1 <= h - 1)
            wt = vf[None, :] * ok.astype(jnp.float32)
            fx = x_i - x0f
            fy = y_i - y0f
            gx = jnp.arange(w, dtype=jnp.float32)
            gy = jnp.arange(h, dtype=jnp.float32)
            ox = ((x0f[..., None] == gx) * (1 - fx)[..., None]
                  + ((x0f + 1)[..., None] == gx) * fx[..., None])
            oy = ((y0f[..., None] == gy) * (1 - fy)[..., None]
                  + ((y0f + 1)[..., None] == gy) * fy[..., None])
            ox = ox * wt[..., None]
        votes = jnp.einsum("zeh,zew->zhw", oy, ox, precision=precision)
        return dsi + votes, None

    dsi0 = jnp.zeros((nz, h, w), dtype=jnp.float32)
    dsi, _ = jax.lax.scan(frame, dsi0, (xy0, valid, phi))
    return dsi
