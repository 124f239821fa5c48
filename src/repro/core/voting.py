"""Volumetric ray-counting (R): DSI voting.

Three formulations, all numerically reconciled by tests:

  1. `vote_scatter`       — the CPU/GPU-idiomatic port: scatter-add into
                            the volume (what the FPGA's Vote Execute Unit
                            does with DRAM read-modify-write). Reference
                            semantics; slow on TPU (random HBM traffic).
  2. `vote_onehot_matmul` — the TPU-native reformulation (DESIGN.md §2):
                            per depth plane, votes = Ox^T @ Oy with
                            one-hot (nearest) or two-hot (bilinear) event
                            row encodings. Runs on the MXU; no scatter.
     `vote_onehot_chunk`  — (2) for nearest votes of several frames at
                            once, as the matmul sweep runs it: int8
                            one-hot rows of all F·E events of a chunk in
                            ONE s8 x s8 -> s32 contraction per plane, added
                            to the int32 DSI once per chunk, so the DSI
                            makes one HBM round trip per F frames. On the
                            TPU the row producers and the add fuse into
                            the contraction; no row reaches HBM. 0/1 rows
                            and weights make it exact: the same integer
                            counts as (1) and (2), bit for bit. What is
                            left is the dense contraction itself,
                            h·w MACs a plane for each vote, which only a
                            tile-sparse vote or (3) removes.
  3. kernels/backproject_vote — the Pallas kernel implementing (2) fused
                            with P(Z0->Zi), tiled for VMEM.

Both nearest and bilinear voting are exact in formulation (2):
bilinear 4-neighbour weights are separable, (1-fx,fx) ⊗ (1-fy,fy).

Out-of-bounds projections are dropped ("projection missing judgement"
performed by the paper's Nearest Voxel Finder).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.quant.fixed_point import round_half_away

Array = jax.Array


def _sanitize(coord: Array) -> Array:
    """Clamp non-finite / absurd coords to a harmless far-out-of-bounds value.

    Invalid (masked) events are parked at -1e4 by the data pipeline, but a
    near-singular homography denominator can still produce inf/NaN; any
    such coordinate must fail the bounds check rather than poison the
    votes (0 * NaN = NaN). Also keeps round()->int32 overflow-free.
    """
    c = jnp.where(jnp.isfinite(coord), coord, jnp.float32(-1e6))
    return jnp.clip(c, -1e6, 1e6)


def _round_half_up(x: Array) -> Array:
    """RTL-style nearest-pixel rounding (floor(x+0.5)); jnp.round would be
    half-to-even and disagree with the hardware convention at exact .5."""
    return jnp.floor(x + 0.5)


def _bounds_mask_nearest(xi: Array, yi: Array, w: int, h: int) -> Array:
    xr, yr = _round_half_up(xi), _round_half_up(yi)
    return (xr >= 0) & (xr <= w - 1) & (yr >= 0) & (yr <= h - 1)


def _bounds_mask_bilinear(xi: Array, yi: Array, w: int, h: int) -> Array:
    x0, y0 = jnp.floor(xi), jnp.floor(yi)
    return (x0 >= 0) & (x0 + 1 <= w - 1) & (y0 >= 0) & (y0 + 1 <= h - 1)


# ---------------------------------------------------------------------------
# 1. Scatter formulation (algorithmic baseline; FPGA Vote-Execute semantics)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("w", "h", "mode"))
def vote_scatter(
    dsi: Array, x_i: Array, y_i: Array, *, w: int, h: int, mode: str = "nearest",
    weights: Array | None = None,
) -> Array:
    """Scatter-add votes into dsi (Nz, h, w).

    x_i, y_i: (Nz, E) projected coords per plane. mode: nearest|bilinear.
    weights: optional (Nz, E) per-event vote weight (default 1).
    """
    x_i, y_i = _sanitize(x_i), _sanitize(y_i)
    nz = dsi.shape[0]
    base = jnp.ones(x_i.shape, dtype=jnp.float32) if weights is None else weights
    if mode == "nearest":
        m = _bounds_mask_nearest(x_i, y_i, w, h)
        xr = jnp.clip(_round_half_up(x_i).astype(jnp.int32), 0, w - 1)
        yr = jnp.clip(_round_half_up(y_i).astype(jnp.int32), 0, h - 1)
        votes = jnp.where(m, base, 0.0)
        if dsi.dtype in (jnp.int16, jnp.int32):
            votes = votes.astype(dsi.dtype)
        z_idx = jnp.broadcast_to(jnp.arange(nz, dtype=jnp.int32)[:, None], x_i.shape)
        return dsi.at[z_idx, yr, xr].add(votes)
    elif mode == "bilinear":
        m = _bounds_mask_bilinear(x_i, y_i, w, h)
        x0 = jnp.clip(jnp.floor(x_i).astype(jnp.int32), 0, w - 2)
        y0 = jnp.clip(jnp.floor(y_i).astype(jnp.int32), 0, h - 2)
        fx = x_i - x0.astype(x_i.dtype)
        fy = y_i - y0.astype(y_i.dtype)
        z_idx = jnp.broadcast_to(jnp.arange(nz, dtype=jnp.int32)[:, None], x_i.shape)
        wmask = jnp.where(m, base, 0.0)
        out = dsi.astype(jnp.float32) if dsi.dtype != jnp.float32 else dsi
        for dx, dy, wgt in (
            (0, 0, (1 - fx) * (1 - fy)),
            (1, 0, fx * (1 - fy)),
            (0, 1, (1 - fx) * fy),
            (1, 1, fx * fy),
        ):
            out = out.at[z_idx, y0 + dy, x0 + dx].add(wmask * wgt)
        return out if dsi.dtype == jnp.float32 else out.astype(dsi.dtype)
    raise ValueError(f"unknown voting mode: {mode}")


# ---------------------------------------------------------------------------
# 2. One-hot matmul formulation (TPU-native; runs on the MXU)
# ---------------------------------------------------------------------------


def onehot_rows_nearest(coord: Array, size: int, valid: Array) -> Array:
    """(..., E) coords -> (..., E, size) one-hot rows; invalid rows all-zero."""
    idx = _round_half_up(coord).astype(jnp.int32)
    grid = jnp.arange(size, dtype=jnp.int32)
    rows = (idx[..., None] == grid).astype(jnp.float32)
    return rows * valid[..., None].astype(jnp.float32)


def twohot_rows_bilinear(coord: Array, size: int, valid: Array) -> Array:
    """(..., E) coords -> (..., E, size) two-hot rows with (1-f, f) weights."""
    c0 = jnp.floor(coord).astype(jnp.int32)
    f = (coord - c0.astype(coord.dtype)).astype(jnp.float32)
    grid = jnp.arange(size, dtype=jnp.int32)
    lo = (c0[..., None] == grid).astype(jnp.float32) * (1.0 - f)[..., None]
    hi = ((c0 + 1)[..., None] == grid).astype(jnp.float32) * f[..., None]
    return (lo + hi) * valid[..., None].astype(jnp.float32)


@partial(jax.jit, static_argnames=("w", "h", "mode"))
def vote_onehot_matmul(
    dsi: Array, x_i: Array, y_i: Array, *, w: int, h: int, mode: str = "nearest",
    weights: Array | None = None,
) -> Array:
    """Per-plane votes = Oy^T @ Ox  ∈ (h, w), accumulated into dsi (Nz,h,w).

    The contraction over events is a matmul — the systolic-array
    reformulation of the FPGA's scatter unit (DESIGN.md §2).
    """
    x_i, y_i = _sanitize(x_i), _sanitize(y_i)
    if mode == "nearest":
        valid = _bounds_mask_nearest(x_i, y_i, w, h)
        ox = onehot_rows_nearest(x_i, w, valid)  # (Nz, E, w)
        oy = onehot_rows_nearest(y_i, h, valid)  # (Nz, E, h)
    elif mode == "bilinear":
        valid = _bounds_mask_bilinear(x_i, y_i, w, h)
        ox = twohot_rows_bilinear(x_i, w, valid)
        oy = twohot_rows_bilinear(y_i, h, valid)
    else:
        raise ValueError(f"unknown voting mode: {mode}")
    if weights is not None:
        ox = ox * weights[..., None]
    # fractional bilinear weights are contracted in full f32 (a TPU's
    # default is one bf16 pass); 0/1 nearest rows are exact at any precision
    precision = jax.lax.Precision.HIGHEST if mode == "bilinear" else None
    votes = jnp.einsum("zeh,zew->zhw", oy, ox,
                       precision=precision)  # MXU contraction over events
    if dsi.dtype in (jnp.int16, jnp.int32):
        # RTL rounding convention: half away from zero, matching the
        # fixed-point quantizers — jnp.round would be half-to-even and
        # disagree with quant/fixed_point at exact half-integer votes
        votes = round_half_away(votes).astype(dsi.dtype)
    return dsi + votes


def vote_onehot_chunk(dsi: Array, x_i: Array, y_i: Array, weights: Array, *,
                      w: int, h: int) -> Array:
    """Nearest votes of F frames into an integer dsi (Nz, h, w) at once.

    x_i, y_i: (F, Nz, E) projected coords; weights: (F, E) 0/1 vote
    weights (event validity times frame validity). Per plane, the rows
    of all F·E events are int8 one-hots and one dot_general contracts
    them into int32 counts, which are added to the DSI once.

    Exact for 0/1 weights only (a nonzero weight votes 1): the sweep's
    masks are 0/1 by contract (`SWEEP_INPUT_CONTRACTS`), so this is
    `vote_onehot_matmul` over the F frames, bit for bit. Callers with
    fractional weights use `vote_onehot_matmul`.
    """
    x_i, y_i = _sanitize(x_i), _sanitize(y_i)
    nz = x_i.shape[1]
    keep = _bounds_mask_nearest(x_i, y_i, w, h) & (weights[:, None, :] != 0)

    def rows(coord: Array, size: int, mask: Array | None) -> Array:
        idx = _round_half_up(coord).astype(jnp.int32)
        if mask is not None:  # an index off the grid gives an all-zero row
            idx = jnp.where(mask, idx, -1)
        idx = jnp.moveaxis(idx, 0, 1).reshape(nz, -1)  # (Nz, F*E)
        return (idx[..., None] == jnp.arange(size, dtype=jnp.int32)
                ).astype(jnp.int8)

    # a dropped event needs one all-zero row of its pair, not two
    oy, ox = rows(y_i, h, keep), rows(x_i, w, None)
    votes = jax.lax.dot_general(oy, ox, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.int32)
    return dsi + votes.astype(dsi.dtype)
