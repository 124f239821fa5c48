"""Disparity Space Image (DSI): the ray-density volume.

Layout: (Nz, h, w), z-major so one depth plane is a contiguous (h, w)
image — matching both the FPGA's per-PE_Zi plane buffers and the Pallas
kernel's per-grid-step VMEM tile.

Scores are int32 while accumulating (overflow-safe), stored/checkpointed
as int16 per the paper's DSI-score quantization (Table 1). A property test
guards the paper's implicit claim that 16 bits never saturate for
1024-event frames (max votes per voxel per keyframe <= #events between
keyframes, bounded in practice by a few thousand).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.camera import CameraModel
from repro.core.geometry import depth_planes

Array = jax.Array

DSI_STORE_DTYPE = jnp.int16  # paper Table 1: DSI scores, 16-bit integer
DSI_ACCUM_DTYPE = jnp.int32  # accumulation dtype (saturation-checked on store)


def store_clip_bounds() -> tuple[float, float]:
    """The (min, max) saturating-store clamp as float literals.

    Single source of truth shared by `to_storage` and the fused Pallas
    kernel's in-VMEM int16 store — and the pair the quantization-contract
    linter expects as clamp provenance on any float->int16 cast
    (`EMVSQuantPolicy.sanctioned_clip_bounds()` contains it via the
    Table-1 'dsi' format).
    """
    info = jnp.iinfo(DSI_STORE_DTYPE)
    return float(info.min), float(info.max)


@dataclasses.dataclass(frozen=True)
class DSIConfig:
    width: int = 240
    height: int = 180
    num_planes: int = 128
    z_min: float = 0.5
    z_max: float = 5.0
    inverse_depth: bool = True

    @property
    def shape(self) -> tuple[int, int, int]:
        return (self.num_planes, self.height, self.width)

    def planes(self) -> Array:
        return depth_planes(self.z_min, self.z_max, self.num_planes, self.inverse_depth)

    @staticmethod
    def for_camera(cam: CameraModel, num_planes: int = 128, z_min: float = 0.5,
                   z_max: float = 5.0, inverse_depth: bool = True) -> "DSIConfig":
        return DSIConfig(cam.width, cam.height, num_planes, z_min, z_max, inverse_depth)


def zeros(cfg: DSIConfig, dtype=DSI_ACCUM_DTYPE) -> Array:
    return jnp.zeros(cfg.shape, dtype=dtype)


def to_storage(dsi: Array) -> Array:
    """int32 accumulator -> int16 storage with saturation (RTL-style clip)."""
    info = jnp.iinfo(DSI_STORE_DTYPE)
    return jnp.clip(dsi, info.min, info.max).astype(DSI_STORE_DTYPE)


def from_storage(dsi: Array) -> Array:
    return dsi.astype(DSI_ACCUM_DTYPE)


def storage_roundtrip(dsi: Array) -> Array:
    """Apply int16 store semantics to an accumulator DSI (any leading dims).

    Voting accumulates in int32 (or float32 for bilinear); the device
    checkpoints DSI scores as int16 (Table 1). This clips exactly like the
    RTL store path and returns the accumulator dtype, so downstream
    detection sees the quantized scores. Elementwise, hence safe for both
    a single (Nz, h, w) volume and a batched (S, Nz, h, w) sweep.
    """
    return from_storage(to_storage(dsi))


def fuse_harmonic_mean(a: Array, b: Array) -> Array:
    """Voxel-wise fusion of two cameras' DSIs defined at one reference
    view (Ghosh & Gallego 2022, arXiv 2207.10494): the harmonic mean
    F = 2ab / (a + b), which is 0 wherever either camera cast no vote.

    Integer votes (nearest voting) give F = 2ab / (a + b) rounded once
    to float32, to nearest, ties to even: computed in int32 with three
    exact floor divisions (`_div_round_f32`), so every backend returns
    the same bits, which a float32 divide, not correctly rounded on
    every chip, would not. A reference gets them by dividing in float64
    and rounding once to float32. Votes of 2**15 or more, which would
    overflow 2ab in int32, and float votes (bilinear) are fused in
    float32 as p = a * b, F = (p + p) / (a + b), whose last place may
    differ between backends; integer maps pay for that divide only when
    some voxel holds such a vote.
    """
    voted = (a > 0) & (b > 0)

    def by_float_divide() -> Array:
        af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
        p = af * bf
        return jnp.where(voted, (p + p) / jnp.where(voted, af + bf, 1.0),
                         0.0)

    if not jnp.issubdtype(a.dtype, jnp.integer):
        return by_float_divide()
    exact = voted & (a < 2**15) & (b < 2**15)
    ai = jnp.where(exact, a.astype(jnp.int32), 1)
    bi = jnp.where(exact, b.astype(jnp.int32), 1)
    fused = jnp.where(exact, _div_round_f32(2 * ai * bi, ai + bi), 0.0)
    wide = voted & ~exact
    return jax.lax.cond(jnp.any(wide),
                        lambda f: jnp.where(wide, by_float_divide(), f),
                        lambda f: f, fused)


def _floor_div(t: Array, s: Array) -> tuple[Array, Array]:
    """floor(t / s) and t mod s, for int32 0 <= t < 2**31 and
    1 <= s < 2**16 with t / s < 2**16: a float32 quotient lies within one
    of the integer one, and the int32 remainder corrects it."""
    d = (t.astype(jnp.float32) / s.astype(jnp.float32)).astype(jnp.int32)
    r = t - d * s
    d, r = jnp.where(r < 0, d - 1, d), jnp.where(r < 0, r + s, r)
    return jnp.where(r >= s, d + 1, d), jnp.where(r >= s, r - s, r)


def _div_round_f32(n: Array, s: Array) -> Array:
    """n / s rounded to the nearest float32 (ties to even), for int32
    1 <= s <= n < 2**31 with n / s < 2**16, in integer arithmetic."""
    q, r = _floor_div(n, s)
    f_hi, r = _floor_div(r << 15, s)
    f_lo, r = _floor_div(r << 10, s)
    frac = (f_hi << 10) | f_lo  # the first 25 bits of r / s
    width = 32 - jax.lax.clz(q)  # bits of q, 1..16
    mant = (q << (24 - width)) | (frac >> (width + 1))  # 24 significant bits
    guard = (frac >> width) & 1
    sticky = ((frac & ((1 << width) - 1)) != 0) | (r != 0)
    mant = mant + (guard & (sticky | (mant & 1)).astype(jnp.int32))
    scale = jax.lax.bitcast_convert_type((width - 24 + 127) << 23, jnp.float32)
    return mant.astype(jnp.float32) * scale


def saturation_fraction(dsi: Array) -> Array:
    """Fraction of voxels that would clip at int16 — paper's 16b adequacy claim."""
    info = jnp.iinfo(DSI_STORE_DTYPE)
    return jnp.mean((dsi > info.max) | (dsi < info.min))


def store_saturation_fraction(dsi: Array) -> Array:
    """Fraction of voxels sitting AT the int16 store limits (inclusive).

    `saturation_fraction` asks the pre-store question ("would this
    accumulator clip?") and is identically zero on anything that already
    went through `storage_roundtrip`. Live streams only ever see stored
    volumes, so the streaming monitor uses this boundary-inclusive form:
    a voxel at exactly ±int16 max either clipped or is about to, and
    either way the paper's "16 bits never saturate" claim is at risk.
    Elementwise, so batched (S, Nz, h, w) sweeps work unchanged.
    """
    info = jnp.iinfo(DSI_STORE_DTYPE)
    return jnp.mean((dsi >= info.max) | (dsi <= info.min))
