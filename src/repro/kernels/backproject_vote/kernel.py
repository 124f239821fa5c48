"""Pallas TPU kernel: fused proportional back-projection + DSI voting
+ int16 saturating store + depth max/argmax detection reduction.

This is the Proportional Projection Module of the paper (PE_Zi array +
Vote Execute Unit), re-architected for the TPU memory hierarchy:

  FPGA                                  TPU (this kernel)
  ----------------------------------    ----------------------------------
  multiple PE_Zi, one depth plane 	    grid axis 0 = depth-plane blocks
    each                                  (BZ planes per step)
  Buf_I double buffering of event       grid axis 1 = event frames, minor;
    frames                                Pallas pipelines HBM->VMEM DMAs
                                          of frame f+1 under compute of f
  Scalar MAC units (P(Z0->Zi))          VPU multiply-add on (E,) vectors
  Nearest Voxel Finder + miss judge     int8 plane-coord quantization +
                                          round/floor + bounds mask
  Vote Address Generator + Vote         one-hot/two-hot row construction +
    Execute Unit (DRAM RMW scatter)       MXU matmul  votes = Oy^T @ Ox,
                                          accumulated in a VMEM-resident
                                          (BZ, h_pad, w_pad) scratch block
  DSI store (on-chip BRAM, int16)       in-VMEM clip to the int16 range +
                                          cast, written back to HBM once
  Ray Counter -> depth map readout      streaming max/argmax + parabola
                                          state carried across z-blocks in
                                          VMEM scratch (the local_max
                                          reduction, fused)

Tiling: the full (h_pad, w_pad) plane tile lives in VMEM
(184*256*4 B = 188 KiB) — the DAVIS-scale DSI plane is small relative to
the 16 MiB scoped-VMEM budget, so we tile over depth, not space. Votes accumulate in a
float32 VMEM scratch block revisited across all frames (axis 1 minor);
on the last frame step the block is stored (int16 saturating when
quantized) and folded into the detection state, so the stored DSI makes
exactly one HBM trip and the max/argmax never reads it back — the no-
DRAM-round-trip datapath the paper's speedup comes from
(docs/kernel_fusion.md walks the stages and the VMEM budget).

Events arrive as (F, 1, E) rows, events on lanes, and the one-hot
factors are built transposed, (pixels, E): the event-index contraction
feeds the MXU one (h_pad, E) x (w_pad, E)^T matmul per plane and frame —
systolic-friendly dims (multiples of 8/128 via padding), and no
lane-to-sublane relayout (docs/kernel_fusion.md, "Block layout").

Detection semantics are bitwise those of `kernels/local_max` (and hence
of `core/detection.detect_structure`): first-max-wins streaming argmax
with running (c[z*-1], c[z*], c[z*+1]) capture, clamped-index boundary
conventions, and the clipped parabola offset. The z-block grid axis is
MAJOR (frames minor), so blocks complete in ascending global-z order and
the streaming scan across grid steps is valid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

Array = jax.Array

LANE = 128
SUBLANE = 8

# int16 saturating-store range (Table 1 'dsi' format; the in-kernel clamp
# literals must equal EMVSQuantPolicy.sanctioned_clip_bounds() entries or
# the quantization-contract linter flags the float->int16 cast)
from repro.core.dsi import store_clip_bounds

DSI_STORE_MIN, DSI_STORE_MAX = store_clip_bounds()

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel(
    x_ref,  # (FS, 1, E) raw canonical x coords, events on lanes
    y_ref,  # (FS, 1, E)
    valid_ref,  # (FS, 1, E) float32 1/0
    phi_ref,  # (FS, BZ, 3) alpha, beta_x, beta_y  (per frame, per plane)
    dsi_ref,  # (BZ, h_pad, w_pad) stored DSI block (int16 when quantized)
    conf_ref,  # (h_pad, w_pad) float32 running max over z (output)
    zf_ref,  # (h_pad, w_pad) float32 argmax, parabola-refined at the end
    acc_ref,  # VMEM scratch (BZ, h_pad, w_pad) float32 vote accumulator
    prev_ref,  # VMEM scratch (h_pad, w_pad) value at z-1
    cprev_ref,  # VMEM scratch (h_pad, w_pad) value at z*-1
    cnext_ref,  # VMEM scratch (h_pad, w_pad) value at z*+1
    pwb_ref,  # VMEM scratch (h_pad, w_pad) 1.0 iff z-1 set a new best
    *,
    cx: float,
    cy: float,
    w: int,
    h: int,
    nz: int,
    bz: int,
    fs: int,
    nf: int,
    mode: str,
    quantized: bool,
    onehot_dtype,
):
    zb = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((zb == 0) & (f == 0))
    def _init_detect():
        # DSI scores are >= 0; -1 never wins, so z=0 always sets a best
        conf_ref[...] = jnp.full_like(conf_ref, -1.0)
        zf_ref[...] = jnp.zeros_like(zf_ref)
        prev_ref[...] = jnp.zeros_like(prev_ref)
        cprev_ref[...] = jnp.zeros_like(cprev_ref)
        cnext_ref[...] = jnp.zeros_like(cnext_ref)
        pwb_ref[...] = jnp.zeros_like(pwb_ref)

    e = x_ref.shape[2]
    w_pad = acc_ref.shape[2]
    h_pad = acc_ref.shape[1]

    # pixel index down the sublanes, one event per lane: the one-hot rows
    # are built transposed, (pixels, E), so an event row (1, E) broadcasts
    # over sublanes and never moves between lanes and sublanes. Integer
    # iotas (Mosaic has no float iota), converted once per grid step.
    pix_x = jax.lax.broadcasted_iota(jnp.int32, (w_pad, e), 0).astype(jnp.float32)
    pix_y = jax.lax.broadcasted_iota(jnp.int32, (h_pad, e), 0).astype(jnp.float32)
    acc_type = jnp.int32 if onehot_dtype == jnp.int8 else jnp.float32
    # fp32 rows (bilinear weights) are contracted exactly, not in one
    # bf16 pass; 0/1 bf16/int8 rows are exact at any precision
    precision = (jax.lax.Precision.HIGHEST if onehot_dtype == jnp.float32
                 else None)

    # one frame at a time: a rolled loop keeps one frame's factors live,
    # where unrolling `fs` frames ran the bilinear variant out of VMEM
    @pl.loop(0, fs)
    def _frame(j):
        x0 = x_ref[j] - cx  # (1, E) centred canonical coords
        y0 = y_ref[j] - cy
        vv = valid_ref[j]
        for p in range(bz):
            # P(Z0 -> Zi): one multiply-add per coordinate (the PE_Zi
            # scalar MACs); the frame's (1, 1) coefficients broadcast
            # over its events
            alpha = phi_ref[j, p:p + 1, 0:1]
            bx = phi_ref[j, p:p + 1, 1:2]
            by = phi_ref[j, p:p + 1, 2:3]
            xi = alpha * x0 + bx + cx
            yi = alpha * y0 + by + cy
            if quantized and mode == "nearest":
                # Table 1: plane coords carry int8 — the SAME policy method
                # as the XLA datapath (project_frame), applied in the same
                # order (quantize BEFORE the vote sanitize), so the
                # formulations agree bitwise by construction
                from repro.quant.policies import TABLE1

                xi = TABLE1.quantize_plane_coord_values(xi)
                yi = TABLE1.quantize_plane_coord_values(yi)
            xi = jnp.clip(jnp.where(jnp.isfinite(xi), xi, -1e6), -1e6, 1e6)
            yi = jnp.clip(jnp.where(jnp.isfinite(yi), yi, -1e6), -1e6, 1e6)

            if mode == "nearest":
                xr = jnp.floor(xi + 0.5)
                yr = jnp.floor(yi + 0.5)
                # miss judgement against the LOGICAL sensor bounds
                ok = (xr >= 0) & (xr <= w - 1) & (yr >= 0) & (yr <= h - 1)
                wt = vv * ok.astype(jnp.float32)
                # int8 rows (§Perf E1): 0/1 one-hots and the 0/1 validity
                # mask are exact in int8; the MXU's int8 path runs 2x bf16
                ox = (pix_x == xr).astype(onehot_dtype) * wt.astype(onehot_dtype)
                oy = (pix_y == yr).astype(onehot_dtype)
            else:  # bilinear: separable two-hot rows
                xf = jnp.floor(xi)
                yf = jnp.floor(yi)
                ok = ((xf >= 0) & (xf + 1 <= w - 1) & (yf >= 0)
                      & (yf + 1 <= h - 1))
                wt = (vv * ok.astype(jnp.float32)).astype(onehot_dtype)
                fx = (xi - xf).astype(onehot_dtype)
                fy = (yi - yf).astype(onehot_dtype)
                ox = ((pix_x == xf).astype(onehot_dtype) * (1 - fx)
                      + (pix_x == xf + 1).astype(onehot_dtype) * fx)
                oy = ((pix_y == yf).astype(onehot_dtype) * (1 - fy)
                      + (pix_y == yf + 1).astype(onehot_dtype) * fy)
                ox = ox * wt

            # votes = Oy @ Ox^T on the MXU, contracting the event (lane)
            # axis of both; int8 operands accumulate in int32 (exact:
            # counts <= E), float in fp32 (exact: counts << 2^24)
            votes = jax.lax.dot_general(
                oy, ox,
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=acc_type,
            )  # (h_pad, w_pad)
            acc_ref[p, :, :] += votes.astype(jnp.float32)

    @pl.when(f == nf - 1)
    def _store_and_detect():
        # All frames voted into this z-block: store it (once) and fold it
        # into the streaming detection state while it is still VMEM-resident.
        for p in range(bz):
            acc = acc_ref[p, :, :]
            if quantized:
                # int16 saturating store (core/dsi.to_storage semantics);
                # the clamp sanctions the float->int cast for the linter
                stored = jnp.clip(acc, DSI_STORE_MIN, DSI_STORE_MAX).astype(
                    jnp.int16)
                dsi_ref[p, :, :] = stored
                # detection sees the POST-store values — same order as the
                # XLA path (storage_roundtrip, then detect)
                cur = stored.astype(jnp.float32)
            else:
                dsi_ref[p, :, :] = acc
                cur = acc

            # streaming max/argmax update (bitwise kernels/local_max):
            # capture c[z*+1] one step after the argmax was set
            zg = (zb * bz + p).astype(jnp.float32)  # global plane index
            cnext_new = jnp.where(pwb_ref[...] > 0.0, cur, cnext_ref[...])
            is_new_best = cur > conf_ref[...]
            cprev_ref[...] = jnp.where(is_new_best, prev_ref[...],
                                       cprev_ref[...])
            zf_ref[...] = jnp.where(is_new_best, zg, zf_ref[...])
            conf_ref[...] = jnp.where(is_new_best, cur, conf_ref[...])
            # z*+1 unseen yet for a fresh best: default to 0 until captured
            cnext_ref[...] = jnp.where(is_new_best, jnp.zeros_like(cur),
                                       cnext_new)
            pwb_ref[...] = is_new_best.astype(jnp.float32)
            prev_ref[...] = cur

    @pl.when((zb == pl.num_programs(0) - 1) & (f == nf - 1))
    def _finalize_parabola():
        # boundary conventions match the ref oracle's index clamping:
        #   z*=0    -> cm = c0 (clip(z-1))     z*=nz-1 -> cp = c0
        best = conf_ref[...]
        zbest = zf_ref[...]
        cm = jnp.where(zbest == 0.0, best, cprev_ref[...])
        cp = jnp.where(zbest == float(nz - 1), best, cnext_ref[...])
        denom = cm - 2.0 * best + cp
        offset = jnp.where(jnp.abs(denom) > 1e-6, 0.5 * (cm - cp) / denom, 0.0)
        offset = jnp.clip(offset, -0.5, 0.5)
        zf_ref[...] = zbest + offset


@functools.partial(
    jax.jit,
    static_argnames=("cx", "cy", "w", "h", "block_z", "frames_per_step", "mode",
                     "quantized", "onehot_dtype", "interpret"),
)
def backproject_vote_pallas(
    x0: Array,  # (F, E) canonical-plane x coords
    y0: Array,  # (F, E)
    valid: Array,  # (F, E) float32
    phi: Array,  # (F, Nz, 3)
    *,
    cx: float,
    cy: float,
    w: int,
    h: int,
    block_z: int = 8,
    frames_per_step: int = 1,
    mode: str = "nearest",
    quantized: bool = False,
    onehot_dtype=jnp.bfloat16,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Fused sweep: returns padded `(dsi, conf, zf)`.

    dsi  — (Nz, h_pad, w_pad) int16 when `quantized` (saturating store
           applied in-kernel), float32 otherwise
    conf — (h_pad, w_pad) float32 depth-axis max of the STORED DSI
    zf   — (h_pad, w_pad) float32 parabola-refined argmax

    `interpret` resolves via `repro.kernels.platform.resolve_interpret`
    (None = compiled on TPU/GPU, interpreter elsewhere; False raises on
    platforms without a Pallas compile path).
    """
    F, E = x0.shape
    nz = phi.shape[1]
    assert nz % block_z == 0, (nz, block_z)
    assert F % frames_per_step == 0, (F, frames_per_step)
    w_pad = _round_up(w, LANE)
    h_pad = _round_up(h, SUBLANE)
    fs = frames_per_step
    nf = F // fs
    grid = (nz // block_z, nf)
    store_dtype = jnp.int16 if quantized else jnp.float32

    kern = functools.partial(
        _kernel, cx=cx, cy=cy, w=w, h=h, nz=nz, bz=block_z, fs=fs, nf=nf,
        mode=mode, quantized=quantized, onehot_dtype=onehot_dtype,
    )
    # events as (F, 1, E): a block's last two dims (1, E) equal the
    # array's, which Mosaic accepts for any E and any frames_per_step
    events = [a.reshape(F, 1, E) for a in (x0, y0, valid)]
    event_spec = pl.BlockSpec((fs, 1, E), lambda z, f: (f, 0, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            event_spec, event_spec, event_spec,
            pl.BlockSpec((fs, block_z, 3), lambda z, f: (f, z, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_z, h_pad, w_pad), lambda z, f: (z, 0, 0)),
            # conf/zf blocks are revisited by every grid step: constant
            # index map keeps them VMEM-resident for the whole sweep
            pl.BlockSpec((h_pad, w_pad), lambda z, f: (0, 0)),
            pl.BlockSpec((h_pad, w_pad), lambda z, f: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nz, h_pad, w_pad), store_dtype),
            jax.ShapeDtypeStruct((h_pad, w_pad), jnp.float32),
            jax.ShapeDtypeStruct((h_pad, w_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_z, h_pad, w_pad), jnp.float32),  # acc
            pltpu.VMEM((h_pad, w_pad), jnp.float32),  # prev
            pltpu.VMEM((h_pad, w_pad), jnp.float32),  # c_prev_of_best
            pltpu.VMEM((h_pad, w_pad), jnp.float32),  # c_next_of_best
            pltpu.VMEM((h_pad, w_pad), jnp.float32),  # prev_was_best
        ],
        interpret=resolve_interpret(interpret),
    )(*events, phi)
