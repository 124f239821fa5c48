"""Paper Table 3: per-frame runtime breakdown and event processing rate.

The paper's columns are Intel i5 (software EMVS) vs Eventor (FPGA). The
portable analogue here:

  * "software path"   — scatter-formulation EMVS (the CPU-idiomatic
                         algorithm the paper ran on the i5), jit-compiled
  * "accelerated path" — our TPU-native one-hot-matmul formulation (the
                         Eventor analogue; on real v5e hardware this is
                         the path the dry-run/roofline characterizes)

Both are measured wall-clock on this host for the *structure* of Table 3
(P(Z0) vs P(Z0->Zi)&R split, normal vs key frames, Mev/s). Absolute
numbers are CPU-host numbers, not TPU numbers — the roofline report
covers the target-hardware projection.

Pipelining (paper Fig 6): for normal frames the P(Z0) stage of frame
f+1 overlaps the PE_Zi work of frame f, so the effective per-frame time
is max(stages) for normal frames and sum(stages) for key frames.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks._emvs_common import sequence
from repro.core.geometry import SE3, apply_homography, propagate_to_planes
from repro.core.pipeline import EMVSOptions, precompute_segment_geometry
from repro.core.voting import vote_onehot_matmul, vote_scatter

EVENTS_PER_FRAME = 1024


def _time(fn, *args, reps: int = 20) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run() -> dict:
    cam, scene, frames, dsi_cfg = sequence("simulation_3planes")
    planes = dsi_cfg.planes()
    z0 = planes[dsi_cfg.num_planes // 2]
    T_w_ref = SE3(frames.poses.R[0], frames.poses.t[0])
    geoms = precompute_segment_geometry(cam, frames, T_w_ref, planes, z0)
    xy, valid = frames.xy[0], frames.valid[0].astype(jnp.float32)
    H, phi = geoms.H[0], jax.tree.map(lambda a: a[0], geoms.phi)

    # stage P(Z0)
    p_z0 = jax.jit(lambda H, xy: apply_homography(H, xy))
    t_pz0 = _time(p_z0, H, xy)

    # stage P(Z0->Zi) + R, both formulations
    @jax.jit
    def prop_and_vote_scatter(xy0, valid, alpha, bx, by):
        from repro.core.geometry import PlaneSweepCoeffs

        x_i, y_i = propagate_to_planes(cam, xy0, PlaneSweepCoeffs(alpha, bx, by))
        dsi = jnp.zeros(dsi_cfg.shape, jnp.int32)
        w = jnp.broadcast_to(valid[None, :], x_i.shape)
        return vote_scatter(dsi, x_i, y_i, w=cam.width, h=cam.height,
                            mode="nearest", weights=w)

    @jax.jit
    def prop_and_vote_matmul(xy0, valid, alpha, bx, by):
        from repro.core.geometry import PlaneSweepCoeffs

        x_i, y_i = propagate_to_planes(cam, xy0, PlaneSweepCoeffs(alpha, bx, by))
        dsi = jnp.zeros((dsi_cfg.num_planes, cam.height, cam.width), jnp.float32)
        w = jnp.broadcast_to(valid[None, :], x_i.shape)
        return vote_onehot_matmul(dsi, x_i, y_i, w=cam.width, h=cam.height,
                                  mode="nearest", weights=w)

    xy0 = p_z0(H, xy)
    t_sw = _time(prop_and_vote_scatter, xy0, valid, phi.alpha, phi.beta_x,
                 phi.beta_y)
    t_hw = _time(prop_and_vote_matmul, xy0, valid, phi.alpha, phi.beta_x,
                 phi.beta_y)

    def pack(t_stage2):
        normal = max(t_pz0, t_stage2)  # pipelined (Fig 6 upper)
        key = t_pz0 + t_stage2  # serial (Fig 6 lower)
        return {
            "P(Z0) us": t_pz0 * 1e6,
            "P(Z0->Zi)&R us": t_stage2 * 1e6,
            "normal frame us": normal * 1e6,
            "key frame us": key * 1e6,
            "normal Mev/s": EVENTS_PER_FRAME / normal / 1e6,
            "key Mev/s": EVENTS_PER_FRAME / key / 1e6,
        }

    # --- TPU v5e projection of the matmul formulation -------------------
    # votes = Oy^T @ Ox per plane: 2 * E * (h + w) * min(h,w)-free matmul
    # ~= 2 * E * h_pad * w_pad MACs per plane. With Nz=dsi planes:
    from repro.launch.roofline import HBM_BW, PEAK_FLOPS

    e, nz = EVENTS_PER_FRAME, dsi_cfg.num_planes
    h_pad, w_pad = 184, 256  # kernel tile padding (SUBLANE/LANE aligned)
    flops_frame = 2.0 * e * h_pad * w_pad * nz  # one-hot matmul votes
    bytes_frame = (nz * h_pad * w_pad * 4  # DSI block revisit (fp32 acc)
                   + e * 4 * 4)  # event coords + phi traffic
    t_mxu = flops_frame / PEAK_FLOPS
    t_hbm = bytes_frame / HBM_BW
    t_frame_v5e = max(t_mxu, t_hbm)
    # §Perf E1: int8 one-hot rows are exact (0/1 values, int32 accumulate)
    # and run the MXU at 2x the bf16 rate (v5e: 394 TOPS int8)
    t_mxu_int8 = flops_frame / (2 * PEAK_FLOPS)
    t_frame_int8 = max(t_mxu_int8, t_hbm)
    v5e = {
        "flops/frame": flops_frame,
        "bytes/frame": bytes_frame,
        "MXU-bound us": t_mxu * 1e6,
        "HBM-bound us": t_hbm * 1e6,
        "projected us/frame": t_frame_v5e * 1e6,
        "projected Mev/s/chip": e / t_frame_v5e / 1e6,
        "speedup vs paper Eventor": e / t_frame_v5e / 1e6 / 1.86,
        "int8 votes us/frame (E1)": t_frame_int8 * 1e6,
        "int8 votes Mev/s/chip (E1)": e / t_frame_int8 / 1e6,
        "int8 speedup vs Eventor": e / t_frame_int8 / 1e6 / 1.86,
    }

    return {"software_scatter": pack(t_sw), "matmul_eventor_analogue": pack(t_hw),
            "v5e_projection": v5e,
            "paper": {"cpu_normal_Mev/s": 1.76, "eventor_normal_Mev/s": 1.86,
                      "eventor_power_W": 1.86, "cpu_power_W": 45.0}}


def main() -> None:
    out = run()
    print("== Table 3: runtime per 1024-event frame (host measurements) ==")
    for name in ("software_scatter", "matmul_eventor_analogue"):
        r = out[name]
        print(f"-- {name} --")
        for k, v in r.items():
            print(f"   {k:18s} {v:12.2f}")
    print("-- v5e roofline projection (matmul formulation, per chip) --")
    for k, v in out["v5e_projection"].items():
        print(f"   {k:26s} {v:14.2f}")
    print("   NOTE: the matmul formulation is an MXU algorithm; its host-CPU")
    print("   wall time above is expected to LOSE to scatter on CPU.")
    p = out["paper"]
    print(f"paper reference: CPU {p['cpu_normal_Mev/s']} Mev/s @ "
          f"{p['cpu_power_W']} W; Eventor {p['eventor_normal_Mev/s']} Mev/s @ "
          f"{p['eventor_power_W']} W (24x energy efficiency)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
