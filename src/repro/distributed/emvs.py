"""Distributed EMVS: the paper's technique as a first-class multi-pod feature.

Parallelism mapping (DESIGN.md §2, mirrors the FPGA's three levels):

  axis     paper's level                 here
  -----    -------------------------     ------------------------------
  model    DSI-level (multiple PE_Zi)    depth planes sharded; each rank
                                         votes its own plane slice —
                                         ZERO communication during R
  data     event-level (pipelining)      event frames sharded; partial
                                         DSIs merged by ONE integer psum
                                         (votes are additive => exact)
  pod      key-frame level (new)         segments processed concurrently;
                                         local DSIs independent by
                                         construction (DSI resets per
                                         key frame) — pods only exchange
                                         final depth maps

Two entry points:

  * `make_emvs_step` — the data/model(/pod)-sharded step over
    precomputed geometry (H, phi), used by the production dry-run. The
    vote merge is an integer psum ONLY on the nearest datapath, where
    votes are integral counts — the lossless counterpart of the int8
    gradient compression in `compression.py` (the paper's bandwidth
    insight: narrow integer payloads on the links). Bilinear votes carry
    fractional weights and stay float32 through the merge.

  * `process_segments_sharded` — the key-frame-level production backend:
    consumes the exact `SegmentBatch` of
    `repro.core.pipeline.process_segments_batched` (frame padding votes
    zero via `frame_valid`) and runs the same sweep body with the
    segment axis sharded across mesh devices, so concurrent segments
    vote on different devices. Selectable via `run_emvs(sweep="sharded")`
    and `StreamConfig(sweep="sharded")`; per-segment outputs are
    bit-identical to the batched backend on the integer/nearest
    datapaths and allclose on bilinear (tests/test_sharded_sweep.py).
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.core.camera import CameraModel
from repro.core.detection import DepthMap, detect_structure
from repro.core.dsi import DSIConfig
from repro.core.geometry import PlaneSweepCoeffs, apply_homography, propagate_to_planes
from repro.core.pipeline import EMVSOptions, SegmentBatch, sweep_segment_batch
from repro.core.voting import vote_onehot_matmul

Array = jax.Array

# Mesh axis name of the key-frame-segment axis (the `pod` level above,
# spelled out: independent segments shard across devices).
SEGMENT_AXIS = "segments"


def _vote_local(cam: CameraModel, xy: Array, valid: Array, frame_valid: Array,
                H: Array, phi: Array, nz_local: int, mode: str) -> Array:
    """Vote local frames into a local (Nz_loc, h, w) plane slice (scan).

    `frame_valid` is the per-frame padding mask of `SegmentBatch`: padded
    frames repeat a real frame (finite geometry) and vote with weight 0,
    so callers no longer need F to divide the data axis exactly.
    """
    dsi0 = jnp.zeros((nz_local, cam.height, cam.width), jnp.float32)

    def body(dsi, frame):
        xy_f, valid_f, fv_f, H_f, phi_f = frame
        xy0 = apply_homography(H_f, xy_f)
        coeffs = PlaneSweepCoeffs(phi_f[:, 0], phi_f[:, 1], phi_f[:, 2])
        x_i, y_i = propagate_to_planes(cam, xy0, coeffs)
        w = valid_f.astype(jnp.float32) * fv_f.astype(jnp.float32)
        w = jnp.broadcast_to(w[None, :], x_i.shape)
        return vote_onehot_matmul(dsi, x_i, y_i, w=cam.width, h=cam.height,
                                  mode=mode, weights=w), None

    dsi, _ = jax.lax.scan(body, dsi0, (xy, valid, frame_valid, H, phi))
    return dsi


def make_emvs_step(cam: CameraModel, dsi_cfg: DSIConfig, mesh: Mesh, *,
                   mode: str = "nearest", data_axis: str = "data",
                   model_axis: str = "model", pod_axis: str | None = None,
                   vote_dtype=jnp.int32):
    """Build the sharded EMVS segment step for `mesh`.

    Inputs (global logical shapes; leading G = segments when pod_axis —
    see `emvs_input_specs`, which mirrors them):
        xy          (G?, F, E, 2)   valid (G?, F, E)
        frame_valid (G?, F)         H     (G?, F, 3, 3)
        phi         (G?, F, Nz, 3)
    Returns (dsi (G?, Nz, h, w) z-sharded, depth, mask, conf (G?, h, w)).
    dsi is int32 for nearest voting, float32 for bilinear.
    """
    nz = dsi_cfg.num_planes
    n_model = mesh.shape[model_axis]
    assert nz % n_model == 0, (nz, n_model)
    nz_loc = nz // n_model
    planes_all = dsi_cfg.planes()

    def seg_body(xy, valid, frame_valid, H, phi):
        # local: xy (F_loc, E, 2), phi (F_loc, Nz_loc, 3)
        dsi = _vote_local(cam, xy, valid, frame_valid, H, phi, nz_loc, mode)
        if mode == "nearest":
            # event-level merge: ONE integer all-reduce (exact for the
            # integral nearest counts). §Perf E2: int16 (the paper's
            # Table-1 DSI width) halves the link payload; per-shard
            # partial counts <= events/shard << 32767, and the int32
            # upcast after the psum keeps downstream math exact.
            dsi = jax.lax.psum(dsi.astype(vote_dtype), data_axis)
            dsi = dsi.astype(jnp.int32)
        else:
            # bilinear votes are fractional weights: narrowing the link
            # payload to an integer dtype would silently truncate them,
            # so the merge stays float32 (still one all-reduce).
            dsi = jax.lax.psum(dsi, data_axis)
        # detection needs full-z per pixel: gather plane slices over model
        dsi_full = jax.lax.all_gather(dsi, model_axis, axis=0, tiled=True)
        dm = detect_structure(dsi_full.astype(jnp.float32), planes_all)
        return dsi, dm.depth, dm.mask, dm.confidence

    if pod_axis is None:
        in_specs = (P(data_axis, None, None), P(data_axis, None),
                    P(data_axis),
                    P(data_axis, None, None), P(data_axis, model_axis, None))
        out_specs = (P(model_axis, None, None), P(), P(), P())
        body = seg_body
    else:
        # key-frame-level parallelism: leading segment axis over pods
        def body(xy, valid, frame_valid, H, phi):
            return jax.vmap(seg_body)(xy, valid, frame_valid, H, phi)

        in_specs = (P(pod_axis, data_axis, None, None), P(pod_axis, data_axis, None),
                    P(pod_axis, data_axis),
                    P(pod_axis, data_axis, None, None),
                    P(pod_axis, data_axis, model_axis, None))
        out_specs = (P(pod_axis, model_axis, None, None), P(pod_axis),
                     P(pod_axis), P(pod_axis))

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def emvs_input_specs(dsi_cfg: DSIConfig, *, frames: int, events: int,
                     segments: int | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for the distributed EMVS step (dry-run).

    Regenerated from the `SegmentBatch`-shaped pipeline inputs: `xy`,
    `valid` and `frame_valid` are exactly the event-side fields of
    `repro.core.pipeline.SegmentBatch` (frame padding votes zero through
    `frame_valid`); `H`/`phi` replace the batch's raw poses because the
    distributed step consumes precomputed ARM-side geometry.

    Segment axis: when `segments` is not None the specs gain the leading
    G axis consumed by the pod path (`make_emvs_step(pod_axis=...)`),
    which shards whole key-frame segments across pods — G must divide
    the pod axis size. The order of the returned dict is the positional
    argument order of the step.
    """
    lead = () if segments is None else (segments,)
    f32 = jnp.float32
    return {
        "xy": jax.ShapeDtypeStruct(lead + (frames, events, 2), f32),
        "valid": jax.ShapeDtypeStruct(lead + (frames, events), f32),
        "frame_valid": jax.ShapeDtypeStruct(lead + (frames,), f32),
        "H": jax.ShapeDtypeStruct(lead + (frames, 3, 3), f32),
        "phi": jax.ShapeDtypeStruct(lead + (frames, dsi_cfg.num_planes, 3), f32),
    }


# ---------------------------------------------------------------------------
# Key-frame-level segment sharding: the production `sweep="sharded"` backend
# ---------------------------------------------------------------------------


def make_segment_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices with the segment axis.

    The default backend mesh for `run_emvs(sweep="sharded")` and
    `EMVSStreamEngine` with `StreamConfig(sweep="sharded")`. The axis is
    `Auto`: the sweep's outputs feed unsharded jits (the point cloud's
    vmap), which `Explicit` axes (`jax.make_mesh`'s default) would refuse.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    return jax.make_mesh((len(devs),), (SEGMENT_AXIS,),
                         axis_types=(AxisType.Auto,), devices=devs)


def segment_axis_size(mesh: Mesh, axis_name: str = SEGMENT_AXIS) -> int:
    """Size of the mesh's segment axis, with a clear error when absent.

    A user-supplied mesh must name its segment axis `axis_name` (default
    "segments"); without this check a mismatched mesh would surface as an
    opaque KeyError deep inside the sweep wiring.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names} but the sharded sweep needs a "
            f"'{axis_name}' axis — build the mesh with make_segment_mesh() "
            f"or name its segment axis '{axis_name}'")
    return mesh.shape[axis_name]


@lru_cache(maxsize=None)
def _sharded_sweep_fn(cam: CameraModel, dsi_cfg: DSIConfig, opts: EMVSOptions,
                      mesh: Mesh, axis_name: str):
    """jit(shard_map(sweep body)) for one (options, mesh) combination.

    The shard_map body is `sweep_segment_batch` — the identical traced
    program `process_segments_batched` jits — applied to each device's
    local (S/n, ...) slice of the batch. Segments are independent by
    construction (the DSI resets per key frame), so there are ZERO
    collectives: the only communication is the output gather jit inserts
    when the caller reads the sharded result.
    """
    spec = P(axis_name)

    def local(batch: SegmentBatch):
        return sweep_segment_batch(cam, dsi_cfg, batch, opts)

    # A single PartitionSpec acts as a pytree prefix: every SegmentBatch
    # leaf (and every output leaf) shards its leading segment axis.
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))


def process_segments_sharded(
    cam: CameraModel,
    dsi_cfg: DSIConfig,
    batch: SegmentBatch,
    opts: EMVSOptions,
    *,
    mesh: Mesh | None = None,
    axis_name: str = SEGMENT_AXIS,
) -> tuple[Array, DepthMap]:
    """`process_segments_batched` with the segment axis sharded over `mesh`.

    Drop-in `sweep="sharded"` backend: consumes the same `SegmentBatch`
    (padded frames vote zero via `frame_valid`), applies the same
    `EMVSOptions` surface (all three formulations, nearest/bilinear,
    quantized int16 store, detection thresholds, median filter), and
    returns the same stacked (S, ...) outputs. The batch's segment count
    S must be a multiple of the mesh's segment-axis size; callers pad S
    by repeating a real segment (`run_emvs` and the streaming engine's
    S-bucketing both do) — padded rows are discarded work, never a
    numerics change.

    Per-segment outputs are bit-identical to the batched sweep on the
    integer/nearest datapaths and allclose on bilinear: both backends
    trace the exact same per-segment program; only the axis the segments
    are laid out over differs.
    """
    if mesh is None:
        mesh = make_segment_mesh()
    n = segment_axis_size(mesh, axis_name)
    s = batch.xy.shape[0]
    if s % n != 0:
        raise ValueError(
            f"segment count {s} is not a multiple of the mesh's "
            f"'{axis_name}' axis size {n}; pad the segment list (repeat a "
            f"real segment) before calling process_segments_sharded")
    return _sharded_sweep_fn(cam, dsi_cfg, opts, mesh, axis_name)(batch)
