"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: `jax.experimental.topologies` describes a v5e chip that is
not attached, and each test compiles one program for it at the paper's
operating point (240x180, 128 planes, 1024-event frames), the fused
rig's at VGA. The TPU
compiler refuses here what it would refuse on the chip — a block layout
Mosaic cannot tile, an op it cannot lower, a kernel over its VMEM
budget, a program over the chip's memory.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers import every test file.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig
from repro.core.pipeline import (
    VOTE_CHUNK_FRAMES,
    EMVSOptions,
    SegmentBatch,
    process_segments_batched,
)

E = 1024  # events per frame
FRAMES = 8  # frames in one kernel call
S, C = 4, 8  # segments and frame capacity of one batched sweep
# v5e's default scoped-VMEM budget for one kernel (docs/kernel_fusion.md)
VMEM_BUDGET = 16 * 2**20

VARIANTS = [("nearest", False), ("nearest", True), ("bilinear", False)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Lower Pallas kernels for the TPU although the process runs on CPU."""
    import repro.kernels.platform as platform

    monkeypatch.setattr(platform, "compiled_kernels_supported", lambda: True)


@pytest.fixture(scope="module")
def geometry():
    cam = CameraModel()
    return cam, DSIConfig.for_camera(cam)


def _batch(sharding) -> SegmentBatch:
    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return SegmentBatch(xy=sd(S, C, E, 2), valid=sd(S, C, E),
                        frame_valid=sd(S, C), poses_R=sd(S, C, 3, 3),
                        poses_t=sd(S, C, 3), ref_R=sd(S, 3, 3), ref_t=sd(S, 3))


@pytest.mark.parametrize("frames_per_step", [1, FRAMES])
@pytest.mark.parametrize("mode,quantized", VARIANTS)
def test_fused_kernel_compiles_for_v5e(one_chip, geometry, compiled_pallas,
                                       monkeypatch, mode, quantized,
                                       frames_per_step):
    """The fused vote/store/detect kernel at 240x180x128 fits a 16 MiB
    scoped-VMEM budget and returns the padded DSI and detection maps,
    one frame or all eight per grid step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.backproject_vote.kernel import backproject_vote_pallas

    pallas_call = pl.pallas_call

    def budgeted(*args, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)
        return pallas_call(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", budgeted)
    cam, dsi_cfg = geometry
    nz = dsi_cfg.num_planes
    onehot = jnp.bfloat16 if mode == "nearest" else jnp.float32

    def sweep(x, y, valid, phi):
        return backproject_vote_pallas(
            x, y, valid, phi, cx=cam.cx, cy=cam.cy, w=cam.width, h=cam.height,
            block_z=8, frames_per_step=frames_per_step, mode=mode,
            quantized=quantized, onehot_dtype=onehot, interpret=False)

    ev = jax.ShapeDtypeStruct((FRAMES, E), jnp.float32, sharding=one_chip)
    phi = jax.ShapeDtypeStruct((FRAMES, nz, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(sweep).lower(ev, ev, ev, phi).compile()
    assert "tpu_custom_call" in compiled.as_text()
    dsi, conf, zf = compiled.out_info
    assert dsi.shape == (nz, 184, 256)
    assert dsi.dtype == (jnp.int16 if quantized else jnp.float32)
    assert conf.shape == zf.shape == (184, 256)


@pytest.mark.parametrize("formulation", ["matmul", "kernel"])
@pytest.mark.parametrize("quantized", [False, True])
def test_batched_sweep_compiles_for_v5e(one_chip, geometry, compiled_pallas,
                                        formulation, quantized):
    """`process_segments_batched` at S=4, C=8 fits one v5e chip."""
    cam, dsi_cfg = geometry
    opts = EMVSOptions(formulation=formulation, quantized=quantized,
                       kernel_interpret=False if formulation == "kernel" else None)
    compiled = process_segments_batched.lower(cam, dsi_cfg, _batch(one_chip),
                                              opts).compile()
    mem = compiled.memory_analysis()
    dsi_bytes = S * 4 * dsi_cfg.num_planes * dsi_cfg.height * dsi_cfg.width
    assert mem.output_size_in_bytes >= dsi_bytes
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9 / 4
    assert ("tpu_custom_call" in compiled.as_text()) == (formulation == "kernel")


def test_fused_rig_sweep_compiles_for_v5e_at_vga(one_chip):
    """A rig's sweep at `dsec_rig_fused`'s operating point (640x480, 128
    planes, 888-frame capacity, 4 maps of 2 cameras): one fused float32
    DSI a map, and the program fits one v5e chip."""
    cam = CameraModel(width=640, height=480, fx=560.0, fy=560.0, cx=320.0,
                      cy=240.0)
    dsi_cfg = DSIConfig.for_camera(cam)
    s, k, c = 4, 2, 888

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    batch = SegmentBatch(xy=sd(s, k, c, E, 2), valid=sd(s, k, c, E),
                         frame_valid=sd(s, k, c), poses_R=sd(s, k, c, 3, 3),
                         poses_t=sd(s, k, c, 3), ref_R=sd(s, 3, 3),
                         ref_t=sd(s, 3))
    compiled = process_segments_batched.lower(cam, dsi_cfg, batch,
                                              EMVSOptions()).compile()
    fused, _ = compiled.out_info
    assert fused.shape == (s,) + dsi_cfg.shape and fused.dtype == jnp.float32
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9 / 4


def test_sharded_sweep_compiles_for_v5e_2x2(topo, geometry):
    """The segment-sharded sweep over four v5e chips: one segment per
    chip, outputs sharded on the segment axis, no collectives."""
    from repro.distributed.emvs import make_segment_mesh, process_segments_sharded

    cam, dsi_cfg = geometry
    mesh = make_segment_mesh(topo.devices)
    batch = _batch(NamedSharding(mesh, P("segments")))
    opts = EMVSOptions(quantized=True)
    compiled = jax.jit(
        lambda b: process_segments_sharded(cam, dsi_cfg, b, opts, mesh=mesh)
    ).lower(batch).compile()
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    dsi_sharding = compiled.output_shardings[0]
    assert dsi_sharding.spec == P("segments")
    assert compiled.memory_analysis().output_size_in_bytes < (
        S * 4 * dsi_cfg.num_planes * dsi_cfg.height * dsi_cfg.width)


VGA = CameraModel(width=640, height=480, fx=560.0, fy=560.0, cx=320.0,
                  cy=240.0)
# each cell's sweep: camera, leading (maps[, cameras]), capacity, quantized
CELL_SWEEPS = {
    "davis240": (CameraModel(), (4,), 800, True),
    "vga640": (VGA, (2,), 888, False),
    "dsec_rig_fused": (VGA, (2, 2), 888, False),
}


def _cell_batch(sharding, lead, c) -> SegmentBatch:
    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    s = lead[0]
    return SegmentBatch(xy=sd(*lead, c, E, 2), valid=sd(*lead, c, E),
                        frame_valid=sd(*lead, c), poses_R=sd(*lead, c, 3, 3),
                        poses_t=sd(*lead, c, 3), ref_R=sd(s, 3, 3),
                        ref_t=sd(s, 3))


def _loop_trips(hlo: str) -> set[int]:
    """Trip counts of the compiled program's loops: the constants their
    conditions compare the induction variable against."""
    consts = dict(re.findall(r"(%\S+) = s32\[\]\S* constant\((\d+)\)", hlo))
    return {int(consts[c]) for c in re.findall(
        r"compare\(%\S+, (%\S+)\), direction=LT, metadata=\{op_name=\"[^\"]*"
        r"while/cond", hlo) if c in consts}


def _unfused_shapes(hlo: str) -> set[str]:
    """Shapes of the values the compiled program keeps in memory: every
    instruction outside the computations that fusions call."""
    fused = set(re.findall(r"calls=(%[\w.-]+)", hlo))
    shapes, inside = set(), False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) ", line)
        if head and not line.startswith(" "):
            inside = head.group(1) in fused
        elif not inside:
            shapes.update(re.findall(r" = (\w+\[[\d,]*\])", line))
    return shapes


@pytest.mark.parametrize("cell", sorted(CELL_SWEEPS))
def test_nearest_sweep_votes_four_frames_per_int8_dot(one_chip, cell):
    """At each cell's shape the nearest matmul sweep contracts the
    one-hot rows of 4 x E events in one s8 x s8 -> s32 dot a plane, its
    vote loop runs C/4 trips, the one-hot rows stay fused into the
    contraction (none is a value in memory) and the program fits the
    chip."""
    cam, lead, c, quantized = CELL_SWEEPS[cell]
    dsi_cfg = DSIConfig.for_camera(cam)
    nz, h, w = dsi_cfg.shape
    k = VOTE_CHUNK_FRAMES * E
    lowered = process_segments_batched.lower(
        cam, dsi_cfg, _cell_batch(one_chip, lead, c),
        EMVSOptions(quantized=quantized))
    dots = re.findall(r"stablehlo.dot_general .*: \((tensor<[^>]*>), "
                      r"(tensor<[^>]*>)\) -> (tensor<[^>]*>)", lowered.as_text())
    assert (f"tensor<{nz}x{k}x{h}xi8>", f"tensor<{nz}x{k}x{w}xi8>",
            f"tensor<{nz}x{h}x{w}xi32>") in dots
    assert not [d for d in dots if d[2] == f"tensor<{nz}x{h}x{w}xf32>"]
    compiled = lowered.compile()
    hlo = compiled.as_text()
    trips = _loop_trips(hlo)
    assert c // VOTE_CHUNK_FRAMES in trips and c not in trips
    assert not [s for s in _unfused_shapes(hlo) if s.startswith(
        (f"pred[{nz},{k},", f"s8[{nz},{k},"))]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9 / 4


def test_bilinear_sweep_keeps_its_float32_per_frame_dot(one_chip):
    """Bilinear votes carry fractional weights: the sweep keeps one
    float32 dot at full precision per frame, C trips."""
    cam, lead, c, _ = CELL_SWEEPS["davis240"]
    dsi_cfg = DSIConfig.for_camera(cam)
    nz, h, w = dsi_cfg.shape
    lowered = process_segments_batched.lower(
        cam, dsi_cfg, _cell_batch(one_chip, lead, c),
        EMVSOptions(voting="bilinear"))
    text = lowered.as_text()
    assert re.search(
        rf"stablehlo.dot_general .*precision = \[HIGHEST, HIGHEST\] : "
        rf"\(tensor<{nz}x{E}x{h}xf32>, tensor<{nz}x{E}x{w}xf32>\) -> "
        rf"tensor<{nz}x{h}x{w}xf32>", text)
    assert "xi8>" not in text
    assert c in _loop_trips(lowered.compile().as_text())
