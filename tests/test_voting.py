"""Voting formulations must agree: scatter (FPGA semantics) vs one-hot
matmul (TPU semantics) vs the Pallas kernel — for nearest AND bilinear,
including out-of-bounds, NaN/Inf coords, and masked events. The matmul
sweep's nearest vote, four frames per int8 contraction, must be the
per-frame vote bit for bit."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dsi as dsi_lib
from repro.core.backproject import FrameGeometry
from repro.core.geometry import SE3, PlaneSweepCoeffs
from repro.core.pipeline import (
    EMVSOptions,
    pad_segment_rows,
    precompute_batch_geometry,
    process_segments_batched,
    project_frame,
    vote_frame,
)
from repro.core.voting import vote_onehot_chunk, vote_onehot_matmul, vote_scatter

W, H, NZ, E = 32, 24, 4, 64


def _coords(rng, spread=1.4):
    """Coords spilling beyond bounds on purpose."""
    x = rng.uniform(-0.2 * W, spread * W, (NZ, E)).astype(np.float32)
    y = rng.uniform(-0.2 * H, spread * H, (NZ, E)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


@pytest.mark.parametrize("mode", ["nearest", "bilinear"])
def test_scatter_equals_matmul(mode):
    rng = np.random.default_rng(0)
    x, y = _coords(rng)
    dsi0 = jnp.zeros((NZ, H, W), jnp.float32)
    a = vote_scatter(dsi0, x, y, w=W, h=H, mode=mode)
    b = vote_onehot_matmul(dsi0, x, y, w=W, h=H, mode=mode)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@given(seed=st.integers(0, 500))
@settings(max_examples=15)
def test_scatter_equals_matmul_hypothesis(seed):
    rng = np.random.default_rng(seed)
    x, y = _coords(rng, spread=2.0)
    dsi0 = jnp.zeros((NZ, H, W), jnp.float32)
    for mode in ("nearest", "bilinear"):
        a = vote_scatter(dsi0, x, y, w=W, h=H, mode=mode)
        b = vote_onehot_matmul(dsi0, x, y, w=W, h=H, mode=mode)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_nonfinite_coords_never_vote():
    x = jnp.array([[jnp.nan, jnp.inf, -jnp.inf, 5.0]], jnp.float32)
    y = jnp.array([[2.0, 2.0, 2.0, jnp.nan]], jnp.float32)
    dsi0 = jnp.zeros((1, H, W), jnp.float32)
    for mode in ("nearest", "bilinear"):
        for f in (vote_scatter, vote_onehot_matmul):
            out = f(dsi0, x, y, w=W, h=H, mode=mode)
            assert float(jnp.sum(out)) == 0.0, (mode, f.__name__)
            assert bool(jnp.all(jnp.isfinite(out)))


def test_weights_mask_events():
    rng = np.random.default_rng(3)
    x, y = _coords(rng, spread=0.8)
    wts = jnp.asarray((rng.random((NZ, E)) > 0.5).astype(np.float32))
    dsi0 = jnp.zeros((NZ, H, W), jnp.float32)
    a = vote_scatter(dsi0, x, y, w=W, h=H, mode="nearest", weights=wts)
    b = vote_onehot_matmul(dsi0, x, y, w=W, h=H, mode="nearest", weights=wts)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # total votes == number of in-bounds, unmasked events
    xr, yr = jnp.round(x), jnp.round(y)
    inb = (xr >= 0) & (xr <= W - 1) & (yr >= 0) & (yr <= H - 1)
    assert float(jnp.sum(a)) == float(jnp.sum(wts * inb))


def test_bilinear_votes_sum_to_one_per_event():
    """Bilinear contributions of one in-bounds event must total 1."""
    x = jnp.array([[10.3]], jnp.float32)
    y = jnp.array([[7.8]], jnp.float32)
    dsi0 = jnp.zeros((1, H, W), jnp.float32)
    out = vote_onehot_matmul(dsi0, x, y, w=W, h=H, mode="bilinear")
    assert abs(float(jnp.sum(out)) - 1.0) < 1e-5
    # exactly 4 voxels touched
    assert int(jnp.sum(out > 0)) == 4


def test_int16_dsi_accumulation_and_saturation():
    from repro.core import dsi as dsi_lib

    acc = jnp.full((1, 2, 2), 40000, dsi_lib.DSI_ACCUM_DTYPE)
    stored = dsi_lib.to_storage(acc)
    assert stored.dtype == jnp.int16
    assert int(stored[0, 0, 0]) == 32767  # saturating store
    assert float(dsi_lib.saturation_fraction(acc)) == 1.0
    ok = jnp.full((1, 2, 2), 1000, dsi_lib.DSI_ACCUM_DTYPE)
    assert float(dsi_lib.saturation_fraction(ok)) == 0.0


def test_integer_vote_rounding_half_away():
    """Half-integer votes must round half-AWAY-from-zero (the RTL and
    `quant/fixed_point` convention), not half-to-even like `jnp.round`.

    A bilinear event at x = n + 0.5 produces exact 0.5-weight votes; with
    an integer DSI those used to round 0.5 -> 0 and 2.5 -> 2 (half-even),
    diverging from the fixed-point quantizers one vote at a time."""
    from repro.quant.fixed_point import round_half_away

    halves = jnp.array([-2.5, -1.5, -0.5, 0.5, 1.5, 2.5], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(round_half_away(halves)), [-3.0, -2.0, -1.0, 1.0, 2.0, 3.0]
    )
    # and NOT the half-even results [-2, -2, -0, 0, 2, 2]
    assert not np.array_equal(np.asarray(jnp.round(halves)),
                              np.asarray(round_half_away(halves)))

    # end to end: one event exactly between two columns, integer DSI
    x = jnp.full((NZ, 1), 10.5, jnp.float32)
    y = jnp.full((NZ, 1), 7.0, jnp.float32)
    dsi0 = jnp.zeros((NZ, H, W), jnp.int32)
    out = vote_onehot_matmul(dsi0, x, y, w=W, h=H, mode="bilinear")
    # both 0.5-weight voxels round up to 1 (half-even would drop both to 0)
    assert int(out[0, 7, 10]) == 1
    assert int(out[0, 7, 11]) == 1


# ---------------------------------------------------------------------------
# the sweep's nearest vote: F frames per int8 contraction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("frames", [1, 2, 4, 8])
def test_chunk_vote_is_the_per_frame_vote(frames):
    """F frames voted through one int8 contraction give the integer
    counts of F per-frame matmul votes and of the scatter, bit for bit:
    out-of-bounds, non-finite and zero-weight events vote nothing."""
    rng = np.random.default_rng(frames)
    x, y = (jnp.stack(c) for c in zip(*(_coords(rng, spread=1.6)
                                         for _ in range(frames))))
    x = x.at[0, 0, :3].set(jnp.array([jnp.nan, jnp.inf, -jnp.inf]))
    wts = jnp.asarray((rng.random((frames, E)) > 0.3).astype(np.float32))
    dsi0 = jnp.asarray(rng.integers(0, 50, (NZ, H, W)), jnp.int32)
    got = vote_onehot_chunk(dsi0, x, y, wts, w=W, h=H)
    per_frame = scatter = dsi0
    for f in range(frames):
        wf = jnp.broadcast_to(wts[f][None], (NZ, E))
        per_frame = vote_onehot_matmul(per_frame, x[f], y[f], w=W, h=H,
                                       weights=wf)
        scatter = vote_scatter(scatter, x[f], y[f], w=W, h=H, weights=wf)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per_frame))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(scatter))
    assert int(jnp.sum(got - dsi0)) > 0


@pytest.fixture(scope="module")
def scene_frames():
    """A 64x48 camera over the 3-planes scene, 96-event frames."""
    from repro.core.camera import CameraModel
    from repro.core.dsi import DSIConfig
    from repro.events.aggregation import aggregate
    from repro.events.simulator import (
        SceneConfig, make_scene, make_trajectory, simulate_events)

    cam = CameraModel(width=64, height=48, fx=53.0, fy=53.0, cx=32.0, cy=24.0)
    traj = make_trajectory("simulation_3planes", 16)
    ev = simulate_events(cam, make_scene(SceneConfig(points_per_plane=150)),
                         traj, noise_fraction=0.1, seed=3)
    frames = aggregate(cam, ev, traj, events_per_frame=96)
    return cam, DSIConfig.for_camera(cam, num_planes=16, z_min=0.6,
                                     z_max=4.5), frames


@partial(jax.jit, static_argnames=("cam", "dsi_cfg", "opts"))
def _per_frame_dsis(cam, dsi_cfg, batch, opts):
    """Each row's stored DSI voted one frame a step through `vote_frame`
    (a float32 one-hot matmul per frame): the sweep before chunking."""
    planes = dsi_cfg.planes()
    z0 = planes[dsi_cfg.num_planes // 2]

    def row(xy, valid, fv, R, t, ref_R, ref_t):
        g = precompute_batch_geometry(cam, R, t, SE3(ref_R, ref_t), planes, z0)

        def body(dsi, f):
            xy, valid, fv, H, alpha, beta_x, beta_y = f
            geom = FrameGeometry(H, PlaneSweepCoeffs(alpha, beta_x, beta_y))
            x_i, y_i = project_frame(cam, xy, geom, opts)
            return vote_frame(dsi, x_i, y_i, valid * fv, cam, opts), None

        dsi, _ = jax.lax.scan(body, jnp.zeros(dsi_cfg.shape, jnp.int32),
                              (xy, valid, fv, g.H, g.phi.alpha, g.phi.beta_x,
                               g.phi.beta_y))
        return dsi_lib.storage_roundtrip(dsi) if opts.quantized else dsi

    return jax.vmap(row)(*batch)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("capacity", [4, 5, 8, 12])
def test_nearest_matmul_sweep_is_the_per_frame_sweep(scene_frames, capacity,
                                                     quantized):
    """The chunked sweep's stored DSIs are the per-frame matmul vote's
    and the scatter sweep's, bit for bit, and so are its depth maps:
    invalid events, padding at a row's end and a dropped frame inside a
    chunk vote nothing; a capacity of 5 pads the last chunk."""
    cam, dsi_cfg, frames = scene_frames
    rows = [(frames, (a, a + n)) for a, n in ((0, capacity - 1),
                                               (capacity, capacity - 2))]
    batch = pad_segment_rows(rows, capacity)
    rng = np.random.default_rng(capacity)
    batch = batch._replace(
        valid=batch.valid * (rng.random(batch.valid.shape) > 0.2),
        frame_valid=batch.frame_valid.at[:, 1].set(0.0))
    # a low threshold, so that most voted pixels are detected
    opts = EMVSOptions(quantized=quantized, detection_threshold_c=0.0,
                       detection_min_votes=1.0)
    dsi, dm = process_segments_batched(cam, dsi_cfg, batch, opts)
    want = _per_frame_dsis(cam, dsi_cfg, batch, opts)
    sc_dsi, sc_dm = process_segments_batched(
        cam, dsi_cfg, batch, dataclasses.replace(opts, formulation="scatter"))
    assert dsi.dtype == jnp.int32 and int(jnp.sum(dm.mask)) > 100
    np.testing.assert_array_equal(np.asarray(dsi), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dsi), np.asarray(sc_dsi))
    for a, b in ((dm.mask, sc_dm.mask), (dm.depth, sc_dm.depth)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
