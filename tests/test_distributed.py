"""Distributed semantics on a multi-(host-)device mesh.

These run in ONE subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (tests themselves must keep the main process at 1 device,
per the dry-run isolation rule). The subprocess asserts internally and
prints a marker per check.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")  # subprocess cwd = repo root
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

AUTO = (AxisType.Auto,) * 3
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO[:2])

# --- 1. flash decode == reference ---------------------------------------
from repro.distributed.flash_decode import SeqShard
from repro.models.attention import attention_decode
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (2, 1, 4, 16))
k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 16))
v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 16))
out_s = SeqShard(mesh).decode_attention(q, k, v, jnp.int32(37))
out_r = attention_decode(q, k, v, jnp.int32(37))
assert float(jnp.max(jnp.abs(out_s - out_r))) < 1e-5
print("OK flash_decode")

# --- 2. EP (psum + a2a) == single-device MoE ------------------------------
from repro.distributed.expert_parallel import EPShard
from repro.models.moe import moe_apply, init_moe
from repro.configs import get_config
cfg = get_config("deepseek-moe-16b").reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
params = init_moe(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
y_ref, _ = moe_apply(params, x, cfg)
for disp in ("psum", "a2a"):
    with mesh:
        y_ep, m = EPShard(mesh, dispatch=disp).moe(params, x, cfg)
    assert float(jnp.max(jnp.abs(y_ep - y_ref))) < 1e-4, disp
    assert float(m["moe_drop_frac"]) == 0.0
print("OK expert_parallel")

# --- 3. distributed EMVS votes == single-device pipeline -----------------
from repro.core.camera import CameraModel
from repro.core.dsi import DSIConfig
from repro.core.pipeline import (EMVSOptions, precompute_segment_geometry,
                                 process_segment)
from repro.core.geometry import SE3
from repro.events.simulator import SceneConfig, make_scene, make_trajectory, simulate_events
from repro.events.aggregation import aggregate
from repro.distributed.emvs import emvs_input_specs, make_emvs_step
cam = CameraModel()
scene = make_scene(SceneConfig(points_per_plane=120))
traj = make_trajectory("simulation_3planes", 20)
ev = simulate_events(cam, scene, traj, noise_fraction=0.0)
frames = aggregate(cam, ev, traj, 1024)
dsi_cfg = DSIConfig.for_camera(cam, num_planes=16, z_min=0.6, z_max=4.5)
T_w_ref = SE3(frames.poses.R[0], frames.poses.t[0])
F = int(frames.xy.shape[0])
# pad F up to a multiple of the data axis with repeats of the last frame:
# frame_valid zeroes their votes, so no truncation is needed any more
F_pad = -(-F // 4) * 4
pad = jax.tree.map(lambda a: np.concatenate(
    [np.asarray(a)] + [np.asarray(a)[-1:]] * (F_pad - F)), frames)
frame_valid = jnp.asarray((np.arange(F_pad) < F).astype(np.float32))
planes = dsi_cfg.planes()
geoms = precompute_segment_geometry(cam, pad, T_w_ref, planes,
                                    planes[dsi_cfg.num_planes // 2])
phi = jnp.stack([geoms.phi.alpha, geoms.phi.beta_x, geoms.phi.beta_y], axis=-1)
for voting in ("nearest", "bilinear"):
    dsi_ref, dm_ref = process_segment(cam, dsi_cfg, frames, T_w_ref,
                                      EMVSOptions(formulation="matmul",
                                                  voting=voting,
                                                  median_filter=False))
    step = make_emvs_step(cam, dsi_cfg, mesh, mode=voting)
    with mesh:
        dsi_d, depth, mask, conf = step(pad.xy, pad.valid.astype(jnp.float32),
                                        frame_valid, geoms.H, phi)
    if voting == "nearest":
        # integral counts + integer psum: exact
        assert int(jnp.max(jnp.abs(dsi_d.astype(jnp.int32)
                                    - dsi_ref.astype(jnp.int32)))) == 0
    else:
        # fractional bilinear weights stay float32 through the psum
        # (regression: an integer-narrowed merge truncated them to zero
        # error ~1); only summation order differs from the reference
        assert dsi_d.dtype == jnp.float32, dsi_d.dtype
        err = float(jnp.max(jnp.abs(dsi_d - dsi_ref.astype(jnp.float32))))
        assert err < 1e-3, err
    assert bool(jnp.all(mask == dm_ref.mask)), voting
print("OK distributed_emvs")

# --- 3b. emvs_input_specs match the step signature (dry-run lowering) -----
specs = emvs_input_specs(dsi_cfg, frames=F_pad, events=int(frames.xy.shape[1]))
assert list(specs) == ["xy", "valid", "frame_valid", "H", "phi"]
assert specs["frame_valid"].shape == (F_pad,)
with mesh:
    jax.jit(make_emvs_step(cam, dsi_cfg, mesh)).lower(*specs.values())
mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=AUTO)
specs3 = emvs_input_specs(dsi_cfg, frames=4, events=64, segments=2)
assert all(s.shape[0] == 2 for s in specs3.values())
with mesh3:
    jax.jit(make_emvs_step(cam, dsi_cfg, mesh3, pod_axis="pod")).lower(
        *specs3.values())
print("OK emvs_input_specs")

# --- 4. sharded train step == single-device step --------------------------
from repro.training.train_step import (TrainOptions, init_train_state,
                                       make_train_step, state_specs)
from repro.training.optimizer import AdamWConfig
from repro.distributed import sharding as shd
from jax.sharding import NamedSharding
cfg2 = get_config("qwen3-8b").reduced()
opts = TrainOptions(microbatches=2, remat=True,
                    opt=AdamWConfig(warmup_steps=1, total_steps=8))
state = init_train_state(jax.random.PRNGKey(0), cfg2, opts)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg2.vocab_size),
         "targets": jax.random.randint(key, (8, 32), 0, cfg2.vocab_size)}
s_ref, m_ref = jax.jit(make_train_step(cfg2, opts))(
    jax.tree.map(lambda x: x, state), batch)
plan = shd.ShardingPlan.for_mesh(mesh)
sspec = state_specs(cfg2, jax.eval_shape(lambda: state), mesh, plan)
state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                        is_leaf=lambda x: isinstance(x, P))
step_sharded = jax.jit(make_train_step(cfg2, opts, mesh),
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None))
with mesh:
    s_shd, m_shd = step_sharded(state, batch)
assert abs(float(m_ref["loss"]) - float(m_shd["loss"])) < 2e-2, (
    float(m_ref["loss"]), float(m_shd["loss"]))
print("OK sharded_train_step")

# --- 5. elastic restore onto a DIFFERENT mesh -----------------------------
import tempfile
from repro.training import checkpoint as ckpt
with tempfile.TemporaryDirectory() as d:
    ckpt.save(d, 7, s_shd)
    mesh2 = jax.make_mesh((2, 2), ("data", "model"), axis_types=AUTO[:2])  # "lost" half the devices
    sspec2 = state_specs(cfg2, jax.eval_shape(lambda: state), mesh2,
                         shd.ShardingPlan.for_mesh(mesh2))
    sh2 = jax.tree.map(lambda s: NamedSharding(mesh2, s), sspec2,
                       is_leaf=lambda x: isinstance(x, P))
    restored = ckpt.restore(d, 7, jax.eval_shape(lambda: state), sh2)
    for a, b in zip(jax.tree.leaves(s_shd.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
print("OK elastic_restore")
print("ALL_DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_distributed_suite():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=1500, env=env,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "ALL_DISTRIBUTED_OK" in r.stdout, (
        f"STDOUT:\n{r.stdout[-3000:]}\nSTDERR:\n{r.stderr[-5000:]}")
