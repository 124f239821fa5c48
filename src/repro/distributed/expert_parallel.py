"""Expert parallelism via shard_map (the production MoE path).

Strategy (see models/moe.py docstring): activations replicated over the
`model` axis, experts sharded over it. Every model-rank routes the same
local token set, gathers tokens for ITS expert slice into a capacity
table, runs its experts, scatter-adds, and one psum over `model`
completes the combine — the same all-reduce a Megatron TP block already
pays, so EP adds no extra collective.

The all_to_all dispatch alternative (tokens physically exchanged between
expert shards) is implemented as `a2a` for the §Perf comparison: it
moves 2*T*k*D/|model| bytes through all_to_all instead of T*D through
the psum, which wins when top_k << |model| and loses when activations
were TP-replicated anyway.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ArchConfig
from repro.models.moe import _capacity, moe_apply, router_probs

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EPShard:
    """shard_map-based MoE executor bound to a mesh."""

    mesh: Mesh
    model_axis: str = "model"
    token_axes: tuple[str, ...] = ("data",)
    dispatch: str = "psum"  # psum | a2a
    combine_dtype: Any = jnp.float32  # bf16 halves the combine-psum bytes
    # §Perf H2: ZeRO-3 expert weights. Experts arrive FSDP-sharded over
    # `data` and are all-gathered *inside* the shard_map body in their
    # storage dtype (bf16) — half the gather bytes of the GSPMD boundary
    # reshard (which gathers in fp32 on this backend). The AD transpose
    # of all_gather is psum_scatter, so expert-weight gradients leave as
    # reduce-scatters instead of full all-reduces.
    zero3: bool = False

    def _fsdp_dim(self, shape: tuple[int, ...]) -> int | None:
        from repro.distributed.sharding import fsdp_dim

        fs = self.mesh.shape.get("data", 1)
        if fs <= 1 or not self.zero3:
            return None
        # dim 0 (experts) carries `model`; FSDP picks among the rest
        return fsdp_dim(shape, fs, taken=(0,))

    def _specs(self, params: dict) -> dict:
        m = self.model_axis

        def leaf(path, x):
            pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", ""))) for p in path)
            if "experts" in pstr:
                spec: list = [m] + [None] * (len(x.shape) - 1)
                d = self._fsdp_dim(x.shape)
                if d is not None:
                    spec[d] = "data"
                return P(*spec)
            return P(*([None] * len(x.shape)))

        return jax.tree_util.tree_map_with_path(leaf, params)

    def _gather_dims(self, params: dict) -> dict:
        """Per expert-weight gather dim, from GLOBAL shapes (pre-shard_map)."""
        if not self.zero3:
            return {}
        return {name: self._fsdp_dim(w.shape)
                for name, w in params["experts"].items()}

    def moe(self, params: dict, x: Array, cfg: ArchConfig) -> tuple[Array, dict]:
        """x: (T, D) logical-global tokens. Returns (y, metrics)."""
        m = self.model_axis
        ep_size = self.mesh.shape[m]
        x_spec = P(self.token_axes, None)
        p_specs = self._specs(params)
        gather_dims = self._gather_dims(params)

        def zero3_gather(p: dict) -> dict:
            if not gather_dims:
                return p
            experts = {
                name: (jax.lax.all_gather(w, "data", axis=gather_dims[name],
                                          tiled=True)
                       if gather_dims[name] is not None else w)
                for name, w in p["experts"].items()
            }
            return {**p, "experts": experts}

        if self.dispatch == "psum":
            def body(p, xt):
                p = zero3_gather(p)
                idx = jax.lax.axis_index(m)
                y, metrics = moe_apply(p, xt, cfg, axis_name=m,
                                       ep_size=ep_size, ep_index=idx,
                                       combine_dtype=self.combine_dtype)
                metrics = {k: jax.lax.pmean(v, m) for k, v in metrics.items()}
                return y, metrics

            fn = jax.shard_map(body, mesh=self.mesh,
                               in_specs=(p_specs, x_spec),
                               out_specs=(x_spec, {"moe_aux": P(), "moe_drop_frac": P()}),
                               check_vma=False)
            return fn(params, x)

        def body_a2a(p, xt):
            return _moe_all_to_all(zero3_gather(p), xt, cfg, m, ep_size)

        fn = jax.shard_map(body_a2a, mesh=self.mesh,
                           in_specs=(p_specs, P((self.token_axes + (m,)), None)),
                           out_specs=(P((self.token_axes + (m,)), None),
                                      {"moe_aux": P(), "moe_drop_frac": P()}),
                           check_vma=False)
        return fn(params, x)


def _moe_all_to_all(params: dict, x: Array, cfg: ArchConfig, axis: str,
                    ep_size: int) -> tuple[Array, dict]:
    """GShard-style dispatch: tokens travel to their experts via all_to_all.

    Local tokens are packed into (E, C_loc) capacity tables, all_to_all
    swaps the expert axis for the rank axis, experts run on gathered
    tokens, and a second all_to_all returns outputs to their owners.
    """
    mc = cfg.moe
    t, d = x.shape
    e = mc.num_experts
    e_loc = e // ep_size
    cap = _capacity(t, mc) // ep_size + 1  # per-source-rank slots per expert

    gates, idx, aux = router_probs(params, x, mc)
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), mc.top_k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    grp = jnp.searchsorted(se, jnp.arange(e), side="left")
    pos = jnp.arange(t * mc.top_k) - grp[se]
    keep = pos < cap
    drop_frac = 1.0 - keep.mean()
    pos_c = jnp.minimum(pos, cap)

    table_t = jnp.full((e, cap + 1), t, jnp.int32).at[se, pos_c].set(
        jnp.where(keep, st, t))[:, :cap]
    table_g = jnp.zeros((e, cap + 1), jnp.float32).at[se, pos_c].set(
        jnp.where(keep, sg, 0.0))[:, :cap]

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[table_t]  # (E, C, D) tokens this rank sends per expert

    # (E, C, D) -> (ep, E_loc, C, D) -> all_to_all over ranks
    xe = xe.reshape(ep_size, e_loc, cap, d)
    xr = jax.lax.all_to_all(xe, axis, split_axis=0, concat_axis=0, tiled=False)
    # xr: (ep, E_loc, C, D) — slot [r] = tokens from rank r for MY experts
    xr = xr.transpose(1, 0, 2, 3).reshape(e_loc, ep_size * cap, d)

    we_g = params["experts"]["w_gate"]  # (E_loc, D, F) under shard_map
    we_u = params["experts"]["w_up"]
    we_d = params["experts"]["w_down"]
    h = jnp.einsum("ecd,edf->ecf", xr, we_g.astype(xr.dtype))
    if cfg.mlp_variant == "swiglu":
        up = jnp.einsum("ecd,edf->ecf", xr, we_u.astype(xr.dtype))
        h = jax.nn.silu(h.astype(jnp.float32)).astype(xr.dtype) * up
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(xr.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, we_d.astype(xr.dtype))

    # return trip
    ye = ye.reshape(e_loc, ep_size, cap, d).transpose(1, 0, 2, 3)
    yb = jax.lax.all_to_all(ye, axis, split_axis=0, concat_axis=0, tiled=False)
    yb = yb.reshape(e, cap, d)  # (E, C, D) aligned with table_t

    y = jnp.zeros((t + 1, d), jnp.float32)
    y = y.at[table_t].add(yb.astype(jnp.float32) * table_g[..., None])
    y = y[:t]

    if mc.num_shared_experts:
        from repro.models.layers import mlp

        y = y + mlp(params["shared"], x, cfg.mlp_variant).astype(jnp.float32)
    metrics = {"moe_aux": jax.lax.pmean(aux, axis),
               "moe_drop_frac": jax.lax.pmean(drop_frac, axis)}
    return y.astype(x.dtype), metrics
