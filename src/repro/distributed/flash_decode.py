"""Distributed flash-decoding: KV-sequence-sharded one-token attention.

For long-context decode (the `long_500k` cell: batch 1, KV 524288) the
batch axis cannot absorb the `data` mesh axis, so the KV *sequence* is
sharded instead. Each rank computes a partial online-softmax triple
(m, l, acc) over its KV slice; the combine is three tiny collectives
(pmax + 2 psum) of O(B*H*D) — the distributed analogue of split-K
flash-decoding, and the beyond-paper counterpart of the paper's
DSI-level parallelism (partial results merged by an exact reduction,
like partial DSI votes merged by psum).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SeqShard:
    """shard_map flash-decode bound to a mesh. KV sharded over `seq_axis`."""

    mesh: Mesh
    seq_axis: str = "data"

    def decode_attention(self, q: Array, k: Array, v: Array, length: Array
                         ) -> Array:
        """q (B,1,Hq,D) replicated; k/v (B,S,Hkv,D) sharded on S. length:
        scalar int32 — number of valid cache entries."""
        ax = self.seq_axis
        nshards = self.mesh.shape[ax]
        s_global = k.shape[1]
        s_local = s_global // nshards

        def body(q, k, v, length):
            r = jax.lax.axis_index(ax)
            b, _, hq, d = q.shape
            hkv = k.shape[2]
            g = hq // hkv
            qh = q[:, 0].reshape(b, hkv, g, d)
            s = jnp.einsum("bhgd,bkhd->bhgk", qh, k,
                           preferred_element_type=jnp.float32) / d ** 0.5
            pos = r * s_local + jnp.arange(s_local)[None, None, None, :]
            s = jnp.where(pos < length, s, -jnp.inf)
            m_loc = jnp.max(s, axis=-1, keepdims=True)  # (b,hkv,g,1)
            m_loc = jnp.maximum(m_loc, -1e30)  # rank with no valid keys
            p = jnp.exp(s - m_loc)
            p = jnp.where(pos < length, p, 0.0)
            l_loc = jnp.sum(p, axis=-1, keepdims=True)
            acc_loc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                                 preferred_element_type=jnp.float32)
            # exact combine across shards
            m = jax.lax.pmax(m_loc, ax)
            corr = jnp.exp(m_loc - m)
            l = jax.lax.psum(l_loc * corr, ax)
            acc = jax.lax.psum(acc_loc * corr, ax)
            out = acc / jnp.maximum(l, 1e-30)
            return out.reshape(b, 1, hq, d).astype(q.dtype)

        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(None, self.seq_axis, None, None),
                      P(None, self.seq_axis, None, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(q, k, v, jnp.asarray(length))
