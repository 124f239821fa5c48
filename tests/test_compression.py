"""int8 gradient compression: round-trip bounds + error-feedback property
(the bias vanishes over repeated steps — Seide'14 semantics)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.distributed.compression import (
    BLOCK,
    CompressionState,
    compress_decompress,
    compression_error,
    init_state,
)


@given(seed=st.integers(0, 1000), scale=st.sampled_from([1e-4, 1.0, 1e4]))
@settings(max_examples=20)
def test_roundtrip_error_bounded(seed, scale):
    rng = np.random.default_rng(seed)
    g = jnp.asarray((rng.normal(size=300) * scale).astype(np.float32))
    rt = compress_decompress(g)
    # per-block max-abs scaling: error <= scale/2 = blockmax/254 per element
    blocks = np.pad(np.asarray(g), (0, (-len(g)) % BLOCK)).reshape(-1, BLOCK)
    bmax = np.abs(blocks).max(axis=1, keepdims=True)
    bound = np.repeat(bmax / 127.0 / 2.0, BLOCK, axis=1).reshape(-1)[:len(g)]
    assert np.all(np.abs(np.asarray(rt) - np.asarray(g)) <= bound + 1e-12)
    assert float(compression_error(g)) < 0.01  # ~8-bit SNR


def test_zero_and_constant_grads_exact():
    z = jnp.zeros(512)
    assert float(jnp.max(jnp.abs(compress_decompress(z)))) == 0.0
    c = jnp.full(512, 3.25)
    np.testing.assert_allclose(np.asarray(compress_decompress(c)), 3.25,
                               rtol=1e-6)


def test_error_feedback_removes_bias():
    """Accumulated (compressed + residual) updates converge to the true
    sum: || sum_t true_g - sum_t sent_g || stays bounded by one step's
    quantization error, not t * error."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(256, np.float32)
    sent_sum = np.zeros(256, np.float32)
    residual = jnp.zeros(256, jnp.float32)
    for t in range(50):
        g = jnp.asarray(rng.normal(size=256).astype(np.float32))
        gf = g + residual
        sent = compress_decompress(gf)
        residual = gf - sent
        true_sum += np.asarray(g)
        sent_sum += np.asarray(sent)
    # bias bounded by the residual (single-step error), not accumulated
    gap = np.abs(true_sum - sent_sum).max()
    assert gap <= float(jnp.max(jnp.abs(residual))) + 1e-5
    assert gap < 0.05  # vs ~50 steps * per-step error if bias accumulated


def test_compressed_psum_single_axis():
    """shard_map over a size-1 axis exercises the wire path end to end."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_psum

    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(1)
                          .normal(size=(8, 8)).astype(np.float32))}
    state = init_state(g)

    def body(g, r):
        return compressed_psum(g, CompressionState(residual=r), "data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    mean, new_state = fn(g, state.residual)
    np.testing.assert_allclose(np.asarray(mean["w"]), np.asarray(g["w"]),
                               atol=np.abs(np.asarray(g["w"])).max() / 127)
