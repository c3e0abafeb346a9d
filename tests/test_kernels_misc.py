"""Sweep tests: consensus_mix + rmsnorm kernels vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.compressors import StochasticQuantizer
from repro.core import topology as tp
from repro.core.consensus import collapse_mixing
from repro.kernels import consensus_mix_pytree, ops
from repro.kernels.consensus_mix import (consensus_mix_2d,
                                         quantized_consensus_mix_2d)
from repro.kernels.ref import consensus_mix_ref, rmsnorm_ref

KEY = jax.random.key(11)


@pytest.mark.parametrize("m,d,block", [
    (2, 64, 32), (5, 1000, 128), (8, 4096, 2048), (16, 257, 64),
])
def test_consensus_mix_2d(m, d, block):
    a = jnp.asarray(collapse_mixing(
        tp.metropolis_weights(tp.ring_graph(m)), 7), jnp.float32)
    w = jax.random.normal(KEY, (m, d))
    out = consensus_mix_2d(a, w, block_d=block, interpret=True)
    ref = consensus_mix_ref(a, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,d,bits,chunk,block", [
    (5, 1024, 8, 128, 512),     # multi-tile, multi-chunk per tile
    (4, 1000, 8, 256, 512),     # ragged tail
    (3, 130, 4, 64, 128),       # int4
    (6, 37, 8, 256, 2048),      # single partial chunk
])
def test_quantized_consensus_mix_matches_compressor_oracle(m, d, bits,
                                                           chunk, block):
    """The fused quantize->mix->dequantize kernel equals the composition of
    the comm-subsystem wire round-trip (same dither) and the dense mix."""
    a = jnp.asarray(collapse_mixing(
        tp.metropolis_weights(tp.ring_graph(m)), 7), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, d), (m, d)) * 3
    u = jax.random.uniform(jax.random.fold_in(KEY, d + 1), (m, d))
    out = quantized_consensus_mix_2d(a, w, u, bits=bits, chunk=chunk,
                                     block_d=block, interpret=True)
    q = StochasticQuantizer(bits=bits, chunk=chunk)
    ref = a @ q.decompress(q.compress(w, dither=u), d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-5)


def test_quantized_consensus_mix_validates():
    a = jnp.eye(2)
    w = jnp.ones((2, 8))
    with pytest.raises(ValueError, match="bits"):
        quantized_consensus_mix_2d(a, w, w, bits=3, interpret=True)
    with pytest.raises(ValueError, match="divide"):
        quantized_consensus_mix_2d(a, w, w, chunk=3, block_d=8,
                                   interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_consensus_mix_dtypes(dtype):
    m = 4
    a = jnp.asarray(collapse_mixing(
        tp.metropolis_weights(tp.complete_graph(m)), 3), jnp.float32)
    w = jax.random.normal(KEY, (m, 512)).astype(dtype)
    out = consensus_mix_2d(a, w, block_d=128, interpret=True)
    assert out.dtype == dtype
    ref = consensus_mix_ref(a, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32),
                               rtol=tol, atol=tol)


def test_consensus_mix_pytree_roundtrip():
    m = 5
    a = jnp.asarray(collapse_mixing(
        tp.metropolis_weights(tp.line_graph(m)), 9), jnp.float32)
    kw, kb, kx = jax.random.split(KEY, 3)
    tree = {"w": jax.random.normal(kw, (m, 17, 3)),
            "b": jax.random.normal(kb, (m, 5)),
            "nested": {"x": jax.random.normal(kx, (m, 2, 2, 2))}}
    out = consensus_mix_pytree(a, tree, block_d=16)
    for lo, li in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        ref = consensus_mix_ref(a, li.reshape(m, -1)).reshape(li.shape)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows,d,block", [
    (32, 128, 8), (100, 256, 32), (256, 960, 256), (7, 64, 8),
])
def test_rmsnorm_kernel(rows, d, block):
    kx, ks = jax.random.split(KEY)
    x = jax.random.normal(kx, (rows, d))
    scale = jax.random.normal(ks, (d,))
    out = ops.rmsnorm(x, scale, block_rows=block)
    ref = rmsnorm_ref(x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_rmsnorm_multidim_and_bf16():
    x = jax.random.normal(KEY, (2, 3, 5, 128)).astype(jnp.bfloat16)
    scale = jnp.ones((128,), jnp.bfloat16)
    out = ops.rmsnorm(x, scale)
    ref = rmsnorm_ref(x, scale)
    assert out.shape == x.shape and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, jnp.float32),
                               np.asarray(ref, jnp.float32),
                               rtol=2e-2, atol=2e-2)
