"""Fused RMSNorm Pallas-TPU kernel.

One HBM pass: load a (block_rows, d) tile, reduce mean-square along the
feature axis on the VPU, scale, write back — versus the unfused jnp path
(square -> mean -> rsqrt -> mul -> mul) which XLA usually fuses anyway; the
kernel exists because rmsnorm sits on the critical path of *every* block of
every assigned arch and pinning its tiling guarantees no accidental f32
materialisation of the squared activations at 32k sequence lengths.

Grid: (rows // block_rows,).  ``scale`` (d,) stays VMEM-resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float, d: int):
    x = x_ref[...].astype(jnp.float32)            # (block_rows, d)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_2d(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
               block_rows: int = 256, interpret: bool) -> jax.Array:
    """x: (rows, d) — callers flatten leading axes; scale: (d,)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, "pad rows to block_rows"
    grid = (rows // block_rows,)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps, d=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, scale)
