"""Mamba2 SSD (state-space duality) chunked-scan Pallas-TPU kernel.

TPU adaptation (DESIGN.md §2/§6): the CUDA selective-scan is a warp-level
prefix scan — no TPU analogue.  The SSD decomposition instead splits the
recurrence into

    intra-chunk:  y_q  = sum_{k<=q in chunk} C_q . B_k  exp(sum a)  dt_k x_k
                  — a (chunk x chunk) masked matmul pair: pure MXU work
    inter-chunk:  h_c  = exp(total_a) h_{c-1} + (chunk state)
                  — a tiny sequential recurrence

The kernel exploits the *sequential* TPU grid: the chunk index is the
innermost grid axis, and the running state (ds x hd, f32) persists in VMEM
scratch across grid steps — the inter-chunk scan costs zero extra HBM
traffic.  One (batch, head) pair per outer grid step keeps every working
tile (q x hd inputs, q x ds B/C, q x q decay matrix, ds x hd state) inside
the ~16 MB VMEM budget for q = 128..256, hd = 64, ds = 128.

Grid: (b * nh, n_chunks); chunk innermost.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, state_out_ref,
                h_ref, *, chunk: int, nh: int, num_chunks: int,
                seq_len: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (q, hd)
    bb = b_ref[0, 0].astype(jnp.float32)         # (q, ds)
    cc = c_ref[0, 0].astype(jnp.float32)         # (q, ds)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (q, 1)
    a_h = a_ref[0].astype(jnp.float32)           # (1,) — this head's A coeff

    # ragged tail: out-of-range steps behave as dt=0 (decay 1, no input).
    # Also zero x/B/C there — padding may be NaN and 0*NaN = NaN.
    if seq_len % chunk:
        row = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        valid = row < seq_len
        dt = jnp.where(valid, dt, 0.0)
        x = jnp.where(valid, x, 0.0)
        bb = jnp.where(valid, bb, 0.0)
        cc = jnp.where(valid, cc, 0.0)

    a_step = dt * a_h                             # (q, 1) log-decay per step
    cum = jnp.cumsum(a_step, axis=0)              # (q, 1) inclusive
    total = cum[-1:, :]                           # (1, 1)

    # ---- intra-chunk quadratic term (MXU) ----
    # L[q, k] = exp(cum_q - cum_k) for k <= q  (decay from step k+1..q)
    seg = cum - jnp.transpose(cum)                # (q, q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_mat = jnp.where(ki <= qi, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * l_mat * jnp.transpose(dt)   # weight by source dt
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- inter-chunk contribution from carried state ----
    # y_inter[q] = exp(cum_q) * C_q . h_prev
    ch = jax.lax.dot_general(cc, h_ref[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (q, hd)
    y = y + jnp.exp(cum) * ch

    # ---- state update:  h <- exp(total) h + sum_k exp(total-cum_k) dt_k B_k x_k ----
    w = jnp.exp(total - cum) * dt                 # (q, 1)
    upd = jax.lax.dot_general(bb * w, x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (ds, hd)
    h_ref[...] = jnp.exp(total) * h_ref[...] + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = h_ref[...]


def ssd_scan_bhs(xs: jax.Array, bs: jax.Array, cs: jax.Array, dt: jax.Array,
                 a_coef: jax.Array, *, chunk: int = 128,
                 interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Layout (b, nh, s, hd) for x, (b, nh, s, ds) for B/C (already head-
    broadcast), (b, nh, s, 1) f32 for dt, (nh,) for a_coef.

    Returns (y (b, nh, s, hd) f32, final state (b, nh, ds, hd) f32).
    """
    b, nh, s, hd = xs.shape
    ds = bs.shape[-1]
    chunk = min(chunk, s)
    nc = pl.cdiv(s, chunk)
    grid = (b * nh, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nh=nh,
                               num_chunks=nc, seq_len=s)

    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd),
                         lambda bh, ic: (bh // nh, bh % nh, ic, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda bh, ic: (bh // nh, bh % nh, ic, 0)),
            pl.BlockSpec((1, 1, chunk, ds),
                         lambda bh, ic: (bh // nh, bh % nh, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bh, ic: (bh // nh, bh % nh, ic, 0)),
            pl.BlockSpec((1,), lambda bh, ic: (bh % nh,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd),
                         lambda bh, ic: (bh // nh, bh % nh, ic, 0)),
            pl.BlockSpec((1, 1, ds, hd),
                         lambda bh, ic: (bh // nh, bh % nh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, ds, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        interpret=interpret,
    )(xs, bs, cs, dt, a_coef)
    return y, state
