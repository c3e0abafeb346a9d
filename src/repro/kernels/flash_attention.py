"""Blockwise (flash) attention Pallas-TPU kernel.

TPU adaptation of GPU FlashAttention (DESIGN.md §6): instead of warp-level
softmax reductions, the online-softmax state (m, l, acc) lives in VMEM
scratch that persists across the *sequentially executed* innermost grid
dimension — TPU grids are sequential, so the k-block loop is a grid axis
rather than an in-kernel loop, letting Pallas double-buffer the HBM->VMEM
tile streams for k/v while the MXU works on the previous tile.

Grid: (batch*q_heads, num_q_blocks, num_k_blocks)  — k innermost.
Blocks: q tile (block_q, hd), k/v tiles (block_k, hd), out tile (block_q, hd).
VMEM scratch: acc (block_q, hd) f32, m/l (block_q, 128) f32 (lane-replicated
to keep the layout 2-D and aligned).

Features: causal masking, sliding window, logit soft-capping, GQA (kv-head
indexing folded into the BlockSpec index maps) — the union of what the
assigned architectures need (gemma2 softcap+local, mixtral SWA, command-r /
qwen3 / smollm GQA, jamba attention layers).

Masked k-blocks (fully outside the causal/window band) are skipped via
``pl.when``: the MXU work is predicated out, only the (tiny) scratch update
runs.  Entirely-masked *rows* are handled by the usual l==0 guard at the
finalization step.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 block_q: int, block_k: int, sq: int, sk: int,
                 causal: bool, window: Optional[int],
                 softcap: Optional[float], scale: float, num_k_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions: queries sit at the END of the key sequence
    # (decode-style alignment; == standard causal when sq == sk)
    q_off = sk - sq
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_off
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # block-level skip test: is any (q, k) pair in this tile visible?
    q_lo = iq * block_q + q_off
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.bool_(True)
    if causal:
        live = live & (k_lo <= q_hi)
    if window is not None:
        live = live & (k_hi > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        if sk % block_k:                                     # ragged tail
            mask = mask & (k_pos < sk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)           # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked-so-far rows: exp(NEG_INF - NEG_INF) would be 1
        p = jnp.exp(s - jnp.where(m_new <= NEG_INF, 0.0, m_new))
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - jnp.where(m_new <= NEG_INF, 0.0, m_new))
        alpha = jnp.where(m_prev <= NEG_INF, 0.0, alpha)     # first live block
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        if sk % block_k:
            # ragged tail: out-of-bounds v rows may be garbage/NaN padding;
            # p is 0 there but 0*NaN = NaN, so zero them explicitly.
            row = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            v = jnp.where(row < sk, v, 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """Layout (b, h, s, hd) / (b, kvh, s, hd).  Returns (b, h, sq, hd).

    ``interpret`` has no default: ``True`` runs the kernel body in Python
    on the CPU, ``False`` compiles it with Mosaic for the TPU.
    """
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    assert sq % block_q == 0, "pad queries to block_q"
    # Note: queries at negative positions (front padding when sq > sk under
    # causal) attend to nothing and finalize to 0 via the l==0 guard; the
    # ops.py wrapper slices those rows off.

    grid = (b * h, nq, nk)

    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, sq=sq, sk=sk,
        causal=causal, window=window, softcap=softcap, scale=scale,
        num_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bh, iq, ik: (bh // h, bh % h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bh, iq, ik: (bh // h, (bh % h) // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bh, iq, ik: (bh // h, (bh % h) // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bh, iq, ik: (bh // h, bh % h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
