"""Jitted public wrappers around the Pallas kernels.

These adapt the model-zoo layouts to the kernel-native layouts, pad where a
block constraint requires it, and pick interpret mode automatically:
``interpret=True`` whenever the backend is not a TPU, the real Mosaic
path on TPU.  Models call these via ``ApplyOptions(attn_impl=
"pallas")``; the default model path stays the jnp reference so CPU dry-runs
lower without Pallas in the HLO.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import consensus_mix as _cm
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention  (model layout: (b, s, h, hd))
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: (b, sq, h, hd); k/v: (b, sk, kvh, hd) -> (b, sq, h, hd)."""
    b, sq, h, hd = q.shape
    bq = min(block_q, sq)
    pad_q = (-sq) % bq
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if pad_q:
        # pad queries at the FRONT so the end-aligned causal positions of the
        # real queries are unchanged; padded rows are discarded.
        qt = jnp.pad(qt, ((0, 0), (0, 0), (pad_q, 0), (0, 0)))
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=bq, block_k=block_k,
        interpret=_interpret_default())
    if pad_q:
        out = out[:, :, pad_q:]
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# SSD scan  (model layout: xs (b,s,nh,hd), bs/cs (b,s,g,ds), dt (b,s,nh))
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xs: jax.Array, bs: jax.Array, cs: jax.Array, dt: jax.Array,
             a_coef: jax.Array, *, chunk: int = 128
             ) -> Tuple[jax.Array, jax.Array]:
    """Matches ``repro.models.mamba.ssd_chunked``'s contract:
    returns (y (b,s,nh,hd) f32, final state (b,nh,ds,hd) f32)."""
    b, s, nh, hd = xs.shape
    ds = bs.shape[-1]
    xk = xs.transpose(0, 2, 1, 3)
    bk = jnp.broadcast_to(bs[:, :, 0][:, :, None],
                          (b, s, nh, ds)).transpose(0, 2, 1, 3)
    ck = jnp.broadcast_to(cs[:, :, 0][:, :, None],
                          (b, s, nh, ds)).transpose(0, 2, 1, 3)
    dk = dt.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    y, state = _ssd.ssd_scan_bhs(xk, bk, ck, dk, a_coef, chunk=chunk,
                                 interpret=_interpret_default())
    return y.transpose(0, 2, 1, 3), state


# ---------------------------------------------------------------------------
# consensus mixing over a parameter pytree
# ---------------------------------------------------------------------------


def consensus_mix_pytree(a_eff: jax.Array, tree: Any,
                         block_d: int = 2048) -> Any:
    """Apply W <- A_eff W to every leaf with leading server axis M, through
    ONE fused flatten -> kernel -> unflatten pass (leaves concatenated so the
    whole model is a single (M, D) stream)."""
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    sizes = [leaf[0].size for leaf in leaves]
    flat = jnp.concatenate(
        [leaf.reshape(m, -1).astype(jnp.float32) for leaf in leaves], axis=1)
    mixed = _cm.consensus_mix_2d(a_eff, flat, block_d=block_d,
                                 interpret=_interpret_default())
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(mixed[:, off:off + size].reshape(leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# rmsnorm  (model layout: (..., d))
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256) -> jax.Array:
    lead = x.shape[:-1]
    d = x.shape[-1]
    rows = 1
    for n in lead:
        rows *= n
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _rn.rmsnorm_2d(x2, scale, eps=eps, block_rows=br,
                       interpret=_interpret_default())
    if pad:
        y = y[:rows]
    return y.reshape(*lead, d)
