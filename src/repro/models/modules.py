"""Model building blocks (pure-JAX, functional init/apply style).

Conventions
-----------
* A module is a pair of functions ``<name>_init(key, ...) -> params`` and
  ``<name>_apply(params, x, ...) -> y``; params are plain dict pytrees.
* Every weight leaf name is stable — the sharding resolver in
  ``repro/launch/sharding.py`` maps leaf paths to PartitionSpecs.
* ``cfg`` is an ``ArchConfig``; compute happens in ``x.dtype`` (callers pick
  bf16 for deployment-shaped runs, f32 for CPU tests).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, YarnScaling


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(params: Dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction 0.1 m ln(factor) + 1 (1 below factor 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(scaling: YarnScaling, dim: int, theta: float
                          ) -> Tuple[int, int]:
    """The rotary dimensions (of ``dim // 2`` frequencies) between which YaRN
    ramps from extrapolated to interpolated frequencies: where a frequency
    turns ``beta_fast`` and ``beta_slow`` times over the original context,
    floored and ceiled, clipped to ``[0, dim - 1]``."""
    def dim_at(rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim_at(scaling.beta_fast)), 0),
            min(math.ceil(dim_at(scaling.beta_slow)), dim - 1))


def rope_frequencies(head_dim: int, theta: float,
                     scaling: Optional[YarnScaling] = None) -> jax.Array:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is None:
        return freqs
    low, high = yarn_correction_range(scaling, head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[YarnScaling] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).
    ``scaling``: YaRN frequencies and cos/sin magnitude."""
    freqs = rope_frequencies(x.shape[-1], theta, scaling)        # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]                          # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * mag, sin * mag
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA family: qk-norm, softcap, sliding window)
# ---------------------------------------------------------------------------


def attention_init(key, cfg: ArchConfig, dtype=jnp.float32, cross: bool = False) -> Dict:
    d, h, kvh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "w_q": _dense_init(k1, (d, h, hd), dtype),
        "w_k": _dense_init(k2, (d, kvh, hd), dtype),
        "w_v": _dense_init(k3, (d, kvh, hd), dtype),
        "w_o": _dense_init(k4, (h, hd, d), dtype, scale=1.0 / math.sqrt(h * hd)),
    }
    if cfg.use_bias:
        p["b_q"] = jnp.zeros((h, hd), dtype)
        p["b_k"] = jnp.zeros((kvh, hd), dtype)
        p["b_v"] = jnp.zeros((kvh, hd), dtype)
        p["b_o"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params, xq, xkv, cfg: ArchConfig, positions_q, positions_k,
                 *, use_rope: bool):
    q = jnp.einsum("bsd,dhk->bshk", xq, params["w_q"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["w_k"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["w_v"])
    if "b_q" in params:
        q = q + params["b_q"]
        k = k + params["b_k"]
        v = v + params["b_v"]
    if "q_norm" in params:
        q = rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions_q, cfg.rope_theta)
        k = apply_rope(k, positions_k, cfg.rope_theta)
    return q, k, v


def _expand_kv(k: jax.Array, h: int) -> jax.Array:
    """GQA: repeat kv heads up to the q-head count BEFORE the score einsum.

    Sharding rationale (DESIGN.md §5): scores carry a head axis; expanding
    first makes that axis h (divisible by the 16-wide "model" mesh axis for
    every assigned arch with h % 16 == 0), whereas the grouped (kvh, g)
    factorization would cap head-sharding at kvh (= 8 for most GQA archs)
    and replicate multi-GB score tensors per device."""
    kvh = k.shape[2]
    if kvh == h:
        return k
    return jnp.repeat(k, h // kvh, axis=2)


def mha_attend(q: jax.Array, k: jax.Array, v: jax.Array,
               mask: Optional[jax.Array], *, attn_softcap: Optional[float],
               scale: Optional[float] = None) -> jax.Array:
    """Reference attention. q: (b, sq, h, hd); k/v: (b, sk, kvh, hd).
    mask: (sq, sk), (b, sq, sk) or (b, 1, sq, sk).  Materializes the full
    (b, h, sq, sk) scores — fine for decode (sq=1) and short sequences;
    long-sequence paths use ``attend_chunked``."""
    b, sq, h, hd = q.shape
    vd = v.shape[-1]          # may differ from hd (MLA: v_head_dim != qk dim)
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    scores = softcap(scores, attn_softcap)
    if mask is not None:
        while mask.ndim < scores.ndim:
            mask = mask[:, None] if mask.ndim >= 2 and mask.shape[0] == b else mask[None]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, vd)


def _chunk_mask(ic, chunk: int, sk: int, sq: int, causal: bool,
                window: Optional[int]):
    """(sq, chunk) validity of k chunk ``ic`` (queries end-aligned)."""
    q_pos = jnp.arange(sq) + (sk - sq)
    k_pos = ic * chunk + jnp.arange(chunk)
    valid = jnp.broadcast_to(k_pos[None, :] < sk, (sq, chunk))
    if causal:
        valid = valid & (k_pos[None, :] <= q_pos[:, None])
    if window is not None:
        valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
    return valid


def _attend_fwd_impl(q, k, v, causal, window, cap, scale, chunk):
    """Online-softmax forward.  q: (b,sq,h,hd); k/v: (b,sk,h,{hd,vd}).
    Returns (out (b,sq,h,vd) f32, lse (b,h,sq) f32)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    vd = v.shape[-1]
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (sk + pad) // chunk
    kc = jnp.moveaxis(k.reshape(b, nc, chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, h, vd), 1, 0)
    qf = q.astype(jnp.float32) * scale

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        ic, k_c, v_c = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_c.astype(jnp.float32))
        s = softcap(s, cap)
        valid = _chunk_mask(ic, chunk, sk, sq, causal, window)
        s = jnp.where(valid[None, None], s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)                    # (b, h, sq)
        m_new = jnp.maximum(m_prev, m_cur)
        safe_m = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.where(valid[None, None], jnp.exp(s - safe_m[..., None]), 0.0)
        alpha = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - safe_m))
        l_new = alpha * l_prev + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, h, sq), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, vd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (jnp.arange(nc), kc, vc))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.where(l > 0.0, l, 1.0)), -jnp.inf)
    return jnp.moveaxis(out, 1, 2), lse                 # (b, sq, h, vd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attend_core(q, k, v, causal, window, cap, scale, chunk):
    out, _ = _attend_fwd_impl(q, k, v, causal, window, cap, scale, chunk)
    return out


def _attend_core_fwd(q, k, v, causal, window, cap, scale, chunk):
    out, lse = _attend_fwd_impl(q, k, v, causal, window, cap, scale, chunk)
    return out, (q, k, v, out, lse)


def _attend_core_bwd(causal, window, cap, scale, chunk, res, dout):
    """Flash-style backward: recompute scores chunkwise from (q, k, v, lse)
    — O(b*h*sq*chunk) transients instead of saving per-chunk probabilities
    (which is what a naively differentiated scan would do, and is the
    difference between ~0.3 GB and ~16 GB of residuals per layer at 4k)."""
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    vd = v.shape[-1]
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (sk + pad) // chunk
    kc = jnp.moveaxis(k.reshape(b, nc, chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, h, vd), 1, 0)
    qf = q.astype(jnp.float32) * scale
    doutf = jnp.moveaxis(dout.astype(jnp.float32), 2, 1)   # (b, h, sq, vd)
    outf = jnp.moveaxis(out.astype(jnp.float32), 2, 1)
    delta = jnp.sum(doutf * outf, axis=-1)                 # (b, h, sq)
    lse_safe = jnp.where(jnp.isinf(lse), 0.0, lse)

    def body(dq_acc, inp):
        ic, k_c, v_c = inp
        kf = k_c.astype(jnp.float32)
        s_raw = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
        s = softcap(s_raw, cap)
        valid = _chunk_mask(ic, chunk, sk, sq, causal, window)
        p = jnp.where(valid[None, None],
                      jnp.exp(s - lse_safe[..., None]), 0.0)   # (b,h,sq,k)
        dv_c = jnp.einsum("bhqk,bhqd->bkhd", p, doutf)
        dp = jnp.einsum("bhqd,bkhd->bhqk", doutf, v_c.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        if cap is not None:
            ds = ds * (1.0 - jnp.square(s / cap))
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
        dk_c = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)  # qf includes scale
        return dq_acc, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, h, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (jnp.arange(nc), kc, vc))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, nc * chunk, h, hd)[:, :sk]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, nc * chunk, h, vd)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attend_core.defvjp(_attend_core_fwd, _attend_core_bwd)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: Optional[int] = None,
                   attn_softcap: Optional[float] = None,
                   scale: Optional[float] = None,
                   chunk: int = 512) -> jax.Array:
    """Memory-efficient (online-softmax) attention: lax.scan over KV chunks
    with a flash-style custom VJP.

    The pure-JAX twin of ``kernels/flash_attention.py`` — peak activation is
    O(b*h*sq*chunk) instead of O(b*h*sq*sk) in BOTH directions, which is
    what lets 32k prefill and 4k training lower within a v5e's HBM on the
    jnp path (the Pallas kernel covers the TPU runtime; this covers
    XLA-only and the CPU dry-run).  Queries sit at the END of the key
    sequence (q_offset = sk - sq), matching the kernel and ref.py.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    chunk = min(chunk, sk)
    return _attend_core(q, k, v, causal, window, attn_softcap, scale, chunk)


# sk above this uses attend_chunked on the non-Pallas full-sequence path
FULL_ATTEND_MAX_KEYS = 1024


def dispatch_attend(q, k, v, *, causal: bool, window: Optional[int],
                    attn_softcap: Optional[float],
                    scale: Optional[float] = None,
                    attn_impl: str = "reference",
                    head_sharding=None) -> jax.Array:
    """Route a full-sequence attention to pallas / chunked / naive.

    ``head_sharding``: optional NamedSharding for (b, s, h, hd) — pins the
    head axis to the "model" mesh axis so the chunked-attention loop state
    shards by heads instead of replicating (MLA's 128 expanded heads are
    3.2 GB/layer at 32k otherwise)."""
    if head_sharding is not None:
        # expand GQA kv up-front so all three tensors carry the full (and
        # mesh-divisible) head count before pinning
        k = _expand_kv(k, q.shape[2])
        v = _expand_kv(v, q.shape[2])
        q = jax.lax.with_sharding_constraint(q, head_sharding)
        k = jax.lax.with_sharding_constraint(k, head_sharding)
        v = jax.lax.with_sharding_constraint(v, head_sharding)
    if attn_impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=attn_softcap, scale=scale)
    if k.shape[1] > FULL_ATTEND_MAX_KEYS:
        return attend_chunked(q, k, v, causal=causal, window=window,
                              attn_softcap=attn_softcap, scale=scale)
    sq, sk = q.shape[1], k.shape[1]
    mask = causal_mask(sq, sk, q_offset=sk - sq, window=window) if causal \
        else (jnp.ones((sq, sk), bool) if window is None else
              causal_mask(sq, sk, q_offset=sk - sq, window=window))
    return mha_attend(q, k, v, mask if (causal or window) else None,
                      attn_softcap=attn_softcap, scale=scale)


def causal_mask(sq: int, sk: int, q_offset: int = 0,
                window: Optional[int] = None) -> jax.Array:
    """(sq, sk) boolean mask; query i attends key j iff j <= i+off and within
    the sliding window (if any)."""
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    m = kpos[None, :] <= qpos[:, None]
    if window is not None:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    return m


def attention_apply(params: Dict, x: jax.Array, cfg: ArchConfig, *,
                    layer_kind: str = "global",
                    positions: Optional[jax.Array] = None,
                    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                    causal: bool = True,
                    attn_impl: str = "reference",
                    head_sharding=None) -> jax.Array:
    """Self- (or cross-, via kv_override) attention over a full sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    window = cfg.sliding_window if layer_kind == "local" else None
    if kv_override is None:
        q, k, v = _project_qkv(params, x, x, cfg, positions, positions,
                               use_rope=True)
    else:
        mem, _ = kv_override
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
        if "b_q" in params:
            q = q + params["b_q"]
        k = jnp.einsum("bsd,dhk->bshk", mem, params["w_k"])
        v = jnp.einsum("bsd,dhk->bshk", mem, params["w_v"])
        if "b_k" in params:
            k, v = k + params["b_k"], v + params["b_v"]
        causal, window = False, None       # cross-attn sees all memory
    out = dispatch_attend(q, k, v, causal=causal, window=window,
                          attn_softcap=cfg.attn_logit_softcap,
                          attn_impl=attn_impl, head_sharding=head_sharding)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["w_o"])
    if "b_o" in params:
        y = y + params["b_o"]
    return y


# -- incremental decode ------------------------------------------------------


def attention_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                         layer_kind: str, dtype=jnp.bfloat16) -> Dict:
    """Ring-buffer KV cache. Local layers only keep ``sliding_window`` slots."""
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim()
    n = min(max_len, cfg.sliding_window) if (
        layer_kind == "local" and cfg.sliding_window) else max_len
    return {
        "k": jnp.zeros((batch, n, kvh, hd), dtype),
        "v": jnp.zeros((batch, n, kvh, hd), dtype),
        "pos": jnp.full((batch, n), -1, jnp.int32),  # true position of each slot
    }


def attention_decode_step(params: Dict, x: jax.Array, cache: Dict,
                          position: jax.Array, cfg: ArchConfig, *,
                          layer_kind: str = "global") -> Tuple[jax.Array, Dict]:
    """One-token decode. x: (b, 1, d); position: scalar int32 (same for the
    whole batch — standard synchronous decode)."""
    b = x.shape[0]
    n = cache["k"].shape[1]
    pos_b = jnp.broadcast_to(position, (b, 1))
    q, k, v = _project_qkv(params, x, x, cfg, pos_b, pos_b, use_rope=True)
    slot = position % n
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos_b.astype(jnp.int32), slot, axis=1)
    window = cfg.sliding_window if layer_kind == "local" else None
    valid = (cpos >= 0) & (cpos <= position)
    if window is not None:
        valid = valid & (cpos > position - window)
    mask = valid[:, None, :]                                   # (b, 1, n)
    out = mha_attend(q, ck, cv, mask, attn_softcap=cfg.attn_logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["w_o"])
    if "b_o" in params:
        y = y + params["b_o"]
    return y, {"k": ck, "v": cv, "pos": cpos}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        p = {"w_q": _dense_init(ks[0], (d, h, qh), dtype)}
    else:
        p = {"w_dq": _dense_init(ks[0], (d, m.q_lora_rank), dtype),
             "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
             "w_uq": _dense_init(ks[1], (m.q_lora_rank, h, qh), dtype)}
    return {
        **p,
        "w_dkv": _dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_ukv": _dense_init(ks[3], (m.kv_lora_rank, h,
                                     m.qk_nope_head_dim + m.v_head_dim), dtype),
        "w_o": _dense_init(ks[4], (h, m.v_head_dim, d), dtype,
                           scale=1.0 / math.sqrt(h * m.v_head_dim)),
    }


def _mla_rope(x, positions, cfg: ArchConfig):
    """DeepSeek's published rotary: the rope dimensions de-interleaved
    (even ones, then odd) before rotate-half."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling)


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """1/sqrt(query-key head size), times YaRN's mscale(mscale_all_dim)
    squared under rotary scaling."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_qkv(params, x, cfg: ArchConfig, positions):
    """Returns q (b,s,h,qh), latent c_kv (b,s,r), shared k_rope (b,s,rope).
    The KV down-projection, its norm and the rope split run under the
    ``latent_kv`` named scope."""
    m = cfg.mla
    if "w_q" in params:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    else:
        cq = rmsnorm_apply(params["q_norm"],
                           jnp.einsum("bsd,dr->bsr", x, params["w_dq"]),
                           cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = _mla_rope(q_rope, positions, cfg)
    with jax.named_scope("latent_kv"):
        dkv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
        c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
        c_kv = rmsnorm_apply(params["kv_norm"], c_kv, cfg.norm_eps)
        k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return jnp.concatenate([q_nope, q_rope], -1), c_kv, k_rope


def _mla_attend(params, q, c_kv, k_rope, mask, cfg: ArchConfig,
                causal: Optional[bool] = None, head_sharding=None):
    """Expand latent to per-head K/V (under ``latent_kv``) and attend
    (naive/faithful path).

    ``mask`` is used for decode (ring-buffer validity); full-sequence
    callers pass ``causal=True`` and route through ``dispatch_attend`` so
    32k prefill never materializes (sq, sk) scores."""
    m = cfg.mla
    with jax.named_scope("latent_kv"):
        ukv = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_ukv"])
        k_nope, v = jnp.split(ukv, [m.qk_nope_head_dim], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      k_nope.shape[:3] + (m.qk_rope_head_dim,))],
            -1)
    scale = mla_softmax_scale(cfg)
    if causal is not None:
        out = dispatch_attend(q, k, v, causal=causal, window=None,
                              attn_softcap=None, scale=scale,
                              head_sharding=head_sharding)
    else:
        out = mha_attend(q, k, v, mask, attn_softcap=None, scale=scale)
    return jnp.einsum("bshk,hkd->bsd", out.astype(q.dtype), params["w_o"])


def mla_apply(params: Dict, x: jax.Array, cfg: ArchConfig,
              positions: Optional[jax.Array] = None,
              head_sharding=None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, c_kv, k_rope = _mla_qkv(params, x, cfg, positions)
    return _mla_attend(params, q, c_kv, k_rope, None, cfg, causal=True,
                       head_sharding=head_sharding)


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_decode_step(params: Dict, x: jax.Array, cache: Dict,
                    position: jax.Array, cfg: ArchConfig,
                    absorbed: bool = False) -> Tuple[jax.Array, Dict]:
    b = x.shape[0]
    pos_b = jnp.broadcast_to(position, (b, 1))
    q, c_kv, k_rope = _mla_qkv(params, x, cfg, pos_b)
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), position, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), position, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos_b.astype(jnp.int32), position, axis=1)
    mask = ((cpos >= 0) & (cpos <= position))[:, None, :]
    new_cache = {"c_kv": ck, "k_rope": cr, "pos": cpos}
    if absorbed:
        y = _mla_attend_absorbed(params, q, ck, cr, mask, cfg)
    else:
        y = _mla_attend(params, q, ck.astype(x.dtype), cr.astype(x.dtype),
                        mask, cfg)
    return y, new_cache


def _mla_attend_absorbed(params, q, c_kv, k_rope, mask, cfg: ArchConfig):
    """Beyond-paper decode optimization: absorb W_UK into the query and W_UV
    into the output so the latent cache is attended *directly* — avoids
    materialising per-head K/V of size (b, S, h, hd) each step.  Math is
    identical (associativity of matmul)."""
    m = cfg.mla
    w_uk, w_uv = jnp.split(params["w_ukv"], [m.qk_nope_head_dim], axis=-1)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    # q_lat[b,t,h,r] = q_nope . W_UK^T : query expressed in latent space
    q_lat = jnp.einsum("bthk,rhk->bthr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat, c_kv.astype(jnp.float32))
              + jnp.einsum("bthk,bsk->bhts", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    scores = jnp.where(mask[:, None] if mask.ndim == 3 else mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhts,bsr->bthr", probs, c_kv.astype(jnp.float32))  # latent ctx
    out = jnp.einsum("bthr,rhv->bthv", ctx, w_uv.astype(jnp.float32))
    return jnp.einsum("bthv,hvd->btd", out.astype(q.dtype), params["w_o"])


# ---------------------------------------------------------------------------
# MLPs and MoE
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, ff: int, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": _dense_init(k1, (d, ff), dtype),
        "up": _dense_init(k2, (d, ff), dtype),
        "down": _dense_init(k3, (ff, d), dtype),
    }


def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def mlp_apply(params: Dict, x: jax.Array, act: str = "silu") -> jax.Array:
    h = _act(jnp.einsum("bsd,df->bsf", x, params["gate"]), act)
    h = h * jnp.einsum("bsd,df->bsf", x, params["up"])
    return jnp.einsum("bsf,fd->bsd", h, params["down"])


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    """The router over every routed expert, the held experts' weights
    (``MoEConfig.held`` of them), and the shared experts as one MLP."""
    moe = cfg.moe
    d = cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    e, ff = moe.held, moe.d_ff_expert
    p = {
        "router": _dense_init(k1, (d, moe.num_experts), dtype, scale=0.02),
        "w_gate": _dense_init(k2, (e, d, ff), dtype),
        "w_up": _dense_init(k3, (e, d, ff), dtype),
        "w_down": _dense_init(k4, (e, ff, d), dtype),
    }
    if moe.num_shared_experts:
        p["shared"] = mlp_init(k5, d, moe.num_shared_experts *
                               (moe.d_ff_shared or moe.d_ff_expert), dtype)
    return p


def moe_route(params: Dict, tokens: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router of ``tokens`` (..., d): the float32 logits at ``HIGHEST``
    precision, softmax over every routed expert, greedy top-k.  Returns
    (probs (..., e), gate weights (..., k), expert ids (..., k)); the gates
    are the top-k probabilities, renormalised if ``norm_topk_prob``, times
    ``routed_scaling_factor``."""
    moe = cfg.moe
    logits = jnp.einsum("...d,de->...e", tokens.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    return probs, gate_vals * moe.routed_scaling_factor, gate_idx


def _balance_loss(probs: jax.Array, gate_idx: jax.Array, cfg: ArchConfig
                  ) -> jax.Array:
    """Load-balance aux loss alpha * sum_e f_e * P_e over all routed experts
    (f_e: the share of token-slots routed to e times e's count, P_e: the
    mean router probability), of each row of ``probs`` (n, t, e) and
    ``gate_idx`` (n, t, k), averaged over the rows."""
    moe = cfg.moe
    n, t, e = probs.shape
    counts = jax.vmap(lambda i: jnp.zeros((e,), jnp.float32).at[
        i.reshape(-1)].add(1.0))(gate_idx)
    f = counts * e / (t * moe.top_k)
    return jnp.mean(jnp.sum(f * probs.mean(1), -1)) * moe.router_aux_weight


def _load_max(counts: jax.Array) -> jax.Array:
    """The largest count of routed token-slots over the mean count."""
    mean = counts.mean()
    return jnp.where(mean > 0, counts.max() / jnp.where(mean > 0, mean, 1.0),
                     0.0).astype(jnp.float32)


# rows of one grouped-matmul tile; the slot buffer is padded to a multiple
GMM_TILE = 128
# elements of one (k, n) weight tile: 2 MB of bfloat16, 4 MB of the f32
# accumulator of the weight gradient's kernel
GMM_TILE_ELEMS = 1 << 20


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """The kernel's (m, k, n) tiles for one call: ``GMM_TILE`` rows, and the
    largest (k, n) tile of 128-multiples dividing the operand widths (the
    width itself under 128) within ``GMM_TILE_ELEMS``.  Each grid step
    costs a fixed overhead, so at a few dozen rows an expert the weight
    tile has to be large for the kernel to near the weights' bytes: on a
    TPU v5e, 8 experts of 2048 x 1408 with 24 rows each took 0.59 ms
    forward and 1.21 ms forward and backward at 128-wide tiles, 0.21 and
    0.38 ms at these (the weights' bytes alone: 0.056 ms forward)."""
    del m

    def widths(dim):
        return [t for t in range(128, dim + 1, 128) if dim % t == 0] or [dim]

    tk, tn = max(((a, b) for a in widths(k) for b in widths(n)
                  if a * b <= GMM_TILE_ELEMS),
                 key=lambda ab: (ab[0] * ab[1], ab[0]),
                 default=(widths(k)[0], widths(n)[0]))
    return GMM_TILE, tk, tn


def _gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array
         ) -> jax.Array:
    """``megablox.gmm`` with f32 accumulation: the Mosaic kernel where the
    program is lowered for a TPU, the same kernel in interpret mode
    elsewhere.  Tiles from ``_gmm_tiling``, for the backward's calls too."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    def run(lhs, rhs, group_sizes, interpret):
        return megablox.gmm(lhs, rhs, group_sizes, jnp.float32, _gmm_tiling,
                            None, None, False, interpret)

    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes,
        tpu=functools.partial(run, interpret=False),
        default=functools.partial(run, interpret=True))


def _gmm_operand_dtype():
    """bfloat16 operands, what a default-precision float32 matmul computes
    on the TPU; float32 where the matmul precision asks for more."""
    prec = jax.config.jax_default_matmul_precision
    return (jnp.bfloat16 if prec in (None, "default", "bfloat16", "fastest")
            else jnp.float32)


def _moe_grouped(params: Dict, tokens: jax.Array, gates: jax.Array,
                 gate_idx: jax.Array, cfg: ArchConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """Drop-free routed experts over the held share.  ``tokens`` (t, d),
    ``gates``/``gate_idx`` (t, k).  Token-slots routed to a held expert are
    sorted by expert into a static (t*k, d) buffer and the held experts'
    gate, up and down projections run as grouped matmuls (``experts``
    scope) over just those rows; slots routed elsewhere sort to the end and
    add nothing.  Rows past the groups are zeroed before and after each
    call (the kernel leaves them unwritten).  The sort and the combine run
    under ``router``.  Returns (y (t, d), counts (held,))."""
    moe = cfg.moe
    t, d = tokens.shape
    k, held = moe.top_k, moe.held
    rows = t * k
    m = -(-rows // GMM_TILE) * GMM_TILE
    dt = _gmm_operand_dtype()
    with jax.named_scope("router"):
        local = gate_idx - moe.first_held_expert
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held).reshape(-1)       # (t*k,)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        valid = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
        xs = jnp.where(valid, tokens[jnp.pad(order // k, (0, m - rows))],
                       0).astype(dt)

    def mask(x):
        return jnp.where(valid, x, 0)

    with jax.named_scope("experts"):
        g = mask(_gmm(xs, params["w_gate"].astype(dt), group_sizes))
        u = mask(_gmm(xs, params["w_up"].astype(dt), group_sizes))
        h = mask(_act(g, cfg.act) * u).astype(dt)
        out = mask(_gmm(h, params["w_down"].astype(dt), group_sizes))
    with jax.named_scope("router"):
        # back to (token, slot) order, weighted by the gates, summed
        slot_out = out[:rows][jnp.argsort(order)].reshape(t, k, d)
        y = jnp.sum(slot_out * jnp.where(is_held, gates, 0.0)[..., None],
                    axis=1)
    return y.astype(tokens.dtype), group_sizes.astype(jnp.float32)


def _moe_capacity(params: Dict, tokens: jax.Array, gate_vals: jax.Array,
                  gate_idx: jax.Array, cfg: ArchConfig,
                  capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Capacity dispatch over every expert: ``tokens`` (g, tg, d) in G
    groups, each expert taking at most ``capacity`` token-slots of a
    group, the rest dropped.  Returns (y (g, tg, d), load_max)."""
    moe = cfg.moe
    g, tg, d = tokens.shape
    e, k = moe.num_experts, moe.top_k
    with jax.named_scope("router"):
        onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)   # (g, tg, k, e)
        load = _load_max(onehot.sum((0, 1, 2)).astype(jnp.float32))
        # position of each (token, slot) within its expert's per-group buffer
        flat = onehot.reshape(g, tg * k, e)
        pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(g, tg, k, e)
        pos = (pos_in_expert * onehot).sum(-1)                  # (g, tg, k)
        keep = pos < capacity
        gate_vals = gate_vals * keep

        # Gather-based dispatch: scatter only TOKEN INDICES (tiny, s32) into
        # the (g, e, cap) slot table, then build expert inputs with a
        # batched gather.  Scatter-adding full token VECTORS into (g, e,
        # cap, d) defeats the SPMD partitioner (it replicates the buffer
        # across all groups — ~21 GB/device for deepseek-v2 at 32k
        # prefill); the batched gather partitions cleanly along the group
        # axis.  Slot `capacity` / token id `tg` are the drop sentinels.
        safe_pos = jnp.where(keep, pos, capacity)               # (g, tg, k)
        grange = jnp.arange(g)[:, None]
        token_ids = jnp.broadcast_to(jnp.arange(tg), (g, tg))
        slot_token = jnp.full((g, e, capacity + 1), tg, jnp.int32)
        for slot in range(k):                                   # k small/static
            slot_token = slot_token.at[
                grange, gate_idx[:, :, slot], safe_pos[:, :, slot]].set(
                    token_ids)
        slot_token = slot_token[:, :, :capacity]                # (g, e, cap)
        tokens_pad = jnp.pad(tokens, ((0, 0), (0, 1), (0, 0)))  # sentinel -> 0
        expert_in = jnp.take_along_axis(
            tokens_pad, slot_token.reshape(g, e * capacity)[..., None],
            axis=1).reshape(g, e, capacity, d)
    with jax.named_scope("experts"):
        h = _act(jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"]),
                 cfg.act)
        h = h * jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"])
        expert_out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    with jax.named_scope("router"):
        expert_out = jnp.pad(expert_out,
                             ((0, 0), (0, 0), (0, 1), (0, 0)))  # sentinel -> 0
        flat_out = expert_out.reshape(g, e * (capacity + 1), d)
        y = jnp.zeros((g, tg, d), tokens.dtype)
        for slot in range(k):
            idx = gate_idx[:, :, slot] * (capacity + 1) + safe_pos[:, :, slot]
            picked = jnp.take_along_axis(flat_out, idx[..., None], axis=1)
            y = y + picked * gate_vals[:, :, slot, None].astype(tokens.dtype)
    return y, load


def moe_apply(params: Dict, x: jax.Array, cfg: ArchConfig,
              capacity_factor: float = 1.25,
              no_drop: bool = False, groups: int = 1,
              group_sharding: Optional[Any] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Routed experts plus the shared ones.  Two dispatches:

    * grouped (where the layer holds a share of the routed experts, as one
      chip of an expert-parallel deployment): the drop-free grouped-matmul
      path of ``_moe_grouped``; the work follows the routed rows.
    * capacity (a layer holding every expert, ``_moe_capacity``): with
      ``no_drop`` one group of capacity t, exact (decode and serving);
      else ``groups`` splits the b*s tokens into G independent token
      groups, each with capacity ``cf * (t/G) * k / e``.  The sharded
      plans of ``launch/specs.py`` run this path: the SPMD partitioner
      cannot split the grouped-matmul kernel over their expert-sharded
      tables.  This is a2a expert parallelism in pjit form: the grouped
      buffers (G, e, cap_g, d) are token-group-sharded before the expert
      matmul and expert-sharded inside it — the reshard XLA inserts
      between the two IS the all-to-all.  Per-device dispatch memory drops from
      O(e * cap * d) (global capacity, ~40 GB for deepseek-v2 at 524k
      tokens/client) to O(e * cap_g * d / TP) (~0.3 GB).

    The router, its balance loss, the dispatch and the combine run under
    the ``router`` named scope, the expert matmuls under ``experts``.

    Returns (output, aux_loss, load_max): ``load_max`` is the largest count
    of token-slots routed to one held expert over their mean.  The
    load-balance aux loss stays *client local* under DFL — routing
    statistics never leave the client (privacy note in DESIGN.md §4).
    """
    moe = cfg.moe
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    t = b * s
    grouped = moe.held != e
    g = groups if not (grouped or no_drop) and t % max(groups, 1) == 0 else 1
    tokens = x.reshape(g, t // g, d)
    if group_sharding is not None and g > 1:
        tokens = jax.lax.with_sharding_constraint(tokens, group_sharding)
    with jax.named_scope("router"):
        probs, gate_vals, gate_idx = moe_route(params, tokens, cfg)
        rows = b if moe.seq_aux else 1
        aux = _balance_loss(probs.reshape(rows, -1, e),
                            gate_idx.reshape(rows, -1, k), cfg)
    if grouped:
        y, counts = _moe_grouped(params, tokens[0], gate_vals[0],
                                 gate_idx[0], cfg)
        load = _load_max(counts)
    else:
        tg = t // g
        capacity = tg if no_drop else max(1, int(capacity_factor * tg * k / e))
        y, load = _moe_capacity(params, tokens, gate_vals, gate_idx, cfg,
                                capacity)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], tokens, cfg.act)
    return y.reshape(b, s, d), aux, load
