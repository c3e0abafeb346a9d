"""Fused consensus-mixing Pallas-TPU kernel:  W <- A_eff W  in ONE HBM pass.

This is the single-chip half of the beyond-paper "collapsed consensus"
optimization (DESIGN.md §7): the faithful DFL server loop applies A for
T_S rounds, i.e. T_S full read+write passes over every server's parameter
vector.  Since A^{T_S} is an (M x M) matrix that is trivially precomputed on
the host, one streaming pass suffices — the kernel is purely memory-bound,
so collapsing T_S passes into 1 cuts consensus HBM traffic by exactly T_S x.

Layout: the parameter pytree is flattened into a (M, D) matrix (D = total
model params).  A_eff is tiny (M<=64) and stays resident in VMEM across all
grid steps; W streams through in (M, block_d) tiles.

Grid: (D // block_d,).  VMEM per step: M*block_d*4 bytes in + out + M*M.
block_d = 2048 with M = 16 -> 256 KB per buffer: far under VMEM, deep
double-buffering.

``quantized_consensus_mix_2d`` is the compressed-gossip variant: the wire
round-trip of ``comm.compressors.StochasticQuantizer`` (per-chunk scales,
stochastic rounding, dequantize) fused INTO the same single mixing pass —
what a server computes when it applies the collapsed operator to the
int8/int4 payloads it received, without ever materialising the quantized
model in HBM.

``quantized_gossip_round_2d`` is the PHYSICAL-WIRE round kernel: one
delta-coded gossip round of ``wire="physical"`` after the all-gather,
fused gather-dequant-mix-requant — input is the gathered delta code/scale
buffers + the shared f32 reference, output the updated reference, the
mixed iterates, and the re-encoded innovation codes/scales for the NEXT
round's collective; the decoded deltas and pre-encode innovations live
only in VMEM and never materialise in HBM.  Bit-identical to the jnp wire
path (``decode_block`` → accumulate → mix → ``compress``) under shared
dither, which stays the reference oracle (``tests/test_wire.py``).

``quantized_gossip_encode_2d`` covers the ENCODE side of the wire: the
innovation ``W - R`` and its absmax-scaled stochastic rounding fused into
one pass, so the pre-encode delta never round-trips HBM — what each
server computes immediately before the collective (round 0 encodes the
full state: ``R = 0``).

``bucketed_gossip_round_2d`` is the BUCKETED-wire round kernel (PR 6):
the band-carried recursion of ``core.consensus.gossip_scan_wire_bucketed``
— each server holds only its OWN reference row and a running
mixed-reference accumulator — fused encode→gather→dequant→accumulate→
mix→requant around the round's single collective pair.  Together with
``quantized_gossip_encode_2d`` it closes the loop: codes and innovations
live only in VMEM between collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mix_kernel(a_ref, w_ref, o_ref, *, total_d: int, block_d: int):
    i = pl.program_id(0)
    a = a_ref[...].astype(jnp.float32)            # (M, M) resident
    w = w_ref[...].astype(jnp.float32)            # (M, block_d)
    if total_d % block_d:
        col = i * block_d + jax.lax.broadcasted_iota(
            jnp.int32, w.shape, 1)
        w = jnp.where(col < total_d, w, 0.0)      # NaN-safe ragged tail
    o_ref[...] = jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def consensus_mix_2d(a_eff: jax.Array, w: jax.Array, *, block_d: int = 2048,
                     interpret: bool) -> jax.Array:
    """w: (M, D); a_eff: (M, M).  Returns A_eff @ w, one HBM pass."""
    m, d = w.shape
    block_d = min(block_d, d)
    grid = (pl.cdiv(d, block_d),)
    kernel = functools.partial(_mix_kernel, total_d=d, block_d=block_d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),         # A resident
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, d), w.dtype),
        interpret=interpret,
    )(a_eff, w)


# ---------------------------------------------------------------------------
# fused quantize -> mix -> dequantize (the compressed-gossip single-chip path)
# ---------------------------------------------------------------------------


def _quant_mix_kernel(a_ref, w_ref, u_ref, o_ref, *, block_d: int,
                      chunk: int, qmax: float):
    """One (M, block_d) tile: per-(row, chunk) absmax scales, stochastic-
    rounded int codes, dequantize, then the A contraction — the wire
    round-trip of ``comm.compressors.StochasticQuantizer`` fused into the
    mixing pass so the quantized values never touch HBM."""
    a = a_ref[...].astype(jnp.float32)                 # (M, M) resident
    w = w_ref[...].astype(jnp.float32)                 # (M, block_d)
    u = u_ref[...].astype(jnp.float32)                 # dither in [0, 1)
    m = w.shape[0]
    nc = block_d // chunk
    wc = w.reshape(m, nc, chunk)
    absmax = jnp.max(jnp.abs(wc), axis=-1, keepdims=True)
    # multiply by the reciprocal CONSTANT, never divide: XLA's
    # simplifier rewrites float division by a constant to a
    # reciprocal multiply in SOME programs and not others (a 1-ulp
    # scale skew between compilations of the same formula); an
    # explicit literal leaves it nothing to rewrite, and matches
    # ``comm.compressors.StochasticQuantizer._scales`` bitwise
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    q = jnp.clip(jnp.floor(wc * (1.0 / scale) + u.reshape(m, nc, chunk)),
                 -qmax, qmax)
    deq = (q * scale).reshape(m, block_d)
    o_ref[...] = jax.lax.dot_general(
        a, deq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def quantized_consensus_mix_2d(a_eff: jax.Array, w: jax.Array,
                               dither: jax.Array, *, bits: int = 8,
                               chunk: int = 256, block_d: int = 2048,
                               interpret: bool) -> jax.Array:
    """Fused quantize -> mix -> dequantize:  A_eff @ D(C(w))  in one pass.

    ``w``: (M, D) flattened server models; ``a_eff``: the (collapsed)
    mixing operator; ``dither``: (M, D) uniform [0, 1) stochastic-rounding
    noise, generated OUTSIDE the kernel (``jax.random.uniform``) so the
    same randomness can drive the jnp wire simulation — on a real TPU the
    in-kernel ``pltpu.prng_random_bits`` path avoids the HBM read, but the
    interpret-mode CPU backend this container runs has no TPU PRNG.

    Bit-identical to ``StochasticQuantizer(bits, chunk).roundtrip`` followed
    by ``consensus_mix_2d`` when ``chunk`` divides the chosen ``block_d``
    (chunk boundaries then align across tiles), while touching W's HBM
    bytes once instead of three times (quantize pass + mix read + write).
    """
    m, d = w.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    block_d = max(chunk, min(block_d, -(-d // chunk) * chunk))
    if block_d % chunk:
        raise ValueError(f"chunk={chunk} must divide block_d={block_d}")
    # pad to the tile grid up front: trailing zeros quantize to zero codes
    # and contribute nothing to the contraction, so no in-kernel masking
    nb = pl.cdiv(d, block_d)
    pad = nb * block_d - d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        dither = jnp.pad(dither, ((0, 0), (0, pad)))
    qmax = float(2 ** (bits - 1) - 1)
    kernel = functools.partial(_quant_mix_kernel, block_d=block_d,
                               chunk=chunk, qmax=qmax)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, nb * block_d), w.dtype),
        interpret=interpret,
    )(a_eff, w, dither)
    return out[:, :d]


# ---------------------------------------------------------------------------
# fused gather-dequant-mix-requant: one PHYSICAL-WIRE gossip round
# ---------------------------------------------------------------------------


def _wire_round_kernel(a_ref, q_ref, s_ref, r_ref, u_ref, w_ref, or_ref,
                       oq_ref, os_ref, *, block_d: int, chunk: int,
                       qmax: float):
    """One (M, block_d) tile of a delta-coded physical-wire gossip round:
    dequantize the GATHERED delta codes, accumulate them into the shared
    reference tile, mix the references, and re-encode the NEXT innovations
    (mixed - new reference) with fresh absmax scales + dither — the
    decoded-delta and mixed f32 tiles exist only in VMEM."""
    a = a_ref[...].astype(jnp.float32)                 # (M, M) resident
    q = q_ref[...].astype(jnp.float32)                 # (M, block_d) codes
    s = s_ref[...]                                     # (M, nc) scales
    ref = r_ref[...]                                   # (M, block_d) f32
    u = u_ref[...].astype(jnp.float32)                 # dither in [0, 1)
    m = q.shape[0]
    nc = block_d // chunk
    ref = ref + (q.reshape(m, nc, chunk) * s[..., None]).reshape(m, block_d)
    # unrolled left-to-right mul-adds, NOT an MXU dot: the wire paths
    # (consensus._wire_mix_rows / the shard_map round body) accumulate in
    # exactly this order, and matching it is what makes the kernel
    # bit-identical to them rather than ulp-close (M is tiny and the
    # kernel memory-bound, so the MXU buys nothing here)
    mixed = a[:, 0:1] * ref[0]
    for j in range(1, m):
        mixed = mixed + a[:, j:j + 1] * ref[j]
    wc = (mixed - ref).reshape(m, nc, chunk)           # next innovations
    absmax = jnp.max(jnp.abs(wc), axis=-1, keepdims=True)
    # multiply by the reciprocal CONSTANT, never divide: XLA's
    # simplifier rewrites float division by a constant to a
    # reciprocal multiply in SOME programs and not others (a 1-ulp
    # scale skew between compilations of the same formula); an
    # explicit literal leaves it nothing to rewrite, and matches
    # ``comm.compressors.StochasticQuantizer._scales`` bitwise
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    q2 = jnp.clip(jnp.floor(wc * (1.0 / scale) + u.reshape(m, nc, chunk)),
                  -qmax, qmax)
    w_ref[...] = mixed
    or_ref[...] = ref
    oq_ref[...] = q2.reshape(m, block_d).astype(jnp.int8)
    os_ref[...] = scale[..., 0]


def quantized_gossip_round_2d(a: jax.Array, codes: jax.Array,
                              scales: jax.Array, ref: jax.Array,
                              dither: jax.Array, *, bits: int = 8,
                              chunk: int = 256, block_d: int = 2048,
                              interpret: bool):
    """Fused gather-dequant-mix-requant: one delta-coded ``wire="physical"``
    gossip round after the all-gather, in one HBM pass — the single-chip
    half of ``core.consensus.make_gossip_shard_map``'s codec mode.

    Implements the innovation recursion of
    ``core.consensus.gossip_scan_wire``:

        R'      = R + D(codes, scales)        (accumulate gathered deltas)
        W'      = A · R'                      (mix the references)
        delta'  = W' - R'                     (next innovations)
        codes', scales' = C(delta'; dither)   (next round's wire)

    ``codes``: (M, D) int8 delta codes as delivered by the all-gather
    (int4 codes UNPACKED into int8 — ``comm.compressors.pack_int4`` is a
    free view change at the collective boundary); ``scales``: (M, D/chunk)
    per-chunk f32 scales; ``ref``: the (M, D) f32 shared reference state;
    ``dither``: (M, D) uniform [0, 1) rounding noise for the re-encode,
    generated outside for the same reason as ``quantized_consensus_mix_2d``.
    Returns ``(mixed, ref', codes', scales')``.  The decoded deltas and
    the pre-encode innovations never touch HBM: unfused, each round writes
    + re-reads two (M, D) f32 intermediates — 4 extra HBM passes this
    kernel keeps in VMEM (the reference itself is genuine algorithm state
    and lives in HBM either way).

    Bit-identical to the jnp oracle (``decode_block`` -> accumulate ->
    ``consensus._wire_mix_rows`` -> ``compress(dither=u)``) when ``chunk``
    divides ``block_d`` and ``D`` (chunk boundaries then align across
    tiles) and both run under jit — the wire paths always do; an EAGER
    oracle differs by one FMA-contraction ulp in the re-encode scales.
    Asserted in ``tests/test_wire.py``."""
    m, d = codes.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if d % chunk:
        raise ValueError(f"chunk={chunk} must divide D={d} (pad the wire "
                         f"buffer to the block grid first, as the gossip "
                         f"paths do)")
    block_d = max(chunk, min(block_d, d))
    if block_d % chunk:
        raise ValueError(f"chunk={chunk} must divide block_d={block_d}")
    nb = pl.cdiv(d, block_d)
    pad = nb * block_d - d
    if pad:     # ragged tile grid: zero codes / unit scales are inert
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // chunk)),
                         constant_values=1.0)
        ref = jnp.pad(ref, ((0, 0), (0, pad)))
        dither = jnp.pad(dither, ((0, 0), (0, pad)))
    qmax = float(2 ** (bits - 1) - 1)
    nc_blk = block_d // chunk
    kernel = functools.partial(_wire_round_kernel, block_d=block_d,
                               chunk=chunk, qmax=qmax)
    out_w, out_r, out_q, out_s = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.int8),
            jax.ShapeDtypeStruct((m, nb * nc_blk), jnp.float32),
        ],
        interpret=interpret,
    )(a, codes, scales, ref, dither)
    return (out_w[:, :d], out_r[:, :d], out_q[:, :d],
            out_s[:, :d // chunk])


# ---------------------------------------------------------------------------
# fused innovation + encode: the send side of the physical wire
# ---------------------------------------------------------------------------


def _wire_encode_kernel(w_ref, r_ref, u_ref, oq_ref, os_ref, *,
                        block_d: int, chunk: int, qmax: float):
    """One (M, block_d) tile of the wire's SEND side: the innovation
    ``w - r`` and its per-chunk absmax-scaled stochastic rounding in one
    pass — the pre-encode delta exists only in VMEM."""
    w = w_ref[...].astype(jnp.float32)                 # (M, block_d)
    r = r_ref[...]                                     # (M, block_d) f32
    u = u_ref[...].astype(jnp.float32)                 # dither in [0, 1)
    m = w.shape[0]
    nc = block_d // chunk
    wc = (w - r).reshape(m, nc, chunk)                 # innovations
    absmax = jnp.max(jnp.abs(wc), axis=-1, keepdims=True)
    # multiply by the reciprocal CONSTANT, never divide: XLA's
    # simplifier rewrites float division by a constant to a
    # reciprocal multiply in SOME programs and not others (a 1-ulp
    # scale skew between compilations of the same formula); an
    # explicit literal leaves it nothing to rewrite, and matches
    # ``comm.compressors.StochasticQuantizer._scales`` bitwise
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    q = jnp.clip(jnp.floor(wc * (1.0 / scale) + u.reshape(m, nc, chunk)),
                 -qmax, qmax)
    oq_ref[...] = q.reshape(m, block_d).astype(jnp.int8)
    os_ref[...] = scale[..., 0]


def quantized_gossip_encode_2d(w: jax.Array, ref: jax.Array,
                               dither: jax.Array, *, bits: int = 8,
                               chunk: int = 256, block_d: int = 2048,
                               interpret: bool):
    """Fused innovation + encode: ``C(w - ref; dither)`` in one HBM pass —
    the SEND side of the physical wire, what every server computes
    immediately before the round's collective (round 0, ``ref = 0``,
    encodes the full state; that transmission is what error feedback
    tracks).  Unfused, the delta is a full (M, D) f32 HBM round-trip
    before the quantize pass reads it back.

    ``w``: (M, D) iterates; ``ref``: (M, D) f32 decoded references;
    ``dither``: (M, D) uniform [0, 1) rounding noise (generated outside —
    see ``quantized_consensus_mix_2d``).  Returns ``(codes, scales)`` with
    ``codes`` (M, D) UNPACKED int8 (int4 values in int8 storage —
    ``comm.compressors.pack_int4`` is a free view change at the collective
    boundary) and ``scales`` (M, D/chunk) f32.  Bit-identical to
    ``StochasticQuantizer.encode_block`` of ``w - ref`` under jit when
    ``chunk`` divides ``D`` (asserted in ``tests/test_wire.py``)."""
    m, d = w.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if d % chunk:
        raise ValueError(f"chunk={chunk} must divide D={d} (pad the wire "
                         f"buffer to the bucket grid first, as the gossip "
                         f"paths do)")
    block_d = max(chunk, min(block_d, d))
    if block_d % chunk:
        raise ValueError(f"chunk={chunk} must divide block_d={block_d}")
    nb = pl.cdiv(d, block_d)
    pad = nb * block_d - d
    if pad:     # ragged tile grid: zero deltas quantize to zero codes
        w = jnp.pad(w, ((0, 0), (0, pad)))
        ref = jnp.pad(ref, ((0, 0), (0, pad)))
        dither = jnp.pad(dither, ((0, 0), (0, pad)))
    qmax = float(2 ** (bits - 1) - 1)
    nc_blk = block_d // chunk
    kernel = functools.partial(_wire_encode_kernel, block_d=block_d,
                               chunk=chunk, qmax=qmax)
    out_q, out_s = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.int8),
            jax.ShapeDtypeStruct((m, nb * nc_blk), jnp.float32),
        ],
        interpret=interpret,
    )(w, ref, dither)
    return out_q[:, :d], out_s[:, :d // chunk]


# ---------------------------------------------------------------------------
# fused bucketed round: the band-carried recursion of the PR-6 wire
# ---------------------------------------------------------------------------


def _bucketed_round_kernel(a_ref, q_ref, s_ref, r_ref, c_ref, u_ref,
                           oa_ref, or_ref, oq_ref, os_ref, *, block_d: int,
                           chunk: int, qmax: float):
    """One (M, block_d) tile of a BUCKETED delta-coded gossip round:
    dequantize the gathered codes, fold each server's own decoded delta
    into its reference row (``r`` is the band — row i is server i's OWN
    reference, so the update is elementwise, no (M, M) fan-out),
    accumulate the mixed deltas into ``acc``, and re-encode the next
    innovations ``acc - r`` with fresh scales + dither."""
    a = a_ref[...].astype(jnp.float32)                 # (M, M) resident
    q = q_ref[...].astype(jnp.float32)                 # (M, block_d) codes
    s = s_ref[...]                                     # (M, nc) scales
    r = r_ref[...]                                     # (M, block_d) band
    acc = c_ref[...]                                   # (M, block_d) f32
    u = u_ref[...].astype(jnp.float32)                 # dither in [0, 1)
    m = q.shape[0]
    nc = block_d // chunk
    dec = (q.reshape(m, nc, chunk) * s[..., None]).reshape(m, block_d)
    r = r + dec
    # unrolled left-to-right mul-adds, NOT an MXU dot — same reason and
    # same order as ``_wire_round_kernel``: this is what keeps the kernel
    # bit-identical to the bucketed wire paths
    for j in range(m):
        acc = acc + a[:, j:j + 1] * dec[j]
    wc = (acc - r).reshape(m, nc, chunk)               # next innovations
    absmax = jnp.max(jnp.abs(wc), axis=-1, keepdims=True)
    # multiply by the reciprocal CONSTANT, never divide: XLA's
    # simplifier rewrites float division by a constant to a
    # reciprocal multiply in SOME programs and not others (a 1-ulp
    # scale skew between compilations of the same formula); an
    # explicit literal leaves it nothing to rewrite, and matches
    # ``comm.compressors.StochasticQuantizer._scales`` bitwise
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    q2 = jnp.clip(jnp.floor(wc * (1.0 / scale) + u.reshape(m, nc, chunk)),
                  -qmax, qmax)
    oa_ref[...] = acc
    or_ref[...] = r
    oq_ref[...] = q2.reshape(m, block_d).astype(jnp.int8)
    os_ref[...] = scale[..., 0]


def bucketed_gossip_round_2d(a: jax.Array, codes: jax.Array,
                             scales: jax.Array, ref: jax.Array,
                             acc: jax.Array, dither: jax.Array, *,
                             bits: int = 8, chunk: int = 256,
                             block_d: int = 2048, interpret: bool):
    """Fused encode→gather→dequant→accumulate→mix→requant, bucketed: one
    round of ``core.consensus.gossip_scan_wire_bucketed``'s band-carried
    recursion after the all-gather, in one HBM pass.

    Implements (rows = servers, everything elementwise over D)::

        dec   = D(codes, scales)       (gathered decoded deltas)
        ref'  = ref + dec              (each row: its OWN reference band)
        acc'  = acc + A · dec          (running (A · R_t) accumulator)
        delta'= acc' - ref'            (next innovations; f32 iterate)
        codes', scales' = C(delta'; dither)

    ``codes``: (M, D) int8 delta codes as delivered by the all-gather
    (int4 UNPACKED into int8); ``scales``: (M, D/chunk) f32; ``ref`` /
    ``acc``: the (M, D) f32 band state (own-reference rows and mixed-
    reference accumulators — together ~3 vectors per server instead of the
    per-leaf form's (M+1)); ``dither``: (M, D) uniform [0, 1) noise for
    the re-encode.  Returns ``(acc', ref', codes', scales')`` — the mixed
    iterate IS ``acc'`` (cast to the model dtype by the caller).  The
    decoded deltas and pre-encode innovations never touch HBM; vs the
    unfused jnp round that is 4 (M, D) f32 HBM passes saved.

    Bit-identical to the jnp oracle (``decode_block`` → band update →
    left-to-right accumulate → ``encode_block``) under jit when ``chunk``
    divides ``block_d`` and ``D`` — asserted for both code widths in
    ``tests/test_wire.py``."""
    m, d = codes.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if d % chunk:
        raise ValueError(f"chunk={chunk} must divide D={d} (pad the wire "
                         f"buffer to the bucket grid first, as the gossip "
                         f"paths do)")
    block_d = max(chunk, min(block_d, d))
    if block_d % chunk:
        raise ValueError(f"chunk={chunk} must divide block_d={block_d}")
    nb = pl.cdiv(d, block_d)
    pad = nb * block_d - d
    if pad:     # ragged tile grid: zero codes / unit scales are inert
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // chunk)),
                         constant_values=1.0)
        ref = jnp.pad(ref, ((0, 0), (0, pad)))
        acc = jnp.pad(acc, ((0, 0), (0, pad)))
        dither = jnp.pad(dither, ((0, 0), (0, pad)))
    qmax = float(2 ** (bits - 1) - 1)
    nc_blk = block_d // chunk
    kernel = functools.partial(_bucketed_round_kernel, block_d=block_d,
                               chunk=chunk, qmax=qmax)
    out_a, out_r, out_q, out_s = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.int8),
            jax.ShapeDtypeStruct((m, nb * nc_blk), jnp.float32),
        ],
        interpret=interpret,
    )(a, codes, scales, ref, acc, dither)
    return (out_a[:, :d], out_r[:, :d], out_q[:, :d],
            out_s[:, :d // chunk])


# ---------------------------------------------------------------------------
# software-pipelined bucketed round: the bounded-staleness wire body
# ---------------------------------------------------------------------------


def _bucketed_round_pipelined_kernel(a_ref, q_ref, s_ref, w_ref, r_ref,
                                     c_ref, u_ref, oa_ref, or_ref, oq_ref,
                                     os_ref, *, block_d: int, chunk: int,
                                     qmax: float):
    """One (M, block_d) tile of a PIPELINED bucketed round: the send side
    (encode this round's innovation, advance the own sent-reference band)
    runs first and depends only on local state, so its codes can leave on
    the wire while the consume side folds the DELAYED codes (round t-s)
    into the accumulator — the in-kernel order mirrors the data-dependence
    split that lets XLA overlap the collective with the round's FMA work
    in ``core.consensus``'s stale bodies."""
    a = a_ref[...].astype(jnp.float32)                 # (M, M) resident
    q = q_ref[...].astype(jnp.float32)                 # DELAYED codes
    s = s_ref[...]                                     # delayed scales
    w = w_ref[...].astype(jnp.float32)                 # current iterates
    r = r_ref[...]                                     # sent-reference band
    acc = c_ref[...]                                   # (M, block_d) f32
    u = u_ref[...].astype(jnp.float32)                 # dither in [0, 1)
    m = q.shape[0]
    nc = block_d // chunk
    # SEND side: encode w - r against the up-to-date sent reference
    wc = (w - r).reshape(m, nc, chunk)
    absmax = jnp.max(jnp.abs(wc), axis=-1, keepdims=True)
    # multiply by the reciprocal CONSTANT, never divide: XLA's
    # simplifier rewrites float division by a constant to a
    # reciprocal multiply in SOME programs and not others (a 1-ulp
    # scale skew between compilations of the same formula); an
    # explicit literal leaves it nothing to rewrite, and matches
    # ``comm.compressors.StochasticQuantizer._scales`` bitwise
    scale = jnp.where(absmax > 0, absmax * (1.0 / qmax), 1.0)
    q2 = jnp.clip(jnp.floor(wc * (1.0 / scale) + u.reshape(m, nc, chunk)),
                  -qmax, qmax)
    # own-decode: the sent reference advances by what just shipped, from
    # LOCAL codes — never waits on the gather of this round's codes
    r = r + (q2 * scale).reshape(m, block_d)
    # CONSUME side: fold the delayed deltas.  (a · scale) folded per chunk
    # BEFORE the code multiply, unrolled left-to-right — the exact product
    # order of ``gossip_scan_wire_bucketed``'s stale body, which is what
    # keeps the kernel bit-identical to it
    c3 = q.reshape(m, nc, chunk)
    acc3 = acc.reshape(m, nc, chunk)
    for j in range(m):
        acc3 = acc3 + (a[:, j:j + 1] * s[j])[:, :, None] * c3[j]
    oa_ref[...] = acc3.reshape(m, block_d)
    or_ref[...] = r
    oq_ref[...] = q2.reshape(m, block_d).astype(jnp.int8)
    os_ref[...] = scale[..., 0]


def bucketed_gossip_round_pipelined_2d(a: jax.Array, codes: jax.Array,
                                       scales: jax.Array, w: jax.Array,
                                       ref: jax.Array, acc: jax.Array,
                                       dither: jax.Array, *, bits: int = 8,
                                       chunk: int = 256, block_d: int = 2048,
                                       interpret: bool):
    """Fused SOFTWARE-PIPELINED bucketed round: one round of
    ``core.consensus.gossip_scan_wire_bucketed``'s bounded-staleness
    recursion (``staleness >= 1``) in one HBM pass.

    Implements (rows = servers; ``codes``/``scales`` are the DELAYED
    payload from round ``t - s``, pulled off the staleness ring)::

        codes', scales' = C(w - ref; dither)   (this round's innovation)
        ref'  = ref + D(codes', scales')       (own-decode, local codes)
        acc'  = acc + A · D(codes, scales)     (consume the stale deltas)

    The send side (first two lines) has no data dependence on the delayed
    payload, so round t's collective overlaps round t's accumulate — the
    double-buffering the stale wire bodies express with their code/scale
    ring carry.  The iterate gate ``w <- where(t >= s, acc', w)`` stays
    OUTSIDE the kernel: it is ring-phase control, not tile math.

    ``w``: (M, D) iterates (any float dtype; cast to f32 in-tile);
    ``codes``: (M, D) int8 delayed delta codes (int4 UNPACKED into int8);
    ``scales``: (M, D/chunk) f32 delayed scales; ``ref`` / ``acc``: the
    (M, D) f32 band state; ``dither``: (M, D) uniform [0, 1) noise.
    Returns ``(acc', ref', codes', scales')`` — ``codes'``/``scales'`` are
    what this round SHIPS (push to the staleness ring), ``acc'`` the
    consume result.  Bit-identical to the stale jnp oracle (encode →
    own-decode → folded left-to-right accumulate) under jit — asserted in
    ``tests/test_overlap.py``."""
    m, d = codes.shape
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if d % chunk:
        raise ValueError(f"chunk={chunk} must divide D={d} (pad the wire "
                         f"buffer to the bucket grid first, as the gossip "
                         f"paths do)")
    block_d = max(chunk, min(block_d, d))
    if block_d % chunk:
        raise ValueError(f"chunk={chunk} must divide block_d={block_d}")
    nb = pl.cdiv(d, block_d)
    pad = nb * block_d - d
    if pad:     # ragged tile grid: zero codes / unit scales are inert
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
        scales = jnp.pad(scales, ((0, 0), (0, pad // chunk)),
                         constant_values=1.0)
        w = jnp.pad(w, ((0, 0), (0, pad)))
        ref = jnp.pad(ref, ((0, 0), (0, pad)))
        acc = jnp.pad(acc, ((0, 0), (0, pad)))
        dither = jnp.pad(dither, ((0, 0), (0, pad)))
    qmax = float(2 ** (bits - 1) - 1)
    nc_blk = block_d // chunk
    kernel = functools.partial(_bucketed_round_pipelined_kernel,
                               block_d=block_d, chunk=chunk, qmax=qmax)
    out_a, out_r, out_q, out_s = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((m, m), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, nc_blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.float32),
            jax.ShapeDtypeStruct((m, nb * block_d), jnp.int8),
            jax.ShapeDtypeStruct((m, nb * nc_blk), jnp.float32),
        ],
        interpret=interpret,
    )(a, codes, scales, w, ref, acc, dither)
    return (out_a[:, :d], out_r[:, :d], out_q[:, :d],
            out_s[:, :d // chunk])
