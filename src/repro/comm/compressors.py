"""Lossy compressors for inter-server gossip messages.

Every compressor is a pure ``compress``/``decompress`` pair over 2-D
``(M, d)`` arrays — row i is server i's flattened outgoing message — with
static output shapes, so both directions trace cleanly inside jit.  The
consensus period then mixes the DECOMPRESSED values
(``core.consensus.CompressedBackend``): mathematically that is exactly what
every receiver reconstructs from the on-wire payload.

Wire model.  Gossip is linear in the payloads, so one compressed message
per server per consensus period, forwarded T_S hops ("payload flooding"),
realises the whole T_S-round period on decompressed values.  The on-wire
cost accounted by ``comm.accounting.BytesTracker`` is therefore

    live directed links  x  T_S rounds  x  wire_bytes_per_row.

Compressors:

* ``IdentityCompressor``            exact passthrough (accounting baseline).
* ``StochasticQuantizer(bits, chunk)``  int8/int4 with per-chunk absmax
      scales and UNBIASED stochastic rounding ``q = floor(x * (1/s) + u)``,
      ``u ~ U[0, 1)``: ``E[decompress] = x``, so quantization noise is
      zero-mean and error feedback only has to absorb its variance.  With
      no rng key the rounding degrades to deterministic round-to-nearest.
* ``TopKCompressor(ratio)``         per-row magnitude top-k: values plus
      explicit int32 indices cross the wire.
* ``RandomKCompressor(ratio)``      k coordinates sampled per call from the
      SHARED rng key: every server transmits the same coordinate set, so
      the indices never cross the wire (receivers regenerate them from the
      shared seed) and the gossip operator acts identically per coordinate.

``make_compressor`` parses the ``DFLConfig.compression`` /
``--compression`` spec grammar::

    none | int8[:CHUNK] | int4[:CHUNK] | top_k:RATIO | random_k:RATIO

Wire codecs.  The quantizers double as SHARD-SHAPED wire codecs for the
physical-wire gossip paths (``core.consensus.make_gossip_shard_map`` /
``make_ring_gossip`` with ``codec=``): ``StochasticQuantizer.encode_block``
turns one flattened block into the exact byte layout that crosses the
collective — int8 codes (two int4 codes packed per byte via ``pack_int4``)
plus per-chunk f32 scales — and ``decode_block`` inverts it.  Both are thin
wrappers over the same ``compress``/``decompress`` math, so the in-graph
wire simulation and the physical collective path share ONE numerics
definition; under the shared dither convention (``wire_dither``) the two
are bit-identical (asserted in ``tests/test_wire.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# int4 byte packing + the shared wire-dither convention
# ---------------------------------------------------------------------------


def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack int4 codes (int8 array, values in [-8, 7]) two per byte along
    the last axis: element ``2i`` in the low nibble, ``2i+1`` in the high
    nibble.  An odd-length axis is padded with one zero code (the receiver
    slices it off in ``unpack_int4``).  Exactly invertible, so routing
    codes through ``pack_int4``/``unpack_int4`` never changes numerics —
    it only halves the bytes the collective moves."""
    if codes.shape[-1] % 2:
        codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, 1)])
    u = jax.lax.bitcast_convert_type(codes, jnp.uint8)
    lo = u[..., 0::2] & 0x0F
    hi = (u[..., 1::2] & 0x0F) << 4
    return jax.lax.bitcast_convert_type(lo | hi, jnp.int8)


def unpack_int4(packed: jax.Array, length: int) -> jax.Array:
    """Inverse of ``pack_int4``: (..., ceil(length/2)) bytes -> (..., length)
    sign-extended int8 codes."""
    u = jax.lax.bitcast_convert_type(packed, jnp.uint8)
    lo = (u & 0x0F).astype(jnp.int8)
    hi = ((u >> 4) & 0x0F).astype(jnp.int8)
    # sign-extend the 4-bit values: v in [0, 15] -> (v ^ 8) - 8 in [-8, 7]
    both = jnp.stack([lo, hi], axis=-1)
    both = ((both ^ 8) - 8).astype(jnp.int8)
    flat = both.reshape(both.shape[:-2] + (-1,))
    return flat[..., :length]


def bucket_block(d_tot: int, block: int, chunk: int) -> Tuple[int, int]:
    """``(blk, nb)`` of the BUCKETED physical-wire layout: the whole server
    pytree flattened to ``d_tot`` elements and cut into ``nb`` equal blocks
    of ``blk`` elements (zero-padded tail).  ``blk`` is ``min(block,
    d_tot)`` rounded UP to a multiple of ``lcm(chunk, 2)``, so (a) chunk
    boundaries never cross a block — every block encodes independently —
    and (b) a block's packed-int4 codes are a whole number of bytes, making
    per-block views of the packed code buffer free slices.  Shared by the
    bucketed gossip programs (``core.consensus.gossip_scan_wire_bucketed``
    / ``make_gossip_shard_map``'s codec mode) and the byte ledger
    (``comm.accounting.tree_bucketed_wire_bytes_per_server``), which must
    agree on the padded layout for the HLO byte audit to close."""
    d_tot = max(int(d_tot), 1)
    unit = chunk if chunk % 2 == 0 else 2 * chunk
    blk = -(-min(block, d_tot) // unit) * unit
    return blk, -(-d_tot // blk)


def wire_dither(key: jax.Array, shape: Tuple[int, ...], *, leaf, rnd,
                server, block) -> jax.Array:
    """THE stochastic-rounding dither of the wire paths: uniform [0, 1)
    noise keyed by ``(leaf index, gossip round, server row, block index)``.

    Every wire execution — the in-graph simulation
    (``core.consensus.gossip_scan_wire_bucketed`` and the legacy per-leaf
    form), the physical shard_map / ring collectives, and the
    error-feedback residual update — derives its dither from this one
    convention, which is what makes them bit-identical under a shared
    key: the same (leaf, round, server, block) cell always rounds with
    the same noise, no matter which execution produced it.  All four
    coordinates may be traced (the shard_map paths fold in
    ``lax.axis_index`` and loop counters).

    The per-element noise is a keyed counter hash (``_mix32`` murmur
    avalanche over the element counters, same idiom as
    ``keyed_index_sample``), NOT a threefry ``jax.random.uniform``: the
    dither is regenerated every gossip round on every device over the
    whole bucket, and at benchmark scale the ~20-round threefry was the
    single largest per-round compute on the wire path (~35% of the
    consensus period) — the 24-bit-resolution hash has the avalanche
    quality stochastic rounding needs at a fraction of the ALU work.
    The four scalar ``fold_in``s stay threefry: they are O(1) and define
    the coordinate keying."""
    k = jax.random.fold_in(key, leaf)
    k = jax.random.fold_in(k, rnd)
    k = jax.random.fold_in(k, server)
    k = jax.random.fold_in(k, block)
    kd = jax.random.key_data(k).astype(jnp.uint32)
    n = int(np.prod(shape, dtype=np.int64))
    ctr = jax.lax.iota(jnp.uint32, n)
    x = _mix32((ctr ^ kd[-1]) * jnp.uint32(0x9E3779B9) ^ kd[0])
    return ((x >> jnp.uint32(8)).astype(jnp.float32)
            * jnp.float32(2.0 ** -24)).reshape(shape)


# ---------------------------------------------------------------------------
# counter-based O(k) index sampling (random-k at LM scale)
# ---------------------------------------------------------------------------


def _mix32(x: jax.Array) -> jax.Array:
    """murmur3-style avalanche on uint32 (the Feistel round function)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def keyed_index_sample(key: jax.Array, d: int, k: int) -> jax.Array:
    """``k`` DISTINCT uniform indices in ``[0, d)`` in O(k) work: encrypt
    the counters ``0..k-1`` with a keyed 4-round Feistel bijection over the
    smallest even-bit power-of-two domain ``>= d`` and cycle-walk any value
    that lands outside ``[0, d)`` back through the cipher.

    This replaces the ``jax.random.permutation`` sampler, whose O(D log D)
    sort (and O(D) memory) is fine at benchmark scale but prohibitive at LM
    scale — the bijection gives the same guarantees random-k needs (distinct
    indices, per-coordinate uniformity over keys, identical on every server
    given the shared key) at O(k).  Cycle-walking terminates because the
    cipher is a bijection: the walk traverses a cycle that must re-enter
    ``[0, d)`` (expected < 4 steps; the domain is < 4d).

    ``d`` is capped at ``2^31 - 1``: the indices gather with int32 (the
    width jnp indexing uses without x64), and past that the wrap would
    silently alias coordinates — and past ``2^32`` the uint32 cipher stops
    being a bijection.  That is also the per-axis size ceiling of the
    arrays these coordinates index, so the cap costs nothing in practice;
    lifting it means moving the Feistel (and the gather) to 64-bit."""
    if not 0 < k <= d:
        raise ValueError(f"need 0 < k <= d, got k={k}, d={d}")
    if d > np.iinfo(np.int32).max:
        raise ValueError(
            f"keyed_index_sample is 32-bit (uint32 cipher, int32 gather "
            f"indices): d={d} exceeds 2^31 - 1 and would silently alias "
            f"coordinates")
    half = max(1, -(-max(d - 1, 1).bit_length() // 2))    # ceil(bits/2)
    mask = jnp.uint32((1 << half) - 1)
    round_keys = jax.random.bits(key, (4,), dtype=jnp.uint32)

    def feistel(x):
        left, right = x >> half, x & mask
        for rk in round_keys:
            left, right = right, left ^ (_mix32(right ^ rk) & mask)
        return (left << half) | right

    def walk(x):
        return jax.lax.while_loop(lambda v: v >= d, lambda v: feistel(v), x)

    idx = jax.vmap(walk)(feistel(jnp.arange(k, dtype=jnp.uint32)))
    return idx.astype(jnp.int32)


class Compressed(NamedTuple):
    """On-wire representation of one compressed ``(M, d)`` message batch.

    ``data`` is the payload (quantized codes or kept values); ``scale`` the
    per-chunk dequantization scales (quantizers only); ``idx`` the kept
    coordinates (sparsifiers only — shape ``(M, k)`` for top-k, shared
    ``(k,)`` for seed-coordinated random-k).  Unused fields are ``None``."""

    data: jax.Array
    scale: Optional[jax.Array] = None
    idx: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base: a pure compress/decompress pair + metadata-derived wire bytes.

    ``wire_bits_data`` is the TRUE on-wire width of one ``data`` element —
    it may be narrower than the array dtype carrying it in memory (int4
    codes ride in int8 arrays).  ``idx_on_wire`` is False when receivers
    can reconstruct the indices without transmission (shared-seed
    random-k).  ``shape_preserving`` marks compressors whose round-trip is
    purely elementwise over the input's natural shape (chunking along the
    LAST axis only): ``roundtrip_tree`` then skips the ``(M, d)`` flatten
    entirely, which under pjit is the difference between per-shard local
    compute and replicating every leaf (the flatten merges sharded weight
    axes)."""

    wire_bits_data = 32
    idx_on_wire = True
    shape_preserving = False

    name = "?"

    def compress(self, x: jax.Array,
                 key: Optional[jax.Array] = None) -> Compressed:
        raise NotImplementedError

    def decompress(self, comp: Compressed, d: int) -> jax.Array:
        raise NotImplementedError

    def roundtrip(self, x: jax.Array,
                  key: Optional[jax.Array] = None) -> jax.Array:
        """What the receivers reconstruct: D(C(x)), in ``x``'s dtype."""
        return self.decompress(self.compress(x, key),
                               x.shape[-1]).astype(x.dtype)

    def wire_bytes_per_row(self, d: int) -> int:
        """On-wire bytes of ONE server's compressed d-element message,
        derived from the ACTUAL compressed representation (``jax.eval_shape``
        over ``compress`` — payload metadata, not a closed form; the
        independent closed forms live in ``comm.accounting.
        analytic_row_bytes`` and the two are cross-checked by tests and the
        ``compressed_consensus`` benchmark)."""
        return self.wire_bytes_per_leaf((1, d))

    def wire_bytes_per_leaf(self, shape) -> int:
        """Bytes of one server's compressed message for a server-tree leaf
        of the given shape (leading axis = server): what actually crosses
        the wire, derived from the payload metadata of compressing exactly
        what ``roundtrip_tree`` compresses — the flat ``(1, d)`` row for
        flatten-based compressors, the natural ``(1, *w)`` shape for
        shape-preserving ones (their chunk count follows the leaf's last
        axis)."""
        shape = tuple(shape)
        if not self.shape_preserving:
            shape = (1, int(np.prod(shape[1:])))
        else:
            shape = (1,) + shape[1:]
        comp = jax.eval_shape(
            lambda x: self.compress(x, key=jax.random.key(0)),
            jax.ShapeDtypeStruct(shape, jnp.float32))
        total = int(np.ceil(comp.data.size * self.wire_bits_data / 8))
        if comp.scale is not None:
            total += comp.scale.size * comp.scale.dtype.itemsize
        if comp.idx is not None and self.idx_on_wire:
            total += comp.idx.size * comp.idx.dtype.itemsize
        return total


@dataclasses.dataclass(frozen=True)
class IdentityCompressor(Compressor):
    """Exact passthrough — the float32-wire baseline of the accounting, and
    the compressor under which the whole layer degenerates exactly."""

    name = "identity"
    shape_preserving = True

    def compress(self, x, key=None):
        del key
        return Compressed(data=x)

    def decompress(self, comp, d):
        return comp.data[..., :d]


@dataclasses.dataclass(frozen=True)
class StochasticQuantizer(Compressor):
    """int8/int4 quantization with per-chunk absmax scales.

    The LAST axis of the input is split into ``chunk``-element chunks (the
    last may be partial); chunk c gets scale ``s_c = absmax_c / qmax``
    (``qmax = 2^{bits-1}-1``) and codes ``q = clip(floor(x * (1/s_c) + u),
    -qmax, qmax)`` with dither ``u ~ U[0, 1)`` — unbiased stochastic
    rounding (round-to-nearest when no key is given).  The grid step is
    applied as a multiply by the per-chunk reciprocal ``1/s_c`` (division
    was the hottest per-element op of the physical-wire round); ``s_c``
    itself stays the on-wire scale, and every encoder — in-graph,
    shard_map, Pallas — derives the same reciprocal bitwise.  On the wire: UNPADDED codes
    + one f32 scale per chunk; int4 codes are carried in int8 arrays in
    memory but counted at 4 bits.

    Shape preserving: every op is elementwise except a last-axis-only
    reshape, so ``(M, *w)`` leaves compress in their natural layout — under
    pjit each device quantizes its local shard (chunk boundaries follow the
    leaf's rows, which is also what a real per-tensor wire format does),
    no gather, no flatten.  Pass ``dither`` explicitly to share the
    randomness with a fused kernel (``kernels.consensus_mix.
    quantized_consensus_mix_2d`` parity)."""

    bits: int = 8
    chunk: int = 256

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"bits must be 4 or 8, got {self.bits}")
        if self.chunk < 1:
            raise ValueError("chunk must be >= 1")

    @property
    def name(self):
        return f"int{self.bits}"

    @property
    def wire_bits_data(self):
        return self.bits

    shape_preserving = True

    @property
    def qmax(self) -> float:
        return float(2 ** (self.bits - 1) - 1)

    def _scales(self, x32: jax.Array) -> jax.Array:
        """(..., nc) per-chunk scales over the last axis of a float32 array
        (zero-padded virtually: a trailing partial chunk uses only its real
        elements)."""
        length = x32.shape[-1]
        nc = -(-length // self.chunk)
        pad = nc * self.chunk - length
        if pad:
            x32 = jnp.pad(x32, [(0, 0)] * (x32.ndim - 1) + [(0, pad)])
        return self._chunk_scales(
            x32.reshape(x32.shape[:-1] + (nc, self.chunk)))

    def _chunk_scales(self, x3: jax.Array) -> jax.Array:
        """(..., nc) scales of f32 chunks laid out as (..., nc, chunk)."""
        absmax = jnp.max(jnp.abs(x3), axis=-1)
        # multiply by the reciprocal CONSTANT, never divide: XLA's
        # simplifier rewrites float division by a constant into a
        # reciprocal multiply in SOME programs and not in others, which
        # skews the scale by 1 ulp between two compilations of this same
        # formula (observed between a shard_map wire program and the
        # in-graph oracle it must match bitwise).  An explicit literal
        # leaves the compiler nothing to rewrite; the Pallas consensus
        # kernels use the same form.
        return jnp.where(absmax > 0, absmax * (1.0 / self.qmax), 1.0)

    def _per_elem(self, scale: jax.Array, d: int) -> jax.Array:
        """Broadcast (..., nc) chunk scales back onto the d real last-axis
        elements — codes ship UNPADDED, only the scales carry the chunk
        structure."""
        return jnp.repeat(scale, self.chunk, axis=-1)[..., :d]

    def compress(self, x, key=None, *, dither=None):
        d = x.shape[-1]
        x32 = x.astype(jnp.float32)
        if dither is None:
            dither = (jax.random.uniform(key, x32.shape)
                      if key is not None else 0.5)
        if d % self.chunk == 0:
            # chunk-multiple fast path (every bucketed-wire block, by
            # ``bucket_block`` construction): scale in the (..., nc,
            # chunk) layout so the chunk reciprocal broadcasts, instead
            # of materialising a full-width per-element scale vector.
            # Same multiply/add/floor operands element for element, so
            # the codes are bitwise identical to the general path.
            x3 = x32.reshape(x32.shape[:-1] + (-1, self.chunk))
            u3 = (dither if jnp.ndim(dither) == 0
                  else jnp.reshape(dither, x3.shape))
            q, scale = self.encode_chunks(x3, u3)
            return Compressed(data=q.reshape(x32.shape), scale=scale)
        scale = self._scales(x32)
        q = jnp.clip(jnp.floor(x32 * self._per_elem(1.0 / scale, d)
                               + dither),
                     -self.qmax, self.qmax).astype(jnp.int8)
        return Compressed(data=q, scale=scale)

    def encode_chunks(self, x3: jax.Array, dither) -> Tuple[jax.Array,
                                                            jax.Array]:
        """Quantize f32 chunks laid out as ``(..., nc, chunk)``: UNPACKED
        int8 codes of the same shape and ``(..., nc)`` scales.  The chunk
        view is the bucketed shard_map wire's native layout, so its encode
        never reshapes; ``compress`` calls this on its chunk-multiple path,
        which keeps the two one numerics definition.

        Quantize by MULTIPLYING with the reciprocal of the on-wire scale
        (per chunk, so the two tiny divisions are amortised over ``chunk``
        elements): per-element division was the single hottest op of the
        physical-wire round on a host backend.  The reciprocal is computed
        from the canonical wire scale — every encoder (in-graph, shard_map,
        Pallas kernels) derives the same ``1/s_c`` bitwise, which is what
        keeps their codes identical."""
        scale = self._chunk_scales(x3)
        q = jnp.clip(jnp.floor(x3 * (1.0 / scale)[..., None] + dither),
                     -self.qmax, self.qmax).astype(jnp.int8)
        return q, scale

    def decompress(self, comp, d):
        scale = self._per_elem(comp.scale, d)
        return comp.data[..., :d].astype(jnp.float32) * scale

    # -- shard-shaped wire codec (the physical-wire gossip byte layout) ------
    def encode_block(self, x: jax.Array, dither) -> Tuple[jax.Array,
                                                          jax.Array]:
        """Encode a block (last axis = the flattened slice a device ships)
        into its ON-WIRE representation: ``(codes, scales)`` where ``codes``
        is int8 — for ``bits=4``, two codes packed per byte
        (``pack_int4``) — and ``scales`` one f32 per chunk.  A thin wrapper
        over ``compress``, so the wire format and the in-graph simulation
        are ONE numerics definition: under the same dither,
        ``decode_block(*encode_block(x, u))`` is bitwise
        ``decompress(compress(x, dither=u))``.

        Zero padding is scale-neutral by construction: ``|0|`` never raises
        a chunk's absmax, an all-pad chunk gets scale 1, and a pad element
        quantizes to code ``floor(0 + u) = 0`` for every dither ``u < 1`` —
        so zero-padded tails decode to exact zeros and cannot perturb the
        real data's quantization grid (asserted in ``tests/test_wire.py``).
        """
        comp = self.compress(x, dither=dither)
        codes = pack_int4(comp.data) if self.bits == 4 else comp.data
        return codes, comp.scale

    def decode_block(self, codes: jax.Array, scales: jax.Array,
                     length: int) -> jax.Array:
        """Invert ``encode_block``: unpack (int4) and dequantize to f32."""
        q = unpack_int4(codes, length) if self.bits == 4 else codes
        return self.decompress(Compressed(data=q, scale=scales), length)

    def code_chunks(self, codes: jax.Array, length: int) -> jax.Array:
        """Unpacked integer codes as f32 in per-chunk layout ``(..., nc,
        chunk)`` — the fused-decode surface of the bucketed wire.  Gossip
        consumers fold the per-chunk scales (and the mixing-row weight)
        into one broadcast factor per chunk, so dequantize never
        materialises a full-width per-element scale vector:
        ``(code_chunks(c, d) * scales[..., None]).reshape(..., d)`` is
        bitwise ``decode_block(c, scales, d)`` — the same scale-times-code
        products in the same order, only the broadcast shape differs.
        Requires ``length`` to be a chunk multiple (bucket blocks are, by
        ``bucket_block`` construction)."""
        if length % self.chunk:
            raise ValueError(
                f"code_chunks needs a chunk-multiple length, got {length} "
                f"with chunk={self.chunk}")
        q = unpack_int4(codes, length) if self.bits == 4 else codes
        return q.astype(jnp.float32).reshape(
            q.shape[:-1] + (length // self.chunk, self.chunk))

    def wire_block_bytes(self, length: int) -> Tuple[int, int]:
        """(code bytes, scale bytes) of one encoded ``length``-element
        block — the exact operand sizes of the physical-wire collective,
        cross-checked against compiled-HLO shapes in ``tests/test_wire.py``.
        """
        nc = -(-length // self.chunk)
        code_bytes = -(-length // 2) if self.bits == 4 else length
        return code_bytes, 4 * nc


@dataclasses.dataclass(frozen=True)
class TopKCompressor(Compressor):
    """Per-row magnitude top-k sparsification: each server keeps its
    ``k = max(1, round(ratio * d))`` largest-|.| coordinates.  Biased (EF
    recommended); both values AND int32 indices cross the wire — contrast
    ``RandomKCompressor``, whose shared coordinates cost zero index bytes."""

    ratio: float = 0.05

    name = "top_k"

    def __post_init__(self):
        if not 0.0 < self.ratio <= 1.0:
            raise ValueError(f"top_k ratio must be in (0, 1], got {self.ratio}")

    def k_for(self, d: int) -> int:
        return max(1, min(d, int(round(self.ratio * d))))

    def compress(self, x, key=None):
        del key
        k = self.k_for(x.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(x), k)
        vals = jnp.take_along_axis(x, idx, axis=1)
        return Compressed(data=vals, idx=idx.astype(jnp.int32))

    def decompress(self, comp, d):
        m = comp.data.shape[0]
        out = jnp.zeros((m, d), jnp.float32)
        rows = jnp.arange(m)[:, None]
        return out.at[rows, comp.idx].set(comp.data.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class RandomKCompressor(Compressor):
    """Seed-coordinated random-k sparsification: ONE random coordinate set
    per call (from the shared rng key) used by every server, so receivers
    regenerate the indices from the seed and only the values cross the wire.
    Biased per call (no d/k rescale — error feedback absorbs it, and the
    unscaled form keeps values bounded, which quantizer-style downstream
    stages prefer).

    Coordinates come from the counter-based ``keyed_index_sample`` —
    O(k) work and memory (a keyed Feistel bijection over the counters)
    instead of the O(D log D) full ``jax.random.permutation`` sort, which
    is what makes seed-regeneration viable at LM scale on the receivers."""

    ratio: float = 0.05

    name = "random_k"
    idx_on_wire = False

    def __post_init__(self):
        if not 0.0 < self.ratio <= 1.0:
            raise ValueError(
                f"random_k ratio must be in (0, 1], got {self.ratio}")

    def k_for(self, d: int) -> int:
        return max(1, min(d, int(round(self.ratio * d))))

    def compress(self, x, key=None):
        if key is None:
            raise ValueError("random_k needs the shared rng key (the "
                             "coordinate set IS the seed)")
        d = x.shape[1]
        idx = keyed_index_sample(key, d, self.k_for(d))
        return Compressed(data=x[:, idx], idx=idx)

    def decompress(self, comp, d):
        m = comp.data.shape[0]
        out = jnp.zeros((m, d), jnp.float32)
        return out.at[:, comp.idx].set(comp.data.astype(jnp.float32))


def make_compressor(spec: str) -> Compressor:
    """Parse a compression spec string (see module docstring grammar).

    ``"none"`` deliberately raises: it means the compression layer is OFF
    (no wrapper is built at all), not that an identity compressor runs —
    callers guard on it before resolving a compressor."""
    s = spec.strip()
    if s in ("none", ""):
        raise ValueError("compression='none' disables the layer; there is "
                         "no compressor to build")
    head, _, arg = s.partition(":")
    if head in ("int8", "int4"):
        chunk = int(arg) if arg else 256
        return StochasticQuantizer(bits=int(head[3:]), chunk=chunk)
    if head in ("top_k", "random_k"):
        if not arg:
            raise ValueError(f"{head} needs a keep ratio, e.g. '{head}:0.05'")
        cls = TopKCompressor if head == "top_k" else RandomKCompressor
        return cls(ratio=float(arg))
    if head == "identity":
        return IdentityCompressor()
    raise ValueError(f"unknown compression spec {spec!r}; expected none | "
                     f"int8[:chunk] | int4[:chunk] | top_k:ratio | "
                     f"random_k:ratio")


# ---------------------------------------------------------------------------
# pytree wrappers over the (M, d) row layout
# ---------------------------------------------------------------------------


def roundtrip_tree(compressor: Compressor, tree: Any,
                   key: Optional[jax.Array] = None,
                   flat_sharding=None) -> Any:
    """Wire-simulate a server tree (leaves ``(M, *w)``): each leaf is
    flattened to ``(M, d)`` rows, compressed and decompressed per leaf (the
    rng key folded per leaf index so dither/coordinates differ across
    leaves), and reshaped back in the leaf's dtype.

    Shape-preserving compressors (identity, the quantizers) skip the
    flatten and round-trip each leaf in its natural ``(M, *w)`` layout —
    elementwise per-shard work under pjit.  Flatten-based compressors
    (top-k / random-k need the whole row to rank coordinates) reshape to
    ``(M, d)``; ``flat_sharding`` is an optional NamedSharding for that
    view (e.g. ``P("server", ("replica", "model"))`` — the same constraint
    ``consensus.gossip_scan_blocked`` uses): without it the partitioner
    replicates the merged weight axes, which at LM scale is an OOM."""
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i) if key is not None else None
        if compressor.shape_preserving:
            out.append(compressor.roundtrip(leaf, k))
            continue
        x = leaf.reshape(leaf.shape[0], -1)
        if flat_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, flat_sharding)
        y = compressor.roundtrip(x, k)
        if flat_sharding is not None:
            y = jax.lax.with_sharding_constraint(y, flat_sharding)
        out.append(y.reshape(leaf.shape))
    return jax.tree.unflatten(treedef, out)


def tree_message_elems(tree: Any) -> int:
    """Elements of ONE server's message (the per-row model size): the sum
    over leaves of everything behind the leading server axis."""
    return sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(tree))


def tree_wire_bytes_per_server(compressor: Compressor, tree: Any) -> int:
    """On-wire bytes of one server's full compressed message: the per-leaf
    ``wire_bytes_per_leaf`` summed over leaves (chunking/top-k rounding
    apply per leaf — and per leaf ROW for shape-preserving compressors —
    exactly as the in-graph wire simulation does)."""
    return sum(compressor.wire_bytes_per_leaf(l.shape)
               for l in jax.tree.leaves(tree))
