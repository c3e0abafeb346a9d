"""Server-side consensus updates (Eq. 5/7) as JAX ops.

The parameter pytree during DFL training carries a leading *server* axis of
size M (possibly preceded by a client axis — see ``dfl.py``).  A consensus
round is ``W <- A W`` applied leaf-wise:

    new_w[i] = a_ii * w[i] + sum_{j in N_i} a_ij * w[j]      (Eq. 5)

Execution strategies, all bit-identical in math:

* ``gossip_scan``    — the *faithful* schedule: T_S sequential rounds
                       (lax.fori_loop), each an einsum over the server axis.
                       Under pjit with the server axis sharded this lowers to
                       one all-gather (or neighbour exchanges) per round —
                       exactly the paper's per-iteration message pattern.
* ``gossip_scan_blocked`` — the same schedule streamed over fixed-size
                       parameter blocks (deterministic working set).
* ``gossip_collapsed`` — beyond-paper: precompute A_eff = A^{T_S} on the host
                       (M x M, trivial) and apply it in ONE round.  Output is
                       mathematically identical; collective rounds drop T_S x.
* ``gossip_chebyshev`` — beyond-paper: degree-k Chebyshev polynomial in A
                       reaching the same contraction with ~sqrt fewer rounds;
                       useful when rounds must stay iterative (fault probing
                       between rounds).
* ``make_gossip_shard_map`` — the production path: explicit blocked
                       all-gathers under shard_map, taking the mixing matrix
                       as a *traced operand* so one compiled program serves
                       every per-epoch graph.

``ring_gossip_shard_map`` additionally shows the TPU-native neighbour
exchange (lax.ppermute) for ring graphs under shard_map.

**Consensus backends.**  ``ConsensusBackend`` wraps each strategy behind one
interface consumed by ``dfl.build_dfl_epoch_step``:

    backend.mix(server_tree, a_p)            T_S rounds of W <- A W
    backend.mix_push_sum(state, a_p)         the ratio-consensus variant

``a_p`` is an optional traced per-epoch ``(M, M)`` mixing matrix (dynamic
federation); ``None`` selects the static topology matrix the backend was
built with.  ``make_backend`` maps a ``DFLConfig.consensus_mode`` string to
a backend; ``ShardMapBackend`` is mesh-aware and therefore constructed by
the launcher (``launch.sharding.fl_consensus_backend``) and injected via
``DFLConfig.consensus_backend``.

**Compressed consensus.**  ``CompressedBackend`` wraps any backend with the
``repro.comm`` wire simulation — lossy compression (quantization /
sparsification) of each server's outgoing message plus optional error
feedback — so every execution strategy composes with every compressor; the
host-side byte ledger is ``comm.accounting.BytesTracker``.

**Robust (Byzantine-screening) gossip.**  ``trimmed_mean_mix`` /
``median_mix`` / ``clipped_mix`` replace the weighted round ``W <- A W``
with neighbor-screening aggregation rules that tolerate adversarial
servers: coordinatewise trimmed mean (discard the ``f`` largest and ``f``
smallest supported values per coordinate, mean the rest — breakdown point
``2f < c`` with ``c`` the supported neighborhood size, self included),
coordinatewise median, and self-centered clipping (neighbor innovations
norm-clipped against the receiver's own model, expressed as an effective
per-round mixing matrix ``clip_weights`` so the round stays the einsum
``mix_pytree``).  All three are pure traced functions of ``(A_p, tree)``,
so they compose with the per-epoch matrices of dynamic federation;
``TrimmedMeanBackend`` / ``MedianBackend`` / ``ClippedGossipBackend``
register them through ``make_backend`` (``"trimmed_mean[:f]"`` /
``"median"`` / ``"clipped[:mult]"``).  Screening discards the Eq.-6
weights (a trimmed/median round is an unweighted mean over the surviving
values), so none has a push-sum analogue (``supports_directed=False``) and
none can run on the quantized physical wire (the screen must see every
neighbor's plaintext values) — both combinations refuse loudly.

**Physical wire.**  ``CompressedBackend(wire="physical")`` makes the
compressed format the format that actually crosses the interconnect:
every gossip round quantizes the local block to int8 / packed-int4 codes +
per-chunk scales *before* the collective, gathers the code buffer, and
dequantizes-and-mixes after — ``make_gossip_shard_map`` /
``make_ring_gossip`` with ``codec=`` are the collective programs,
``gossip_scan_wire`` the in-graph reference twin (bit-identical under the
shared dither convention ``comm.compressors.wire_dither``).  The wire
model changes with it: the simulated wire quantizes ONCE per period
(payload flooding — gossip is linear in the payloads), the physical wire
encodes at every hop.  What each hop encodes is the DELTA against the
receivers' shared decoded reference (innovation coding, the recursion in
``gossip_scan_wire``): the delta's magnitude contracts with consensus, so
per-hop quantization noise vanishes where the tolerance bites — raw-state
re-quantization instead floors the disagreement at the int8 grid (
measured ~1e-2 on the fig-3 task, 10x outside the paper's tolerance).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import compressors as _compressors
from repro.comm import error_feedback as _ef
from repro.core.topology import lambda_2 as tp_lambda_2

def _mix_leaf(a: jax.Array, leaf: jax.Array) -> jax.Array:
    """new[i] = sum_j a[i, j] * leaf[j, ...] over the leading server axis.

    Contracts in the LEAF's dtype: under pjit the server axis is sharded, so
    this lowers to an all-gather of (M x shard) — doing it in bf16 moves and
    holds half the bytes of the promoted-f32 contraction (A itself is tiny
    and cast down; one bf16 rounding per round matches what real multi-host
    gossip over bf16 wires would do)."""
    return jnp.tensordot(a.astype(leaf.dtype), leaf, axes=([1], [0]))


def mix_pytree(a: jax.Array, tree: Any) -> Any:
    """One consensus round ``W <- A W`` applied to every leaf."""
    return jax.tree.map(functools.partial(_mix_leaf, a), tree)


def gossip_scan(a: jax.Array, tree: Any, t_server: int) -> Any:
    """Faithful T_S-round consensus (Alg. 1 server loop).

    One fori_loop PER LEAF (leaves gossip independently, so round-leaf
    reordering is exact): XLA schedules the per-leaf while-loops one after
    another, keeping only one leaf's (M x shard) all-gather live at a time
    instead of the whole model's."""
    if t_server == 0:
        return tree

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, t_server, lambda _, w: _mix_leaf(a, w), leaf)

    return jax.tree.map(leaf_loop, tree)


def gossip_scan_stale(a: jax.Array, tree: Any, t_server: int,
                      staleness: int) -> Any:
    """Bounded-staleness consensus: round ``t`` mixes the ``s``-round-old
    iterate, ``W_(t+1) = A W_(t-s)``, freezing ``W_(t+1) = W_t`` while no
    delayed iterate exists yet (``t < s``) — the overlap model where a
    server consumes neighbor state that left ``s`` rounds ago while its own
    round-``t`` send is still in flight.  In exact arithmetic the period
    composes to ``A^(T_S // (s+1))``: of every ``s+1`` rounds only one
    advances the chain (the rest re-mix the same delayed iterate), which is
    the staleness-augmented contraction ``schedule.SigmaTracker`` monitors.
    ``staleness=0`` IS ``gossip_scan`` — the call branches to the literally
    unchanged synchronous body, so the degeneration is bitwise."""
    if staleness <= 0:
        return gossip_scan(a, tree, t_server)
    if t_server == 0:
        return tree

    def leaf_loop(leaf):
        # carry the last s+1 iterates: hist[u] = W_(t-s+u) at the start of
        # round t (clamped to W_0 before round s)
        def one_round(t, hist):
            new = jax.lax.cond(t >= staleness,
                               lambda: _mix_leaf(a, hist[0]),
                               lambda: hist[-1])
            return hist[1:] + (new,)

        hist = jax.lax.fori_loop(0, t_server, one_round,
                                 (leaf,) * (staleness + 1))
        return hist[-1]

    return jax.tree.map(leaf_loop, tree)


def gossip_scan_tv(a_rounds: jax.Array, tree: Any) -> Any:
    """Time-varying consensus: round t applies ``a_rounds[t]``.

    ``a_rounds`` layout — a traced ``(T_S, M, M)`` stack with one mixing
    matrix PER ROUND, not per epoch: ``a_rounds[t]`` is the operator of
    consensus round ``t`` within a single consensus period, so the leading
    axis is the round index and its length is this period's T_S.  This is
    the fully general form of Eq. 5 where the server graph may change
    BETWEEN ROUNDS (link failures mid-consensus, straggler reweighting).
    Contrast ``schedule.TopologySchedule``, which emits ONE ``(M, M)``
    matrix per epoch ``A_p``; to feed such a per-epoch matrix here,
    broadcast it to ``(T_S, M, M)`` — a stack of T_S identical matrices is
    exactly ``gossip_scan(a, tree, T_S)`` (same per-round operator, same
    ordering).  Each round preserves the server mean when every
    ``a_rounds[t]`` is doubly stochastic, and the ordered product of the
    stack governs the contraction (``topology.sigma_product`` with t_s=1
    per entry)."""
    if a_rounds.shape[0] == 0:
        return tree

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, a_rounds.shape[0],
            lambda i, w: _mix_leaf(a_rounds[i], w), leaf)

    return jax.tree.map(leaf_loop, tree)


def gossip_scan_blocked(a: jax.Array, tree: Any, t_server: int,
                        block: int = 4_194_304,
                        flat_sharding=None) -> Any:
    """Faithful T_S-round gossip, streamed over fixed-size parameter blocks.

    Blocks gossip independently, so iterating (block-major, round-minor)
    instead of (round-major, leaf-minor) is *exactly* the same operator —
    but the live working set per step is one (M, block) gather instead of a
    full parameter leaf per server (which at 27B+ scales is multi-GB per
    in-flight leaf; XLA-CPU additionally upcasts bf16 contractions to f32,
    doubling it).  Used by the epoch step whenever the model is large;
    ``gossip_scan`` remains the reference for tests and small models.
    """
    if t_server == 0:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    dtype = leaves[0].dtype
    sizes = [l[0].size for l in leaves]
    flat = jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)
    d = flat.shape[1]
    nb = max(1, -(-d // block))
    pad = nb * block - d
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    if flat_sharding is not None:
        # keep the flattened model sharded over the intra-client axes —
        # without this the concat of heterogeneously-sharded leaves makes
        # the partitioner replicate the whole model per device.
        flat = jax.lax.with_sharding_constraint(flat, flat_sharding)
    blocks = jnp.moveaxis(flat.reshape(m, nb, block), 1, 0)   # (nb, M, blk)
    a_cast = a.astype(dtype)

    def per_block(_, blk):
        out = jax.lax.fori_loop(
            0, t_server, lambda _i, w: jnp.tensordot(a_cast, w,
                                                     axes=([1], [0])), blk)
        return None, out

    _, mixed = jax.lax.scan(per_block, None, blocks)
    flat = jnp.moveaxis(mixed, 0, 1).reshape(m, nb * block)[:, :d]
    if flat_sharding is not None:
        flat = jax.lax.with_sharding_constraint(flat, flat_sharding)
    out, off = [], 0
    new_leaves = []
    for leaf, size in zip(leaves, sizes):
        new_leaves.append(flat[:, off:off + size].reshape(leaf.shape))
        off += size
    return jax.tree.unflatten(treedef, new_leaves)


# ---------------------------------------------------------------------------
# quantized-wire gossip: the per-round physical wire model, in-graph
# ---------------------------------------------------------------------------

DEFAULT_GOSSIP_BLOCK = 4_194_304


def _wire_mix_rows(a32: jax.Array, g: jax.Array) -> jax.Array:
    """``out[i] = sum_j a32[i, j] * g[j]`` accumulated LEFT TO RIGHT in f32
    — the exact multiply-add order of the shard_map round body (one term
    per server, f32 accumulator), so the in-graph wire simulation is
    bit-identical to the physical collective path, not merely allclose."""
    m = g.shape[0]
    ones = (1,) * (g.ndim - 1)
    acc = a32[:, 0].reshape((-1,) + ones) * g[0]
    for j in range(1, m):
        acc = acc + a32[:, j].reshape((-1,) + ones) * g[j]
    return acc


def _wire_dither_rows(codec, key, m: int, nb: int, blk: int, *, leaf,
                      rnd, block_ids=None):
    """(m, nb, blk) dither for one round of one leaf under the shared
    convention, or the deterministic 0.5 when no key is given."""
    del codec
    if key is None:
        return 0.5
    blocks = jnp.arange(nb) if block_ids is None else block_ids
    return jax.vmap(lambda s: jax.vmap(
        lambda b: _compressors.wire_dither(
            key, (blk,), leaf=leaf, rnd=rnd, server=s, block=b))(
                blocks))(jnp.arange(m))


def gossip_scan_wire(a: jax.Array, tree: Any, t_server: int, codec,
                     key: Optional[jax.Array] = None, *,
                     block: int = DEFAULT_GOSSIP_BLOCK,
                     block_major: bool = False) -> Any:
    """Per-round quantized-WIRE gossip, in-graph: the reference numerics of
    the physical collective paths.  Every round, every server encodes the
    DELTA between its iterate and the receivers' shared decoded estimate of
    it (innovation coding) to wire codes (``codec.encode_block`` — int8 /
    packed int4 + per-chunk scales) with the shared dither convention
    (``comm.compressors.wire_dither``); every receiver accumulates the
    decoded deltas into its reference copy of every sender and mixes those
    references:

        delta_t = W_t - R_{t-1}          (encoded; crosses the wire)
        R_t     = R_{t-1} + D(C(delta_t))
        W_{t+1} = A · R_t                (R_0 = 0)

    Why deltas and not the raw state: re-quantizing the full iterate at
    every hop injects absmax-scaled noise 25x per period — measured on the
    fig-3 task, stochastic rounding random-walks at a ~1e-2 disagreement
    floor and round-to-nearest locks a dead-zone bias of ~3 grid steps
    (err 0.12), both far outside the paper's tolerance.  The delta's
    absmax CONTRACTS with consensus, so the per-hop quantization noise
    vanishes exactly where the tolerance bites; round 0 (R_0 = 0) still
    ships the full state, and that transmission is what period-level error
    feedback tracks (``wire_roundtrip_tree``).  Same codes + scales per
    round on the wire — the byte ledger is unchanged.

    LEGACY per-leaf layout (PR 5): every leaf is blocked and encoded
    independently, with per-(leaf, round, server, block) dither, so a
    realistic pytree pays two collectives per block per leaf per round.
    The shipping paths moved to the BUCKETED layout
    (``gossip_scan_wire_bucketed`` — one flattened code buffer for the
    whole tree, one collective pair per round); this function stays as the
    per-leaf reference oracle of ``kernels.consensus_mix.
    quantized_gossip_round_2d`` and the layout the per-leaf byte counter
    (``comm.accounting.physical_leaf_bytes``) describes.
    ``block_major=True`` streams (block-major, round-minor) like
    ``gossip_scan_blocked`` — the identical operator bit for bit, since
    blocks gossip and encode independently.

    Zero padding of the ragged tail block is harmless by construction: a
    zero element never raises its chunk's absmax and quantizes to code
    ``floor(0 + u) = 0`` for every dither ``u < 1``, so pad deltas stay
    exactly zero, references stay zero, and pads mix to zero (see
    ``StochasticQuantizer.encode_block``)."""
    if t_server == 0:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    a32 = a.astype(jnp.float32)
    new_leaves = []
    for li, leaf in enumerate(leaves):
        dtype = leaf.dtype
        flat = leaf.reshape(m, -1)
        d = flat.shape[1]
        blk = min(block, d)
        nb = -(-d // blk)
        if nb * blk != d:
            flat = jnp.pad(flat, ((0, 0), (0, nb * blk - d)))
        rows = flat.reshape(m, nb, blk)

        def one_round(t, carry, li=li, blk=blk, nb=nb, dtype=dtype):
            rows, ref = carry                        # (m, nb, blk) each
            delta = rows.astype(jnp.float32) - ref
            dither = _wire_dither_rows(codec, key, m, nb, blk, leaf=li,
                                       rnd=t)
            codes, scales = codec.encode_block(delta, dither)
            ref = ref + codec.decode_block(codes, scales, blk)
            return _wire_mix_rows(a32, ref).astype(dtype), ref

        if block_major:
            def per_block(_, xs, li=li, blk=blk, dtype=dtype):
                rows_b, b = xs                       # (m, blk), block index

                def rnd_fn(t, carry):
                    w, ref = carry
                    delta = w.astype(jnp.float32) - ref
                    dither = _wire_dither_rows(
                        codec, key, m, 1, blk, leaf=li, rnd=t,
                        block_ids=b[None])
                    codes, scales = codec.encode_block(
                        delta[:, None, :], dither)
                    ref = ref + codec.decode_block(codes, scales,
                                                   blk)[:, 0]
                    return _wire_mix_rows(a32, ref).astype(dtype), ref

                out, _ = jax.lax.fori_loop(
                    0, t_server, rnd_fn,
                    (rows_b, jnp.zeros_like(rows_b, jnp.float32)))
                return None, out

            _, mixed = jax.lax.scan(
                per_block, None, (jnp.moveaxis(rows, 1, 0), jnp.arange(nb)))
            rows = jnp.moveaxis(mixed, 0, 1)
        else:
            rows, _ = jax.lax.fori_loop(
                0, t_server, one_round,
                (rows, jnp.zeros_like(rows, jnp.float32)))
        flat = rows.reshape(m, nb * blk)[:, :d]
        new_leaves.append(flat.reshape(leaf.shape))
    return jax.tree.unflatten(treedef, new_leaves)


def _bucket_flat(leaves) -> jax.Array:
    """(m, d_tot) bucket view of a server tree's leaves, every leaf cast to
    the FIRST leaf's dtype (the bucket's single wire dtype) and flattened
    row-wise in leaf order."""
    m = leaves[0].shape[0]
    dtype = leaves[0].dtype
    return jnp.concatenate(
        [leaf.astype(dtype).reshape(m, -1) for leaf in leaves], axis=1)


def _bucket_split(flat: jax.Array, leaves, treedef) -> Any:
    """Invert ``_bucket_flat``: slice the (m, >=d_tot) bucket back into the
    original leaf shapes/dtypes (any pad tail is dropped)."""
    out, off = [], 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape[1:], dtype=np.int64))
        out.append(flat[:, off:off + size].reshape(leaf.shape)
                   .astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def _bucket_dither_rows(codec, key, m: int, d_pad: int, *, rnd):
    """(m, d_pad) dither for one round of the BUCKETED wire — one
    ``wire_dither`` draw per server over the whole padded bucket (leaf and
    block coordinates pinned to 0: the bucket is one logical block of one
    logical leaf), or the deterministic 0.5 without a key."""
    del codec
    if key is None:
        return 0.5
    return jax.vmap(lambda s: _compressors.wire_dither(
        key, (d_pad,), leaf=0, rnd=rnd, server=s, block=0))(jnp.arange(m))


def gossip_scan_wire_bucketed(a: jax.Array, tree: Any, t_server: int,
                              codec, key: Optional[jax.Array] = None, *,
                              block: int = DEFAULT_GOSSIP_BLOCK,
                              staleness: int = 0) -> Any:
    """BUCKETED quantized-wire gossip, in-graph: the reference numerics of
    the physical collective paths since PR 6.  Same innovation recursion as
    ``gossip_scan_wire`` (delta-coded against the receivers' shared decoded
    reference — see there for why deltas and not raw state), but the whole
    pytree is flattened into ONE zero-padded code buffer per server
    (``comm.compressors.bucket_block`` layout), so every round ships
    exactly one code buffer + one scale buffer per server — what the
    shard_map program realises as one s8 all-gather + one f32 all-gather.

    The ``(M, d)`` reference matrix of the per-leaf form is factored into a
    per-server band: server ``i`` carries only its OWN reference row
    ``r_i`` and a running accumulator ``acc_i`` of the mixed references,
    using ``R_t = R_{t-1} + Δ_t`` to fold the mix incrementally::

        delta_t = W_t - r_(t-1)                (encoded; crosses the wire)
        r_t     = r_(t-1) + D(C(delta_t))_i    (own decoded innovation)
        acc_t   = acc_(t-1) + sum_j a[i,j] * D(C(delta_t))_j
        W_(t+1) = acc_t                        (acc_0-pre = 0, r_0-pre = 0)

    which telescopes to ``acc_t = (A · R_t)_i`` exactly — same fixed point,
    same contraction, but the per-device live state drops from ``(M+1)``
    rows to 3 (iterate, own reference, accumulator): the 926→~600 MB RSS
    fix of the shard_map wire.  The sum over ``j`` accumulates LEFT TO
    RIGHT in f32, one term per server, matching the shard_map round body
    term for term, so this simulation is bit-identical to the physical
    program under a shared key (asserted for int8 AND packed int4 in
    ``tests/test_wire.py``).  Mixed-dtype trees ride the wire in the FIRST
    leaf's dtype (one bucket, one wire dtype) and are cast back on exit.

    Zero padding of the bucket tail is harmless for the same reason as in
    ``gossip_scan_wire``: pad deltas quantize to zero codes and never
    perturb a real chunk's absmax scale (pads occupy whole chunks — the
    bucket block is a chunk multiple).

    **Bounded staleness** (``staleness=s > 0``): round ``t`` consumes the
    gathered code+scale buffers of round ``t - s`` while its own round-``t``
    encode is issued — the carry grows a ring of the last ``s`` in-flight
    gathered buffers (the software-pipelined / double-buffered form: the
    collective that ships round ``t`` overlaps the decode+mix work of round
    ``t - s``).  The sender encodes against its up-to-date SENT reference
    (own decodes fold in at production time), so innovations never
    double-ship; receivers need no per-neighbor reference at all — the
    accumulator telescopes over whatever decoded deltas have arrived, which
    is exactly why delta codes tolerate lateness: the sum over rounds
    commutes.  The iterate freezes until the first delayed buffer lands
    (``t < s``) and the last ``s`` rounds' codes are never consumed
    (bounded staleness discards the tail), composing to ``A^(T_S//(s+1))``
    in exact arithmetic.  ``staleness=0`` takes the literally unchanged
    synchronous body above — bitwise degeneration, the PR-5/6 oracle
    pattern."""
    if t_server == 0:
        return tree
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    dtype = leaves[0].dtype
    with jax.named_scope("wire_pack"):
        flat = _bucket_flat(leaves)
        d_tot = flat.shape[1]
        blk, nb = _compressors.bucket_block(d_tot, block, codec.chunk)
        d_pad = nb * blk
        if d_pad != d_tot:
            flat = jnp.pad(flat, ((0, 0), (0, d_pad - d_tot)))
    a32 = a.astype(jnp.float32)
    zeros = jnp.zeros((m, d_pad), jnp.float32)

    if staleness == 0:
        def one_round(t, carry):
            w, ref, acc = carry        # (m, d_pad): wire dtype, f32, f32
            with jax.named_scope("wire_encode"):
                delta = w.astype(jnp.float32) - ref
                dither = _bucket_dither_rows(codec, key, m, d_pad, rnd=t)
                codes, scales = codec.encode_block(delta, dither)
            # fused dequantize-and-mix, folded exactly like the shard_map
            # round body: per-chunk scales (and the mixing weight) broadcast
            # onto raw f32 codes, one server term at a time — the same
            # scale-times-code and weight-times-scale products in the same
            # order, which is what keeps the simulation bit-identical to the
            # physical program
            with jax.named_scope("wire_decode_mix"):
                c3 = codec.code_chunks(codes, d_pad)   # (m, nc, chunk)
                ref = ref + (c3 * scales[..., None]).reshape(m, d_pad)
                ws = a32[:, :, None] * scales          # (m, m, nc): ws[i, j]
                acc3 = acc.reshape(m, -1, codec.chunk)
                for j in range(m):
                    acc3 = acc3 + ws[:, j, :, None] * c3[j]
                acc = acc3.reshape(m, d_pad)
                return acc.astype(dtype), ref, acc

        out, _, _ = jax.lax.fori_loop(0, t_server, one_round,
                                      (flat, zeros, zeros))
        with jax.named_scope("wire_pack"):
            return _bucket_split(out, leaves, treedef)

    # ring of the last `staleness` in-flight (codes, scales) buffers; zero
    # codes + unit scales decode to nothing, so the pre-fill consumed
    # before round s is inert
    code_abs = jax.eval_shape(
        lambda x: codec.encode_block(x, 0.5)[0],
        jax.ShapeDtypeStruct((m, d_pad), jnp.float32))
    ring_c = jnp.zeros((staleness,) + code_abs.shape, code_abs.dtype)
    ring_s = jnp.ones((staleness, m, d_pad // codec.chunk), jnp.float32)

    def one_round_stale(t, carry):
        w, sref, acc, rc, rs = carry
        # produce round t: encode against the SENT reference, fold the own
        # decode in immediately (the next innovation must not re-ship it)
        with jax.named_scope("wire_encode"):
            delta = w.astype(jnp.float32) - sref
            dither = _bucket_dither_rows(codec, key, m, d_pad, rnd=t)
            codes, scales = codec.encode_block(delta, dither)
        with jax.named_scope("wire_decode_mix"):
            own3 = codec.code_chunks(codes, d_pad)     # (m, nc, chunk)
            sref = sref + (own3 * scales[..., None]).reshape(m, d_pad)
            # consume round t - s: the oldest gathered buffer in the ring
            old_c, old_s = rc[0], rs[0]
            c3 = codec.code_chunks(old_c, d_pad)
            ws = a32[:, :, None] * old_s               # (m, m, nc): ws[i, j]
            acc3 = acc.reshape(m, -1, codec.chunk)
            for j in range(m):
                acc3 = acc3 + ws[:, j, :, None] * c3[j]
            acc = acc3.reshape(m, d_pad)
            # the iterate advances only once a delayed buffer has landed
            w = jnp.where(t >= staleness, acc.astype(dtype), w)
        with jax.named_scope("wire_gather"):
            rc = jnp.concatenate([rc[1:], codes[None]], axis=0)
            rs = jnp.concatenate([rs[1:], scales[None]], axis=0)
        return w, sref, acc, rc, rs

    out, _, _, _, _ = jax.lax.fori_loop(
        0, t_server, one_round_stale, (flat, zeros, zeros, ring_c, ring_s))
    with jax.named_scope("wire_pack"):
        return _bucket_split(out, leaves, treedef)


def wire_decode_mix(ref: jax.Array, acc: jax.Array, codes: jax.Array,
                    scales: jax.Array, g_codes: jax.Array,
                    g_scales: jax.Array, row: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Decode and mix one round of the bucketed wire on one device, in the
    bucket's chunk view: ``ref``/``acc`` ``(nc, chunk)`` f32, this device's
    own UNPACKED int8 ``codes`` ``(nc, chunk)`` and ``scales`` ``(nc,)``,
    every server's ``g_codes`` ``(M, nc, chunk)`` int8 and ``g_scales``
    ``(M, nc)``, and this device's mixing ``row`` ``(M,)``.  Returns
    ``(ref', acc')``::

        ref' = ref + code * scale                       (own row, local)
        acc' = acc + sum_j (row[j] * g_scales[j]) * g_codes[j]

    Each chunk's scale and mixing weight fold into ONE factor per chunk
    that broadcasts along the chunk, and the codes convert to f32 inside
    the same elementwise pass, so no f32 copy of the gathered codes and
    no per-element scale array is ever materialised.  The sum runs one
    server at a time, left to right, with all M terms (the row is traced:
    a zero weight still adds its term) — the products and order of
    ``gossip_scan_wire_bucketed``, which keeps the physical program
    bit-identical to it."""
    ref = ref + codes.astype(jnp.float32) * scales[:, None]
    ws = row[:, None] * g_scales                        # (M, nc) folded
    for j in range(g_codes.shape[0]):
        acc = acc + ws[j][:, None] * g_codes[j].astype(jnp.float32)
    return ref, acc


def bucketed_roundtrip_tree(codec, tree: Any,
                            key: Optional[jax.Array] = None, *,
                            block: int = DEFAULT_GOSSIP_BLOCK,
                            rnd: int = 0) -> Any:
    """One wire round-trip of a server tree in the BUCKETED physical byte
    layout: the whole pytree flattened (first leaf's dtype), zero-padded to
    the ``comm.compressors.bucket_block`` grid, and encoded/decoded with
    the shared round-``rnd`` bucket dither — exactly what round ``rnd`` of
    the bucketed physical gossip ships of each server's OWN model.  The
    error-feedback hook of the bucketed wire (successor of the per-leaf
    ``wire_roundtrip_tree``): bucket chunk boundaries cross leaf
    boundaries, so the per-leaf round-trip no longer reproduces the
    transmission."""
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    flat = _bucket_flat(leaves).astype(jnp.float32)
    d_tot = flat.shape[1]
    blk, nb = _compressors.bucket_block(d_tot, block, codec.chunk)
    d_pad = nb * blk
    if d_pad != d_tot:
        flat = jnp.pad(flat, ((0, 0), (0, d_pad - d_tot)))
    dither = _bucket_dither_rows(codec, key, m, d_pad, rnd=rnd)
    codes, scales = codec.encode_block(flat, dither)
    y = codec.decode_block(codes, scales, d_pad)
    return _bucket_split(y, leaves, treedef)


def wire_roundtrip_tree(codec, tree: Any, key: Optional[jax.Array] = None,
                        *, block: int = DEFAULT_GOSSIP_BLOCK,
                        rnd: int = 0) -> Any:
    """One wire round-trip of a server tree in the LEGACY per-leaf physical
    byte layout: each leaf row flattened, zero-padded to ``block``-element
    blocks, and encoded/decoded with the shared round-``rnd`` dither —
    exactly what round ``rnd`` of the per-leaf physical gossip
    (``gossip_scan_wire``) ships of each server's OWN model.  The shipping
    paths use ``bucketed_roundtrip_tree`` since PR 6; this stays the
    round-0 oracle of the per-leaf reference."""
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    out = []
    for li, leaf in enumerate(leaves):
        flat = leaf.reshape(m, -1)
        d = flat.shape[1]
        blk = min(block, d)
        nb = -(-d // blk)
        if nb * blk != d:
            flat = jnp.pad(flat, ((0, 0), (0, nb * blk - d)))
        rows = flat.reshape(m, nb, blk).astype(jnp.float32)
        dither = _wire_dither_rows(codec, key, m, nb, blk, leaf=li, rnd=rnd)
        codes, scales = codec.encode_block(rows, dither)
        y = codec.decode_block(codes, scales, blk)
        out.append(y.reshape(m, nb * blk)[:, :d].reshape(leaf.shape)
                   .astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# push-sum (ratio) consensus for DIRECTED server graphs
#
# When link failures make the graph directed, no doubly-stochastic matrix
# may exist on its support (Eq. 6 is unsatisfiable): the best a node can do
# locally is split its mass over its out-neighbours — a ROW-stochastic A
# (topology.out_degree_weights).  Naive gossip with such an A converges to
# the Perron-weighted average pi' W (pi the left Perron vector of A), a
# BIASED aggregate.  Push-sum / ratio consensus (Kempe et al. 2003;
# Nedic & Olshevsky 2015) fixes this by mixing a numerator AND a scalar
# weight with the column-stochastic transpose P = A' and reading out the
# ratio:
#
#     num <- P num,   w <- P w,     z_i = num_i / w_i
#
# P column-stochastic preserves both sums (sum num = sum W_0, sum w = M),
# and P^t -> v 1' (sum v = 1), so num -> v * sum(W_0), w -> v * M and every
# ratio z_i -> the exact uniform mean — the skew v cancels.  Operationally
# each round IS the row-stochastic protocol run in push mode: node i sends
# a[i, j]-weighted shares of its (num, w) along its OUT-edges; P = A' is
# just that send pattern written as a matrix acting on the receive side.
# When A is doubly stochastic, P = A' is row-stochastic too, w stays at 1
# identically and push-sum degenerates to plain gossip.
# ---------------------------------------------------------------------------


class PushSumState(NamedTuple):
    """Numerator pytree (leaves ``(M, *w)``) + per-server scalar weight
    ``(M,)``.  Invariants under mixing: weights stay positive and sum to M;
    ``ratio()`` of a freshly-initialised state is the values themselves."""

    values: Any          # numerator pytree, leading server axis M
    weight: jax.Array    # (M,) float, > 0, sum == M

    def ratio(self) -> Any:
        """The unbiased read-out z_i = num_i / w_i, broadcast leaf-wise."""
        return jax.tree.map(
            lambda v: v / self.weight.reshape(
                (-1,) + (1,) * (v.ndim - 1)).astype(v.dtype),
            self.values)


def init_push_sum(tree: Any) -> PushSumState:
    """Start of a consensus period: numerator = the server models, weight =
    1 for every server.  Weights RESET here each period by design: with a
    persistent weight the finite-round ratio is no longer exact on
    consensus states (P^t(c*1)/P^t(1) == c for all t only when num and w
    start aligned), and re-weighting the numerator by a carried weight
    provably re-introduces the Perron bias — see docs/dynamic_federation.md."""
    m = jax.tree.leaves(tree)[0].shape[0]
    return PushSumState(tree, jnp.ones((m,), jnp.float32))


def _push_leaf(p: jax.Array, leaf: jax.Array) -> jax.Array:
    return jnp.tensordot(p.astype(leaf.dtype), leaf, axes=([1], [0]))


def gossip_push_sum(a: jax.Array, state: PushSumState,
                    t_server: int) -> PushSumState:
    """T_S rounds of push-sum over a ROW-stochastic ``a`` (shape (M, M),
    support = directed graph + self-loops, e.g. topology.out_degree_weights).

    Numerator and weight are mixed with the same column-stochastic operator
    ``P = a.T``; they interact only at read-out (``.ratio()``), so each leaf
    loops independently exactly like ``gossip_scan``.  The weight recursion
    is a tiny (M,) matvec and costs nothing next to the parameter leaves."""
    if t_server == 0:
        return state
    p = a.T

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, t_server, lambda _, w: _push_leaf(p, w), leaf)

    values = jax.tree.map(leaf_loop, state.values)
    weight = jax.lax.fori_loop(
        0, t_server, lambda _, w: (p @ w.astype(p.dtype)).astype(w.dtype),
        state.weight)
    return PushSumState(values, weight)


def gossip_push_sum_blocked(a: jax.Array, state: PushSumState,
                            t_server: int, block: int = 4_194_304,
                            flat_sharding=None) -> PushSumState:
    """Blocked push-sum: the ``gossip_scan_blocked`` streaming schedule run
    in ratio-consensus form.  The numerator pytree is streamed through the
    same fixed-``block`` machinery with the column-stochastic operator
    ``P = a.T`` (blocks mix independently, so block-major iteration is the
    identical operator), while the ``(M,)`` weight recursion is a trivial
    matvec outside the stream.  Accepts a traced per-epoch ``a``.

    Functional form of ``BlockedGossipBackend.mix_push_sum`` (which is
    just the generic ``ConsensusBackend.mix_push_sum`` over the blocked
    ``_mix``) — one source of truth for the streaming push-sum logic."""
    if t_server == 0:
        return state
    return BlockedGossipBackend(
        None, t_server, block=block,
        flat_sharding=flat_sharding).mix_push_sum(state, a)


def gossip_push_sum_tv(a_rounds: jax.Array,
                       state: PushSumState) -> PushSumState:
    """Time-varying push-sum: round t mixes with ``a_rounds[t].T``.

    ``a_rounds`` follows the ``gossip_scan_tv`` layout — a traced
    ``(T_S, M, M)`` stack of ROW-stochastic matrices, one per round.  Every
    round preserves sum(num) and sum(w) (each transpose is column
    stochastic), so the ratio read-out stays unbiased under arbitrary
    per-round graph changes as long as the sequence is jointly strongly
    connected."""
    if a_rounds.shape[0] == 0:
        return state

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, a_rounds.shape[0],
            lambda i, w: _push_leaf(a_rounds[i].T, w), leaf)

    values = jax.tree.map(leaf_loop, state.values)
    weight = jax.lax.fori_loop(
        0, a_rounds.shape[0],
        lambda i, w: (a_rounds[i].T @ w.astype(a_rounds.dtype)).astype(w.dtype),
        state.weight)
    return PushSumState(values, weight)


def collapse_mixing(a: np.ndarray, t_server: int) -> np.ndarray:
    """A_eff = A^{T_S} (host-side, float64). Doubly stochastic by closure."""
    return np.linalg.matrix_power(np.asarray(a, dtype=np.float64), t_server)


def gossip_collapsed(a_eff: jax.Array, tree: Any) -> Any:
    """Single-round application of the collapsed operator A^{T_S}."""
    return mix_pytree(a_eff, tree)


# ---------------------------------------------------------------------------
# Chebyshev-accelerated gossip (beyond-paper)
# ---------------------------------------------------------------------------


def chebyshev_coefficients(a: np.ndarray, rounds: int) -> float:
    """Return the contraction sigma achieved by ``rounds`` Chebyshev steps
    (for reporting).  Uses lambda_2 of the symmetric mixing matrix."""
    ev = np.sort(np.abs(np.linalg.eigvalsh(a)))[::-1]
    lam2 = ev[1] if len(ev) > 1 else 0.0
    if lam2 == 0.0:
        return 0.0
    # |T_k(1/lam2)|^{-1} with T_k the Chebyshev polynomial of the first kind
    x = 1.0 / lam2
    return float(1.0 / np.cosh(rounds * np.arccosh(x)))


def gossip_chebyshev(a: jax.Array, tree: Any, rounds: int, lam2) -> Any:
    """Chebyshev semi-iterative consensus:  w_k = 2 c_k/(lam2 c_{k+1}) A w_{k-1}
    - (c_{k-1}/c_{k+1}) w_{k-2}, with c_k = cosh(k acosh(1/lam2)).

    Reaches sigma ~ 2 rho^k (rho = (1-sqrt(1-lam2^2))/lam2) instead of lam2^k:
    ~sqrt(1/(1-lam2)) fewer rounds for the same contraction.  Exactly
    mean-preserving like plain gossip (each update is an affine combination
    of doubly-stochastic operators with coefficients summing to 1).

    ``lam2`` may be a host-side float (static topology) or a TRACED scalar
    — the per-epoch spectral estimate a ``TopologySchedule`` feeds through
    ``schedule.EpochSchedule.lam2`` under dynamic federation.  The
    recursion therefore carries the bounded ratio ``r_k = c_{k-1}/c_k`` in
    place of the coefficients themselves (the raw c_k overflow f32 within
    a few rounds when lam2 is small):

        alpha_k = 2x / (2x - r_k),  beta_k = r_k / (2x - r_k),
        r_{k+1} = 1 / (2x - r_k),   x = 1/lam2,  r_1 = lam2,

    with ``alpha_k - beta_k = 1`` (mean preservation) for every lam2.
    A clamped ``lam2 -> 0`` degenerates gracefully to plain repeated
    mixing (alpha -> 1, beta -> 0)."""
    if rounds == 0:
        return tree
    if isinstance(lam2, (int, float)) and lam2 <= 0.0:
        return mix_pytree(a, tree)
    x = 1.0 / jnp.maximum(jnp.asarray(lam2, jnp.float32), 1e-6)
    r = 1.0 / x          # r_1 = c_0 / c_1 = lam2

    w_prev = tree
    w_cur = mix_pytree(a, tree)  # k = 1: the first semi-iterate is just A w
    for _ in range(1, rounds):
        denom = 2.0 * x - r
        alpha, beta = 2.0 * x / denom, r / denom
        mixed = mix_pytree(a, w_cur)
        w_next = jax.tree.map(
            lambda m, p: (alpha * m - beta * p).astype(m.dtype), mixed, w_prev)
        w_prev, w_cur = w_cur, w_next
        r = 1.0 / denom
    return w_cur


def lambda2_traced(a: jax.Array) -> jax.Array:
    """|lambda_2| of a traced symmetric mixing matrix, computed in-graph
    (tiny (M, M) eigendecomposition).  Fallback for calling a spectral
    backend with a traced ``A_p`` but no host-side estimate — the engine
    normally feeds ``topology.lambda_2`` through the schedule instead."""
    if a.shape[0] < 2:
        return jnp.zeros((), jnp.float32)
    ev = jnp.sort(jnp.abs(jnp.linalg.eigvalsh(a)))
    return ev[-2].astype(jnp.float32)


# ---------------------------------------------------------------------------
# shard_map gossip: fully-manual blocked server gossip (the production path)
# ---------------------------------------------------------------------------


def make_gossip_shard_map(mesh, t_server: int, leaf_specs: Any, *,
                          axis_name: str = "server",
                          block: int = 16_777_216, codec=None,
                          stochastic: bool = True,
                          gather_codes: bool = True,
                          with_shipped: bool = False,
                          staleness: int = 0) -> Callable:
    """T_S-round gossip as an explicit shard_map program, returned as
    ``run(operator, tree)`` with the ``(M, M)`` mixing ``operator`` a
    *traced operand* — one compiled program serves every per-epoch graph
    (dynamic federation), and a compile-time-constant operator recovers the
    static case.  Pass ``A`` for plain gossip ``W <- A W``; pass ``A.T``
    (the column-stochastic transpose) to mix a push-sum numerator — the
    body applies ``operator`` row-wise either way.

    Inside the shard_map every device flattens its LOCAL weight shards into
    one vector and scans over fixed ``block``-element slices; each slice
    runs the full T_S-round loop (blocks gossip independently, so
    block-major iteration is the identical operator).  Per-round transfer
    is one bf16 all-gather of (M, block) over the server axis — memory is
    deterministic (~(M+2) x block x 2 bytes live) and dtype is under our
    control, unlike the pjit einsum form where XLA-CPU upcasts the
    contraction operand to f32 *before* the gather and overlaps per-leaf
    loops (~12 GB of f32 gathers at 27B scale).

    ``leaf_specs``: PartitionSpec pytree of the server tree (leading
    'server' axis + intra-client weight axes) — used as in_specs and
    out_specs; the operator itself rides in replicated.

    **Quantized wire mode** (``codec=`` a ``comm.compressors.
    StochasticQuantizer``): the returned ``run(operator, tree, key)``
    flattens the device's ENTIRE local tree into one zero-padded bucket
    (``comm.compressors.bucket_block`` layout) and delta-codes it against
    the receivers' shared decoded reference — see
    ``gossip_scan_wire_bucketed`` for the recursion and why innovations
    rather than raw state — to int8 / packed-int4 codes + per-chunk f32
    scales *before* the gather.  Each round is then exactly ONE all-gather
    of s8 codes plus one of f32 scales no matter how many leaves the
    pytree has (two collective sites in the compiled HLO, guarded by a
    regression test), and the collective operand is 1/4 (int8) or 1/8
    (int4) of the f32 wire, for real, asserted against compiled HLO.  The
    per-leaf form's ``(M, block)`` resident reference matrix is factored
    into a per-device band — iterate, OWN reference row, and mixed-
    reference accumulator, ~3 bucket-sized vectors live — which is the
    926→~600 MB RSS fix at benchmark scale.  Dither follows the shared
    ``comm.compressors.wire_dither`` convention with the bucket's (leaf,
    block) coordinates pinned to 0 and the server coordinate the device's
    LINEARIZED mesh position (server-major): when ``leaf_specs`` shard
    weight axes over further mesh axes (tp / fsdp), the shards of one
    server row draw DISTINCT rounding noise; on a pure ``(server,)`` mesh
    it reduces to the server index — which is what keeps the program
    bit-identical to ``gossip_scan_wire_bucketed`` (whose rows are
    unsharded) under the same key.  ``stochastic=False`` builds the
    deterministic round-to-nearest program (no key needed).
    ``gather_codes=False`` is the simulated twin for parity tests: the
    same code values cross the wire at full f32 width — 4x the bytes,
    identical ops — asserted bitwise equal to the physical program,
    proving the narrow wire changes encoding width only.  Zero-padded
    bucket tails are harmless: pad deltas quantize to zero codes and never
    perturb real chunks' scales (see ``StochasticQuantizer.encode_block``).

    ``with_shipped=True`` makes ``run`` return ``(mixed tree, shipped
    tree)`` where ``shipped`` is each device's own round-0 decoded
    transmission — the error-feedback hook: it is computed INSIDE the
    program, with the exact local-shard bucket/chunk/dither layout that
    crossed the wire (an outside ``bucketed_roundtrip_tree`` would only
    reproduce it for unsharded rows).

    **Bounded staleness** (``staleness=s > 0``, codec mode only): the round
    body becomes software-pipelined — round ``t``'s code+scale gather is
    issued at production time and pushed into an in-flight ring carried by
    the loop, while the mix consumes the gathered buffers of round
    ``t - s`` popped from the ring head.  Nothing on the FMA path depends
    on this round's collective, so the gather overlaps the decode+mix work
    (double buffering at ``s=1``).  Semantics, freeze-before-``s``, and the
    per-period contraction ``A^(T_S//(s+1))`` match
    ``gossip_scan_wire_bucketed(staleness=s)`` bitwise; ``staleness=0``
    compiles the literally unchanged synchronous body.  The plain
    (``codec=None``) path REFUSES staleness: without the delta-coded wire
    there is no innovation stream whose lateness telescopes away.
    """
    from jax.sharding import PartitionSpec as P

    if with_shipped and codec is None:
        raise ValueError("with_shipped is the wire codec's error-feedback "
                         "hook; it needs codec=")
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    if staleness and codec is None:
        raise ValueError(
            "bounded staleness needs the delta-coded wire (codec=): the "
            "plain shard_map path gossips raw state, which has no "
            "innovation stream to consume late — build with a quantizer "
            "codec or use staleness=0")
    other_axes = [ax for ax in mesh.axis_names if ax != axis_name]
    n_other = int(np.prod([mesh.shape[ax] for ax in other_axes],
                          dtype=np.int64)) if other_axes else 1

    def body(a, kd, tree):
        m = a.shape[0]
        idx = jax.lax.axis_index(axis_name)
        row = a[idx].astype(jnp.float32)                 # (M,) my weights
        key = (jax.random.wrap_key_data(kd)
               if codec is not None and stochastic else None)
        sub = 0
        for ax in other_axes:
            sub = sub * mesh.shape[ax] + jax.lax.axis_index(ax)
        wire_server = idx * n_other + sub
        leaves, treedef = jax.tree.flatten(tree)
        dtype = leaves[0].dtype
        # Wire-format control: carry the gossip stream as u16 bit-patterns
        # of the bf16 payload.  Integer buffers are exempt from XLA-CPU's
        # float-normalization pass, which otherwise upcasts every
        # loop-carried bf16 buffer to f32 — a 2x params-sized artifact this
        # container's backend would report that a TPU (native bf16) never
        # allocates.  On TPU the bitcasts are free view changes.
        wire = jnp.uint16 if dtype == jnp.bfloat16 else None

        def to_wire(x):
            return jax.lax.bitcast_convert_type(x, wire) if wire else x

        def from_wire(x):
            return (jax.lax.bitcast_convert_type(x, jnp.bfloat16)
                    if wire else x)

        if codec is not None:
            # BUCKETED wire path: the device's whole local tree is ONE
            # zero-padded code buffer, so each round is exactly one s8
            # all-gather + one f32 all-gather no matter how many leaves
            # the pytree has — and the carry is 3 bucket-sized vectors
            # (iterate, own reference row, mixed-reference accumulator)
            # instead of the per-leaf form's (M, blk) reference matrix;
            # see ``gossip_scan_wire_bucketed`` for the telescoped
            # recursion and why acc_t == (A · R_t)_i exactly.  The loop
            # carries the bucket in its chunk view ``(nc, chunk)``: encode,
            # gather, decode and mix all work per chunk, so on a tiled
            # device nothing is relaid out inside the loop — the leaves are
            # packed into that view once and split out of it once.
            chunk = codec.chunk
            with jax.named_scope("wire_pack"):
                flat = jnp.concatenate(
                    [to_wire(leaf.astype(dtype)).reshape(-1)
                     for leaf in leaves])
                d_tot = flat.size
                blk, nb = _compressors.bucket_block(d_tot, block, chunk)
                d_pad = nb * blk
                if d_pad != d_tot:
                    flat = jnp.pad(flat, (0, d_pad - d_tot))
                nc = d_pad // chunk
                rows = flat.reshape(nc, chunk)

            def encode_round(t, delta):
                """Round-``t`` bucket encode under the shared dither
                convention — ONE definition used by both the loop body and
                the out-of-loop ``shipped`` pre-pass, so the pre-pass is
                elementwise-identical to what round 0 puts on the wire.
                The dither is the flat bucket's, row-major in the chunk
                view.  Returns UNPACKED (nc, chunk) int8 codes and (nc,)
                scales."""
                if key is not None:
                    dither = _compressors.wire_dither(
                        key, (nc, chunk), leaf=0, rnd=t,
                        server=wire_server, block=0)
                else:
                    dither = 0.5
                return codec.encode_chunks(delta, dither)

            def gather(codes, scales):
                """Every server's round codes and chunk scales: int8 codes
                cross as they are, int4 codes packed two to a byte over
                the flat bucket (``pack_int4``)."""
                with jax.named_scope("wire_gather"):
                    if codec.bits == 4:
                        codes = _compressors.pack_int4(codes.reshape(-1))
                    if gather_codes:
                        g_codes = jax.lax.all_gather(codes, axis_name)
                    else:
                        # simulated twin: the same code VALUES cross the
                        # wire at full f32 width (the f32 -> int8
                        # round-trip is exact on code integers), so the
                        # collective moves 4x the bytes but the decode
                        # still happens after the gather — keeping the
                        # multiply-add structure, and therefore the FMA
                        # contraction, identical to the physical program:
                        # the two are asserted BITWISE equal, proving the
                        # narrow wire changes encoding width only, never
                        # the numerics
                        g_codes = jax.lax.all_gather(
                            codes.astype(jnp.float32),
                            axis_name).astype(codes.dtype)
                    return g_codes, jax.lax.all_gather(scales, axis_name)

            def code_rows(g_codes):
                """Gathered wire codes as (M, nc, chunk) int8."""
                if codec.bits == 4:
                    g_codes = _compressors.unpack_int4(g_codes, d_pad)
                return g_codes.reshape(m, nc, chunk)

            def round_fn_wire(t, carry):
                """One bucketed quantized-wire round, delta-coded: encode
                the innovation of my bucket against the receivers' shared
                decoded reference of me, gather CODES (not floats), fold
                my own decoded delta into my reference row and every
                row's into the mixed-reference accumulator.  The delta's
                absmax contracts with consensus, so per-hop quantization
                noise vanishes instead of flooring (see
                ``gossip_scan_wire``)."""
                # iterate and accumulator stay two carries even for an f32
                # model, where they hold the same values from round 1 on:
                # starting the accumulator as ``where(t > 0, w, 0)`` from
                # one carry let the v5e compiler (libtpu 0.0.34) drop the
                # select, so round 0 mixed onto the iterate
                w, ref, acc = carry            # (nc, chunk) each
                with jax.named_scope("wire_encode"):
                    delta = from_wire(w).astype(jnp.float32) - ref
                    codes, scales = encode_round(t, delta)
                g_codes, g_scales = gather(codes, scales)
                with jax.named_scope("wire_decode_mix"):
                    ref, acc = wire_decode_mix(
                        ref, acc, codes, scales, code_rows(g_codes),
                        g_scales, row)
                    return to_wire(acc.astype(dtype)), ref, acc

            def round_fn_wire_stale(t, carry):
                """Software-pipelined bounded-staleness round: ISSUE round
                ``t``'s gather here (pushed onto the in-flight ring) while
                the mix consumes the ring head — round ``t - staleness``'s
                buffers.  No data path connects this round's collective to
                this round's FMA work, so the gather overlaps the
                decode+mix.  The sender's reference advances with its OWN
                codes, computed locally rather than sliced from the gather
                (same values — the gather round-trips code integers
                exactly — but keeps the reference update off the
                collective's critical path), so innovations stay
                single-shipped; the iterate freezes until the first
                delayed buffer lands (``t < staleness``)."""
                w, ref, acc, rc, rs = carry
                with jax.named_scope("wire_encode"):
                    delta = from_wire(w).astype(jnp.float32) - ref
                    codes, scales = encode_round(t, delta)
                g_codes, g_scales = gather(codes, scales)
                with jax.named_scope("wire_decode_mix"):
                    ref, acc = wire_decode_mix(
                        ref, acc, codes, scales, code_rows(rc[0]), rs[0],
                        row)
                    w = jnp.where(t >= staleness,
                                  to_wire(acc.astype(dtype)), w)
                with jax.named_scope("wire_gather"):
                    rc = jnp.concatenate([rc[1:], g_codes[None]], axis=0)
                    rs = jnp.concatenate([rs[1:], g_scales[None]], axis=0)
                return w, ref, acc, rc, rs

            zeros = jnp.zeros((nc, chunk), jnp.float32)
            if with_shipped:
                # what this device shipped of its own model (the EF hook)
                # is its round-0 decoded transmission: ref_1 = dec_0[own].
                # Recompute it in a pre-pass OUTSIDE the loop — the same
                # ``encode_round(0, flat - 0)`` expression the loop body
                # evaluates, decoded locally (own row only, no gather) —
                # instead of carrying a 4th bucket vector + a per-round
                # select through the fori_loop: the loop body stays THE
                # SAME program as the plain runner (bitwise-identical
                # mixed output, single gather pair in the compiled HLO)
                # and the pre-pass costs one encode instead of t_server
                # bucket-sized selects.
                with jax.named_scope("wire_encode"):
                    codes0, scales0 = encode_round(
                        0, from_wire(rows).astype(jnp.float32) - zeros)
                    shipped = codes0.astype(jnp.float32) * scales0[:, None]
            else:
                shipped = zeros
            if staleness == 0:
                w, _, _ = jax.lax.fori_loop(
                    0, t_server, round_fn_wire, (rows, zeros, zeros))
            else:
                # in-flight ring pre-filled with zero codes + unit scales
                # (decode to nothing), so consumption is unconditional and
                # inert before round ``staleness``
                code_shape = ((m, d_pad // 2) if codec.bits == 4
                              else (m, nc, chunk))
                ring_c = jnp.zeros((staleness,) + code_shape, jnp.int8)
                ring_s = jnp.ones((staleness, m, nc), jnp.float32)
                w, _, _, _, _ = jax.lax.fori_loop(
                    0, t_server, round_fn_wire_stale,
                    (rows, zeros, zeros, ring_c, ring_s))
            with jax.named_scope("wire_pack"):
                out = from_wire(w).reshape(-1)
                shipped = shipped.reshape(-1)
                new_leaves, shipped_leaves, off = [], [], 0
                for leaf in leaves:
                    size = leaf.size
                    new_leaves.append(out[off:off + size].astype(leaf.dtype)
                                      .reshape(leaf.shape))
                    shipped_leaves.append(
                        shipped[off:off + size].astype(leaf.dtype)
                        .reshape(leaf.shape))
                    off += size
            mixed = jax.tree.unflatten(treedef, new_leaves)
            if not with_shipped:
                return mixed
            return mixed, jax.tree.unflatten(treedef, shipped_leaves)

        def round_fn(_i, w):
            g = from_wire(jax.lax.all_gather(w, axis_name))      # (M, blk)
            # unrolled mul-adds (M is tiny); f32 accumulate per block
            acc = row[0] * g[0].astype(jnp.float32)
            for j in range(1, m):
                acc = acc + row[j] * g[j].astype(jnp.float32)
            return to_wire(acc.astype(dtype))

        def gossip_leaf(flat):
            """Blocked in-place gossip over one flattened (wire) leaf.

            The ragged tail block is zero-padded; zeros survive the wire
            format exactly (they mix to zero), so the pad is sliced back
            off unchanged."""
            d = flat.size
            blk = min(block, d)
            nb = -(-d // blk)
            if nb * blk != d:
                flat = jnp.pad(flat, (0, nb * blk - d))
            if nb == 1:
                return jax.lax.fori_loop(0, t_server, round_fn, flat)[:d]

            def per_block(i, buf):
                w = jax.lax.dynamic_slice(buf, (i * blk,), (blk,))
                w = jax.lax.fori_loop(0, t_server, round_fn, w)
                return jax.lax.dynamic_update_slice(buf, w, (i * blk,))

            return jax.lax.fori_loop(0, nb, per_block, flat)[:d]

        # Per-leaf loops CHAINED via optimization_barrier: leaves gossip
        # independently, so XLA would otherwise schedule their while-loops
        # concurrently and hold every leaf's wire buffers at once; the
        # token dependency forces one leaf in flight at a time.
        new_leaves = []
        token = None
        for leaf in leaves:
            wl = to_wire(leaf.astype(dtype)).reshape(-1)
            if token is not None:
                wl, token = jax.lax.optimization_barrier((wl, token))
            out = gossip_leaf(wl)
            token = out[0]
            new_leaves.append(
                from_wire(out).astype(leaf.dtype).reshape(leaf.shape))
        return jax.tree.unflatten(treedef, new_leaves)

    out_specs = ((leaf_specs, leaf_specs)
                 if codec is not None and with_shipped else leaf_specs)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, None), P(None), leaf_specs),
                       out_specs=out_specs, check_vma=False)
    if codec is None:
        return lambda a, tree: sm(a, jnp.zeros((2,), jnp.uint32), tree)

    def run(a, tree, key=None):
        if stochastic:
            if key is None:
                raise ValueError(
                    "this wire program was built stochastic=True and needs "
                    "the rounding key; build with stochastic=False for "
                    "deterministic round-to-nearest")
            kd = jax.random.key_data(key)
        else:
            kd = jnp.zeros((2,), jnp.uint32)
        if t_server == 0:       # nothing crosses the wire (or is shipped)
            return ((tree, jax.tree.map(jnp.zeros_like, tree))
                    if with_shipped else tree)
        return sm(a, kd, tree)

    return run


# ---------------------------------------------------------------------------
# shard_map ring gossip: explicit neighbour exchange over ICI
# ---------------------------------------------------------------------------


def ring_gossip_step(w: jax.Array, *, axis_name: str, self_weight: float,
                     neighbor_weight: float) -> jax.Array:
    """One gossip round on a ring graph executed INSIDE shard_map: each server
    shard receives its two ring neighbours via collective_permute — the
    literal 'server communicates with neighbours' of Alg. 1, mapped onto the
    physical ICI ring."""
    m = jax.lax.psum(1, axis_name)
    fwd = [(i, (i + 1) % m) for i in range(m)]
    bwd = [((i + 1) % m, i) for i in range(m)]
    left = jax.lax.ppermute(w, axis_name, perm=fwd)
    right = jax.lax.ppermute(w, axis_name, perm=bwd)
    return (self_weight * w + neighbor_weight * (left + right)).astype(w.dtype)


def make_ring_gossip(mesh: jax.sharding.Mesh, axis_name: str, t_server: int,
                     self_weight: float, neighbor_weight: float, *,
                     codec=None, stochastic: bool = True,
                     gather_codes: bool = True) -> Callable:
    """Build a shard_map'd T_S-round ring gossip over ``axis_name``.

    The input pytree must have its leading (server) axis sharded over
    ``axis_name``; other axes pass through unchanged.

    **Quantized wire mode** (``codec=`` a quantizer): the returned
    ``run(tree, key)`` ppermutes int8 / packed-int4 CODES + per-chunk
    scales instead of the float payload — each round encodes the local
    shard's DELTA against the receivers' decoded reference once
    (innovation coding, see ``gossip_scan_wire``) and ships the same code
    buffer to both ring neighbours; every consumer (neighbours AND the
    own-carry self term) accumulates the decoded delta into its reference
    of the sender and mixes references — the same one-numerics-definition
    as ``make_gossip_shard_map``'s wire mode.  Dither follows
    ``comm.compressors.wire_dither`` with the local flattened shard as one
    block (block index 0); ``gather_codes=False`` builds the simulated
    twin (the same code values ppermuted at f32 width — bitwise identical)
    for the parity tests."""
    from jax.sharding import PartitionSpec as P

    def per_shard(kd, tree):
        def body(_, w):
            return jax.tree.map(
                lambda x: ring_gossip_step(
                    x, axis_name=axis_name, self_weight=self_weight,
                    neighbor_weight=neighbor_weight),
                w)
        return jax.lax.fori_loop(0, t_server, body, tree)

    def per_shard_wire(kd, tree):
        m = jax.lax.psum(1, axis_name)
        idx = jax.lax.axis_index(axis_name)
        key = jax.random.wrap_key_data(kd) if stochastic else None
        fwd = [(i, (i + 1) % m) for i in range(m)]
        bwd = [((i + 1) % m, i) for i in range(m)]
        leaves, treedef = jax.tree.flatten(tree)
        shapes = [l.shape for l in leaves]

        def step(t, carry):
            flats, refs = carry
            new_flats, new_refs = [], []
            for li, (flat, ref3) in enumerate(zip(flats, refs)):
                # delta-coded wire (see gossip_scan_wire): each node keeps
                # a decoded reference of itself and of both ring
                # neighbours; only the innovation w - ref_self is encoded,
                # so per-hop quantization noise contracts with consensus
                r_self, r_left, r_right = ref3
                length = flat.size
                delta = flat.astype(jnp.float32) - r_self
                if key is not None:
                    dither = _compressors.wire_dither(
                        key, (length,), leaf=li, rnd=t, server=idx, block=0)
                else:
                    dither = 0.5
                codes, scales = codec.encode_block(delta, dither)
                if gather_codes:
                    wire_codes = codes
                    unwire = lambda c: c          # noqa: E731
                else:
                    # simulated twin: the same code values at f32 width —
                    # decode still happens after the ppermute, keeping the
                    # FMA-contraction structure identical to the physical
                    # program (see make_gossip_shard_map), hence bitwise
                    wire_codes = codes.astype(jnp.float32)
                    unwire = lambda c: c.astype(codes.dtype)  # noqa: E731
                d_left = codec.decode_block(
                    unwire(jax.lax.ppermute(wire_codes, axis_name,
                                            perm=fwd)),
                    jax.lax.ppermute(scales, axis_name, perm=fwd), length)
                d_right = codec.decode_block(
                    unwire(jax.lax.ppermute(wire_codes, axis_name,
                                            perm=bwd)),
                    jax.lax.ppermute(scales, axis_name, perm=bwd), length)
                r_self = r_self + codec.decode_block(codes, scales, length)
                r_left = r_left + d_left
                r_right = r_right + d_right
                # Contraction-stable mixing: accumulate one weighted term
                # per add, exactly like the all-gather round body.  Every
                # add then exposes the SAME candidate multiply in both wire
                # programs, so LLVM's FMA contraction makes the same choice
                # and gather_codes=True / False stay BITWISE identical —
                # ``nw * (left + right)`` instead adds two raw dequant
                # sums in physical mode (contractible) but materialized
                # floats in simulated mode (not), and the two programs
                # drift by one rounding.
                acc = self_weight * r_self
                acc = acc + neighbor_weight * r_left
                acc = acc + neighbor_weight * r_right
                new_flats.append(acc.astype(flat.dtype))
                new_refs.append((r_self, r_left, r_right))
            return new_flats, new_refs

        flats = [l.reshape(-1) for l in leaves]
        zeros = [tuple(jnp.zeros_like(f, jnp.float32) for _ in range(3))
                 for f in flats]
        flats, _ = jax.lax.fori_loop(0, t_server, step, (flats, zeros))
        return jax.tree.unflatten(
            treedef, [f.reshape(s) for f, s in zip(flats, shapes)])

    def spec_for(tree):
        return jax.tree.map(lambda x: P(axis_name, *([None] * (x.ndim - 1))), tree)

    def run(tree, key=None):
        specs = spec_for(tree)
        body = per_shard if codec is None else per_shard_wire
        if codec is not None and stochastic:
            if key is None:
                raise ValueError(
                    "this wire program was built stochastic=True and needs "
                    "the rounding key")
            kd = jax.random.key_data(key)
        else:
            kd = jnp.zeros((2,), jnp.uint32)
        return jax.shard_map(body, mesh=mesh, in_specs=(P(None), specs),
                             out_specs=specs)(kd, tree)

    return run


# ---------------------------------------------------------------------------
# consensus backends: one interface over every execution strategy
# ---------------------------------------------------------------------------


class ConsensusBackend:
    """One consensus period (Eq. 5/7) behind one interface.

    ``mix(tree, a_p)`` runs T_S rounds of ``W <- A W`` on a server-leading
    pytree; ``mix_push_sum(state, a_p)`` runs the ratio-consensus variant
    (numerator and weight both mixed by the column-stochastic ``A'``, see
    ``gossip_push_sum``).  ``a_p`` is an optional *traced* per-epoch
    ``(M, M)`` mixing matrix — the dynamic engine passes a fresh one every
    epoch through the SAME compiled program; ``None`` selects the static
    matrix the backend was built with.

    Class flags gate what a backend can express:

    * ``supports_traced`` — can consume a traced ``A_p``.
    * ``supports_directed`` — applies the literal ``W <- A W`` update, so
      row-stochastic A and the push-sum correction are well-defined.
    * ``mesh_bound`` — closed over a fixed physical mesh (shard_map): the
      server axis cannot survive fault surgery that changes M.
    * ``needs_spectral`` — wants a per-epoch spectral estimate ``lam2``
      alongside a traced ``A_p`` (Chebyshev); the dynamic engine feeds it
      through ``schedule.EpochSchedule.lam2``.
    * ``compressed`` — a ``CompressedBackend`` wrapper (lossy wire
      simulation + error feedback around an inner backend).
    * ``robust`` — a Byzantine-screening backend (trimmed mean / median /
      clipped): must see every neighbor's plaintext values, so it cannot
      ride the quantized physical wire, and its update is not the literal
      ``W <- A W``, so no push-sum analogue exists.

    ``staleness`` (instance attribute, default 0) is the bounded-staleness
    depth ``s``: round ``t`` mixes with round ``t - s``'s messages
    (``gossip_scan_stale`` / the software-pipelined wire bodies), composing
    to ``A^(T_S // (s+1))`` per period in exact arithmetic — the
    staleness-augmented contraction ``schedule.SigmaTracker`` monitors.
    Only the literal T_S-round schedules carry it (gossip, gossip_blocked,
    the shard_map codec wire); every other backend refuses at build, and
    push-sum refuses at call time (the exact ``(M,)`` weight recursion has
    no delayed twin, so a stale numerator over a fresh weight would be
    inconsistent).
    """

    name = "?"
    supports_traced = True
    supports_directed = True
    mesh_bound = False
    needs_spectral = False
    compressed = False
    robust = False
    staleness = 0

    def __init__(self, a_static: Optional[np.ndarray], t_server: int):
        self.a_static = (None if a_static is None
                         else jnp.asarray(a_static, jnp.float32))
        self.t_server = t_server

    def _resolve(self, a_p: Optional[jax.Array]) -> jax.Array:
        if a_p is not None:
            return a_p
        if self.a_static is None:
            raise ValueError(f"{self.name!r} backend was built without a "
                             f"static mixing matrix; pass a per-epoch A_p")
        return self.a_static

    def mix(self, tree: Any, a_p: Optional[jax.Array] = None,
            lam2=None) -> Any:
        """T_S rounds of ``W <- A W`` over the leading server axis.
        ``lam2`` is the optional per-epoch spectral hint, consumed only by
        ``needs_spectral`` backends and ignored everywhere else."""
        del lam2
        return self._mix(tree, self._resolve(a_p))

    def mix_stats(self, tree: Any, a_p: Optional[jax.Array] = None,
                  lam2=None) -> Tuple[Any, jax.Array]:
        """``mix`` plus the period's per-source screen-activity counts —
        ``(mixed, rejected)`` with ``rejected[j]`` how many values server
        j had discarded/clipped by its receivers' screens.  Non-``robust``
        backends screen nothing: the counts are identically zero and the
        value path is EXACTLY ``mix`` (the robust backends override this
        with their shared-body stats variants)."""
        m = self._resolve(a_p).shape[0]
        return (self.mix(tree, a_p, lam2=lam2),
                jnp.zeros((m,), jnp.float32))

    def mix_push_sum(self, state: PushSumState,
                     a_p: Optional[jax.Array] = None) -> PushSumState:
        """Ratio consensus: numerator streamed through the SAME execution
        strategy with ``P = A'``, weight by the trivial ``(M,)`` matvec."""
        if not self.supports_directed:
            raise ValueError(
                f"consensus backend {self.name!r} has no ratio-consensus "
                f"analogue: its value update is not the literal W <- A W, "
                f"so a numerator/weight pair mixed by it would be "
                f"inconsistent")
        if self.staleness:
            raise ValueError(
                f"consensus backend {self.name!r} has staleness="
                f"{self.staleness}, but ratio consensus mixes a "
                f"numerator/weight PAIR and the exact (M,) weight "
                f"recursion has no delayed twin — a stale numerator over "
                f"a fresh weight breaks the mass-conservation invariant; "
                f"use staleness=0 with push-sum")
        p = jnp.swapaxes(self._resolve(a_p), 0, 1)
        return PushSumState(self._mix(state.values, p),
                            self._mix_weight(state.weight, p))

    def _mix_weight(self, weight: jax.Array, p: jax.Array) -> jax.Array:
        return jax.lax.fori_loop(
            0, self.t_server,
            lambda _, w: (p @ w.astype(p.dtype)).astype(w.dtype), weight)

    def _mix(self, tree: Any, a: jax.Array) -> Any:
        raise NotImplementedError


class GossipBackend(ConsensusBackend):
    """The reference per-leaf einsum schedule (``gossip_scan``; with
    ``staleness=s > 0``, ``gossip_scan_stale`` — whose ``s=0`` branch IS
    ``gossip_scan``, so the default construction is bitwise unchanged)."""

    name = "gossip"

    def __init__(self, a_static, t_server, *, staleness: int = 0):
        super().__init__(a_static, t_server)
        self.staleness = staleness

    def _mix(self, tree, a):
        return gossip_scan_stale(a, tree, self.t_server, self.staleness)


class BlockedGossipBackend(ConsensusBackend):
    """``gossip_scan_blocked``: fixed-block streaming — the pjit production
    path whose live working set is one (M, block) gather, not a full leaf.

    Under ``staleness=s > 0`` the plain (uncompressed) mix delegates to
    ``gossip_scan_stale``: the delayed-iterate history would multiply the
    blocked path's live set by ``s+1`` for no wire benefit — only the
    delta-coded wire (``gossip_scan_wire_bucketed``) pipelines; the
    physical-wire wrap (``CompressedBackend``) keeps the bucketed stale
    body either way."""

    name = "gossip_blocked"

    def __init__(self, a_static, t_server, *, block: int = 4_194_304,
                 flat_sharding=None, staleness: int = 0):
        super().__init__(a_static, t_server)
        self.block = block
        self.flat_sharding = flat_sharding
        self.staleness = staleness

    def _mix(self, tree, a):
        if self.staleness:
            return gossip_scan_stale(a, tree, self.t_server, self.staleness)
        return gossip_scan_blocked(a, tree, self.t_server, block=self.block,
                                   flat_sharding=self.flat_sharding)


class CollapsedBackend(ConsensusBackend):
    """One round with ``A_eff = A^{T_S}`` — host-side float64 collapse for
    the static matrix, in-program (M x M, trivial) collapse for a traced
    per-epoch ``A_p``."""

    name = "collapsed"

    def __init__(self, a_static, t_server):
        super().__init__(a_static, t_server)
        self._eff_static = (None if a_static is None else jnp.asarray(
            collapse_mixing(np.asarray(a_static), t_server), jnp.float32))

    def _eff(self, a_p: Optional[jax.Array]) -> jax.Array:
        if a_p is None:
            if self._eff_static is None:
                raise ValueError("'collapsed' backend was built without a "
                                 "static mixing matrix; pass a per-epoch A_p")
            return self._eff_static
        return jax.lax.fori_loop(
            0, self.t_server, lambda _, p: a_p @ p,
            jnp.eye(a_p.shape[0], dtype=a_p.dtype))

    def mix(self, tree, a_p=None, lam2=None):
        del lam2
        return gossip_collapsed(self._eff(a_p), tree)

    def mix_push_sum(self, state, a_p=None):
        # (A^{T_S})' == (A')^{T_S}: one collapsed round of the transpose
        effp = jnp.swapaxes(self._eff(a_p), 0, 1)
        weight = (effp @ state.weight.astype(effp.dtype)).astype(
            state.weight.dtype)
        return PushSumState(mix_pytree(effp, state.values), weight)


class ChebyshevBackend(ConsensusBackend):
    """Chebyshev semi-iterative gossip.

    Spectral data rides OUTSIDE the matrix: for the static topology,
    ``lambda_2(A)`` is computed on the host at construction; for a traced
    per-epoch ``A_p`` (dynamic federation) the matching per-epoch estimate
    arrives as the traced ``lam2`` operand — the engine computes it
    host-side per epoch (``topology.lambda_2`` via
    ``schedule.EpochSchedule.lam2``) since the ratio-parametrised recursion
    in ``gossip_chebyshev`` handles traced coefficients.  A traced ``A_p``
    with no estimate falls back to the in-graph ``lambda2_traced``.  The
    affine recursion has negative coefficients, so no ratio-consensus
    (push-sum) analogue exists."""

    name = "chebyshev"
    supports_directed = False
    needs_spectral = True

    def __init__(self, a_static, t_server, *, rounds: Optional[int] = None):
        super().__init__(a_static, t_server)
        self.lam2 = (None if a_static is None
                     else tp_lambda_2(np.asarray(a_static)))
        self.rounds = rounds or max(1, int(np.ceil(np.sqrt(max(t_server,
                                                               1)))))

    def mix(self, tree, a_p=None, lam2=None):
        a = self._resolve(a_p)
        if lam2 is None:
            lam2 = self.lam2 if a_p is None else lambda2_traced(a_p)
        if lam2 is None:
            raise ValueError("'chebyshev' was built without a static mixing "
                             "matrix; pass (a_p, lam2) per call")
        return gossip_chebyshev(a, tree, self.rounds, lam2)


class ExactMeanBackend(ConsensusBackend):
    """The idealised sigma_A = 0 limit (hierarchical FL with a root
    aggregator): ignores the mixing matrix entirely, so the directed /
    push-sum interpretations are undefined for it."""

    name = "exact_mean"
    supports_directed = False

    def _mix(self, tree, a):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x.mean(axis=0, keepdims=True),
                                       x.shape), tree)


# ---------------------------------------------------------------------------
# robust (Byzantine-screening) gossip: trimmed mean / median / clipped
# ---------------------------------------------------------------------------


def _support(a: jax.Array) -> jax.Array:
    """Boolean (M, M) gossip support of a mixing matrix: every positive
    entry plus the diagonal — a server always counts its OWN value among
    the screened candidates, even on graphs whose self-weight is 0."""
    return (a > 0) | jnp.eye(a.shape[0], dtype=bool)


def _rank_keep_mean_stats(a: jax.Array, leaf: jax.Array,
                          keep_rule) -> Tuple[jax.Array, jax.Array]:
    """Coordinatewise rank-screened neighbor mean — the shared core of the
    trimmed-mean and median rounds — plus its screen-activity readout.

    For each receiver ``i`` and each coordinate, the supported values
    (``leaf[j]`` for every ``j`` in i's support, self included) are ranked
    by a stable double-argsort (ties broken by source index, so the keep
    set is deterministic), ``keep_rule(rank, cnt)`` selects which ranks
    survive, and the output is the UNWEIGHTED mean of the survivors summed
    in ORIGINAL source order — which is why ``keep_rule = (0 <= r < cnt)``
    (the f=0 trim) is bitwise the plain masked neighbor mean.
    Non-neighbors are masked to +inf, so they occupy the ranks at and above
    ``cnt`` and no admissible rule can keep them.  A receiver whose whole
    neighborhood is screened away (past the breakdown point on a traced
    graph, unverifiable at build time) holds its own value.

    Returns ``(out, rejected)`` where ``rejected`` is the per-SOURCE
    screen-activity count: ``rejected[j]`` = how many (receiver,
    coordinate) pairs discarded server j's supported value this round.
    The rank screens discard a FIXED number of values per neighborhood
    (the informative signal is WHOSE values land in the discarded ranks —
    an attacker's coordinates are rejected far above the honest base
    rate).  Callers that only need ``out`` take element 0 and XLA
    dead-code-eliminates the counting — the plain path stays bitwise and
    cost-identical."""
    m = a.shape[0]
    sup = _support(a)
    cnt = sup.sum(axis=1)                                    # (M,) int
    supb = sup.reshape((m, m) + (1,) * (leaf.ndim - 1))
    vals = jnp.broadcast_to(leaf[None], (m,) + leaf.shape)   # (M, M, *w)
    big = jnp.where(supb, vals, jnp.asarray(jnp.inf, leaf.dtype))
    order = jnp.argsort(big, axis=1)
    rank = jnp.argsort(order, axis=1)
    cntb = cnt.reshape((m,) + (1,) * leaf.ndim)
    keep = keep_rule(rank, cntb) & supb
    kept = jnp.where(keep, vals, jnp.zeros((), leaf.dtype))
    kcnt = keep.sum(axis=1)
    out = kept.sum(axis=1) / jnp.maximum(kcnt, 1).astype(leaf.dtype)
    rejected = (supb & ~keep).sum(
        axis=tuple(i for i in range(keep.ndim) if i != 1),
        dtype=jnp.float32)                                   # (M,) per source
    return jnp.where(kcnt > 0, out, leaf), rejected


def _rank_keep_mean(a: jax.Array, leaf: jax.Array, keep_rule) -> jax.Array:
    """``_rank_keep_mean_stats`` without the screen-activity readout."""
    return _rank_keep_mean_stats(a, leaf, keep_rule)[0]


def trimmed_mean_mix(a: jax.Array, tree: Any, f: int) -> Any:
    """One coordinatewise-trimmed-mean screening round: per receiver and
    coordinate, discard the ``f`` largest and ``f`` smallest supported
    values and average the rest (unweighted).  Tolerates up to ``f``
    arbitrary values per neighborhood as long as ``2f < c``; with ``f=0``
    it IS the plain masked neighbor mean, bitwise."""
    if f < 0:
        raise ValueError(f"trimmed mean needs f >= 0, got {f}")
    return jax.tree.map(
        lambda leaf: _rank_keep_mean(
            a, leaf, lambda r, c: (r >= f) & (r < c - f)), tree)


def median_mix(a: jax.Array, tree: Any) -> Any:
    """One coordinatewise-median screening round: per receiver and
    coordinate, the median of the supported values (mean of the two middle
    ranks when the neighborhood is even) — trimmed mean pushed to its
    breakdown point ``f < c/2`` without choosing f."""
    return jax.tree.map(
        lambda leaf: _rank_keep_mean(
            a, leaf, lambda r, c: (r >= (c - 1) // 2) & (r <= c // 2)),
        tree)


def clip_weights(a: jax.Array, tree: Any,
                 clip_mult: float = 1.0) -> jax.Array:
    """Self-centered clipping as an EFFECTIVE per-round mixing matrix.

    Each receiver ``i`` clips every neighbor's innovation against its own
    model: the off-diagonal weight becomes ``a[i,j] * min(1, tau_i /
    ||x_j - x_i||)`` and the clipped-away mass returns to the self-loop,
    so a round is the ordinary einsum ``mix_pytree(C, tree)`` and composes
    with everything that consumes a mixing matrix.  The threshold ``tau_i``
    is ``clip_mult x`` the MEDIAN tree-wide distance from ``i`` to its
    supported neighbors — self-annealing: as the honest servers contract,
    tau shrinks with them and the clip bites harder on anything still far
    away (the attacker), while at ``tau -> inf`` the round degenerates to
    the exact weighted gossip.  Distances are tree-wide l2 norms via the
    Gram identity (one (M, M) accumulation, no (M, M, *w) tensor)."""
    return clip_weights_stats(a, tree, clip_mult)[0]


def clip_weights_stats(a: jax.Array, tree: Any, clip_mult: float = 1.0
                       ) -> Tuple[jax.Array, jax.Array]:
    """``clip_weights`` plus its screen-activity readout: ``clipped[j]`` =
    how many receivers clipped sender j's innovation this round (links
    where the clip factor actually bit, ``fac < 1``).  One shared body, so
    the effective matrix is bitwise identical whether or not the count is
    consumed (XLA dead-code-eliminates it on the plain path)."""
    m = a.shape[0]
    off = _support(a) & ~jnp.eye(m, dtype=bool)
    d2 = jnp.zeros((m, m), jnp.float32)
    for leaf in jax.tree.leaves(tree):
        x = leaf.reshape(m, -1).astype(jnp.float32)
        g = x @ x.T
        sq = jnp.diagonal(g)
        d2 = d2 + (sq[:, None] + sq[None, :] - 2.0 * g)
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    masked = jnp.where(off, dist, jnp.inf)
    srt = jnp.sort(masked, axis=1)
    k = off.sum(axis=1)
    med = jnp.take_along_axis(
        srt, jnp.maximum((k - 1) // 2, 0)[:, None], axis=1)[:, 0]
    tau = clip_mult * med                    # inf for an isolated receiver
    fac = jnp.where(dist > 0.0,
                    jnp.minimum(1.0, tau[:, None] / jnp.maximum(dist, 1e-30)),
                    1.0)
    c_off = jnp.where(off, a.astype(jnp.float32) * fac, 0.0)
    clipped = (off & (fac < 1.0)).sum(axis=0, dtype=jnp.float32)  # per source
    return c_off + jnp.diag(1.0 - c_off.sum(axis=1)), clipped


def clipped_mix(a: jax.Array, tree: Any, clip_mult: float = 1.0) -> Any:
    """One clipped-gossip round: build the state-dependent effective matrix
    and apply the ordinary weighted round with it."""
    return mix_pytree(clip_weights(a, tree, clip_mult), tree)


def gossip_scan_trimmed(a: jax.Array, tree: Any, t_server: int,
                        f: int) -> Any:
    """T_S rounds of trimmed-mean screening (per-leaf fori_loop, mirroring
    ``gossip_scan``'s schedule — leaves screen independently)."""
    if f < 0:
        raise ValueError(f"trimmed mean needs f >= 0, got {f}")
    if t_server == 0:
        return tree

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, t_server,
            lambda _, w: _rank_keep_mean(
                a, w, lambda r, c: (r >= f) & (r < c - f)), leaf)

    return jax.tree.map(leaf_loop, tree)


def gossip_scan_median(a: jax.Array, tree: Any, t_server: int) -> Any:
    """T_S rounds of coordinatewise-median screening."""
    if t_server == 0:
        return tree

    def leaf_loop(leaf):
        return jax.lax.fori_loop(
            0, t_server,
            lambda _, w: _rank_keep_mean(
                a, w, lambda r, c: (r >= (c - 1) // 2) & (r <= c // 2)),
            leaf)

    return jax.tree.map(leaf_loop, tree)


def gossip_scan_clipped(a: jax.Array, tree: Any, t_server: int,
                        clip_mult: float = 1.0) -> Any:
    """T_S rounds of clipped gossip.  The effective matrix depends on the
    WHOLE tree's current state (tree-wide distances), so rounds cannot run
    per leaf: a plain unrolled loop over the (static) round count."""
    for _ in range(t_server):
        tree = clipped_mix(a, tree, clip_mult)
    return tree


# -- screen-activity variants: same rounds, plus the per-source counts -----


def _rank_scan_stats(a: jax.Array, tree: Any, t_server: int,
                     keep_rule) -> Tuple[Any, jax.Array]:
    """T_S rank-screened rounds returning ``(tree, rejected)`` with
    ``rejected[j]`` the total (receiver, coordinate, round, leaf) count of
    server j's screened-away values this period.  The value path is the
    exact ``_rank_keep_mean`` round sequence — only the f32 count rides
    alongside the ``fori_loop`` carry."""
    m = a.shape[0]
    if t_server == 0:
        return tree, jnp.zeros((m,), jnp.float32)

    def leaf_loop(leaf):
        def body(_, carry):
            w, rej = carry
            out, r = _rank_keep_mean_stats(a, w, keep_rule)
            return out, rej + r
        return jax.lax.fori_loop(0, t_server, body,
                                 (leaf, jnp.zeros((m,), jnp.float32)))

    leaves, treedef = jax.tree.flatten(tree)
    results = [leaf_loop(l) for l in leaves]
    out = treedef.unflatten([r[0] for r in results])
    rejected = sum(r[1] for r in results)
    return out, rejected


def gossip_scan_trimmed_stats(a: jax.Array, tree: Any, t_server: int,
                              f: int) -> Tuple[Any, jax.Array]:
    """``gossip_scan_trimmed`` + per-source screen-activity counts."""
    if f < 0:
        raise ValueError(f"trimmed mean needs f >= 0, got {f}")
    return _rank_scan_stats(
        a, tree, t_server, lambda r, c: (r >= f) & (r < c - f))


def gossip_scan_median_stats(a: jax.Array, tree: Any,
                             t_server: int) -> Tuple[Any, jax.Array]:
    """``gossip_scan_median`` + per-source screen-activity counts."""
    return _rank_scan_stats(
        a, tree, t_server,
        lambda r, c: (r >= (c - 1) // 2) & (r <= c // 2))


def gossip_scan_clipped_stats(a: jax.Array, tree: Any, t_server: int,
                              clip_mult: float = 1.0
                              ) -> Tuple[Any, jax.Array]:
    """``gossip_scan_clipped`` + per-source counts of links whose clip
    factor bit (``fac < 1``), summed over rounds and receivers."""
    clipped = jnp.zeros((a.shape[0],), jnp.float32)
    for _ in range(t_server):
        c, hit = clip_weights_stats(a, tree, clip_mult)
        tree = mix_pytree(c, tree)
        clipped = clipped + hit
    return tree, clipped


class TrimmedMeanBackend(ConsensusBackend):
    """Coordinatewise trimmed-mean gossip (``gossip_scan_trimmed``).

    Screens up to ``f`` arbitrary (Byzantine) values per neighborhood per
    coordinate; construction fails fast when the STATIC graph is already
    past the breakdown point (some supported neighborhood, self included,
    has ``c <= 2f`` values — the screen would discard everything).  A
    traced per-epoch ``A_p`` cannot be checked at build time; a fully
    screened receiver then holds its own value (see ``_rank_keep_mean``).

    ``f == 0`` requests no screening at all, so the backend degenerates to
    the EXACT weighted schedule (``gossip_scan``) — bitwise identical to
    the unprotected ``'gossip'`` backend, the identity the adversarial
    suite (``tests/test_robust.py``) pins."""

    name = "trimmed_mean"
    supports_directed = False
    robust = True

    def __init__(self, a_static, t_server, *, f: int = 1):
        super().__init__(a_static, t_server)
        if f < 0:
            raise ValueError(f"trimmed mean needs f >= 0, got {f}")
        self.f = f
        if a_static is not None and f > 0:
            a = np.asarray(a_static)
            cnt = int(((a > 0) | np.eye(a.shape[0], dtype=bool))
                      .sum(axis=1).min())
            if cnt <= 2 * f:
                raise ValueError(
                    f"trimmed_mean with f={f} is past its breakdown point "
                    f"on this graph: a server has only {cnt} supported "
                    f"values (self included) but the screen discards "
                    f"2f={2 * f} per coordinate and needs > 2f survivors' "
                    f"worth of margin; lower f or densify the graph")

    def _mix(self, tree, a):
        if self.f == 0:
            return gossip_scan(a, tree, self.t_server)
        return gossip_scan_trimmed(a, tree, self.t_server, self.f)

    def mix_stats(self, tree, a_p=None, lam2=None):
        del lam2
        a = self._resolve(a_p)
        if self.f == 0:
            # no screening requested: the exact weighted schedule, with
            # identically-zero counts (the f=0 bitwise identity holds)
            return (gossip_scan(a, tree, self.t_server),
                    jnp.zeros((a.shape[0],), jnp.float32))
        return gossip_scan_trimmed_stats(a, tree, self.t_server, self.f)


class MedianBackend(ConsensusBackend):
    """Coordinatewise-median gossip (``gossip_scan_median``): the maximal
    screen — tolerates any minority of attackers per neighborhood
    (breakdown point f < c/2) at the cost of discarding the most
    information per round."""

    name = "median"
    supports_directed = False
    robust = True

    def _mix(self, tree, a):
        return gossip_scan_median(a, tree, self.t_server)

    def mix_stats(self, tree, a_p=None, lam2=None):
        del lam2
        return gossip_scan_median_stats(self._resolve(a_p), tree,
                                        self.t_server)


class ClippedGossipBackend(ConsensusBackend):
    """Clipped gossip (``gossip_scan_clipped``): neighbor innovations
    norm-clipped against the receiver's own model via the effective matrix
    ``clip_weights``, so each round remains the weighted einsum and the
    honest-and-agreed fixed point is EXACTLY preserved (an all-equal tree
    has zero innovations and C == A).  Unlike the rank screens it keeps
    the Eq.-6 weights for everything inside the clip radius."""

    name = "clipped"
    supports_directed = False
    robust = True

    def __init__(self, a_static, t_server, *, clip_mult: float = 1.0):
        super().__init__(a_static, t_server)
        if not clip_mult > 0.0:
            raise ValueError(f"clipped needs clip_mult > 0, got {clip_mult}")
        self.clip_mult = clip_mult

    def _mix(self, tree, a):
        return gossip_scan_clipped(a, tree, self.t_server,
                                   clip_mult=self.clip_mult)

    def mix_stats(self, tree, a_p=None, lam2=None):
        del lam2
        return gossip_scan_clipped_stats(self._resolve(a_p), tree,
                                         self.t_server,
                                         clip_mult=self.clip_mult)


class ShardMapBackend(ConsensusBackend):
    """The production explicit-collective path (``make_gossip_shard_map``):
    blocked u16-wire all-gathers over the mesh's server axis, with the
    mixing matrix a traced operand.  Mesh-aware, so it is built by the
    launcher (``launch.sharding.fl_consensus_backend``) and injected via
    ``DFLConfig.consensus_backend``; being bound to a physical mesh axis it
    cannot survive fault surgery that changes M (``mesh_bound``)."""

    name = "shard_map"
    mesh_bound = True

    def __init__(self, mesh, a_static, t_server, leaf_specs, *,
                 axis_name: str = "server", block: int = 16_777_216,
                 staleness: int = 0):
        super().__init__(a_static, t_server)
        self.mesh = mesh
        self.leaf_specs = leaf_specs
        self.axis_name = axis_name
        self.block = block
        self.staleness = staleness
        self._run = make_gossip_shard_map(mesh, t_server, leaf_specs,
                                          axis_name=axis_name, block=block)
        self._wire_runners = {}

    def _mix(self, tree, a):
        if self.staleness:
            raise ValueError(
                "shard_map bounded staleness rides the delta-coded wire "
                "only (make_gossip_shard_map refuses codec=None): wrap "
                "with a physical-wire CompressedBackend or use staleness=0")
        return self._run(a, tree)

    def wire_runner(self, codec, *, stochastic: bool = True,
                    gather_codes: bool = True,
                    with_shipped: bool = False) -> Callable:
        """The physical-wire twin of this backend's program — same mesh,
        specs and block, but the all-gather moves the codec's int8 /
        packed-int4 codes instead of the float payload.
        ``with_shipped=True`` additionally returns each device's round-0
        decoded transmission (the error-feedback hook, computed inside the
        program with the exact local-shard wire layout).  Built on demand
        and cached per (codec, mode); ``CompressedBackend(wire='physical')``
        is the caller.  The backend's ``staleness`` threads through to the
        software-pipelined wire body."""
        k = (codec, bool(stochastic), bool(gather_codes),
             bool(with_shipped), self.staleness)
        if k not in self._wire_runners:
            self._wire_runners[k] = make_gossip_shard_map(
                self.mesh, self.t_server, self.leaf_specs,
                axis_name=self.axis_name, block=self.block, codec=codec,
                stochastic=stochastic, gather_codes=gather_codes,
                with_shipped=with_shipped, staleness=self.staleness)
        return self._wire_runners[k]


# ---------------------------------------------------------------------------
# compressed consensus: the comm subsystem's wrapper over any backend
# ---------------------------------------------------------------------------


class CompressedBackend(ConsensusBackend):
    """Lossy-compression wrapper around any ``ConsensusBackend`` — the
    ``repro.comm`` subsystem's hook into the consensus period.

    The wrapped period mixes the DECOMPRESSED server messages: ``mix``
    becomes ``inner.mix(D(C(W)))`` — mathematically what every receiver
    reconstructs from the on-wire payload — optionally with error feedback
    (``comm.error_feedback.ef_roundtrip``) whose per-server residual rides
    in ``dfl.DFLState.ef_residual``.  Because the T_S rounds are linear in
    the payloads, shipping each server's ONE compressed payload and letting
    it propagate T_S hops realises the whole period, so the on-wire cost is
    live-links x T_S x compressed-row bytes (``comm.accounting.
    BytesTracker``).  With the identity compressor (and a zero residual)
    every output is bitwise the inner backend's.

    The push-sum variant compresses the NUMERATOR only; the tiny ``(M,)``
    weight rides uncompressed (one f32 scalar per message, counted by the
    tracker).  Capability flags delegate to the inner backend, so the
    wrapper composes with einsum / blocked / collapsed / chebyshev /
    shard_map and both mixing modes.

    ``wire`` selects where compression happens:

    * ``"simulated"`` (default, the PR-4 wire model) — quantize ONCE per
      period in-graph (payload flooding: gossip is linear in the payloads,
      so one compressed payload per server forwarded T_S hops realises the
      period) and let the inner backend's collectives move floats; bytes
      are a host-side ledger.
    * ``"physical"`` — the codes ARE what crosses the interconnect: every
      round quantizes before the collective and dequantizes after
      (``gossip_scan_wire_bucketed`` for the pjit paths,
      ``ShardMapBackend.wire_runner`` for explicit collectives), so each
      hop re-quantizes like a real store-and-forward relay and every
      collective operand is int8 / packed int4 — in the BUCKETED layout:
      the whole tree as one padded code buffer, one collective pair per
      round.  Only the quantizers define a wire byte format, and only the
      literal T_S-round schedules (gossip / gossip_blocked / shard_map)
      have a per-round wire.  Error feedback tracks the round-0
      transmission of each server's OWN model
      (``bucketed_roundtrip_tree``) — later hops' stochastic-rounding
      error is zero-mean and untracked."""

    compressed = True

    def __init__(self, inner: ConsensusBackend,
                 compressor: "_compressors.Compressor", *,
                 error_feedback: bool = True, flat_sharding=None,
                 wire: str = "simulated",
                 wire_block: Optional[int] = None):
        if getattr(inner, "compressed", False):
            raise ValueError("refusing to wrap an already-compressed "
                             "backend: double compression double-counts "
                             "wire bytes and compounds loss")
        if wire not in ("simulated", "physical"):
            raise ValueError(f"wire must be 'simulated' or 'physical', "
                             f"got {wire!r}")
        if wire == "physical":
            if getattr(inner, "robust", False):
                raise ValueError(
                    f"wire='physical' ships quantized codes through the "
                    f"collectives, but the robust screening backend "
                    f"{inner.name!r} must rank/clip every neighbor's "
                    f"plaintext values before mixing — robust gossip "
                    f"composes with wire='simulated' compression only")
            if not isinstance(compressor, _compressors.StochasticQuantizer):
                raise ValueError(
                    "wire='physical' ships quantized codes through the "
                    "collectives; only the int8/int4 quantizers define a "
                    "wire byte format — top_k/random_k/identity run "
                    "wire='simulated'")
            if inner.name not in ("gossip", "gossip_blocked", "shard_map"):
                raise ValueError(
                    f"wire='physical' re-quantizes at every gossip hop, so "
                    f"it needs the literal T_S-round W <- A W schedule; "
                    f"backend {inner.name!r} has no per-round wire — use "
                    f"'gossip', 'gossip_blocked' or the shard_map backend")
        if getattr(inner, "staleness", 0) and wire != "physical":
            raise ValueError(
                "bounded staleness + wire='simulated' is incoherent: the "
                "simulated wire quantizes ONCE per period (no per-round "
                "in-flight buffers exist to be late), so the delayed-"
                "consumption model has nothing physical to model — use "
                "wire='physical' or staleness=0")
        self.inner = inner
        self.compressor = compressor
        self.error_feedback = error_feedback
        self.wire = wire
        # the block partitioning of the physical byte layout: follow the
        # inner backend's streaming block when it has one, so the EF
        # residual and the byte ledger see the exact on-wire layout
        self.wire_block = (getattr(inner, "block", None) or wire_block
                           or DEFAULT_GOSSIP_BLOCK)
        # NamedSharding of the flattened (M, d) leaf views under pjit —
        # same constraint (and same reason) as gossip_scan_blocked's
        self.flat_sharding = flat_sharding
        self.a_static = inner.a_static
        self.t_server = inner.t_server
        self.staleness = getattr(inner, "staleness", 0)
        self.name = f"compressed[{inner.name}+{compressor.name}" + (
            "+wire" if wire == "physical" else "") + "]"
        self.supports_traced = inner.supports_traced
        self.supports_directed = inner.supports_directed
        self.mesh_bound = inner.mesh_bound
        self.needs_spectral = inner.needs_spectral

    def _wire(self, tree: Any, residual: Optional[Any],
              key: Optional[jax.Array]):
        """Simulate the wire: (decompressed message tree, new residual)."""
        if residual is not None and self.error_feedback:
            return _ef.ef_roundtrip(self.compressor, tree, residual, key,
                                    flat_sharding=self.flat_sharding)
        return _compressors.roundtrip_tree(
            self.compressor, tree, key,
            flat_sharding=self.flat_sharding), residual

    def _mix_physical(self, tree: Any, a: jax.Array, *, residual, key):
        """Run one physical-wire consensus period on a (possibly
        transposed) operator: EF correction + round-0 residual update, then
        the per-round quantized collectives in the BUCKETED layout (one
        code + one scale buffer per server per round).  Returns ``(mixed
        tree, new residual)``.  The residual is ``corrected - (round-0
        decoded transmission)``: for the shard_map backend that
        transmission comes back from INSIDE the collective program
        (``with_shipped`` — the only layout-exact source when leaf specs
        shard weight axes); the pjit paths recompute it with
        ``bucketed_roundtrip_tree``, whose global-row layout is exactly
        what ``gossip_scan_wire_bucketed`` encodes.  The pjit gossip and
        gossip_blocked backends share one bucketed program — bucket blocks
        encode and gossip independently, so there is no block-major /
        round-major distinction left to preserve."""
        codec = self.compressor
        ef = residual is not None and self.error_feedback
        if ef:
            tree = jax.tree.map(lambda x, e: x + e.astype(x.dtype),
                                tree, residual)
        if isinstance(self.inner, ShardMapBackend):
            run = self.inner.wire_runner(codec, stochastic=key is not None,
                                         with_shipped=ef)
            if ef:
                out, shipped = run(a, tree, key)
                residual = jax.tree.map(lambda c, q: c - q, tree, shipped)
            else:
                out = run(a, tree, key)
            return out, residual
        if ef:
            shipped = bucketed_roundtrip_tree(codec, tree, key,
                                              block=self.wire_block)
            residual = jax.tree.map(lambda c, q: c - q, tree, shipped)
        return gossip_scan_wire_bucketed(
            a, tree, self.inner.t_server, codec, key,
            block=self.wire_block, staleness=self.staleness), residual

    # -- the EF-threading entry points the epoch step calls ------------------
    def mix_compressed(self, tree: Any, a_p: Optional[jax.Array] = None, *,
                       residual: Optional[Any] = None,
                       key: Optional[jax.Array] = None, lam2=None):
        """``(inner.mix of the wire-simulated tree, new EF residual)`` —
        or, under ``wire='physical'``, the per-round quantized-collective
        period."""
        if self.wire == "physical":
            del lam2
            return self._mix_physical(tree, self._resolve(a_p),
                                      residual=residual, key=key)
        msg, new_res = self._wire(tree, residual, key)
        return self.inner.mix(msg, a_p, lam2=lam2), new_res

    def mix_push_sum_compressed(self, state: PushSumState,
                                a_p: Optional[jax.Array] = None, *,
                                residual: Optional[Any] = None,
                                key: Optional[jax.Array] = None):
        if self.wire == "physical":
            if not self.supports_directed:
                raise ValueError(
                    f"consensus backend {self.name!r} has no "
                    f"ratio-consensus analogue")
            # the numerator rides the quantized wire (operator = the
            # column-stochastic transpose); the tiny (M,) weight recursion
            # stays exact, one f32 scalar per message on the ledger
            p = jnp.swapaxes(self._resolve(a_p), 0, 1)
            values, new_res = self._mix_physical(state.values, p,
                                                 residual=residual, key=key)
            weight = self.inner._mix_weight(state.weight, p)
            return PushSumState(values, weight), new_res
        msg, new_res = self._wire(state.values, residual, key)
        return self.inner.mix_push_sum(PushSumState(msg, state.weight),
                                       a_p), new_res

    # -- plain ConsensusBackend interface (no EF state threaded) -------------
    def mix(self, tree, a_p=None, lam2=None):
        return self.mix_compressed(tree, a_p, lam2=lam2)[0]

    def mix_push_sum(self, state, a_p=None):
        return self.mix_push_sum_compressed(state, a_p)[0]


BACKEND_MODES = ("gossip", "gossip_blocked", "collapsed", "chebyshev",
                 "exact_mean", "trimmed_mean", "median", "clipped")


def make_backend(mode: str, a_static: Optional[np.ndarray], t_server: int, *,
                 chebyshev_rounds: Optional[int] = None,
                 gossip_flat_sharding=None,
                 block: int = DEFAULT_GOSSIP_BLOCK,
                 compression: str = "none",
                 error_feedback: bool = False,
                 wire: str = "simulated",
                 staleness: int = 0) -> ConsensusBackend:
    """Map a ``DFLConfig.consensus_mode`` string to a ``ConsensusBackend``.

    The robust screens take an optional spec argument after a colon:
    ``"trimmed_mean[:f]"`` (default f=1) and ``"clipped[:mult]"`` (default
    clip_mult=1.0); ``"median"`` is parameter-free.

    ``compression`` other than ``"none"`` (a ``comm.compressors.
    make_compressor`` spec, e.g. ``"int8"`` / ``"top_k:0.05"``) wraps the
    resolved backend in a ``CompressedBackend``, optionally with error
    feedback; ``wire`` selects the simulated (once-per-period, host byte
    ledger) vs physical (codes through the collectives, per-round) wire —
    see ``CompressedBackend``.  ``shard_map`` is absent on purpose: it
    needs a mesh and per-leaf PartitionSpecs, so the launcher builds it
    directly (``launch.sharding.fl_consensus_backend``, which applies the
    same compression wrap).

    ``staleness`` (bounded-staleness depth, see ``gossip_scan_stale``)
    threads into the literal T_S-round schedules only — every other mode
    has no per-round message stream to delay and refuses loudly."""
    base, _, arg = mode.partition(":")
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    if staleness and base not in ("gossip", "gossip_blocked"):
        raise ValueError(
            f"bounded staleness needs the literal T_S-round W <- A W "
            f"schedule (round t consumes round t-s's messages); mode "
            f"{mode!r} has no per-round message stream to delay — use "
            f"'gossip'/'gossip_blocked' (or the launcher's shard_map "
            f"backend) or staleness=0")
    if mode == "gossip":
        backend = GossipBackend(a_static, t_server, staleness=staleness)
    elif mode == "gossip_blocked":
        backend = BlockedGossipBackend(a_static, t_server, block=block,
                                       flat_sharding=gossip_flat_sharding,
                                       staleness=staleness)
    elif mode == "collapsed":
        backend = CollapsedBackend(a_static, t_server)
    elif mode == "chebyshev":
        backend = ChebyshevBackend(a_static, t_server,
                                   rounds=chebyshev_rounds)
    elif mode == "exact_mean":
        backend = ExactMeanBackend(a_static, t_server)
    elif base == "trimmed_mean":
        if arg and not arg.isdigit():
            raise ValueError(f"bad trimmed_mean spec {mode!r}: expected "
                             f"'trimmed_mean[:f]' with integer f >= 0")
        backend = TrimmedMeanBackend(a_static, t_server,
                                     f=int(arg) if arg else 1)
    elif base == "median":
        if arg:
            raise ValueError(f"bad median spec {mode!r}: the coordinatewise "
                             f"median takes no parameter")
        backend = MedianBackend(a_static, t_server)
    elif base == "clipped":
        try:
            clip_mult = float(arg) if arg else 1.0
        except ValueError:
            raise ValueError(f"bad clipped spec {mode!r}: expected "
                             f"'clipped[:mult]' with float mult > 0")
        backend = ClippedGossipBackend(a_static, t_server,
                                       clip_mult=clip_mult)
    else:
        raise ValueError(f"unknown consensus mode {mode!r}")
    if compression != "none":
        backend = CompressedBackend(
            backend, _compressors.make_compressor(compression),
            error_feedback=error_feedback,
            flat_sharding=gossip_flat_sharding,
            wire=wire, wire_block=block)
    return backend
