"""Physical-wire quantized gossip: the int8/int4 codes that actually cross
the collectives.  Covers the shard-shaped codec (encode_block/decode_block
== the compressor round-trip, bit for bit), the shared dither convention,
the in-graph wire reference vs the blocked streaming schedule (bitwise),
the CompressedBackend wire='physical' dispatch + error feedback, pad-tail
neutrality, the counter-based O(k) random-k sampler, the fused
gather-dequant-mix-requant kernels (per-leaf and bucketed), the BUCKETED
wire layout (one padded code buffer + one scale buffer for the whole
pytree -> one all-gather pair per round), the engine's physical byte
ledger, and — in subprocesses with a forced multi-device mesh — the
shard_map / ring collective programs: physical vs simulated bitwise
parity, the compiled-HLO proof that the all-gather / ppermute operands
are s8 codes + f32 scales (not bf16/f32 payload), and the
one-collective-pair-per-round site count invariant in the leaf count."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import accounting as acc
from repro.comm import compressors as cp
from repro.core import (DFLConfig, EpochSchedule, FLTopology,
                        build_dfl_epoch_step, init_dfl_state, make_engine)
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.data import RegressionSpec, make_regression_task
from repro.optim import sgd

M, T_S = 5, 7


def _ring(m=M):
    return jnp.asarray(tp.metropolis_weights(tp.ring_graph(m)), jnp.float32)


def _tree(key, m=M):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m, 4, 33)) * 2,
            "b": jax.random.normal(k2, (m, 7))}


# ---------------------------------------------------------------------------
# the codec: one numerics definition, packed int4, shared dither
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("length", [1, 2, 7, 16, 255])
def test_pack_unpack_int4_roundtrip(length):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(-8, 8, (3, length)), jnp.int8)
    packed = cp.pack_int4(codes)
    assert packed.shape[-1] == -(-length // 2)          # two codes per byte
    np.testing.assert_array_equal(
        np.asarray(cp.unpack_int4(packed, length)), np.asarray(codes))


@pytest.mark.parametrize("spec", ["int8:16", "int4:16", "int8", "int4:8"])
def test_encode_block_is_the_compressor_roundtrip(spec, rng_key):
    """decode_block(encode_block(x)) is BITWISE decompress(compress(x))
    under the same dither — the wire format and the in-graph simulation
    share one numerics definition."""
    q = cp.make_compressor(spec)
    x = jax.random.normal(rng_key, (M, 100)) * 3
    u = cp.wire_dither(jax.random.key(0), x.shape, leaf=0, rnd=2, server=1,
                       block=3)
    codes, scales = q.encode_block(x, u)
    ref = q.decompress(q.compress(x, dither=u), x.shape[-1])
    np.testing.assert_array_equal(
        np.asarray(q.decode_block(codes, scales, x.shape[-1])),
        np.asarray(ref))
    code_bytes, scale_bytes = q.wire_block_bytes(100)
    assert codes.shape[-1] == code_bytes         # int8: 1 B/code; int4: 2/B
    assert scales.shape[-1] * 4 == scale_bytes


def test_wire_dither_convention_is_coordinate_keyed():
    key = jax.random.key(3)
    base = cp.wire_dither(key, (8,), leaf=0, rnd=1, server=2, block=3)
    again = cp.wire_dither(key, (8,), leaf=0, rnd=1, server=2, block=3)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(again))
    for other in ({"leaf": 1, "rnd": 1, "server": 2, "block": 3},
                  {"leaf": 0, "rnd": 2, "server": 2, "block": 3},
                  {"leaf": 0, "rnd": 1, "server": 3, "block": 3},
                  {"leaf": 0, "rnd": 1, "server": 2, "block": 4}):
        assert not np.array_equal(
            np.asarray(cp.wire_dither(key, (8,), **other)), np.asarray(base))
    u = np.asarray(base)
    assert (u >= 0).all() and (u < 1).all()      # floor(0 + u) == 0 for pads


# ---------------------------------------------------------------------------
# counter-based random-k sampling (O(k) at LM scale)
# ---------------------------------------------------------------------------


def test_keyed_index_sample_distinct_uniform_coordinated():
    for d, k in ((10, 10), (1000, 37), (257, 1), (2, 2)):
        idx = np.asarray(cp.keyed_index_sample(jax.random.key(3), d, k))
        assert len(set(idx.tolist())) == k                    # a bijection
        assert idx.min() >= 0 and idx.max() < d
    # seed coordination: the property that makes random-k index-free on
    # the wire — every server regenerates the identical coordinate set
    a = cp.keyed_index_sample(jax.random.key(5), 100, 10)
    b = cp.keyed_index_sample(jax.random.key(5), 100, 10)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="0 < k <= d"):
        cp.keyed_index_sample(jax.random.key(0), 4, 5)
    # 32-bit ceiling: past int32 the gather indices would silently alias
    with pytest.raises(ValueError, match="32-bit"):
        cp.keyed_index_sample(jax.random.key(0), 1 << 31, 8)


def test_keyed_index_sample_lm_scale_is_o_k():
    """d = 2^30: the old jax.random.permutation sampler would allocate and
    sort a 4 GB index vector; the counter hash touches k counters."""
    idx = np.asarray(jax.jit(
        lambda key: cp.keyed_index_sample(key, 1 << 30, 8))(
            jax.random.key(1)))
    assert len(set(idx.tolist())) == 8
    assert idx.min() >= 0 and idx.max() < (1 << 30)


def test_random_k_compressor_uses_counter_sampler(rng_key):
    c = cp.RandomKCompressor(ratio=0.1)
    x = jax.random.normal(rng_key, (4, 50))
    comp = c.compress(x, rng_key)
    np.testing.assert_array_equal(
        np.asarray(comp.idx),
        np.asarray(cp.keyed_index_sample(rng_key, 50, 5)))


# ---------------------------------------------------------------------------
# in-graph wire gossip: schedules agree bitwise; pads are inert
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ["int8:16", "int4:16"])
@pytest.mark.parametrize("transpose", [False, True],
                         ids=["symmetric", "push_sum_operator"])
def test_wire_round_major_equals_block_major_bitwise(spec, transpose,
                                                     rng_key):
    """The einsum-style (round-major) and blocked-streaming (block-major)
    wire schedules are the identical operator bit for bit — blocks gossip
    and encode independently."""
    a = _ring()
    a = jnp.swapaxes(a, 0, 1) if transpose else a
    codec = cp.make_compressor(spec)
    tree = _tree(rng_key)
    key = jax.random.key(11)
    o1 = jax.jit(lambda t: cns.gossip_scan_wire(
        a, t, T_S, codec, key, block=32))(tree)
    o2 = jax.jit(lambda t: cns.gossip_scan_wire(
        a, t, T_S, codec, key, block=32, block_major=True))(tree)
    for l1, l2 in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_wire_gossip_zero_pad_tail_is_inert(rng_key):
    """The ragged tail block is zero-padded; zeros never perturb a real
    chunk's absmax scale and quantize to zero codes, so the ragged run is
    bitwise the explicitly-padded run and pads stay exactly zero."""
    a = _ring()
    codec = cp.StochasticQuantizer(bits=8, chunk=16)
    key = jax.random.key(2)
    w = jax.random.normal(rng_key, (M, 132)) * 3        # 132 = 4*32 + 4
    ragged = cns.gossip_scan_wire(a, {"w": w}, T_S, codec, key,
                                  block=32)["w"]
    padded = cns.gossip_scan_wire(
        a, {"w": jnp.pad(w, ((0, 0), (0, 28)))}, T_S, codec, key,
        block=32)["w"]
    np.testing.assert_array_equal(np.asarray(ragged),
                                  np.asarray(padded[:, :132]))
    np.testing.assert_array_equal(np.asarray(padded[:, 132:]), 0.0)
    # unit form: a chunk straddling real data and pad keeps the scale of
    # its real elements (|0| never raises an absmax)
    x = jnp.asarray(np.r_[np.full(4, 8.0), np.zeros(12)], jnp.float32)
    _, scales = codec.encode_block(x[None], 0.5)
    assert float(scales[0, 0]) == pytest.approx(8.0 / 127.0)


def test_wire_roundtrip_tree_matches_round0(rng_key):
    """wire_roundtrip_tree IS round 0 of the wire gossip: one round of
    gossip with the identity operator reproduces it exactly."""
    codec = cp.StochasticQuantizer(bits=8, chunk=16)
    tree = _tree(rng_key)
    key = jax.random.key(7)
    ship = cns.wire_roundtrip_tree(codec, tree, key, block=32)
    eye = jnp.eye(M, dtype=jnp.float32)
    one_round = cns.gossip_scan_wire(eye, tree, 1, codec, key, block=32)
    for l1, l2 in zip(jax.tree.leaves(ship), jax.tree.leaves(one_round)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the bucketed wire: one code buffer for the whole pytree
# ---------------------------------------------------------------------------


def test_bucket_block_layout():
    """blk rounds UP to a multiple of lcm(chunk, 2) so chunks never
    straddle blocks and int4 packs pairwise without a ragged byte."""
    assert cp.bucket_block(139, 1 << 24, 16) == (144, 1)   # pad to unit
    assert cp.bucket_block(139, 32, 16) == (32, 5)         # tile small blk
    assert cp.bucket_block(7, 1 << 24, 3) == (12, 1)       # odd chunk: x2
    assert cp.bucket_block(1, 1, 2) == (2, 1)


def test_bucketed_wire_is_leaf_structure_invariant(rng_key):
    """The bucketed wire flattens the whole pytree into ONE padded code
    buffer, so splitting the same payload across different leaf
    boundaries changes nothing — bitwise.  (The legacy per-leaf layout
    re-padded and re-scaled every leaf.)"""
    a = _ring()
    codec = cp.StochasticQuantizer(bits=8, chunk=16)
    key = jax.random.key(6)
    w = jax.random.normal(rng_key, (M, 132)) * 3
    one = cns.gossip_scan_wire_bucketed(a, {"w": w}, T_S, codec, key,
                                        block=32)
    two = cns.gossip_scan_wire_bucketed(
        a, {"a": w[:, :100], "b": w[:, 100:]}, T_S, codec, key, block=32)
    np.testing.assert_array_equal(
        np.asarray(one["w"]),
        np.asarray(jnp.concatenate([two["a"], two["b"]], axis=1)))


def test_bucketed_roundtrip_tree_matches_round0(rng_key):
    """bucketed_roundtrip_tree IS round 0 of the bucketed wire gossip:
    one identity-operator round reproduces it exactly."""
    codec = cp.StochasticQuantizer(bits=8, chunk=16)
    tree = _tree(rng_key)
    key = jax.random.key(7)
    ship = cns.bucketed_roundtrip_tree(codec, tree, key, block=32)
    eye = jnp.eye(M, dtype=jnp.float32)
    one = cns.gossip_scan_wire_bucketed(eye, tree, 1, codec, key,
                                        block=32)
    for l1, l2 in zip(jax.tree.leaves(ship), jax.tree.leaves(one)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# CompressedBackend wire='physical': dispatch, EF, push-sum, validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["gossip", "gossip_blocked"])
def test_physical_backend_matches_wire_reference(mode, rng_key):
    """Every in-graph mode of CompressedBackend(wire='physical') runs the
    ONE bucketed wire recursion — gossip_scan_wire_bucketed is the oracle
    for both, bit for bit, and the EF residual is what round 0 withheld
    under the same bucket layout."""
    be = cns.make_backend(mode, np.asarray(_ring()), T_S, block=32,
                          compression="int8:16", error_feedback=True,
                          wire="physical")
    assert be.wire == "physical" and be.wire_block == 32
    assert be.name == f"compressed[{mode}+int8+wire]"
    tree = _tree(rng_key)
    key = jax.random.key(4)
    res0 = jax.tree.map(jnp.zeros_like, tree)
    out, res = be.mix_compressed(tree, key=key, residual=res0)
    ref = cns.gossip_scan_wire_bucketed(_ring(), tree, T_S, be.compressor,
                                        key, block=32)
    for l1, l2 in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    # EF: the residual is what round 0 withheld of each server's own model
    ship = cns.bucketed_roundtrip_tree(be.compressor, tree, key, block=32)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(res[k]),
                                      np.asarray(tree[k] - ship[k]))


def test_physical_push_sum_weight_exact(rng_key):
    a_dir = tp.out_degree_weights(tp.directed_ring(M))
    be = cns.make_backend("gossip", a_dir, T_S, block=64,
                          compression="int8:16", wire="physical")
    tree = _tree(rng_key)
    key = jax.random.key(8)
    ps, _ = be.mix_push_sum_compressed(cns.init_push_sum(tree), key=key)
    w = np.asarray(ps.weight)
    assert (w > 0).all()
    np.testing.assert_allclose(w.sum(), M, rtol=1e-5)
    # the numerator rode the quantized bucketed wire, transposed operator
    ref = cns.gossip_scan_wire_bucketed(
        jnp.asarray(a_dir, jnp.float32).T, tree, T_S, be.compressor, key,
        block=64)
    np.testing.assert_array_equal(np.asarray(ps.values["w"]),
                                  np.asarray(ref["w"]))


def test_physical_wire_validation():
    a_np = np.asarray(_ring())
    with pytest.raises(ValueError, match="wire byte format"):
        cns.make_backend("gossip", a_np, T_S, compression="top_k:0.1",
                         wire="physical")
    with pytest.raises(ValueError, match="wire byte format"):
        cns.make_backend("gossip", a_np, T_S, compression="identity",
                         wire="physical")
    for mode in ("collapsed", "chebyshev", "exact_mean"):
        with pytest.raises(ValueError, match="per-round wire"):
            cns.make_backend(mode, a_np, T_S, compression="int8",
                             wire="physical")
    with pytest.raises(ValueError, match="simulated.*physical|physical"):
        cns.CompressedBackend(cns.make_backend("gossip", a_np, T_S),
                              cp.make_compressor("int8"), wire="bogus")


def test_active_wire_resolution():
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=2,
                      t_server=2)
    from repro.core.dfl import active_wire
    assert active_wire(DFLConfig(topology=topo)) == \
        ("simulated", cns.DEFAULT_GOSSIP_BLOCK)
    cfg = DFLConfig(topology=topo, compression="int8", wire="physical")
    assert active_wire(cfg)[0] == "physical"
    be = cns.make_backend("gossip_blocked", topo.mixing_matrix(), 2,
                          block=128, compression="int8", wire="physical")
    cfg = DFLConfig(topology=topo, consensus_backend=be)
    assert active_wire(cfg) == ("physical", 128)


# ---------------------------------------------------------------------------
# epoch-step + engine integration
# ---------------------------------------------------------------------------


def _setup(m=4, n=2, t_c=3, t_s=8):
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    return topo, task


def test_physical_epoch_step_converges_near_uncompressed():
    topo, task = _setup()
    opt = sgd(1e-3)
    cfg_ref = DFLConfig(topology=topo)
    cfg_phy = DFLConfig(topology=topo, compression="int8:16",
                        error_feedback=True, wire="physical")
    step_ref = jax.jit(build_dfl_epoch_step(cfg_ref, task["loss_fn"], opt))
    step_phy = jax.jit(build_dfl_epoch_step(cfg_phy, task["loss_fn"], opt))
    s_ref = init_dfl_state(cfg_ref, jnp.zeros((2,)), opt, jax.random.key(0))
    s_phy = init_dfl_state(cfg_phy, jnp.zeros((2,)), opt, jax.random.key(0))
    for _ in range(4):
        s_ref, _ = step_ref(s_ref, task["batches"])
        s_phy, _ = step_phy(s_phy, task["batches"])
    ref = np.asarray(s_ref.client_params)
    out = np.asarray(s_phy.client_params)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() < 0.05 * np.abs(ref).max()
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree.leaves(s_phy.ef_residual))


def test_physical_dynamic_push_sum_epoch_step():
    topo, task = _setup()
    opt = sgd(1e-3)
    cfg = DFLConfig(topology=topo, mixing="push_sum", compression="int8:16",
                    error_feedback=True, wire="physical", dynamic=True)
    step = jax.jit(build_dfl_epoch_step(cfg, task["loss_fn"], opt))
    state = init_dfl_state(cfg, jnp.zeros((2,)), opt, jax.random.key(0))
    mask = jnp.ones((topo.num_servers, topo.clients_per_server), jnp.float32)
    for e in range(3):
        a_np = tp.out_degree_weights(tp.random_direction_drop(
            topo.adjacency(), 0.3, np.random.default_rng(e),
            ensure_strong=True))
        state, _ = step(state, task["batches"],
                        EpochSchedule(mask, jnp.asarray(a_np, jnp.float32)))
        w = np.asarray(state.psum_weight)
        assert (w > 0).all()
        np.testing.assert_allclose(w.sum(), topo.num_servers, rtol=1e-5)
    assert np.isfinite(np.asarray(state.client_params)).all()


@pytest.mark.parametrize("mixing", ["symmetric", "push_sum"])
def test_engine_physical_ledger_counts_collective_bytes(mixing):
    """Under wire='physical' the BytesTracker charges exactly the bucketed
    codes + scales the collectives gather — the closed form
    accounting.tree_bucketed_wire_bytes_per_server — for BOTH mixing
    modes: push-sum's (M,) weight never crosses a collective (it mixes by
    an in-graph replicated matvec), so no +4 B/msg surcharge may appear
    on the physical ledger (the HLO byte audit counts none)."""
    topo, task = _setup()
    engine = make_engine(topo, task["loss_fn"], sgd(1e-3), mixing=mixing,
                         compression="int8:16", error_feedback=True,
                         wire="physical")
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    _, rec = engine.run_epoch(state, 0, task["batch_fn"])
    q = engine._compressor
    row = acc.tree_bucketed_wire_bytes_per_server(
        q, jnp.zeros((topo.num_servers, 2)), cns.DEFAULT_GOSSIP_BLOCK)
    links = 2 * topo.num_servers                        # directed ring edges
    assert rec["wire_mb"] * 1e6 == links * topo.t_server * row
    # at this toy scale (2 params/server) the 16-element bucket pad
    # dominates, so the ratio is exactly baseline/padded — below 1; real
    # payloads amortise the pad (benchmarks record ~3.9x for int8)
    assert rec["wire_ratio"] == pytest.approx((4 * 2) / row)


def test_engine_zero_gossip_epoch_reports_zero_wire():
    """t_server=0: no gossip rounds, nothing on the wire — the record must
    carry THIS epoch's 0.0 (the update() return), never a stale or
    missing history entry."""
    topo = FLTopology(num_servers=4, clients_per_server=2, t_client=3,
                      t_server=0, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    engine = make_engine(topo, task["loss_fn"], sgd(1e-3),
                         compression="int8:16", wire="physical")
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    for epoch in range(2):
        state, rec = engine.run_epoch(state, epoch, task["batch_fn"])
        assert rec["wire_mb"] == 0.0
        assert np.isfinite(rec["loss"])


def test_push_sum_weight_surcharge_only_on_simulated_wire():
    """BytesTracker: the +4 B/msg push-sum weight rides the SIMULATED wire
    only; the physical ledger is the bare bucketed row on both mixings."""
    q = cp.StochasticQuantizer(bits=8, chunk=16)
    a = np.asarray(_ring())
    row, links = 40, 2 * M
    phys_ps = acc.BytesTracker(q, push_sum=True, wire="physical")
    phys = acc.BytesTracker(q, push_sum=False, wire="physical")
    sim_ps = acc.BytesTracker(q, push_sum=True)
    kw = dict(row_bytes=row, elems_per_row=10)
    assert phys_ps.update(a, T_S, **kw) == phys.update(a, T_S, **kw) \
        == links * T_S * row
    assert sim_ps.update(a, T_S, **kw) == links * T_S * (row + 4)


def test_physical_bytes_closed_form():
    q = cp.StochasticQuantizer(bits=8, chunk=16)
    # d=132, block=32: 5 blocks of (32 codes + 2 scales x 4 B) = 5 x 40
    assert acc.physical_leaf_bytes(q, (M, 132), 32) == 5 * 40
    q4 = cp.StochasticQuantizer(bits=4, chunk=16)
    assert acc.physical_leaf_bytes(q4, (M, 132), 32) == 5 * (16 + 8)
    tree = {"w": jnp.zeros((M, 132)), "b": jnp.zeros((M, 7))}
    assert acc.tree_physical_wire_bytes_per_server(q, tree, 32) == \
        5 * 40 + (7 + 4)
    with pytest.raises(ValueError, match="quantizers"):
        acc.physical_leaf_bytes(cp.TopKCompressor(0.1), (M, 10), 32)


def test_bucketed_bytes_closed_form():
    """tree_bucketed_wire_bytes_per_server: d_tot = 132 + 7 = 139 -> one
    144-element bucket (chunk unit 16): 144 codes + 9 scales; int4 packs
    two codes per byte; a small block tiles instead."""
    q = cp.StochasticQuantizer(bits=8, chunk=16)
    tree = {"w": jnp.zeros((M, 132)), "b": jnp.zeros((M, 7))}
    assert acc.tree_bucketed_wire_bytes_per_server(q, tree, 1 << 24) == \
        144 + 9 * 4
    q4 = cp.StochasticQuantizer(bits=4, chunk=16)
    assert acc.tree_bucketed_wire_bytes_per_server(q4, tree, 1 << 24) == \
        72 + 9 * 4
    assert acc.tree_bucketed_wire_bytes_per_server(q, tree, 32) == \
        5 * (32 + 2 * 4)
    with pytest.raises(ValueError, match="quantizers"):
        acc.tree_bucketed_wire_bytes_per_server(cp.TopKCompressor(0.1),
                                                tree, 32)


def test_trainer_cli_wire_flag():
    from repro.launch.train import build_parser
    args = build_parser().parse_args(["--compression", "int8", "--wire",
                                      "physical"])
    assert args.wire == "physical"
    assert build_parser().parse_args([]).wire == "simulated"


def test_plan_wire_defaults():
    from repro.launch.plans import plan_for
    for arch in ("mixtral_8x22b", "deepseek_v2_236b", "jamba_1_5_large_398b"):
        assert plan_for(arch).wire == "physical", arch
        assert plan_for(arch).compression == "int8"
    assert plan_for("smollm_360m").wire == "simulated"


def test_wire_runner_cache_hits_for_fresh_equal_codec():
    """ShardMapBackend.wire_runner caches per (codec, mode) with
    VALUE-hashed codecs: a freshly constructed StochasticQuantizer of
    equal config must return the SAME runner (a miss would retrace and
    recompile the collective program every epoch); a different config or
    mode must not."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("server",))
    be = cns.ShardMapBackend(mesh, np.eye(1, dtype=np.float32), T_S,
                             {"w": P("server", None)})
    r1 = be.wire_runner(cp.StochasticQuantizer(bits=8, chunk=16))
    assert be.wire_runner(cp.StochasticQuantizer(bits=8, chunk=16)) is r1
    assert be.wire_runner(cp.StochasticQuantizer(bits=4, chunk=16)) \
        is not r1
    assert be.wire_runner(cp.StochasticQuantizer(bits=8, chunk=16),
                          with_shipped=True) is not r1
    assert len(be._wire_runners) == 3


# ---------------------------------------------------------------------------
# the fused gather-dequant-mix-requant kernel (jnp wire path = the oracle)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_gossip_round_kernel_bitwise(bits, rng_key):
    """The fused delta-round kernel reproduces the jnp wire recursion
    (decode -> accumulate reference -> mix -> encode next innovations) bit
    for bit, chained over several rounds."""
    from repro.kernels.consensus_mix import quantized_gossip_round_2d

    m, d, chunk = M, 1024, 32
    q = cp.StochasticQuantizer(bits=bits, chunk=chunk)
    a = _ring()
    x = jax.random.normal(rng_key, (m, d)) * 3
    u0 = jax.random.uniform(jax.random.key(1), (m, d))
    comp = q.compress(x, dither=u0)         # round-0 wire state (R_0 = 0)

    @jax.jit
    def oracle(codes, scales, ref, u):
        ref = ref + q.decompress(cp.Compressed(data=codes, scale=scales), d)
        mixed = cns._wire_mix_rows(a, ref)
        nxt = q.compress(mixed - ref, dither=u)
        return mixed, ref, nxt.data, nxt.scale

    @jax.jit
    def kernel(codes, scales, ref, u):
        return quantized_gossip_round_2d(a, codes, scales, ref, u,
                                         bits=bits, chunk=chunk,
                                         block_d=256, interpret=True)

    codes_r, scales_r = comp.data, comp.scale
    codes_k, scales_k = comp.data, comp.scale
    ref_r = ref_k = jnp.zeros((m, d), jnp.float32)
    for t in range(1, 4):
        u = jax.random.uniform(jax.random.key(10 + t), (m, d))
        w_r, ref_r, codes_r, scales_r = oracle(codes_r, scales_r, ref_r, u)
        w_k, ref_k, codes_k, scales_k = kernel(codes_k, scales_k, ref_k, u)
        np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
        np.testing.assert_array_equal(np.asarray(ref_k), np.asarray(ref_r))
        np.testing.assert_array_equal(np.asarray(codes_k),
                                      np.asarray(codes_r))
        np.testing.assert_array_equal(np.asarray(scales_k),
                                      np.asarray(scales_r))


def test_quantized_gossip_round_kernel_validation(rng_key):
    from repro.kernels.consensus_mix import quantized_gossip_round_2d
    codes = jnp.zeros((M, 100), jnp.int8)
    ref = jnp.zeros((M, 100), jnp.float32)
    with pytest.raises(ValueError, match="divide D"):
        quantized_gossip_round_2d(_ring(), codes, jnp.ones((M, 4)), ref,
                                  jnp.zeros((M, 100)), chunk=32,
                                  interpret=True)
    with pytest.raises(ValueError, match="bits"):
        quantized_gossip_round_2d(_ring(), codes, jnp.ones((M, 4)), ref,
                                  jnp.zeros((M, 100)), bits=3, chunk=25,
                                  interpret=True)


@pytest.mark.parametrize("bits", [8, 4])
def test_bucketed_round_kernels_bitwise(bits, rng_key):
    """The bucketed-wire kernels — encode (round 0) + the fused
    decode-accumulate-mix-requant round — chained over 3 rounds reproduce
    the jnp bucketed recursion bit for bit: codes, scales, reference band
    and accumulator alike."""
    from repro.kernels.consensus_mix import (bucketed_gossip_round_2d,
                                             quantized_gossip_encode_2d)

    m, d, chunk, block_d = 4, 96, 16, 32
    q = cp.StochasticQuantizer(bits=bits, chunk=chunk)
    # dyadic lazy-ring operator: 0.5 / 0.25 products are exact in f32, so
    # the comparison is FMA-neutral — the pallas kernel and the XLA
    # oracle may fuse the multiply-adds differently, and with exact
    # products both roundings coincide bit for bit
    a_np = np.eye(m, dtype=np.float32) * 0.5
    for i in range(m):
        a_np[i, (i + 1) % m] += 0.25
        a_np[i, (i - 1) % m] += 0.25
    a = jnp.asarray(a_np)
    w0 = jax.random.normal(rng_key, (m, d)) * 3
    u = [jax.random.uniform(jax.random.key(20 + t), (m, d))
         for t in range(4)]

    @jax.jit
    def oracle(w0):
        ref, accum = jnp.zeros((m, d)), jnp.zeros((m, d))
        w, outs = w0, []
        for t in range(3):
            comp = q.compress(w - ref, dither=u[t])
            dec = q.decompress(cp.Compressed(comp.data, comp.scale), d)
            ref = ref + dec
            for j in range(m):
                accum = accum + a[:, j, None] * dec[j]
            w = accum
            outs.append((comp.data, comp.scale, ref, accum))
        return outs

    @jax.jit
    def kernels(w0):
        codes, scales = quantized_gossip_encode_2d(
            w0, jnp.zeros((m, d)), u[0], bits=bits, chunk=chunk,
            block_d=block_d, interpret=True)
        ref, accum, outs = jnp.zeros((m, d)), jnp.zeros((m, d)), []
        for t in range(3):
            accum, ref, nxt_c, nxt_s = bucketed_gossip_round_2d(
                a, codes, scales, ref, accum, u[t + 1], bits=bits,
                chunk=chunk, block_d=block_d, interpret=True)
            outs.append((codes, scales, ref, accum))
            codes, scales = nxt_c, nxt_s
        return outs

    for t, (got, want) in enumerate(zip(kernels(w0), oracle(w0))):
        for name, g, r in zip(("codes", "scales", "ref", "acc"), got,
                              want):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(r),
                err_msg=f"bits={bits} round={t} {name}")


def test_bucketed_kernel_validation():
    from repro.kernels.consensus_mix import (bucketed_gossip_round_2d,
                                             quantized_gossip_encode_2d)
    w = jnp.zeros((M, 100))
    with pytest.raises(ValueError, match="bits"):
        quantized_gossip_encode_2d(w, w, w, bits=3, interpret=True)
    with pytest.raises(ValueError, match="divide D"):
        quantized_gossip_encode_2d(w, w, w, chunk=32, interpret=True)
    codes = jnp.zeros((M, 100), jnp.int8)
    with pytest.raises(ValueError, match="divide D"):
        bucketed_gossip_round_2d(_ring(), codes, jnp.ones((M, 4)), w, w,
                                 w, chunk=32, interpret=True)


# ---------------------------------------------------------------------------
# the collectives themselves: shard_map + ring subprocess parity & HLO
# ---------------------------------------------------------------------------

_SHARD_MAP_WIRE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.comm import compressors as cp
from repro.comm import accounting as acc

m, t_s, blk, chunk = 4, 5, 32, 16
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
tree = {"w": jax.random.normal(jax.random.key(0), (m, 4, 33)) * 2,
        "b": jax.random.normal(jax.random.key(1), (m, 7)),
        "c": jax.random.normal(jax.random.key(2), (m, 11))}
specs = {"w": P("server", None, None), "b": P("server", None),
         "c": P("server", None)}
key = jax.random.key(9)
a = jnp.asarray(tp.metropolis_weights(tp.ring_graph(m)), jnp.float32)

# --- bitwise parity: the bucketed collective program == the in-graph
# bucketed reference under shared dither, both operators, int8 AND int4
for bits in (8, 4):
    codec = cp.StochasticQuantizer(bits=bits, chunk=chunk)
    run_p = cns.make_gossip_shard_map(mesh, t_s, specs, block=blk,
                                      codec=codec)
    run_s = cns.make_gossip_shard_map(mesh, t_s, specs, block=blk,
                                      codec=codec, gather_codes=False)
    ref_fn = jax.jit(lambda op, t: cns.gossip_scan_wire_bucketed(
        op, t, t_s, codec, key, block=blk))
    for op in (a, a.T):               # symmetric + push-sum numerator
        out_p, out_s, ref = run_p(op, tree, key), run_s(op, tree, key), \
            ref_fn(op, tree)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(out_p[k]), np.asarray(ref[k]), err_msg=k)
            np.testing.assert_array_equal(
                np.asarray(out_p[k]), np.asarray(out_s[k]), err_msg=k)
    # with_shipped (the EF hook) is a where-select in the loop carry, not
    # a peeled round: the mixed output stays bitwise the plain program's,
    # and the round-0 transmission is bucketed_roundtrip_tree
    run_ef = cns.make_gossip_shard_map(mesh, t_s, specs, block=blk,
                                       codec=codec, with_shipped=True)
    mixed, shipped = run_ef(a, tree, key)
    plain = run_p(a, tree, key)
    ship_ref = jax.jit(lambda t: cns.bucketed_roundtrip_tree(
        codec, t, key, block=blk))(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(mixed[k]),
                                      np.asarray(plain[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(shipped[k]),
                                      np.asarray(ship_ref[k]), err_msg=k)

# --- compiled HLO: exactly ONE all-gather pair (codes + scales) in the
# round body, invariant in the leaf count — the whole pytree rides one
# bucket, and the gathered bytes ARE the ledger's bucketed closed form
for nleaf in (1, 3, 7):
    t2 = {f"l{i}": jax.random.normal(jax.random.key(i), (m, 13 + 5 * i))
          for i in range(nleaf)}
    s2 = {f"l{i}": P("server", None) for i in range(nleaf)}
    d_tot = sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(t2))
    for bits, ws in ((8, False), (4, False), (8, True)):
        q = cp.StochasticQuantizer(bits=bits, chunk=chunk)
        run = cns.make_gossip_shard_map(mesh, t_s, s2, block=blk, codec=q,
                                        with_shipped=ws)
        hlo = jax.jit(run).lower(a, t2, key).compile().as_text()
        cols = acc.hlo_collective_bytes(hlo)
        gathers = [c for c in cols if c["op"] == "all-gather"]
        # 2 SITES in the fori_loop body (executed t_s times each)
        assert len(gathers) == 2, (nleaf, bits, ws, gathers)
        assert sorted(c["dtype"] for c in gathers) == ["f32", "s8"], \
            (nleaf, bits, ws, gathers)
        got = sum(c["bytes"] // m for c in gathers)
        want = acc.tree_bucketed_wire_bytes_per_server(q, t2, blk)
        assert got == want, (nleaf, bits, ws, got, want)
        # nothing payload-sized crosses in float — and int4's s8 buffer
        # is half of int8's via the closed form above
        assert not any(c["dtype"] in ("f32", "bf16", "u16")
                       and c["bytes"] // m >= 4 * d_tot
                       for c in cols), cols

# the uncompressed program really does gather the f32 payload (baseline)
hlo0 = jax.jit(cns.make_gossip_shard_map(mesh, t_s, specs, block=blk)
               ).lower(a, tree).compile().as_text()
base = acc.hlo_collective_bytes(hlo0)
assert any(c["dtype"] == "f32" and c["bytes"] // m == 4 * blk
           for c in base), base
print("OK")
"""


@pytest.mark.slow
def test_shard_map_physical_wire_parity_and_hlo():
    """The tentpole, end to end: the BUCKETED shard_map wire program is
    bitwise the in-graph reference under shared dither (physical ==
    simulated == gossip_scan_wire_bucketed, both operators, int8 AND
    packed int4, with and without the EF hook), and the compiled HLO
    proves each round is exactly one all-gather of s8 codes + one of f32
    scales — regardless of leaf count — whose bytes equal
    accounting.tree_bucketed_wire_bytes_per_server, never a payload-sized
    float buffer."""
    r = subprocess.run([sys.executable, "-c", _SHARD_MAP_WIRE],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]


_RING_WIRE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import consensus as cns
from repro.comm import compressors as cp
from repro.comm import accounting as acc

m, t_s = 4, 6
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
tree = {"w": jax.random.normal(jax.random.key(0), (m, 3, 11)) * 2}
key = jax.random.key(5)
sw, nw = 0.5, 0.25
base = cns.make_ring_gossip(mesh, "server", t_s, sw, nw)(tree)
for bits in (8, 4):
    codec = cp.StochasticQuantizer(bits=bits, chunk=8)
    rp = cns.make_ring_gossip(mesh, "server", t_s, sw, nw, codec=codec)
    rs = cns.make_ring_gossip(mesh, "server", t_s, sw, nw, codec=codec,
                              gather_codes=False)
    op = np.asarray(rp(tree, key)["w"])
    np.testing.assert_array_equal(op, np.asarray(rs(tree, key)["w"]))
    # quantized ring stays near the exact ring (sanity, not parity; int4
    # re-quantizes a ~N(0, 2) payload at every one of the 6 hops)
    tol = 0.1 if bits == 8 else 0.8
    assert np.abs(op - np.asarray(base["w"])).max() < tol, bits
codec = cp.StochasticQuantizer(bits=8, chunk=8)
rp = cns.make_ring_gossip(mesh, "server", t_s, sw, nw, codec=codec)
hlo = jax.jit(rp).lower(tree, key).compile().as_text()
cols = acc.hlo_collective_bytes(hlo)
perms = [c for c in cols if c["op"] == "collective-permute"]
assert sorted({c["dtype"] for c in perms}) == ["f32", "s8"], perms
L = 33                                              # local 3*11 payload
assert all(c["bytes"] == L for c in perms if c["dtype"] == "s8"), perms
assert all(c["bytes"] == 4 * -(-L // 8) for c in perms
           if c["dtype"] == "f32"), perms
print("OK")
"""


@pytest.mark.slow
def test_ring_physical_wire_parity_and_hlo():
    """make_ring_gossip with a codec: ppermute of s8 codes + f32 scales,
    bitwise identical to its simulated (floats-on-the-wire) twin."""
    r = subprocess.run([sys.executable, "-c", _RING_WIRE],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]


_ENGINE_WIRE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import (FLTopology, TopologySchedule, init_dfl_state,
                        make_engine)
from repro.data import RegressionSpec, make_regression_task
from repro.launch import sharding as shd
from repro.optim import sgd

m = 4
topo = FLTopology(num_servers=m, clients_per_server=2, t_client=4,
                  t_server=5, graph_kind="ring")
task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5), seed=0)
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
server_abs = jax.eval_shape(lambda: jnp.zeros((m, 2), jnp.float32))
backend = shd.fl_consensus_backend(topo, mesh, server_abs, tp_axis=None,
                                   block=8, compression="int8:2",
                                   error_feedback=True, wire="physical")
assert backend.wire == "physical" and backend.mesh_bound
# chunk=2 matches d=2: a wider chunk would pad the bucketed code buffer
# past the 8-byte f32 baseline and push the tiny-model ratio below 1
finals = {}
for name, kw in (("einsum_wire", {"compression": "int8:2",
                                  "error_feedback": True,
                                  "wire": "physical"}),
                 ("shard_map_wire", {"consensus_backend": backend})):
    engine = make_engine(
        topo, task["loss_fn"], sgd(1e-3),
        topology_schedule=TopologySchedule(kind="edge_drop", drop_prob=0.4,
                                           seed=3), **kw)
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    state, hist = engine.run(state, 3, task["batch_fn"])
    finals[name] = np.asarray(state.client_params)
    assert hist["wire_ratio"][-1] > 1.0
# same rng stream, same codec numerics -> the einsum wire reference and
# the physical shard_map collectives agree to fp tolerance end to end
# (the wire block differs: 8 vs DEFAULT_GOSSIP_BLOCK covers whole rows
# either way at d=2... keep blocks equal for the strict check)
np.testing.assert_allclose(finals["shard_map_wire"], finals["einsum_wire"],
                           rtol=2e-4, atol=2e-5)
print("OK")
"""


@pytest.mark.slow
def test_engine_shard_map_physical_wire_matches_einsum_wire():
    """Dynamic engine, edge-drop schedule, int8 physical wire: the
    mesh-aware shard_map collective path tracks the in-graph einsum wire
    reference through full epochs."""
    r = subprocess.run([sys.executable, "-c", _ENGINE_WIRE],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]
