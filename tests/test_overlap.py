"""PR-10 overlap contracts: superepoch megastep parity, bounded-staleness
gossip, the device-sync ledger, and the software-pipelined wire kernel.

Three families of assertions:

* **degeneration** — ``staleness=0`` and ``superepoch=1`` are not "almost"
  the old paths, they ARE the old paths: history and final state bitwise
  equal under partial participation + edge drops + drop/rejoin churn.
* **parity** — the fused K-epoch megastep reproduces the barrier engine's
  per-epoch history element-for-element at K in {1, 2, 4}, through fault
  surgery (blocks split at fault epochs), and the pipelined Pallas round
  kernel is bit-identical to the stale jnp oracle.
* **overlap semantics** — ``gossip_scan_stale`` realises the exact
  operator ``A^{floor(T_S / (s+1))}``, s=1 still converges on the m=8
  regression within the fig-3 tolerance, and the superepoch engine issues
  exactly ONE host readback per dispatched block (counted through the
  injectable ``_device_get`` hook).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFLConfig, FLTopology, FaultSchedule,
                        ParticipationSchedule, TopologySchedule,
                        build_dfl_superepoch_step, gossip_scan_stale,
                        init_dfl_state, make_backend, make_engine,
                        stack_epoch_schedules)
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.core.schedule import EpochSchedule, SigmaTracker
from repro.comm.compressors import StochasticQuantizer, pack_int4
from repro.data import RegressionSpec, make_regression_task
from repro.kernels.consensus_mix import bucketed_gossip_round_pipelined_2d
from repro.obs import FIG3_TOLERANCE
from repro.optim import sgd

M, N, GAMMA = 4, 3, 1e-2


def _engine(superepoch=1, staleness=0, *, m=M, n=N, t_client=3, t_server=4,
            faults="drop:3:2,rejoin:5:2", seed=0, epochs_hint=None,
            **cfg_kw):
    """A churny scenario: Bernoulli participation + per-epoch edge drops +
    a drop/rejoin cycle — the harshest schedule the parity claims cover."""
    topo = FLTopology(num_servers=m, clients_per_server=n,
                      t_client=t_client, t_server=t_server,
                      graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.3),
                                seed=seed)
    eng = make_engine(
        topo, task["loss_fn"], sgd(GAMMA),
        participation=ParticipationSchedule(kind="bernoulli", rate=0.6,
                                            seed=seed + 3),
        topology_schedule=TopologySchedule(kind="edge_drop", drop_prob=0.3,
                                           seed=seed + 5),
        faults=FaultSchedule.parse(faults),
        superepoch=superepoch, staleness=staleness, **cfg_kw)
    state = init_dfl_state(eng.cfg, jnp.zeros((2,)), sgd(GAMMA),
                           jax.random.key(seed))
    return eng, state, task["batch_fn"]


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# superepoch: history + state parity with the barrier engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 4])
def test_superepoch_history_parity_bitwise(k):
    """K-epoch megastep == barrier loop, element-bitwise, through
    participation + edge drops + drop/rejoin churn (blocks split at the
    fault epochs)."""
    eng1, st1, bf1 = _engine(1)
    st1, h1 = eng1.run(st1, 7, bf1)
    engk, stk, bfk = _engine(k)
    stk, hk = engk.run(stk, 7, bfk)
    assert set(h1) == set(hk)
    for key in h1:
        assert h1[key] == hk[key], key
    _assert_tree_equal(st1.client_params, stk.client_params)


def test_superepoch_parity_push_sum_and_byzantine():
    """The stacked optional operands (byz codes, per-epoch psum weights)
    ride the scan too: parity holds under push_sum + a byzantine schedule
    + a robust screen, including the per-epoch psum_min_weight and
    screen_rejected columns."""
    from repro.core import ByzantineSchedule
    scenarios = (
        dict(faults="", mixing="push_sum"),
        dict(faults="", consensus_mode="trimmed_mean:1",
             byzantine=ByzantineSchedule.parse("sign_flip:0.3", seed=7)),
    )
    want_cols = ({"psum_min_weight"}, {"byzantine", "screen_rejected"})
    for kw, cols in zip(scenarios, want_cols):
        eng1, st1, bf1 = _engine(1, **kw)
        st1, h1 = eng1.run(st1, 6, bf1)
        eng3, st3, bf3 = _engine(3, **kw)
        st3, h3 = eng3.run(st3, 6, bf3)
        assert set(h1) == set(h3) and cols <= set(h1)
        for key in h1:
            assert h1[key] == h3[key], key
        _assert_tree_equal(st1.client_params, st3.client_params)


def test_superepoch_parity_compressed_wire():
    """wire_mb / wire_ratio history columns match per-epoch: the block
    ledger (``BytesTracker.update_many``) snapshots the cumulative ratio
    after each epoch, not after the block."""
    kw = dict(compression="int8:8", error_feedback=True, wire="physical")
    eng1, st1, bf1 = _engine(1, **kw)
    st1, h1 = eng1.run(st1, 6, bf1)
    eng2, st2, bf2 = _engine(2, **kw)
    st2, h2 = eng2.run(st2, 6, bf2)
    assert "wire_mb" in h1 and "wire_ratio" in h1
    for key in h1:
        assert h1[key] == h2[key], key
    _assert_tree_equal(st1.client_params, st2.client_params)


def test_superepoch_compile_once_per_m_k():
    """The stacked EpochScheduleBatch is a traced operand: one program per
    (M, K), however the masks/matrices/codes vary across blocks."""
    eng, st, bf = _engine(4)
    eng.run(st, 12, bf)
    counts = eng.superepoch_compile_counts()
    assert counts and all(c == 1 for c in counts.values()), counts
    # blocks split at fault epochs 3 and 5 -> K in {4, 3, 2, 1} appear
    assert {k for (_, k) in counts} >= {2, 3}


def test_plan_blocks_cuts_at_faults():
    eng, _, _ = _engine(4)
    blocks = eng._plan_blocks(10)
    # faults at 3 and 5: [0,3) [3,5) [5,10) chunked by <= 4
    assert blocks == [(0, 3), (3, 2), (5, 4), (9, 1)]
    assert sum(k for _, k in blocks) == 10
    starts = [e for e, _ in blocks]
    assert 3 in starts and 5 in starts


def test_stack_epoch_schedules_validation():
    a = np.eye(2, dtype=np.float32)
    mask = np.ones((2, 3), np.float32)
    with pytest.raises(ValueError, match="empty"):
        stack_epoch_schedules([])
    mixed = [EpochSchedule(mask, a, None, np.zeros(2, np.int32)),
             EpochSchedule(mask, a, None, None)]
    with pytest.raises(ValueError, match="uniform operand structure"):
        stack_epoch_schedules(mixed)
    sb = stack_epoch_schedules([EpochSchedule(mask, a)] * 3)
    assert sb.k == 3 and sb.mask.shape == (3, 2, 3)
    assert sb.lam2 is None and sb.byz is None


def test_superepoch_step_refuses_static_and_k0():
    topo = FLTopology(num_servers=2, clients_per_server=2, t_client=1,
                      t_server=1, graph_kind="complete")
    task = make_regression_task(topo, seed=0)
    with pytest.raises(ValueError, match="dynamic"):
        build_dfl_superepoch_step(DFLConfig(topology=topo),
                                  task["loss_fn"], sgd(GAMMA), 2)
    with pytest.raises(ValueError, match=">= 1"):
        build_dfl_superepoch_step(DFLConfig(topology=topo, dynamic=True),
                                  task["loss_fn"], sgd(GAMMA), 0)


# ---------------------------------------------------------------------------
# the device-sync ledger (satellite 1)
# ---------------------------------------------------------------------------


def test_one_device_get_per_dispatch():
    """EVERY host metric readback flows through the injectable
    ``_device_get`` hook: the barrier engine syncs exactly once per epoch
    (not once per metric — the old scattered float()/np.asarray reads),
    and the superepoch engine exactly once per K-epoch block."""
    for superepoch, epochs, dispatches in ((1, 6, 6), (3, 6, 2), (6, 6, 1)):
        eng, st, bf = _engine(superepoch, faults="", mixing="push_sum")
        calls = []
        real = eng._device_get
        eng._device_get = lambda x: (calls.append(1), real(x))[1]
        eng.run(st, epochs, bf)
        assert len(calls) == dispatches, (superepoch, len(calls))


# ---------------------------------------------------------------------------
# bounded staleness: semantics, degeneration, convergence
# ---------------------------------------------------------------------------


def test_gossip_scan_stale_zero_is_gossip_scan():
    a = jnp.asarray(tp.metropolis_weights(tp.ring_graph(5)), jnp.float32)
    tree = {"w": jax.random.normal(jax.random.key(0), (5, 7)),
            "b": jax.random.normal(jax.random.key(1), (5, 2, 3))}
    out0 = jax.jit(lambda t: gossip_scan_stale(a, t, 6, 0))(tree)
    ref = jax.jit(lambda t: cns.gossip_scan(a, t, 6))(tree)
    _assert_tree_equal(out0, ref)


@pytest.mark.parametrize("s,t_server", [(1, 2), (1, 5), (1, 8), (2, 7)])
def test_gossip_scan_stale_exact_operator(s, t_server):
    """Exact arithmetic: T_S stale rounds apply A^{floor(T_S/(s+1))} — the
    contraction SigmaTracker budgets for."""
    a = tp.metropolis_weights(tp.ring_graph(5)).astype(np.float32)
    w = np.random.default_rng(0).normal(size=(5, 4)).astype(np.float32)
    out = jax.jit(lambda t: gossip_scan_stale(
        jnp.asarray(a), t, t_server, s))({"w": jnp.asarray(w)})
    want = np.linalg.matrix_power(a, t_server // (s + 1)) @ w
    np.testing.assert_allclose(np.asarray(out["w"]), want, atol=1e-5)


def test_sigma_tracker_staleness_contraction():
    a = tp.metropolis_weights(tp.ring_graph(5))
    sync = SigmaTracker(5).update(a, 6)
    stale = SigmaTracker(5, staleness=1).update(a, 6)
    ref = SigmaTracker(5).update(a, 3)          # A^3 == 6 rounds at s=1
    assert stale == pytest.approx(ref)
    assert stale > sync                         # weaker contraction


def test_staleness0_engine_bitwise_degeneration():
    """DFLConfig(staleness=0) IS the synchronous path — bitwise, through
    participation + edge drops + churn, on both the einsum and the blocked
    backend."""
    for mode in ("gossip", "gossip_blocked"):
        eng0, st0, bf0 = _engine(1, consensus_mode=mode)
        st0, h0 = eng0.run(st0, 7, bf0)
        engz, stz, bfz = _engine(1, 0, consensus_mode=mode)
        stz, hz = engz.run(stz, 7, bfz)
        for key in h0:
            assert h0[key] == hz[key], (mode, key)
        _assert_tree_equal(st0.client_params, stz.client_params)


def test_staleness1_converges_fig3_m8():
    """s=1 on the m=8 regression: consensus still contracts (operator
    A^{floor(T_S/2)} per epoch) and the run lands within the fig-3
    disagreement tolerance of obs.monitor."""
    eng, st, bf = _engine(2, 1, m=8, n=2, t_client=10, t_server=10,
                          faults="")
    st, hist = eng.run(st, 40, bf)
    assert hist["disagreement"][-1] < FIG3_TOLERANCE
    # and the s=0 twin agrees on the final loss to fig-3 precision
    eng0, st0, bf0 = _engine(2, 0, m=8, n=2, t_client=10, t_server=10,
                             faults="")
    st0, hist0 = eng0.run(st0, 40, bf0)
    assert abs(hist["loss"][-1] - hist0["loss"][-1]) < FIG3_TOLERANCE


def test_staleness_refusal_matrix():
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=1,
                      t_server=2, graph_kind="complete")
    from repro.core import PushSumState, init_push_sum
    with pytest.raises(ValueError, match="staleness"):
        make_backend("gossip", topo.mixing_matrix(), 2,
                     staleness=1).mix_push_sum(
            init_push_sum({"w": jnp.zeros((3, 2))}))
    with pytest.raises(ValueError, match="staleness"):
        make_backend("collapsed", topo.mixing_matrix(), 2, staleness=1)
    with pytest.raises(ValueError, match="negative|>= 0"):
        make_backend("gossip", topo.mixing_matrix(), 2, staleness=-1)
    task = make_regression_task(topo, seed=0)
    from repro.core import build_dfl_epoch_step
    with pytest.raises(ValueError, match="push_sum"):
        build_dfl_epoch_step(
            DFLConfig(topology=topo, mixing="push_sum", staleness=1),
            task["loss_fn"], sgd(GAMMA))
    with pytest.raises(ValueError, match="none"):
        build_dfl_epoch_step(
            DFLConfig(topology=topo, consensus_mode="none", staleness=1),
            task["loss_fn"], sgd(GAMMA))
    # simulated-wire compression + staleness is incoherent: there is no
    # physical collective to overlap
    inner = cns.GossipBackend(topo.mixing_matrix(), 2, staleness=1)
    with pytest.raises(ValueError, match="physical"):
        cns.CompressedBackend(inner, StochasticQuantizer(bits=8, chunk=4),
                              wire="simulated")


@pytest.mark.slow
def test_stale_bucketed_wire_matches_shard_map():
    """The simulated stale wire (``gossip_scan_wire_bucketed`` with
    staleness) is bitwise the multi-device pipelined shard_map program —
    the double-buffered collective really computes the same recursion."""
    r = subprocess.run([sys.executable, "-c", _STALE_WIRE],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]


_STALE_WIRE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.comm import compressors as cp
from repro.comm import accounting as acc

m, blk, chunk = 4, 32, 16
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
tree = {"w": jax.random.normal(jax.random.key(0), (m, 4, 33)) * 2,
        "b": jax.random.normal(jax.random.key(1), (m, 7))}
specs = {"w": P("server", None, None), "b": P("server", None)}
key = jax.random.key(9)
a = jnp.asarray(tp.metropolis_weights(tp.ring_graph(m)), jnp.float32)

for bits in (8, 4):
    codec = cp.StochasticQuantizer(bits=bits, chunk=chunk)
    for s, t_s in ((1, 5), (2, 7)):
        run = cns.make_gossip_shard_map(mesh, t_s, specs, block=blk,
                                        codec=codec, staleness=s)
        ref = jax.jit(lambda t: cns.gossip_scan_wire_bucketed(
            a, t, t_s, codec, key, block=blk, staleness=s))(tree)
        out = run(a, tree, key)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(ref[k]), err_msg=f"{bits}:{s}:{k}")
    # the pipelined program keeps the 2-gather-per-round structure
    run1 = cns.make_gossip_shard_map(mesh, 5, specs, block=blk,
                                     codec=codec, staleness=1)
    hlo = jax.jit(run1).lower(a, tree, key).compile().as_text()
    gathers = [c for c in acc.hlo_collective_bytes(hlo)
               if c["op"] == "all-gather"]
    assert len(gathers) == 2, gathers
    assert sorted(c["dtype"] for c in gathers) == ["f32", "s8"], gathers

# staleness without the delta-coded wire must refuse at build time
try:
    cns.make_gossip_shard_map(mesh, 5, specs, block=blk, staleness=1)
except ValueError as e:
    assert "codec" in str(e)
else:
    raise AssertionError("plain shard_map accepted staleness")
print("OK")
"""


# ---------------------------------------------------------------------------
# the software-pipelined Pallas round kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_pipelined_kernel_matches_stale_oracle(bits):
    """encode -> own-decode -> delayed left-to-right consume, bit-identical
    to the stale wire body's jnp form for both code widths."""
    m, d, chunk = 4, 1024, 128
    codec = StochasticQuantizer(bits=bits, chunk=chunk)
    rng = np.random.default_rng(0)
    a = tp.metropolis_weights(tp.ring_graph(m)).astype(np.float32)
    w = rng.normal(size=(m, d)).astype(np.float32)
    ref = rng.normal(size=(m, d)).astype(np.float32) * 0.1
    acc = rng.normal(size=(m, d)).astype(np.float32) * 0.1
    qmax = 2 ** (bits - 1) - 1
    old_c = rng.integers(-qmax, qmax, size=(m, d)).astype(np.int8)
    old_s = (np.abs(rng.normal(size=(m, d // chunk))) + 0.1
             ).astype(np.float32)
    dither = np.full((m, d), 0.5, np.float32)
    # the oracle consumes codes in the codec's STORAGE layout (packed for
    # int4), the kernel in the UNPACKED all-gather layout
    old_c_oracle = (np.asarray(pack_int4(old_c)) if bits == 4 else old_c)

    def oracle(a, old_c, old_s, w, ref, acc, dither):
        a32 = a.astype(jnp.float32)
        delta = w.astype(jnp.float32) - ref
        codes, scales = codec.encode_block(delta, dither)
        own3 = codec.code_chunks(codes, d)
        ref2 = ref + (own3 * scales[..., None]).reshape(m, d)
        c3 = codec.code_chunks(old_c, d).astype(jnp.float32)
        ws = a32[:, :, None] * old_s
        acc3 = acc.reshape(m, -1, chunk)
        for j in range(m):
            acc3 = acc3 + ws[:, j, :, None] * c3[j]
        return acc3.reshape(m, d), ref2, codes, scales

    oa, orf, oq, osc = jax.jit(oracle)(a, old_c_oracle, old_s, w, ref,
                                       acc, dither)
    ka, kr, kq, ks = jax.jit(
        lambda *xs: bucketed_gossip_round_pipelined_2d(
            *xs, bits=bits, chunk=chunk, block_d=512, interpret=True))(
        a, old_c, old_s, w, ref, acc, dither)
    # the kernel ships UNPACKED codes; unpack the oracle's for bits=4
    oq_flat = np.asarray(codec.code_chunks(oq, d)).reshape(m, d)
    np.testing.assert_array_equal(oq_flat, np.asarray(kq))
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(ka))
    np.testing.assert_array_equal(np.asarray(orf), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(osc), np.asarray(ks))


def test_pipelined_kernel_validation():
    z = jnp.zeros((2, 128), jnp.float32)
    c = jnp.zeros((2, 128), jnp.int8)
    s = jnp.ones((2, 1), jnp.float32)
    with pytest.raises(ValueError, match="bits"):
        bucketed_gossip_round_pipelined_2d(jnp.eye(2), c, s, z, z, z, z,
                                           bits=3, chunk=128, interpret=True)
    with pytest.raises(ValueError, match="divide D"):
        bucketed_gossip_round_pipelined_2d(jnp.eye(2), c[:, :100], s,
                                           z[:, :100], z[:, :100],
                                           z[:, :100], z[:, :100],
                                           chunk=32, interpret=True)
