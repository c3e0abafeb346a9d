"""The dynamic-federation engine: partial participation, time-varying
graphs, fault schedules, one server per device — and its degeneration to
the static paper setting (all-ones mask + static A == seed ``gossip``, to
within a few ulp)."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFLConfig, EpochSchedule, FaultEvent, FaultSchedule,
                        FLTopology, ParticipationSchedule, SigmaTracker,
                        TopologySchedule, build_dfl_epoch_step,
                        init_dfl_state, make_engine, masked_server_mean)
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.data import RegressionSpec, make_regression_task
from repro.optim import momentum, sgd


def _setup(m=5, n=5, t_c=15, t_s=8, seed=0, heterogeneity=0.0):
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(
        heterogeneity=heterogeneity), seed=seed)
    return topo, task["loss_fn"], task["batches"], task["w_star"]


# ---------------------------------------------------------------------------
# exact degeneration to the static paper setting
# ---------------------------------------------------------------------------


def test_all_ones_mask_static_graph_reproduces_gossip():
    """Dynamic step with full participation + the static A reproduces the
    static 'gossip' epoch step to within a few ulp.  Not bitwise: the
    static step bakes A into the program as a constant, the dynamic one
    takes it as an operand, and XLA folds the two differently (after 4
    epochs the installed XLA differs by at most 7 ulp on params and 3 ulp
    on loss).  The bound of 16 ulp leaves room above that."""
    topo, loss_fn, batches, _ = _setup()
    gamma = 1e-3
    opt = sgd(gamma)
    step_s = jax.jit(build_dfl_epoch_step(
        DFLConfig(topology=topo), loss_fn, opt))
    step_d = jax.jit(build_dfl_epoch_step(
        DFLConfig(topology=topo, dynamic=True), loss_fn, opt))
    st_s = init_dfl_state(DFLConfig(topology=topo), jnp.zeros((2,)), opt,
                          jax.random.key(0))
    st_d = st_s
    sched = EpochSchedule(
        jnp.ones((topo.num_servers, topo.clients_per_server), jnp.float32),
        jnp.asarray(topo.mixing_matrix(), jnp.float32))
    for _ in range(4):
        st_s, m_s = step_s(st_s, batches)
        st_d, m_d = step_d(st_d, batches, sched)
    np.testing.assert_array_max_ulp(np.asarray(st_s.client_params),
                                    np.asarray(st_d.client_params), 16)
    np.testing.assert_array_max_ulp(np.asarray(m_s.loss),
                                    np.asarray(m_d.loss), 16)


def test_constant_tv_schedule_matches_gossip_scan(rng_key):
    """gossip_scan_tv with T_S copies of A == gossip_scan(A, ·, T_S)."""
    m, t_s = 6, 9
    a = jnp.asarray(tp.metropolis_weights(tp.ring_graph(m)), jnp.float32)
    tree = {"w": jax.random.normal(rng_key, (m, 4, 3)),
            "b": jax.random.normal(jax.random.fold_in(rng_key, 1), (m, 7))}
    stack = jnp.broadcast_to(a, (t_s,) + a.shape)
    out_tv = cns.gossip_scan_tv(stack, tree)
    out_ref = cns.gossip_scan(a, tree, t_s)
    for l1, l2 in zip(jax.tree.leaves(out_tv), jax.tree.leaves(out_ref)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_tv_gossip_preserves_mean_under_varying_graphs(rng_key):
    """Each round's A_t is doubly stochastic, so any schedule of distinct
    graphs still fixes the server mean."""
    m = 5
    mats = [tp.metropolis_weights(tp.ring_graph(m)),
            tp.metropolis_weights(tp.line_graph(m)),
            tp.metropolis_weights(tp.complete_graph(m))]
    stack = jnp.asarray(np.stack(mats), jnp.float32)
    w = jax.random.normal(rng_key, (m, 11))
    out = cns.gossip_scan_tv(stack, {"w": w})["w"]
    np.testing.assert_allclose(np.asarray(w.mean(0)), np.asarray(out.mean(0)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# masked aggregation (Eq. 4 over the participating set)
# ---------------------------------------------------------------------------


def test_masked_mean_is_subset_mean(rng_key):
    m, n = 4, 6
    x = jax.random.normal(rng_key, (m, n, 3))
    mask_np = (np.random.default_rng(0).random((m, n)) < 0.5)
    mask_np[:, 0] = True                       # keep every server non-empty
    out = masked_server_mean({"w": x}, jnp.asarray(mask_np, jnp.float32))["w"]
    for i in range(m):
        ref = np.asarray(x)[i][mask_np[i]].mean(axis=0)
        np.testing.assert_allclose(np.asarray(out[i]), ref, rtol=1e-6,
                                   atol=1e-6)


def test_masked_mean_iid_participants_preserve_server_mean(rng_key):
    """When every client of a server holds the SAME model (the IID broadcast
    state), the masked mean equals the server mean for every mask —
    participation sampling introduces no bias."""
    m, n = 3, 5
    base = jax.random.normal(rng_key, (m, 1, 4))
    x = jnp.broadcast_to(base, (m, n, 4))
    for seed in range(3):
        mask = (np.random.default_rng(seed).random((m, n)) < 0.4)
        out = masked_server_mean({"w": x}, jnp.asarray(mask, jnp.float32))["w"]
        np.testing.assert_allclose(np.asarray(out), np.asarray(base[:, 0]),
                                   rtol=1e-6, atol=1e-6)


def test_fully_idle_server_carries_model_through_epoch():
    """mask row of zeros: the server's aggregate falls back to the broadcast
    model it started the epoch with."""
    topo, loss_fn, batches, _ = _setup(m=3, n=2, t_c=5, t_s=4)
    opt = sgd(1e-3)
    cfg = DFLConfig(topology=topo, dynamic=True, consensus_mode="none")
    step = jax.jit(build_dfl_epoch_step(cfg, loss_fn, opt))
    state = init_dfl_state(cfg, jnp.ones((2,)), opt, jax.random.key(0))
    mask = np.ones((3, 2), np.float32)
    mask[1] = 0.0                                # server 1 fully idle
    sched = EpochSchedule(jnp.asarray(mask),
                          jnp.asarray(topo.mixing_matrix(), jnp.float32))
    new_state, _ = step(state, batches, sched)
    # with consensus off, idle server 1 must still hold w_0 exactly
    np.testing.assert_array_equal(
        np.asarray(new_state.client_params[1]),
        np.asarray(state.client_params[1]))
    # the training servers moved
    assert np.abs(np.asarray(new_state.client_params[0])
                  - np.asarray(state.client_params[0])).max() > 1e-6


def test_non_participant_data_never_influences_result():
    """Masking client (0, 1) out makes its batch contents irrelevant — same
    result with its data replaced by garbage (participation isolation)."""
    topo, loss_fn, (bx, by), _ = _setup(m=2, n=3, t_c=5, t_s=4)
    opt = sgd(1e-3)
    cfg = DFLConfig(topology=topo, dynamic=True)
    step = jax.jit(build_dfl_epoch_step(cfg, loss_fn, opt))
    state = init_dfl_state(cfg, jnp.zeros((2,)), opt, jax.random.key(0))
    mask = np.ones((2, 3), np.float32)
    mask[0, 1] = 0.0
    sched = EpochSchedule(jnp.asarray(mask),
                          jnp.asarray(topo.mixing_matrix(), jnp.float32))
    out1, _ = step(state, (bx, by), sched)
    bad_bx = bx.at[:, 0, 1].set(1e6)             # garbage in masked slot
    bad_by = by.at[:, 0, 1].set(-1e6)
    out2, _ = step(state, (bad_bx, bad_by), sched)
    np.testing.assert_array_equal(np.asarray(out1.client_params),
                                  np.asarray(out2.client_params))


def test_carry_forward_preserves_optimizer_state():
    """Stateful optimizers: a non-participant's momentum buffer must freeze
    while the shared step count still advances."""
    topo, loss_fn, batches, _ = _setup(m=2, n=2, t_c=3, t_s=2)
    opt = momentum(1e-3)
    cfg = DFLConfig(topology=topo, dynamic=True)
    step = jax.jit(build_dfl_epoch_step(cfg, loss_fn, opt))
    state = init_dfl_state(cfg, jnp.zeros((2,)), opt, jax.random.key(0))
    mask = np.asarray([[1.0, 0.0], [1.0, 1.0]], np.float32)
    sched = EpochSchedule(jnp.asarray(mask),
                          jnp.asarray(topo.mixing_matrix(), jnp.float32))
    new_state, _ = step(state, batches, sched)
    vel_old = np.asarray(state.opt_state.velocity)
    vel_new = np.asarray(new_state.opt_state.velocity)
    np.testing.assert_array_equal(vel_new[0, 1], vel_old[0, 1])  # frozen
    assert np.abs(vel_new[0, 0] - vel_old[0, 0]).max() > 0       # trained
    assert int(new_state.opt_state.count) == topo.t_client


# ---------------------------------------------------------------------------
# participation / topology schedules (host side)
# ---------------------------------------------------------------------------


def test_participation_schedules_shapes_and_determinism():
    for sched in (ParticipationSchedule(),
                  ParticipationSchedule(kind="bernoulli", rate=0.3, seed=3),
                  ParticipationSchedule(kind="fixed_k", k=2, seed=3),
                  ParticipationSchedule(kind="round_robin", k=2)):
        m1 = sched.mask(7, 4, 5)
        m2 = sched.mask(7, 4, 5)
        np.testing.assert_array_equal(m1, m2)       # deterministic in epoch
        assert m1.shape == (4, 5) and m1.dtype == np.float32
        assert set(np.unique(m1)) <= {0.0, 1.0}
        assert (m1.sum(axis=1) >= 1).all()          # min_per_server=1
    with pytest.raises(ValueError):
        ParticipationSchedule(kind="bogus")
    with pytest.raises(ValueError):
        ParticipationSchedule(kind="fixed_k")        # k missing


def test_round_robin_covers_all_clients():
    sched = ParticipationSchedule(kind="round_robin", k=2)
    seen = np.zeros(6, bool)
    for e in range(3):
        seen |= sched.mask(e, 2, 6)[0].astype(bool)
    assert seen.all()


def test_topology_schedule_emits_valid_mixing():
    topo = FLTopology(num_servers=6, clients_per_server=2, t_client=5,
                      t_server=3, graph_kind="ring")
    for sched in (TopologySchedule(),
                  TopologySchedule(kind="edge_drop", drop_prob=0.5, seed=1),
                  TopologySchedule(kind="straggler", weaken=0.9, n_weak=2,
                                   seed=1)):
        for epoch in range(4):
            a = sched.mixing(topo, epoch)
            tp.check_mixing_matrix(a)                # doubly stochastic
            # a degraded graph contracts slower but must still contract
            assert tp.sigma_a(a, 50) < 0.1
    with pytest.raises(ValueError):
        TopologySchedule(kind="bogus")


def test_sigma_tracker_matches_matrix_power():
    a = tp.metropolis_weights(tp.ring_graph(5))
    tr = SigmaTracker(5)
    for p in range(1, 4):
        got = tr.update(a, 6)
        assert got == pytest.approx(tp.sigma_a(a, 6 * p), abs=1e-12)
    # product form agrees with topology.sigma_product
    mats = [a, tp.metropolis_weights(tp.line_graph(5))]
    tr2 = SigmaTracker(5)
    for mat in mats:
        last = tr2.update(mat, 3)
    assert last == pytest.approx(tp.sigma_product(mats, 3), abs=1e-12)


def test_fault_schedule_parse_and_validation():
    fs = FaultSchedule.parse("drop:5:2, rejoin:9:2")
    assert fs.at(5) == (FaultEvent(5, "drop", 2),)
    assert fs.at(9) == (FaultEvent(9, "rejoin", 2),)
    assert fs.at(7) == ()
    assert fs.last_epoch == 9
    assert FaultSchedule.parse("").events == ()
    with pytest.raises(ValueError):
        FaultEvent(1, "explode", 0)


# ---------------------------------------------------------------------------
# end-to-end scenarios
# ---------------------------------------------------------------------------


def test_partial_participation_converges():
    """Bernoulli(0.5) sampling still lands near w* (slower, not broken)."""
    topo, loss_fn, batches, w_star = _setup(t_c=20, t_s=10)
    gamma = 0.4 / (9.0 * topo.t_client)

    def batch_fn(epoch, alive):
        ids = np.asarray(alive)
        return batches[0][:, ids], batches[1][:, ids]

    engine = make_engine(topo, loss_fn, sgd(gamma),
                         participation=ParticipationSchedule(
                             kind="bernoulli", rate=0.5, seed=3))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                           jax.random.key(0))
    state, hist = engine.run(state, 60, batch_fn)
    servers = np.asarray(state.client_params[:, 0])
    err = float(np.linalg.norm(servers - w_star, axis=-1).max())
    assert err < 0.3, err
    assert 0.2 < np.mean(hist["participation"]) < 0.8


def test_edge_drop_schedule_converges():
    """Per-epoch degraded (but repaired-to-connected) graphs still reach
    consensus near w*."""
    topo, loss_fn, batches, w_star = _setup(t_c=20, t_s=10)
    gamma = 0.4 / (9.0 * topo.t_client)

    def batch_fn(epoch, alive):
        ids = np.asarray(alive)
        return batches[0][:, ids], batches[1][:, ids]

    engine = make_engine(topo, loss_fn, sgd(gamma),
                         topology_schedule=TopologySchedule(
                             kind="edge_drop", drop_prob=0.4, seed=5))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                           jax.random.key(0))
    state, hist = engine.run(state, 60, batch_fn)
    servers = np.asarray(state.client_params[:, 0])
    err = float(np.linalg.norm(servers - w_star, axis=-1).max())
    assert err < 0.3, err
    assert hist["disagreement"][-1] < 1e-2
    assert hist["sigma_prod"][-1] < 1e-6


def test_fault_drop_and_rejoin_converges():
    """Mid-run server failure AND recovery: drop server 2 at epoch 8, rejoin
    at epoch 20 (it re-enters with the survivor mean and its own clients'
    data), and the 5-server federation still converges to the full-data w*.
    Extends the static drop-only test in test_dfl_convergence.py."""
    topo, loss_fn, batches, w_star = _setup(t_c=20, t_s=10)
    gamma = 0.35 / (9.0 * topo.t_client)

    def batch_fn(epoch, alive):
        ids = np.asarray(alive)
        return batches[0][:, ids], batches[1][:, ids]

    engine = make_engine(topo, loss_fn, sgd(gamma),
                         faults=FaultSchedule((FaultEvent(8, "drop", 2),
                                               FaultEvent(20, "rejoin", 2))))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                           jax.random.key(0))
    state, hist = engine.run(state, 60, batch_fn)
    assert engine.alive == [0, 1, 3, 4, 2]
    assert hist["num_servers"][7] == 5.0
    assert hist["num_servers"][8] == 4.0
    assert hist["num_servers"][20] == 5.0
    servers = np.asarray(state.client_params[:, 0])
    err = float(np.linalg.norm(servers - w_star, axis=-1).max())
    assert err < 0.3, err
    assert hist["disagreement"][-1] < 1e-2


def test_engine_rejects_bad_fault_events():
    topo, loss_fn, batches, _ = _setup(m=3, n=2, t_c=3, t_s=2)
    gamma = 1e-3

    def batch_fn(epoch, alive):
        ids = np.asarray(alive)
        return batches[0][:, ids], batches[1][:, ids]

    # ids outside the ORIGINAL federation fail at CONSTRUCTION, not mid-run:
    # a fresh server has no data shard (batch_fn slices by original id)
    for kind in ("drop", "rejoin"):
        with pytest.raises(ValueError, match="ORIGINAL"):
            make_engine(topo, loss_fn, sgd(gamma),
                        faults=FaultSchedule((FaultEvent(0, kind, 7),)))
    # dropping a server twice is a runtime liveness error
    engine = make_engine(topo, loss_fn, sgd(gamma),
                         faults=FaultSchedule((FaultEvent(0, "drop", 2),
                                               FaultEvent(0, "drop", 2))))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                           jax.random.key(0))
    with pytest.raises(ValueError, match="not alive"):
        engine.run(state, 1, batch_fn)
    # rejoin of an alive server is also rejected
    engine2 = make_engine(topo, loss_fn, sgd(gamma),
                          faults=FaultSchedule((FaultEvent(0, "rejoin", 1),)))
    state2 = init_dfl_state(engine2.cfg, jnp.zeros((2,)), sgd(gamma),
                            jax.random.key(0))
    with pytest.raises(ValueError, match="already alive"):
        engine2.run(state2, 1, batch_fn)
    # direct fresh-id rejoin (the old crash path: _next_id minting) is gone
    engine3 = make_engine(topo, loss_fn, sgd(gamma))
    state3 = init_dfl_state(engine3.cfg, jnp.zeros((2,)), sgd(gamma),
                            jax.random.key(0))
    with pytest.raises(ValueError, match="ORIGINAL"):
        engine3._rejoin(state3, None)
    with pytest.raises(ValueError, match="ORIGINAL"):
        engine3._rejoin(state3, 5)


def test_dynamic_chebyshev_consumes_traced_a_p():
    """Chebyshev now rides the dynamic engine: the per-epoch spectral
    estimate arrives as a traced operand (``EpochSchedule.lam2``, computed
    host-side by the engine via ``topology.lambda_2``), so the semi-
    iterative recursion serves time-varying graphs through ONE compiled
    program — the formerly-prohibited combination."""
    topo = FLTopology(num_servers=4, clients_per_server=2, t_client=3,
                      t_server=9, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    engine = make_engine(topo, task["loss_fn"], sgd(1e-3),
                         consensus_mode="chebyshev",
                         topology_schedule=TopologySchedule(
                             kind="edge_drop", drop_prob=0.3, seed=5))
    assert engine._needs_spectral
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    state, hist = engine.run(state, 4, task["batch_fn"])
    assert np.isfinite(hist["loss"]).all()
    # the accelerated rounds still contract server disagreement
    assert hist["disagreement"][-1] < 5e-2


def test_chebyshev_backend_traced_matches_reference():
    """ChebyshevBackend.mix with a TRACED (A_p, lam2) pair equals the
    host-side gossip_chebyshev recursion on the same concrete matrix, for
    per-epoch matrices the backend was NOT built with."""
    m, t_s = 5, 9
    base = tp.metropolis_weights(tp.ring_graph(m))
    backend = cns.make_backend("chebyshev", base, t_s)
    assert backend.supports_traced and backend.needs_spectral
    tree = {"w": jax.random.normal(jax.random.key(1), (m, 6))}
    mixed_jit = jax.jit(backend.mix)
    for a_np in (base, tp.metropolis_weights(tp.complete_graph(m)),
                 tp.metropolis_weights(tp.line_graph(m))):
        lam2 = tp.lambda_2(a_np)
        a = jnp.asarray(a_np, jnp.float32)
        out = mixed_jit(tree, a, jnp.float32(lam2))
        ref = cns.gossip_chebyshev(a, tree, backend.rounds, lam2)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(ref["w"]),
                                   rtol=2e-5, atol=2e-5)
        # no lam2 operand: the in-graph eigendecomposition fallback
        out_fb = jax.jit(lambda t, ap: backend.mix(t, ap))(tree, a)
        np.testing.assert_allclose(np.asarray(out_fb["w"]),
                                   np.asarray(ref["w"]),
                                   rtol=2e-4, atol=2e-4)


def test_regression_task_batch_fn_validates_ids():
    """jax gather clamps out-of-range indices; the batch_fn must raise
    instead of silently feeding a duplicate of another server's shard."""
    from repro.data import make_regression_task
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=2,
                      t_server=1)
    task = make_regression_task(topo)
    task["batch_fn"](0, (0, 2))                   # valid subset is fine
    with pytest.raises(ValueError, match="out of range"):
        task["batch_fn"](0, (0, 1, 2, 7))


@pytest.mark.parametrize("mode", ["collapsed", "exact_mean"])
def test_dynamic_consensus_modes_agree_with_static(mode):
    """Dynamic 'collapsed' traces A^{T_S} in-program; with the static A it
    must match the static-mode epoch step (fp32 tolerance)."""
    topo, loss_fn, batches, _ = _setup(m=4, n=3, t_c=6, t_s=5)
    opt = sgd(1e-3)
    step_s = jax.jit(build_dfl_epoch_step(
        DFLConfig(topology=topo, consensus_mode=mode), loss_fn, opt))
    step_d = jax.jit(build_dfl_epoch_step(
        DFLConfig(topology=topo, consensus_mode=mode, dynamic=True),
        loss_fn, opt))
    state = init_dfl_state(DFLConfig(topology=topo), jnp.zeros((2,)), opt,
                           jax.random.key(0))
    sched = EpochSchedule(
        jnp.ones((4, 3), jnp.float32),
        jnp.asarray(topo.mixing_matrix(), jnp.float32))
    out_s, _ = step_s(state, batches)
    out_d, _ = step_d(state, batches, sched)
    np.testing.assert_allclose(np.asarray(out_s.client_params),
                               np.asarray(out_d.client_params),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_server_ids_slicing():
    """FLDataPipeline emits only the alive servers' shards, keyed by
    ORIGINAL identity (a rejoined server gets its own streams back)."""
    from repro.data import DataConfig, FLDataPipeline
    topo = FLTopology(num_servers=4, clients_per_server=2, t_client=3,
                      t_server=1)
    cfg = DataConfig(seq_len=16, per_client_batch=2, vocab_size=64, seed=0)
    pipe = FLDataPipeline(topo, cfg)
    full = pipe.epoch_batches(0)
    sub = pipe.epoch_batches(0, server_ids=(0, 2, 3))
    np.testing.assert_array_equal(np.asarray(full["tokens"][:, [0, 2, 3]]),
                                  np.asarray(sub["tokens"]))
    with pytest.raises(ValueError, match="out of range"):
        pipe.epoch_batches(0, server_ids=(0, 9))


# ---------------------------------------------------------------------------
# server placement (one server per device)
# ---------------------------------------------------------------------------


def _placed_engine(topo, loss_fn, **kw):
    """An engine whose state and batches live on a ('server',) mesh — the
    one-server-per-chip layout, here over the one CPU device."""
    from jax.sharding import NamedSharding
    from repro.launch import sharding as shd
    from repro.launch.train import server_mesh
    mesh = server_mesh(1)
    opt = sgd(1e-3)
    eng = make_engine(topo, loss_fn, opt, **kw)

    def fresh():     # the engine donates its state: one per run
        return init_dfl_state(eng.cfg, jnp.zeros((2,)), opt,
                              jax.random.key(0))

    state_sh = shd.named(shd.fl_state_specs(fresh(), mesh, tp_axis=None),
                         mesh)
    batch_sh = NamedSharding(mesh, shd.fl_batch_spec(mesh, False))
    return eng, fresh, (state_sh, batch_sh), opt


@pytest.mark.parametrize("superepoch", [1, 2])
def test_server_placement_is_numerically_inert(superepoch):
    """Placing the state and the batches (the jit shardings of the
    four-chip layout) on a one-device mesh changes nothing the program
    computes: bitwise the unplaced engine, compiled once."""
    topo, loss_fn, _, _ = _setup(m=3, n=2, t_c=3, t_s=2)
    task = make_regression_task(topo, RegressionSpec(), seed=1)
    eng, fresh, shardings, opt = _placed_engine(topo, loss_fn,
                                                superepoch=superepoch)
    plain = make_engine(topo, loss_fn, opt, superepoch=superepoch)
    placed = dataclasses.replace(eng, shardings=shardings)
    st_p, hist_p = placed.run(jax.device_put(fresh(), shardings[0]), 4,
                              task["batch_fn"])
    st_u, hist_u = plain.run(fresh(), 4, task["batch_fn"])
    np.testing.assert_array_equal(np.asarray(st_p.client_params),
                                  np.asarray(st_u.client_params))
    assert hist_p == hist_u
    leaf = st_p.client_params
    assert leaf.sharding == shardings[0].client_params
    counts = (placed.compile_counts() if superepoch == 1
              else placed.superepoch_compile_counts())
    assert set(counts.values()) == {1}


def test_server_placement_refuses_fault_surgery():
    topo, loss_fn, _, _ = _setup(m=3, n=2, t_c=3, t_s=2)
    eng, _, shardings, _ = _placed_engine(
        topo, loss_fn, faults=FaultSchedule.parse("drop:1:2"))
    with pytest.raises(ValueError, match="placement"):
        dataclasses.replace(eng, shardings=shardings)


_PLACED_FOUR_DEVICES = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.core import (FLTopology, ParticipationSchedule, TopologySchedule,
                        init_dfl_state, make_engine)
from repro.data import RegressionSpec, make_regression_task
from repro.launch import sharding as shd
from repro.launch.train import server_mesh
from repro.optim import sgd

m, epochs, ULP = 4, 7, 4
mesh = server_mesh(m)
topo = FLTopology(num_servers=m, clients_per_server=3, t_client=3,
                  t_server=4, graph_kind="ring")
task = make_regression_task(topo, RegressionSpec(heterogeneity=0.3), seed=0)


def build(kw):
    eng = make_engine(
        topo, task["loss_fn"], sgd(1e-2),
        participation=ParticipationSchedule(kind="bernoulli", rate=0.6,
                                            seed=3),
        topology_schedule=TopologySchedule(kind="edge_drop", drop_prob=0.3,
                                           seed=5), **kw)
    return eng, init_dfl_state(eng.cfg, jnp.zeros((2,)), sgd(1e-2),
                               jax.random.key(0))


def leaves(state):
    return jax.tree.leaves(state._replace(rng=jax.random.key_data(state.rng)))


for kw in ({"mixing": "push_sum"}, {"staleness": 1}, {"superepoch": 2}):
    plain, state = build(kw)
    st_u, h_u = plain.run(state, epochs, task["batch_fn"])
    eng, state = build(kw)
    state_sh = shd.named(shd.fl_state_specs(state, mesh, tp_axis=None), mesh)
    batch_sh = NamedSharding(mesh, shd.fl_batch_spec(mesh, False))
    placed = dataclasses.replace(eng, shardings=(state_sh, batch_sh))
    st_p, h_p = placed.run(jax.device_put(state, state_sh), epochs,
                           task["batch_fn"])
    assert h_p.keys() == h_u.keys(), kw
    for key in h_u:
        if key == "disagreement":
            # the one-pass form (dfl.disagreement_norm) cancels: its square
            # carries rounding of eps * sum_i |w_i|^2 (1.5e-5 measured)
            np.testing.assert_allclose(np.square(h_p[key]),
                                       np.square(h_u[key]), rtol=0,
                                       atol=1e-4, err_msg=str(kw))
        else:
            np.testing.assert_array_max_ulp(np.float32(h_p[key]),
                                            np.float32(h_u[key]), ULP)
    for a, b in zip(leaves(st_u), leaves(st_p), strict=True):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f":
            np.testing.assert_array_max_ulp(a, b, ULP)
        else:
            np.testing.assert_array_equal(a, b)
    assert st_p.client_params.sharding == state_sh.client_params, kw
    assert len(st_p.client_params.sharding.device_set) == m, kw
    counts = (placed.superepoch_compile_counts() if "superepoch" in kw
              else placed.compile_counts())
    assert set(counts.values()) == {1}, (kw, counts)
print("OK")
"""


@pytest.mark.slow
def test_server_placement_on_four_devices_matches_and_compiles_once():
    """One server per device on a forced 4-device host: push-sum, bounded
    staleness and the fused superepoch each reproduce the unplaced engine
    (history, params, optimizer, push-sum weight, key), and each step holds
    one dispatch-cache entry over 7 epochs.  Not bitwise: the partitioner
    splits the cross-server reductions (the loss mean, the disagreement
    sums, the stale mix) into per-device partials, so the installed XLA
    differs by at most 1 ulp on params and 3 ulp on loss (measured); the
    bound is 4 ulp."""
    r = subprocess.run([sys.executable, "-c", _PLACED_FOUR_DEVICES],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]


_FAULTS_FOUR_DEVICES = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.launch.train import train_dynamic

for backend in ("einsum", "blocked"):
    res = train_dynamic("smollm-360m", servers=4, clients=1, t_client=1,
                        t_server=1, epochs=3, seq_len=16, per_client_batch=1,
                        num_layers=1, consensus_backend=backend,
                        faults="drop:1:2,rejoin:2:2", log_every=100)
    assert res["engine"].shardings is None, backend
    assert res["history"]["num_servers"] == [4.0, 3.0, 4.0], backend
    assert np.isfinite(res["history"]["loss"]).all(), backend
print("OK")
"""


@pytest.mark.slow
def test_train_dynamic_runs_fault_scenarios_with_devices_to_spare():
    """With at least M devices ``train_dynamic`` places one server per
    device, which fault surgery cannot follow: a fault scenario still runs,
    unplaced, on a forced 4-device host."""
    r = subprocess.run([sys.executable, "-c", _FAULTS_FOUR_DEVICES],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]
