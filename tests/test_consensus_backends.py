"""The unified consensus-execution backend: every ConsensusBackend must be
allclose-identical to the reference ``gossip_scan`` / ``gossip_push_sum``
under the same EpochSchedule — static, edge_drop, and asymmetric (push-sum)
alike — and the dynamic engine must run the production blocked / shard_map
paths it was previously locked out of.  Also covers the engine donation fix
(single buffered copy) and the psum-weight invariants across drop/rejoin
surgery."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DFLConfig, EpochSchedule, FaultEvent, FaultSchedule,
                        FLTopology, ParticipationSchedule, TopologySchedule,
                        build_dfl_epoch_step, init_dfl_state, make_engine)
from repro.core import consensus as cns
from repro.core import topology as tp
from repro.data import RegressionSpec, make_regression_task
from repro.optim import sgd

M, T_S = 5, 7


def _tree(m, key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m, 4, 3)),
            "b": jax.random.normal(k2, (m, 7))}


def _schedule_mats(kind, epochs=3, m=M, **kw):
    """Per-epoch mixing matrices from a TopologySchedule (host side)."""
    topo = FLTopology(num_servers=m, clients_per_server=2, t_client=2,
                      t_server=T_S, graph_kind="ring",
                      mixing="out_degree" if kind == "asymmetric"
                      else "metropolis")
    sched = TopologySchedule(kind=kind, **kw)
    return [jnp.asarray(sched.mixing(topo, e), jnp.float32)
            for e in range(epochs)]


def _backends():
    a_np = tp.metropolis_weights(tp.ring_graph(M))
    return a_np, {
        "gossip": cns.make_backend("gossip", a_np, T_S),
        "gossip_blocked": cns.make_backend("gossip_blocked", a_np, T_S,
                                           block=5),
        "collapsed": cns.make_backend("collapsed", a_np, T_S),
        # identity compression: the comm wrapper must be invisible in math
        "compressed_identity": cns.make_backend(
            "gossip", a_np, T_S, compression="identity",
            error_feedback=True),
    }


# ---------------------------------------------------------------------------
# backend equivalence vs the reference schedule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,kw", [("static", {}),
                                     ("edge_drop", {"drop_prob": 0.4,
                                                    "seed": 3})])
def test_backends_match_reference_gossip_traced(kind, kw, rng_key):
    """mix(tree, A_p) with a traced per-epoch matrix == gossip_scan(A_p)."""
    _, backends = _backends()
    tree = _tree(M, rng_key)
    for a_p in _schedule_mats(kind, **kw):
        ref = cns.gossip_scan(a_p, tree, T_S)
        for name, backend in backends.items():
            out = jax.jit(backend.mix)(tree, a_p)
            for l1, l2 in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                           rtol=2e-5, atol=2e-5, err_msg=name)


def test_backends_static_matches_reference(rng_key):
    """mix(tree, None) uses the static topology matrix the backend holds."""
    a_np, backends = _backends()
    a = jnp.asarray(a_np, jnp.float32)
    tree = _tree(M, rng_key)
    ref = cns.gossip_scan(a, tree, T_S)
    for name, backend in backends.items():
        out = backend.mix(tree)
        for l1, l2 in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                       rtol=2e-5, atol=2e-5, err_msg=name)


def test_backends_push_sum_match_reference_asymmetric(rng_key):
    """mix_push_sum under row-stochastic per-epoch A_p (the asymmetric
    schedule) == reference gossip_push_sum: values, weights, and the
    unbiased ratio read-out."""
    _, backends = _backends()
    tree = _tree(M, rng_key)
    for a_p in _schedule_mats("asymmetric", drop_prob=0.4, seed=5):
        tp.check_row_stochastic(np.asarray(a_p, np.float64), atol=1e-6)
        ref = cns.gossip_push_sum(a_p, cns.init_push_sum(tree), T_S)
        for name, backend in backends.items():
            out = jax.jit(backend.mix_push_sum)(cns.init_push_sum(tree), a_p)
            np.testing.assert_allclose(np.asarray(out.weight),
                                       np.asarray(ref.weight),
                                       rtol=2e-5, atol=2e-6, err_msg=name)
            for l1, l2 in zip(jax.tree.leaves(out.ratio()),
                              jax.tree.leaves(ref.ratio())):
                np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                           rtol=2e-5, atol=2e-5, err_msg=name)
            # invariants: weights positive, summing to M
            w = np.asarray(out.weight)
            assert (w > 0).all(), (name, w)
            np.testing.assert_allclose(w.sum(), M, rtol=1e-5)


def test_gossip_push_sum_blocked_function(rng_key):
    """The module-level blocked push-sum variant (padding path included)."""
    a = jnp.asarray(tp.out_degree_weights(tp.directed_ring(M)), jnp.float32)
    tree = _tree(M, rng_key)
    out = cns.gossip_push_sum_blocked(a, cns.init_push_sum(tree), T_S,
                                      block=3)
    ref = cns.gossip_push_sum(a, cns.init_push_sum(tree), T_S)
    np.testing.assert_allclose(np.asarray(out.weight), np.asarray(ref.weight),
                               rtol=2e-5)
    for l1, l2 in zip(jax.tree.leaves(out.values),
                      jax.tree.leaves(ref.values)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=2e-5, atol=2e-5)
    # t_server=0 is the identity
    out0 = cns.gossip_push_sum_blocked(a, cns.init_push_sum(tree), 0)
    np.testing.assert_array_equal(np.asarray(out0.values["w"]),
                                  np.asarray(tree["w"]))


def test_make_backend_registry():
    a_np = tp.metropolis_weights(tp.ring_graph(M))
    for mode in cns.BACKEND_MODES:
        backend = cns.make_backend(mode, a_np, T_S)
        assert backend.name == mode
        assert not backend.compressed
    # chebyshev now consumes a traced A_p (+ per-epoch lam2 estimate)
    cheb = cns.make_backend("chebyshev", a_np, T_S)
    assert cheb.supports_traced and cheb.needs_spectral
    assert not cns.make_backend("exact_mean", a_np, T_S).supports_directed
    with pytest.raises(ValueError, match="unknown consensus mode"):
        cns.make_backend("bogus", a_np, T_S)
    with pytest.raises(ValueError, match="static mixing matrix"):
        cns.make_backend("gossip", None, T_S).mix({"w": jnp.ones((M, 2))})
    # direct-API guard rails: no silent garbage from undefined combinations
    with pytest.raises(ValueError, match="ratio-consensus"):
        cns.make_backend("exact_mean", a_np, T_S).mix_push_sum(
            cns.init_push_sum({"w": jnp.ones((M, 2))}))
    # a matrix-less chebyshev is traced-only: static mix has no operator
    with pytest.raises(ValueError, match="static mixing matrix"):
        cns.make_backend("chebyshev", None, T_S).mix({"w": jnp.ones((M, 2))})
    # compression wrapping through the registry
    wrapped = cns.make_backend("gossip", a_np, T_S, compression="int8",
                               error_feedback=True)
    assert wrapped.compressed and wrapped.error_feedback
    assert wrapped.name == "compressed[gossip+int8]"
    assert wrapped.supports_traced and wrapped.supports_directed
    with pytest.raises(ValueError, match="already-compressed"):
        cns.CompressedBackend(wrapped, wrapped.compressor)


# ---------------------------------------------------------------------------
# the lifted prohibitions: dynamic epoch steps on the production paths
# ---------------------------------------------------------------------------


def _dyn_setup(m=4, n=3, t_c=5, t_s=6):
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    return topo, task


@pytest.mark.parametrize("mixing", ["symmetric", "push_sum"])
def test_dynamic_blocked_epoch_step_matches_gossip(mixing):
    """The previously-prohibited combinations — dynamic + gossip_blocked,
    and push_sum + gossip_blocked — agree with the reference gossip path
    under a per-epoch traced A_p."""
    topo, task = _dyn_setup()
    opt = sgd(1e-3)
    states, steps = {}, {}
    for mode in ("gossip", "gossip_blocked"):
        cfg = DFLConfig(topology=topo, consensus_mode=mode, dynamic=True,
                        mixing=mixing)
        steps[mode] = jax.jit(build_dfl_epoch_step(cfg, task["loss_fn"], opt))
        states[mode] = init_dfl_state(cfg, jnp.zeros((2,)), opt,
                                      jax.random.key(0))
    mask = jnp.ones((topo.num_servers, topo.clients_per_server), jnp.float32)
    kind = "asymmetric" if mixing == "push_sum" else "edge_drop"
    mats = _schedule_mats(kind, epochs=3, m=topo.num_servers, drop_prob=0.4,
                          seed=2)
    for a_p in mats:
        for mode in steps:
            states[mode], _ = steps[mode](states[mode], task["batches"],
                                          EpochSchedule(mask, a_p))
    np.testing.assert_allclose(
        np.asarray(states["gossip_blocked"].client_params),
        np.asarray(states["gossip"].client_params), rtol=2e-5, atol=2e-6)
    if mixing == "push_sum":
        np.testing.assert_allclose(
            np.asarray(states["gossip_blocked"].psum_weight),
            np.asarray(states["gossip"].psum_weight), rtol=2e-5)


def test_engine_gossip_blocked_full_scenario_matches_gossip():
    """End to end through the engine — participation sampling, edge drops,
    AND drop/rejoin surgery (per-M re-jit) — the blocked path tracks the
    einsum path allclose."""
    topo = FLTopology(num_servers=4, clients_per_server=3, t_client=5,
                      t_server=6, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=1)
    gamma = 1e-3
    finals = {}
    for mode in ("gossip", "gossip_blocked"):
        engine = make_engine(
            topo, task["loss_fn"], sgd(gamma), consensus_mode=mode,
            participation=ParticipationSchedule(kind="bernoulli", rate=0.6,
                                                seed=2),
            topology_schedule=TopologySchedule(kind="edge_drop",
                                               drop_prob=0.3, seed=4),
            faults=FaultSchedule((FaultEvent(2, "drop", 1),
                                  FaultEvent(5, "rejoin", 1))))
        state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                               jax.random.key(0))
        state, hist = engine.run(state, 7, task["batch_fn"])
        finals[mode] = np.asarray(state.client_params)
        assert engine.alive == [0, 2, 3, 1]
    np.testing.assert_allclose(finals["gossip_blocked"], finals["gossip"],
                               rtol=2e-5, atol=2e-6)


def test_engine_push_sum_blocked_weight_invariants_across_surgery():
    """psum_weight invariants on the blocked path through drop/rejoin
    surgery: reset to ones at each new federation size, positive, summing
    to the live M after every epoch."""
    topo = FLTopology(num_servers=4, clients_per_server=2, t_client=3,
                      t_server=6, graph_kind="ring")
    task = make_regression_task(topo, seed=0)
    engine = make_engine(
        topo, task["loss_fn"], sgd(1e-3), consensus_mode="gossip_blocked",
        mixing="push_sum",
        topology_schedule=TopologySchedule(kind="asymmetric", drop_prob=0.5,
                                           seed=3),
        faults=FaultSchedule((FaultEvent(1, "drop", 2),
                              FaultEvent(3, "rejoin", 2))))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    for epoch in range(5):
        state, rec = engine.run_epoch(state, epoch, task["batch_fn"])
        m_live = engine.topo.num_servers
        w = np.asarray(state.psum_weight)
        assert w.shape == (m_live,)
        assert (w > 0).all(), (epoch, w)
        np.testing.assert_allclose(w.sum(), m_live, rtol=1e-5)
        assert rec["psum_min_weight"] > 0
    # surgery reset: drop mid-state and check the fresh unit weights
    fresh = engine.apply_faults(
        state._replace(psum_weight=state.psum_weight * 2.0), 1)
    np.testing.assert_array_equal(np.asarray(fresh.psum_weight), 1.0)


# ---------------------------------------------------------------------------
# the donation fix
# ---------------------------------------------------------------------------


def test_engine_step_donates_carried_state():
    """The dynamic engine's compiled step donates the carried DFLState, so
    a run holds ONE buffered copy of client params + optimizer state (the
    input buffers are consumed) instead of two."""
    topo = FLTopology(num_servers=3, clients_per_server=2, t_client=3,
                      t_server=4, graph_kind="ring")
    task = make_regression_task(topo, seed=0)
    engine = make_engine(topo, task["loss_fn"], sgd(1e-3))
    state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                           jax.random.key(0))
    params_in = state.client_params
    opt_in = jax.tree.leaves(state.opt_state)
    new_state, _ = engine.run_epoch(state, 0, task["batch_fn"])
    assert params_in.is_deleted()
    assert all(l.is_deleted() for l in opt_in if hasattr(l, "is_deleted"))
    assert not new_state.client_params.is_deleted()
    # the step signature no longer carries the dead `donate` flag
    import inspect
    assert "donate" not in inspect.signature(build_dfl_epoch_step).parameters


# ---------------------------------------------------------------------------
# mesh-bound backends and fault surgery
# ---------------------------------------------------------------------------


def test_engine_rejects_mesh_bound_backend_with_faults():
    topo = FLTopology(num_servers=2, clients_per_server=2, t_client=2,
                      t_server=2, graph_kind="ring")

    class FakeShardMap(cns.ConsensusBackend):
        name = "shard_map"
        mesh_bound = True

        def _mix(self, tree, a):
            return tree

    backend = FakeShardMap(topo.mixing_matrix(), topo.t_server)
    with pytest.raises(ValueError, match="mesh-bound"):
        make_engine(topo, lambda w, b, r: (jnp.zeros(()), {}), sgd(1e-3),
                    consensus_backend=backend,
                    faults=FaultSchedule((FaultEvent(1, "drop", 1),)))


# ---------------------------------------------------------------------------
# CLI plumbing (launch/train.py)
# ---------------------------------------------------------------------------


def test_trainer_cli_exposes_blocked_and_backend_flags():
    from repro.launch.train import (CONSENSUS_BACKENDS, build_parser,
                                    resolve_consensus_backend)
    args = build_parser().parse_args(
        ["--consensus-mode", "gossip_blocked", "--consensus-backend",
         "blocked"])
    assert args.consensus_mode == "gossip_blocked"
    assert args.consensus_backend == "blocked"
    assert set(CONSENSUS_BACKENDS) == {"auto", "einsum", "blocked",
                                       "shard_map"}
    topo = FLTopology(num_servers=2, clients_per_server=2, t_client=2,
                      t_server=2)
    params = {"w": jnp.zeros((3,))}
    # flag -> config plumbing
    assert resolve_consensus_backend("auto", "gossip_blocked", topo,
                                     params) == ("gossip_blocked", None)
    assert resolve_consensus_backend("blocked", "gossip", topo,
                                     params) == ("gossip_blocked", None)
    assert resolve_consensus_backend("einsum", "gossip_blocked", topo,
                                     params) == ("gossip", None)
    with pytest.raises(ValueError, match="undefined"):
        resolve_consensus_backend("blocked", "exact_mean", topo, params)
    if jax.device_count() < topo.num_servers:
        with pytest.raises(ValueError, match="device"):
            resolve_consensus_backend("shard_map", "gossip", topo, params)


def test_trainer_runs_gossip_blocked_end_to_end():
    """--consensus-mode gossip_blocked drives a (tiny) LM epoch."""
    from repro.launch.train import train
    res = train("smollm-360m", servers=2, clients=1, t_client=1, t_server=3,
                epochs=2, seq_len=16, per_client_batch=1, gamma=0.05,
                consensus_mode="gossip_blocked", log_every=100)
    assert len(res["history"]["loss"]) == 2
    assert np.isfinite(res["history"]["loss"]).all()
    assert res["history"]["disagreement"][-1] < 1e-2


def test_dynamic_trainer_depth_cut_and_extra_sinks():
    """train_dynamic cuts the depth and keeps the widths (``num_layers``),
    and hands every epoch event to the caller's ``sinks``."""
    from repro.launch.train import train_dynamic
    from repro.obs import MemorySink
    sink = MemorySink()
    res = train_dynamic("smollm-360m", servers=2, clients=1, t_client=1,
                        t_server=1, epochs=2, seq_len=16, per_client_batch=1,
                        num_layers=1, sinks=(sink,), log_every=100)
    assert res["cfg"].num_layers == 1 and res["cfg"].d_model == 120
    assert sink.history()["loss"] == res["history"]["loss"]
    assert np.isfinite(res["history"]["loss"]).all()


# ---------------------------------------------------------------------------
# shard_map backend (multi-device): subprocess, slow tier
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_shard_map_backend_dynamic_engine_matches_gossip():
    """The ShardMapBackend consumes a traced per-epoch A_p (including the
    push-sum variant) inside the dynamic engine, matching the reference
    gossip engine allclose — on a 4-device forced-host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import (FLTopology, ParticipationSchedule, TopologySchedule,
                        init_dfl_state, make_engine)
from repro.core import consensus as cns
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
                                   block=8)
assert backend.name == "shard_map" and backend.mesh_bound

for mixing, kind in (("symmetric", "edge_drop"), ("push_sum", "asymmetric")):
    base = FLTopology(num_servers=m, clients_per_server=2, t_client=4,
                      t_server=5, graph_kind="ring",
                      mixing="out_degree" if mixing == "push_sum"
                      else "metropolis")
    finals = {}
    for name, kw in (("gossip", {}), ("shard_map",
                                      {"consensus_backend": backend})):
        engine = make_engine(
            base, task["loss_fn"], sgd(1e-3), mixing=mixing,
            participation=ParticipationSchedule(kind="bernoulli", rate=0.7,
                                                seed=1),
            topology_schedule=TopologySchedule(kind=kind, drop_prob=0.4,
                                               seed=3), **kw)
        state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                               jax.random.key(0))
        state, hist = engine.run(state, 3, task["batch_fn"])
        finals[name] = np.asarray(state.client_params)
        if mixing == "push_sum":
            w = np.asarray(state.psum_weight)
            assert (w > 0).all() and abs(w.sum() - m) < 1e-3, w
    np.testing.assert_allclose(finals["shard_map"], finals["gossip"],
                               rtol=2e-4, atol=2e-5)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=480,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_shard_map_traced_operator_matches_dense():
    """make_gossip_shard_map with a TRACED operator: one compiled program
    serves distinct per-epoch matrices, plain and transposed (push-sum)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import consensus as cns
from repro.core import topology as tp
m, t_s = 4, 6
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
specs = {"w": P("server", None)}
run = jax.jit(cns.make_gossip_shard_map(mesh, t_s, specs, block=16))
tree = {"w": jax.random.normal(jax.random.key(0), (m, 40))}
mats = [tp.metropolis_weights(tp.ring_graph(m)),
        tp.metropolis_weights(tp.complete_graph(m)),
        tp.out_degree_weights(tp.directed_ring(m))]
for a_np in mats:
    a = jnp.asarray(a_np, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(run(a, tree)["w"]),
        np.asarray(cns.gossip_scan(a, tree, t_s)["w"]),
        rtol=2e-5, atol=2e-5)
    # transposed operator == push-sum numerator mixing
    np.testing.assert_allclose(
        np.asarray(run(a.T, tree)["w"]),
        np.asarray(cns.gossip_push_sum(
            a, cns.init_push_sum(tree), t_s).values["w"]),
        rtol=2e-5, atol=2e-5)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-3000:]
