"""Benchmark harness — one benchmark per paper table/figure + system perf.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run --only fig3_consensus
    PYTHONPATH=src python -m benchmarks.run --only kernel_micro,topology_sweep
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny sizes

``--smoke`` shrinks every benchmark to seconds (fewer epochs / smaller
trees) so the CI fast job can execute the full harness on every push —
numbers are NOT meaningful in smoke mode, it exists to keep the benchmarks
from rotting; ``--only`` takes a comma-separated subset.

Benchmarks (the paper has one experiment, Fig. 3; the rest exercise the
theory quantities the paper derives and our beyond-paper claims):

  fig3_consensus        Sec. IV / Fig. 3: epochs to consensus + |w - w*|
  thm1_epsilon_sweep    Thm. 1 epsilon vs (gamma, T_S, graph) — prediction
                        vs measured final error
  consensus_strategies  faithful gossip vs collapsed vs Chebyshev: wall time
                        per epoch + rounds to target sigma (beyond-paper)
  topology_sweep        ring/line/star/complete/torus: sigma_A + spectral gap
  dynamic_federation    convergence under full vs sampled participation vs
                        faulty links vs server churn (the scenario engine)
  directed_federation   symmetric vs naive row-stochastic (biased) vs
                        push-sum (unbiased) gossip under directed /
                        asymmetrically-degraded links
  consensus_backends    einsum vs blocked vs shard_map vs shard_map_wire
                        (physical BUCKETED int8 wire; + an int4 variant)
                        consensus execution on the DYNAMIC engine (traced
                        per-epoch A_p): peak-RSS + epoch throughput per
                        backend, one clean subprocess each, cross-backend
                        agreement, and the physical-wire HLO cross-check
                        (per round: ONE all-gather of s8 codes + one of
                        f32 scales matching the bucketed byte ledger)
  compressed_consensus  the repro.comm layer: compressor x backend x wire
                        sweep recording bytes-on-wire (BytesTracker) vs
                        consensus error vs wall-clock; checks int8+EF
                        reaches the fig-3 tolerance at >= 3.5x fewer bytes
                        on BOTH the simulated and the physical wire, and
                        that the metadata byte counts match the analytic
                        forms
  overlapped_consensus  the epoch-barrier kill: per-epoch barrier engine
                        vs the K=8 fused superepoch megastep vs the
                        megastep with staleness-1 gossip on one dynamic
                        scenario — epochs/s + peak RSS per config, the
                        megastep speedup, and the CI-gated
                        staleness0_bitwise degeneration boolean (sha256
                        over final server params)
  byzantine_consensus   attack x defense grid: sign-flip / scaled-noise /
                        inlier-shift attackers vs plain gossip and the
                        robust screens (trimmed mean, median, clipped) —
                        honest-server error, honest disagreement, and the
                        per-defense wall-clock overhead
  obs_phases            the repro.obs telemetry stack on a full dynamic
                        scenario: per-phase wall breakdown (local vs
                        gossip vs surgery vs host aggregation) from the
                        span tracer, obs-on vs obs-off overhead, the
                        bitwise-inertness cross-check, and validating
                        JSONL + Chrome-trace artifacts for CI
  kernel_micro          Pallas-kernel (interpret) vs jnp-oracle parity +
                        CPU wall time (correctness harness, not TPU perf)
  lm_epoch_throughput   DFL epoch wall time on a smoke LM (CPU reference)

Each prints `name,metric,value` CSV rows and writes
experiments/bench_results.csv; the consensus benches additionally dump
experiments/BENCH_consensus.json (the machine-readable perf trajectory
tracked across PRs).

consensus_backends and overlapped_consensus run each configuration in a
child process.  A device belongs to one process, so those benches run
first, before this process touches a JAX backend, and a child that fails
makes the whole run exit nonzero.  The shard_map children need four
devices: four chips, or four CPU devices on a host without an accelerator.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache

RESULTS = []
OUT = os.path.join(os.path.dirname(__file__), "..", "experiments")
SMOKE = False     # set by --smoke: tiny sizes, seconds per bench
FAILED = []       # "bench/config" of every child process that failed


def S(full, smoke):
    """Pick the full-size or the smoke-size value of a benchmark knob."""
    return smoke if SMOKE else full


def record(name, metric, value):
    RESULTS.append((name, metric, value))
    print(f"{name},{metric},{value}")


def run_child(bench, tag, child, args):
    """Run one configuration's child program; return its ``BENCH_JSON``
    payload, or record an ``_error`` row, mark the run failed and return
    None.  The result line is found by its sentinel prefix, never as "the
    last stdout line": jax and engine logging can trail it."""
    import json
    import subprocess
    import sys

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", child, *map(str, args)],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": src})
    sentinel = "BENCH_JSON "
    line = next((ln for ln in reversed(r.stdout.splitlines())
                 if ln.startswith(sentinel)), None)
    if r.returncode == 0 and line is not None:
        return json.loads(line[len(sentinel):])
    err = (r.stderr.strip().splitlines()[-1][:120]
           if r.stderr.strip() else "no BENCH_JSON line")
    record(bench, f"{tag}_error", err.replace(",", ";"))
    FAILED.append(f"{bench}/{tag}")
    return None


def bench_fig3_consensus():
    """Paper Fig. 3: 5x5, T_C=250, T_S=25 — epochs to consensus & error."""
    from repro.core import (DFLConfig, FLTopology, build_dfl_epoch_step,
                            init_dfl_state)
    from repro.data import RegressionSpec, make_regression_data
    from repro.optim import sgd

    topo = FLTopology(num_servers=5, clients_per_server=5,
                      t_client=S(250, 25), t_server=S(25, 5),
                      graph_kind="ring")
    data = make_regression_data(topo, RegressionSpec(), seed=0)
    x, y = jnp.asarray(data["x"]), jnp.asarray(data["y"])

    def loss_fn(w, batch, rng):
        xx, yy = batch
        return 0.5 * jnp.mean((xx @ w - yy) ** 2), {}

    gamma = 0.5 / (9.0 * topo.t_client)
    cfg = DFLConfig(topology=topo)
    opt = sgd(gamma)
    step = jax.jit(build_dfl_epoch_step(cfg, loss_fn, opt),
                   donate_argnums=(0,))
    state = init_dfl_state(cfg, jnp.zeros((2,)), opt, jax.random.key(0))
    batches = (jnp.broadcast_to(x, (topo.t_client,) + x.shape),
               jnp.broadcast_to(y, (topo.t_client,) + y.shape))
    w_star = np.linalg.lstsq(np.asarray(x).reshape(-1, 2),
                             np.asarray(y).reshape(-1), rcond=None)[0]
    consensus_epoch = None
    for epoch in range(S(200, 12)):
        state, metrics = step(state, batches)
        servers = np.asarray(state.client_params[:, 0])
        err = float(np.linalg.norm(servers - w_star, axis=-1).max())
        if consensus_epoch is None and float(
                metrics.server_disagreement) < 1e-3 and err < 0.05:
            consensus_epoch = epoch
    record("fig3_consensus", "epochs_to_consensus_near_wstar",
           consensus_epoch)
    record("fig3_consensus", "server_iters_to_consensus",
           (consensus_epoch + 1) * topo.t_server
           if consensus_epoch is not None else -1)
    record("fig3_consensus", "final_max_err", round(err, 5))
    record("fig3_consensus", "paper_claim_epochs", 160)


def bench_thm1_epsilon_sweep():
    from repro.core import (DFLConfig, FLTopology, build_dfl_epoch_step,
                            init_dfl_state)
    from repro.data import RegressionSpec, make_regression_data
    from repro.optim import sgd

    combos = [(25, 5, "ring"), (25, 25, "ring"),
              (50, 10, "line"), (25, 5, "complete")]
    for (t_c, t_s, graph) in S(combos, combos[:1]):
        topo = FLTopology(num_servers=5, clients_per_server=5, t_client=t_c,
                          t_server=t_s, graph_kind=graph)
        data = make_regression_data(topo, RegressionSpec(heterogeneity=1.0),
                                    seed=1)
        x, y = jnp.asarray(data["x"]), jnp.asarray(data["y"])

        def loss_fn(w, batch, rng):
            xx, yy = batch
            return 0.5 * jnp.mean((xx @ w - yy) ** 2), {}

        gamma = 0.4 / (9.0 * t_c)
        cfg = DFLConfig(topology=topo)
        opt = sgd(gamma)
        step = jax.jit(build_dfl_epoch_step(cfg, loss_fn, opt),
                       donate_argnums=(0,))
        state = init_dfl_state(cfg, jnp.zeros((2,)), opt, jax.random.key(0))
        batches = (jnp.broadcast_to(x, (t_c,) + x.shape),
                   jnp.broadcast_to(y, (t_c,) + y.shape))
        for _ in range(S(150, 10)):
            state, _ = step(state, batches)
        w_star = np.linalg.lstsq(np.asarray(x).reshape(-1, 2),
                                 np.asarray(y).reshape(-1), rcond=None)[0]
        servers = np.asarray(state.client_params[:, 0])
        err = float(np.linalg.norm(servers - w_star, axis=-1).max())
        eps = topo.epsilon_bound(gamma, mu=1.0, lsmooth=9.0, theta=80.0)
        tag = f"tc{t_c}_ts{t_s}_{graph}"
        record("thm1_epsilon", f"{tag}_measured_err", round(err, 5))
        record("thm1_epsilon", f"{tag}_predicted_eps", round(eps, 5))
        record("thm1_epsilon", f"{tag}_bound_holds", bool(err <= eps))


def bench_consensus_strategies():
    from repro.core import consensus as cns
    from repro.core import topology as tp

    m, t_s = 8, 25
    a_np = tp.metropolis_weights(tp.ring_graph(m))
    a = jnp.asarray(a_np, jnp.float32)
    a_eff = jnp.asarray(cns.collapse_mixing(a_np, t_s), jnp.float32)
    tree = {"w": jax.random.normal(jax.random.key(0),
                                   (m, S(1_000_000, 20_000)))}
    lam2 = float(np.sort(np.abs(np.linalg.eigvalsh(a_np)))[::-1][1])

    funcs = {
        "gossip_25rounds": jax.jit(lambda t: cns.gossip_scan(a, t, t_s)),
        "collapsed_1round": jax.jit(lambda t: cns.gossip_collapsed(a_eff, t)),
        "chebyshev_5rounds": jax.jit(
            lambda t: cns.gossip_chebyshev(a, t, 5, lam2)),
    }
    base = None
    reps = S(5, 1)
    for name, fn in funcs.items():
        out = fn(tree)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(reps):
            out = fn(tree)
            jax.block_until_ready(out)
        dt = (time.time() - t0) / reps
        record("consensus_strategies", f"{name}_ms", round(dt * 1000, 2))
        dis = float(jnp.linalg.norm(out["w"] - out["w"].mean(0)))
        record("consensus_strategies", f"{name}_residual_disagreement",
               round(dis, 6))
        if name.startswith("gossip"):
            base = out
        elif name.startswith("collapsed"):
            diff = float(jnp.abs(out["w"] - base["w"]).max())
            record("consensus_strategies", "collapsed_vs_gossip_maxdiff",
                   round(diff, 8))
    sig, rounds = 1.0, 0
    while sig > 0.01 and rounds < 500:
        rounds += 1
        sig = tp.sigma_a(a_np, rounds)
    record("consensus_strategies", "gossip_rounds_to_sigma_0.01", rounds)
    k = 1
    while cns.chebyshev_coefficients(a_np, k) > 0.01 and k < 500:
        k += 1
    record("consensus_strategies", "chebyshev_rounds_to_sigma_0.01", k)


def bench_topology_sweep():
    from repro.core import topology as tp
    for kind in ("ring", "line", "star", "complete"):
        for m in (5, 16):
            a = tp.metropolis_weights(tp.build_graph(kind, m))
            record("topology_sweep", f"{kind}_M{m}_sigma_T25",
                   round(tp.sigma_a(a, 25), 6))
            record("topology_sweep", f"{kind}_M{m}_spectral_gap",
                   round(tp.spectral_gap(a), 6))
    a = tp.metropolis_weights(tp.torus_2d_graph(4, 4))
    record("topology_sweep", "torus_M16_sigma_T25",
           round(tp.sigma_a(a, 25), 6))


def bench_kernel_micro():
    from repro.kernels import ops, ref

    kq, kkv, kx, kb, kc, kd = jax.random.split(jax.random.key(0), 6)
    seq = S(512, 128)
    q = jax.random.normal(kq, (2, seq, 8, 64))
    kv = jax.random.normal(kkv, (2, seq, 2, 64))

    def time_it(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.time()
        out = fn(*args)
        jax.block_until_ready(out)
        return out, (time.time() - t0) * 1000

    o_k, t_k = time_it(lambda a, b, c: ops.flash_attention(a, b, c), q, kv, kv)
    o_r, t_r = time_it(jax.jit(
        lambda a, b, c: ref.attention_ref(a, b, c)), q, kv, kv)
    record("kernel_micro", "flash_attn_err", float(jnp.abs(o_k - o_r).max()))
    record("kernel_micro", "flash_attn_interpret_ms", round(t_k, 1))
    record("kernel_micro", "flash_attn_jnp_ms", round(t_r, 1))

    xs = jax.random.normal(kx, (2, seq, 4, 64))
    bs = jax.random.normal(kb, (2, seq, 1, 128)) * 0.5
    cs = jax.random.normal(kc, (2, seq, 1, 128)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(kd, (2, seq, 4)))
    ac = -jnp.exp(jnp.linspace(-1, 1, 4))
    (y_k, _), t_k = time_it(
        lambda *a: ops.ssd_scan(*a, chunk=128), xs, bs, cs, dt, ac)
    (y_r, _), t_r = time_it(jax.jit(ref.ssd_scan_ref), xs, bs, cs, dt, ac)
    record("kernel_micro", "ssd_err", float(jnp.abs(y_k - y_r).max()))
    record("kernel_micro", "ssd_interpret_ms", round(t_k, 1))
    record("kernel_micro", "ssd_naive_ms", round(t_r, 1))


def bench_dynamic_federation():
    """Convergence under full vs sampled participation vs faulty links vs
    server churn — the scenario axis the static Algorithm 1 cannot express.
    Reports final max error to w*, epochs to reach err<0.5, and the
    time-varying product contraction sigma_prod."""
    from repro.core import (FLTopology, FaultEvent, FaultSchedule,
                            ParticipationSchedule, TopologySchedule,
                            init_dfl_state, make_engine)
    from repro.data import RegressionSpec, make_regression_task
    from repro.optim import sgd

    m, n, t_c, t_s, epochs = 5, 5, S(25, 5), S(10, 4), S(50, 6)
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    loss_fn, batch_fn, w_star = (task["loss_fn"], task["batch_fn"],
                                 task["w_star"])

    gamma = 0.4 / (9.0 * t_c)
    scenarios = {
        "full": {},
        "sampled_50pct": {"participation": ParticipationSchedule(
            kind="bernoulli", rate=0.5, seed=7)},
        "sampled_25pct": {"participation": ParticipationSchedule(
            kind="bernoulli", rate=0.25, seed=7)},
        "faulty_links_p30": {"topology_schedule": TopologySchedule(
            kind="edge_drop", drop_prob=0.3, seed=11)},
        "stragglers_90pct": {"topology_schedule": TopologySchedule(
            kind="straggler", weaken=0.9, n_weak=2, seed=11)},
        "churn_drop_rejoin": {"faults": FaultSchedule((
            FaultEvent(15, "drop", 2), FaultEvent(30, "rejoin", 2)))},
    }
    for name, kw in scenarios.items():
        engine = make_engine(topo, loss_fn, sgd(gamma), **kw)
        state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                               jax.random.key(0))
        t0 = time.time()
        first_hit = None
        for epoch in range(epochs):
            state, rec = engine.run_epoch(state, epoch, batch_fn)
            servers = np.asarray(state.client_params[:, 0])
            err = float(np.linalg.norm(servers - w_star, axis=-1).max())
            if first_hit is None and err < 0.5:
                first_hit = epoch
        dt = time.time() - t0
        record("dynamic_federation", f"{name}_final_err", round(err, 5))
        record("dynamic_federation", f"{name}_epochs_to_err_0.5",
               first_hit if first_hit is not None else -1)
        record("dynamic_federation", f"{name}_sigma_prod",
               f"{rec['sigma_prod']:.3e}")
        record("dynamic_federation", f"{name}_wall_s", round(dt, 2))


def bench_directed_federation():
    """Symmetric gossip vs naive row-stochastic gossip (biased) vs push-sum
    (unbiased) under directed/asymmetrically-degraded server links.  The
    acceptance metric: push-sum's final disagreement AND distance-to-ideal
    stay within tolerance of the symmetric baseline while naive
    row-stochastic gossip stays biased (it converges to the Perron-weighted
    w_pi, not the uniform w*)."""
    from repro.core import (FLTopology, TopologySchedule, init_dfl_state,
                            make_engine, perron_weights)
    from repro.data import (RegressionSpec, make_regression_task,
                            perron_ideal)
    from repro.optim import sgd

    m, n, t_c, t_s, epochs = 5, 5, S(25, 5), S(30, 8), S(80, 6)
    ring = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    directed = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                          t_server=t_s, graph_kind="random_orientation",
                          mixing="out_degree")
    task = make_regression_task(directed, RegressionSpec(concept_shift=2.0),
                                seed=0)
    w_star = task["w_star"]
    d = np.asarray(task["x"]).shape[-1]
    pi = perron_weights(directed.mixing_matrix())
    w_pi = perron_ideal(task["x"], task["y"], pi)
    record("directed_federation", "perron_bias_norm",
           round(float(np.linalg.norm(w_pi - w_star)), 5))

    gamma = 0.4 / (9.0 * t_c)
    scenarios = {
        "symmetric": dict(topo=ring, mixing="symmetric"),
        "naive_row_stochastic": dict(topo=directed, mixing="row_stochastic"),
        "push_sum": dict(topo=directed, mixing="push_sum"),
        "push_sum_asymmetric": dict(
            topo=ring, mixing="push_sum",
            topology_schedule=TopologySchedule(kind="asymmetric",
                                               drop_prob=0.4, seed=11)),
    }
    errs = {}
    for name, sc in scenarios.items():
        kw = {k: v for k, v in sc.items() if k != "topo"}
        engine = make_engine(sc["topo"], task["loss_fn"], sgd(gamma), **kw)
        state = init_dfl_state(engine.cfg, jnp.zeros((d,)), sgd(gamma),
                               jax.random.key(0))
        t0 = time.time()
        state, hist = engine.run(state, epochs, task["batch_fn"])
        dt = time.time() - t0
        servers = np.asarray(state.client_params[:, 0])
        errs[name] = float(np.linalg.norm(servers - w_star, axis=-1).max())
        err_pi = float(np.linalg.norm(servers - w_pi, axis=-1).max())
        record("directed_federation", f"{name}_err_to_wstar",
               round(errs[name], 5))
        record("directed_federation", f"{name}_err_to_wpi", round(err_pi, 5))
        record("directed_federation", f"{name}_final_disagreement",
               f"{hist['disagreement'][-1]:.3e}")
        if "psum_min_weight" in hist:
            record("directed_federation", f"{name}_psum_min_weight",
                   round(hist["psum_min_weight"][-1], 4))
        record("directed_federation", f"{name}_wall_s", round(dt, 2))
    tol = 1.2 * errs["symmetric"] + 0.02
    record("directed_federation", "push_sum_unbiased",
           bool(errs["push_sum"] <= tol and errs["push_sum_asymmetric"] <= tol))
    record("directed_federation", "naive_row_stochastic_biased",
           bool(errs["naive_row_stochastic"] > 1.5 * errs["push_sum"]))


def bench_consensus_backends():
    """Consensus-execution backends on the dynamic engine at a gossip-bound
    model size: einsum (reference per-leaf) vs blocked streaming vs
    shard_map explicit collectives, each driven through the SAME edge_drop
    schedule with a traced per-epoch A_p.  Each backend runs in its own
    subprocess so ru_maxrss is a clean per-path peak; the parent checks the
    paths agree on the final parameters (allclose) and records peak-RSS and
    epoch throughput per backend."""
    child = r'''
import os, sys, json, time, resource
backend = sys.argv[1]
if backend.startswith("shard_map"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
import jax, jax.numpy as jnp, numpy as np
from repro.core import (FLTopology, TopologySchedule, init_dfl_state,
                        make_engine)
from repro.optim import sgd

m, n, t_c, t_s, epochs, d = 4, 2, 2, 10, int(sys.argv[2]), int(sys.argv[3])
topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                  t_server=t_s, graph_kind="ring")

def loss_fn(w, batch, rng):
    # gossip-bound toy objective over a wide parameter vector: the epoch
    # cost is dominated by the consensus period, which is what we meter
    return 0.5 * jnp.mean(w * w) + 0.0 * batch.sum(), {}

def batch_fn(epoch, alive):
    return jnp.zeros((t_c, len(alive), n, 1), jnp.float32)

kw = {}
if backend == "gossip_blocked":
    kw["consensus_mode"] = "gossip_blocked"
elif backend.startswith("shard_map"):
    from repro.launch import sharding as shd
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(m), ("server",))
    server_abs = jax.eval_shape(lambda: jnp.zeros((m, d), jnp.float32))
    ckw = {}
    if backend.startswith("shard_map_wire"):
        ckw = {"compression": ("int4" if backend.endswith("int4")
                               else "int8"),
               "error_feedback": True, "wire": "physical"}
    kw["consensus_backend"] = shd.fl_consensus_backend(
        topo, mesh, server_abs, tp_axis=None, **ckw)
engine = make_engine(topo, loss_fn, sgd(1e-3),
                     topology_schedule=TopologySchedule(
                         kind="edge_drop", drop_prob=0.3, seed=7), **kw)
params = jax.random.normal(jax.random.key(0), (d,), jnp.float32)
state = init_dfl_state(engine.cfg, params, sgd(1e-3), jax.random.key(1))
state, rec = engine.run_epoch(state, 0, batch_fn)    # compile outside timing
wire_mb = rec.get("wire_mb", 0.0)
t0 = time.time()
for epoch in range(1, epochs):
    state, rec = engine.run_epoch(state, epoch, batch_fn)
    wire_mb += rec.get("wire_mb", 0.0)
wall = time.time() - t0
out = {
    "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
    "epochs_per_s": (epochs - 1) / wall,
}
servers = np.asarray(state.client_params[:, 0], np.float64)
out["checksum"] = [float(servers.sum()), float(np.abs(servers).max())]
out["fingerprint"] = servers[:, ::100_000].tolist()
if backend.startswith("shard_map_wire"):
    # physical-wire cross-check: the compiled all-gather operands must be
    # the codec's BUCKETED byte layout (one s8 code buffer + one f32
    # scale buffer per round for the whole tree), and the per-round bytes
    # one server ships must equal what the BytesTracker ledger charges
    # per link message
    from repro.comm.accounting import (hlo_collective_bytes,
                                       tree_bucketed_wire_bytes_per_server)
    cb = kw["consensus_backend"]
    runner = cb.inner.wire_runner(cb.compressor, stochastic=True)
    tree = {"w": jnp.zeros((m, d), jnp.float32)}
    hlo = jax.jit(runner).lower(
        jnp.zeros((m, m), jnp.float32), tree, jax.random.key(0)
    ).compile().as_text()
    cols = hlo_collective_bytes(hlo)
    gathers = [c for c in cols if c["op"] == "all-gather"]
    shipped = sum(c["bytes"] // m for c in gathers)      # one round's pair
    expect = tree_bucketed_wire_bytes_per_server(cb.compressor, tree,
                                                 cb.inner.block)
    out["wire_hlo_gather_sites"] = len(gathers)
    out["wire_hlo_dtypes"] = sorted({c["dtype"] for c in gathers})
    out["wire_hlo_round_bytes"] = shipped
    out["wire_hlo_matches_ledger"] = bool(shipped == expect)
    out["wire_mb"] = wire_mb
# sentinel-prefixed result line: the parent parses by prefix, so stray
# stdout from jax/engine logging can never masquerade as the datapoint
print("BENCH_JSON " + json.dumps(out))
'''
    results = {}
    epochs, d = S(5, 3), S(1_500_000, 100_000)
    for backend in ("gossip", "gossip_blocked", "shard_map",
                    "shard_map_wire", "shard_map_wire_int4"):
        out = run_child("consensus_backends", backend, child,
                        (backend, epochs, d))
        if out is None:
            continue
        results[backend] = out
        record("consensus_backends", f"{backend}_peak_rss_mb",
               round(results[backend]["peak_rss_mb"], 1))
        record("consensus_backends", f"{backend}_epochs_per_s",
               round(results[backend]["epochs_per_s"], 3))
    for backend in ("shard_map_wire", "shard_map_wire_int4"):
        if backend not in results:
            continue
        sw = results[backend]
        record("consensus_backends", f"{backend}_hlo_gather_sites",
               sw["wire_hlo_gather_sites"])
        record("consensus_backends", f"{backend}_hlo_dtypes",
               "+".join(sw["wire_hlo_dtypes"]))
        record("consensus_backends", f"{backend}_hlo_round_bytes",
               sw["wire_hlo_round_bytes"])
        record("consensus_backends", f"{backend}_bytes_match_hlo",
               sw["wire_hlo_matches_ledger"])
        record("consensus_backends", f"{backend}_total_wire_mb",
               round(sw["wire_mb"], 3))
    if "gossip" in results:
        ref_fp = np.asarray(results["gossip"]["fingerprint"])
        ref_ck = np.asarray(results["gossip"]["checksum"])
        for backend in ("gossip_blocked", "shard_map"):
            if backend in results:
                diff = float(np.abs(
                    np.asarray(results[backend]["fingerprint"])
                    - ref_fp).max())
                # the checksum ([sum, max|.|] over the FULL vector) catches
                # divergence outside the strided fingerprint coordinates
                ck = np.asarray(results[backend]["checksum"])
                ck_ok = bool(np.allclose(ck, ref_ck, rtol=1e-5, atol=1e-3))
                record("consensus_backends", f"{backend}_vs_einsum_maxdiff",
                       f"{diff:.3e}")
                record("consensus_backends", f"{backend}_agrees_with_einsum",
                       bool(diff < 1e-4 and ck_ok))


def bench_overlapped_consensus():
    """The epoch-barrier kill: the SAME dynamic scenario (bernoulli
    participation + edge_drop schedule on a gossip-bound model) run by the
    per-epoch barrier engine, by the K=8 fused superepoch megastep, and by
    the megastep with bounded-staleness (s=1) gossip.  Each config runs in
    its own subprocess (clean ru_maxrss, fresh compile caches); the parent
    records epochs/s + peak RSS per config, the megastep's speedup over
    the barrier, and the `staleness0_bitwise` boolean — a sha256 over the
    final server parameters proving the K=8 / staleness=0 megastep is
    BITWISE the barrier engine (the degeneration oracle, CI-gated)."""
    child = r'''
import os, sys, json, time, hashlib, resource
import jax, jax.numpy as jnp, numpy as np
from repro.core import (FLTopology, TopologySchedule, ParticipationSchedule,
                        init_dfl_state, make_engine)
from repro.optim import sgd

superepoch, staleness, epochs, d = (int(sys.argv[1]), int(sys.argv[2]),
                                    int(sys.argv[3]), int(sys.argv[4]))
m, n, t_c, t_s = 4, 2, 2, 10
topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                  t_server=t_s, graph_kind="ring")

def loss_fn(w, batch, rng):
    # toy objective sized so per-epoch device work is SMALL: the per-epoch
    # HOST barrier (dispatch + readback sync) is what the configs differ
    # in, which is exactly the regime the megastep targets
    return 0.5 * jnp.mean(w * w) + 0.0 * batch.sum(), {}

def batch_fn(epoch, alive):
    # hands over HOST numpy, like a real data loader: the device put is
    # part of the metered path (once per epoch vs once per block)
    return np.zeros((t_c, len(alive), n, 1), np.float32)

engine = make_engine(topo, loss_fn, sgd(1e-3),
                     participation=ParticipationSchedule(
                         kind="bernoulli", rate=0.8, seed=3),
                     topology_schedule=TopologySchedule(
                         kind="edge_drop", drop_prob=0.3, seed=7),
                     superepoch=superepoch, staleness=staleness)

def fresh():
    params = jax.random.normal(jax.random.key(0), (d,), jnp.float32)
    return init_dfl_state(engine.cfg, params, sgd(1e-3), jax.random.key(1))

# warm outside timing: the compiled (M, K) step donates its state operand,
# so the timed run gets a FRESH state (warm buffers are consumed)
engine.run(fresh(), max(superepoch, 1), batch_fn)
state = fresh()
t0 = time.time()
state, hist = engine.run(state, epochs, batch_fn)
wall = time.time() - t0
servers = np.asarray(state.client_params[:, 0], np.float32)
out = {
    "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
    "epochs_per_s": epochs / wall,
    # bitwise fingerprint: digest equality <=> final-params bit equality
    "params_sha256": hashlib.sha256(servers.tobytes()).hexdigest(),
    "loss_last": float(hist["loss"][-1]),
}
# sentinel-prefixed result line: the parent parses by prefix, so stray
# stdout from jax/engine logging can never masquerade as the datapoint
print("BENCH_JSON " + json.dumps(out))
'''
    epochs, d = S(256, 32), S(10_000, 4_000)
    configs = (("barrier", 1, 0), ("superepoch8", 8, 0),
               ("superepoch8_stale1", 8, 1))
    results = {}
    for tag, k, s in configs:
        out = run_child("overlapped_consensus", tag, child,
                        (k, s, epochs, d))
        if out is None:
            continue
        results[tag] = out
        record("overlapped_consensus", f"{tag}_epochs_per_s",
               round(results[tag]["epochs_per_s"], 3))
        record("overlapped_consensus", f"{tag}_peak_rss_mb",
               round(results[tag]["peak_rss_mb"], 1))
    if "barrier" in results and "superepoch8" in results:
        record("overlapped_consensus", "superepoch8_speedup_vs_barrier",
               round(results["superepoch8"]["epochs_per_s"]
                     / results["barrier"]["epochs_per_s"], 3))
        # the degeneration oracle: K=8 + staleness=0 must be the barrier
        # engine BITWISE, not merely allclose — CI asserts this boolean
        record("overlapped_consensus", "staleness0_bitwise",
               bool(results["superepoch8"]["params_sha256"]
                    == results["barrier"]["params_sha256"]))
    if "superepoch8_stale1" in results:
        record("overlapped_consensus", "stale1_loss_last",
               f"{results['superepoch8_stale1']['loss_last']:.3e}")


def bench_lm_epoch_throughput():
    from repro.launch.train import train
    epochs, t_c, seq = S(3, 1), S(3, 2), S(128, 32)
    t0 = time.time()
    res = train("smollm-360m", servers=2, clients=2, t_client=t_c,
                t_server=5, epochs=epochs, seq_len=seq, per_client_batch=2,
                gamma=0.05, log_every=100)
    dt = time.time() - t0
    tokens = epochs * t_c * 4 * 2 * seq
    record("lm_epoch_throughput", "smoke_tokens_per_s", round(tokens / dt, 1))
    record("lm_epoch_throughput", "loss_delta",
           round(res["history"]["loss"][0] - res["history"]["loss"][-1], 4))


def bench_compressed_consensus():
    """The repro.comm subsystem: compressor x backend sweep on a 32-d
    regression task (d=2 would make byte ratios meaningless), recording
    bytes-on-wire vs consensus error vs wall-clock.  Acceptance criteria
    recorded as explicit booleans: int8 + error feedback reaches the fig-3
    consensus tolerance (server disagreement < 1e-3, max server error to
    w* < 0.05) while BytesTracker reports >= 3.5x fewer on-wire bytes than
    uncompressed float32 gossip; the metadata byte counts equal the
    analytic closed forms."""
    from repro.comm.accounting import analytic_row_bytes
    from repro.comm.compressors import make_compressor
    from repro.core import FLTopology, init_dfl_state, make_engine
    from repro.data import RegressionSpec, make_regression_task
    from repro.optim import sgd

    m, n, t_c, t_s = 5, 5, S(25, 10), S(25, 10)
    epochs = S(150, 8)
    d = 32
    rng = np.random.default_rng(7)
    w_true = tuple(float(v) for v in
                   np.concatenate([rng.normal(0, 2.0, d - 1), [2.0]]))
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(
        topo, RegressionSpec(w_star=w_true, heterogeneity=0.3), seed=0)
    w_star = task["w_star"]
    gamma = 0.4 / (9.0 * t_c)

    # metadata-vs-analytic cross-check rides along with the sweep
    ok = all(make_compressor(s).wire_bytes_per_row(dd)
             == analytic_row_bytes(make_compressor(s), dd)
             for s in ("int8", "int4", "top_k:0.05", "random_k:0.1")
             for dd in (2, d, 1000))
    record("compressed_consensus", "bytes_metadata_matches_analytic", ok)

    sweep = {
        "none": ("none", False, "simulated"),
        "int8": ("int8", False, "simulated"),
        "int8_ef": ("int8", True, "simulated"),
        "int4_ef": ("int4", True, "simulated"),
        "top_k10_ef": ("top_k:0.10", True, "simulated"),
        # the physical wire: codes through the collectives, re-quantized
        # at every hop — must still reach the fig-3 tolerance
        "int8_ef_phys": ("int8", True, "physical"),
        "int4_ef_phys": ("int4", True, "physical"),
    }
    from repro.core import consensus as cns

    a_np = topo.mixing_matrix()
    stats = {}
    for label, (spec, use_ef, wire) in sweep.items():
        for mode in ("gossip", "gossip_blocked"):
            if mode == "gossip_blocked":
                # inject a right-sized blocked backend: the default 4 MiB
                # block would pad this 32-d model 100k-fold per round
                backend = cns.make_backend(
                    "gossip_blocked", a_np, t_s, block=256,
                    compression=spec, error_feedback=use_ef, wire=wire)
                kw = {"consensus_backend": backend}
            else:
                kw = {"consensus_mode": mode, "compression": spec,
                      "error_feedback": use_ef, "wire": wire}
            engine = make_engine(topo, task["loss_fn"], sgd(gamma), **kw)
            state = init_dfl_state(engine.cfg, jnp.zeros((d,)), sgd(gamma),
                                   jax.random.key(0))
            t0 = time.time()
            state, hist = engine.run(state, epochs, task["batch_fn"])
            wall = time.time() - t0
            servers = np.asarray(state.client_params[:, 0])
            err = float(np.linalg.norm(servers - w_star, axis=-1).max())
            dis = hist["disagreement"][-1]
            tag = f"{label}_{mode}"
            record("compressed_consensus", f"{tag}_final_err", round(err, 5))
            record("compressed_consensus", f"{tag}_final_disagreement",
                   f"{dis:.3e}")
            record("compressed_consensus", f"{tag}_wall_s", round(wall, 2))
            if "wire_mb" in hist:
                record("compressed_consensus", f"{tag}_wire_mb",
                       round(sum(hist["wire_mb"]), 4))
                record("compressed_consensus", f"{tag}_bytes_ratio",
                       round(hist["wire_ratio"][-1], 3))
            stats[tag] = {"err": err, "dis": dis,
                          "ratio": hist.get("wire_ratio", [1.0])[-1]}
    hero = stats["int8_ef_gossip"]
    record("compressed_consensus", "int8_ef_reaches_fig3_tolerance",
           bool(hero["dis"] < 1e-3 and hero["err"] < 0.05))
    record("compressed_consensus", "int8_ef_bytes_ratio_ge_3.5",
           bool(hero["ratio"] >= 3.5))
    phys = stats["int8_ef_phys_gossip"]
    record("compressed_consensus", "physical_int8_ef_reaches_fig3_tolerance",
           bool(phys["dis"] < 1e-3 and phys["err"] < 0.05))
    record("compressed_consensus", "physical_int8_ef_bytes_ratio",
           round(phys["ratio"], 3))


def bench_byzantine_consensus():
    """Attack x defense grid on the fig-3 regression task (homogeneous
    shards so the honest optimum is unambiguous): does each attack break
    plain gossip, and does each robust screen hold under it?  Records the
    honest servers' max error to w*, their mutual disagreement, and wall
    time — the robustness datapoint tracked in BENCH_consensus.json."""
    from repro.core import (ByzantineSchedule, FLTopology, init_dfl_state,
                            make_engine)
    from repro.data import RegressionSpec, make_regression_task
    from repro.optim import sgd

    m, n, t_c, t_s, epochs = 8, 3, S(15, 6), 8, S(40, 4)
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="complete")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.0),
                                seed=0)
    w_star = task["w_star"]
    gamma = 1.5 / (9.0 * t_c)
    attacks = {"none": None,
               "sign_flip": "sign_flip:0.125",
               "scaled_noise": "scaled_noise:0.125:10.0",
               "inlier_shift": "inlier_shift:0.125:1.0"}
    defenses = ("gossip", "trimmed_mean:1", "median", "clipped")
    for aname, spec in attacks.items():
        byz = ByzantineSchedule.parse(spec, seed=3) if spec else None
        honest = np.ones(m, bool)
        if byz is not None:
            honest = byz.codes(0, tuple(range(m)), m) == 0
        for mode in defenses:
            engine = make_engine(topo, task["loss_fn"], sgd(gamma),
                                 consensus_mode=mode, byzantine=byz)
            state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                                   jax.random.key(0))
            t0 = time.time()
            state, _ = engine.run(state, epochs, task["batch_fn"])
            wall = time.time() - t0
            servers = np.asarray(state.client_params[:, 0])[honest]
            err = float(np.linalg.norm(servers - w_star, axis=-1).max())
            dis = float(np.linalg.norm(servers - servers.mean(0),
                                       axis=-1).max())
            tag = f"{aname}_{mode.replace(':', '')}"
            record("byzantine_consensus", f"{tag}_honest_err",
                   round(err, 5))
            record("byzantine_consensus", f"{tag}_honest_disagreement",
                   f"{dis:.3e}")
            record("byzantine_consensus", f"{tag}_wall_s", round(wall, 2))
    record("byzantine_consensus", "attacker_fraction", 0.125)
    record("byzantine_consensus", "graph", "complete8")


def bench_obs_phases():
    """The repro.obs stack on a full dynamic scenario (sampled
    participation + faulty links + drop/rejoin churn + physical int8+EF
    wire): per-phase wall breakdown from the span tracer (local vs gossip
    vs surgery vs host aggregation), obs-on vs obs-off overhead, the
    bitwise-inertness cross-check, and validating JSONL + Chrome trace
    artifacts for CI to upload."""
    from repro.core import (FLTopology, FaultEvent, FaultSchedule,
                            ParticipationSchedule, TopologySchedule,
                            init_dfl_state, make_engine)
    from repro.data import RegressionSpec, make_regression_task
    from repro.obs import (JSONLSink, MemorySink, MetricsHub, Observability,
                           Tracer, load_jsonl, validate_chrome_trace,
                           validate_jsonl)
    from repro.optim import sgd

    m, n, t_c, t_s, epochs = 4, 4, S(20, 4), S(8, 3), S(30, 8)
    topo = FLTopology(num_servers=m, clients_per_server=n, t_client=t_c,
                      t_server=t_s, graph_kind="ring")
    task = make_regression_task(topo, RegressionSpec(heterogeneity=0.5),
                                seed=0)
    gamma = 0.4 / (9.0 * t_c)
    kw = dict(consensus_mode="gossip", compression="int8",
              error_feedback=True, wire="physical",
              participation=ParticipationSchedule(kind="bernoulli",
                                                  rate=0.7, seed=7),
              topology_schedule=TopologySchedule(kind="edge_drop",
                                                 drop_prob=0.3, seed=11),
              faults=FaultSchedule((FaultEvent(epochs // 3, "drop", 2),
                                    FaultEvent(2 * epochs // 3, "rejoin",
                                               2))))

    def run(obs):
        engine = make_engine(topo, task["loss_fn"], sgd(gamma), obs=obs,
                             **kw)
        state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(gamma),
                               jax.random.key(0))
        hist = {}
        t0 = time.time()
        for epoch in range(epochs):
            state, rec = engine.run_epoch(state, epoch, task["batch_fn"])
            for k, v in rec.items():
                hist.setdefault(k, []).append(v)
        return hist, time.time() - t0, engine

    hist_off, wall_off, _ = run(None)

    os.makedirs(OUT, exist_ok=True)
    jsonl_path = os.path.join(OUT, "telemetry_smoke.jsonl")
    trace_path = os.path.join(OUT, "trace_smoke.json")
    tracer = Tracer()
    obs = Observability(
        hub=MetricsHub([MemorySink(),
                        JSONLSink(jsonl_path,
                                  run_info={"bench": "obs_phases",
                                            "smoke": SMOKE})]),
        tracer=tracer, monitor=True)
    hist_on, wall_on, engine = run(obs)
    obs.close()
    tracer.save_chrome(trace_path)

    inert = (set(hist_off) == set(hist_on)
             and all(hist_off[k] == hist_on[k] for k in hist_off))
    record("obs_phases", "bitwise_inert", inert)
    record("obs_phases", "epochs", epochs)
    record("obs_phases", "wall_off_s", round(wall_off, 3))
    record("obs_phases", "wall_on_s", round(wall_on, 3))
    record("obs_phases", "obs_overhead_pct",
           round(100.0 * (wall_on - wall_off) / max(wall_off, 1e-9), 1))
    phase_s = {}
    for sp in tracer.spans:
        phase_s[sp.name] = phase_s.get(sp.name, 0.0) + sp.duration_ns / 1e9
    for name in ("local-period", "gossip-period", "fault-surgery",
                 "host-aggregation"):
        record("obs_phases", f"phase_{name.replace('-', '_')}_s",
               round(phase_s.get(name, 0.0), 4))
    compiles = [ev["args"]["cause"] for ev in tracer.instants
                if ev["name"] == "compile"]
    record("obs_phases", "compiles", len(compiles))
    record("obs_phases", "compile_causes", ";".join(sorted(set(compiles))))
    n_events = len(validate_jsonl(load_jsonl(jsonl_path)))
    import json as _json
    with open(trace_path) as f:
        n_trace = len(validate_chrome_trace(_json.load(f)))
    record("obs_phases", "jsonl_events", n_events)
    record("obs_phases", "trace_events", n_trace)
    record("obs_phases", "scenario",
           "bernoulli0.7+edge_drop0.3+churn+int8_ef_physical")


BENCHES = {
    "fig3_consensus": bench_fig3_consensus,
    "thm1_epsilon_sweep": bench_thm1_epsilon_sweep,
    "consensus_strategies": bench_consensus_strategies,
    "topology_sweep": bench_topology_sweep,
    "dynamic_federation": bench_dynamic_federation,
    "directed_federation": bench_directed_federation,
    "consensus_backends": bench_consensus_backends,
    "compressed_consensus": bench_compressed_consensus,
    "byzantine_consensus": bench_byzantine_consensus,
    "overlapped_consensus": bench_overlapped_consensus,
    "obs_phases": bench_obs_phases,
    "kernel_micro": bench_kernel_micro,
    "lm_epoch_throughput": bench_lm_epoch_throughput,
}
CHILD_BENCHES = ("consensus_backends", "overlapped_consensus")


def main() -> None:
    global SMOKE
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark subset, e.g. "
                         "'kernel_micro,topology_sweep'")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (seconds per bench): keeps benchmarks "
                         "executable in the CI fast job; numbers are not "
                         "meaningful")
    args = ap.parse_args()
    SMOKE = args.smoke
    enable_compile_cache()
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in BENCHES]
        if unknown:
            raise SystemExit(f"unknown benchmark(s) {unknown}; choose from "
                             f"{list(BENCHES)}")
    else:
        names = list(BENCHES)
    # the benches with child processes first: a device belongs to one
    # process, and this one holds it from its first JAX computation on
    names.sort(key=lambda n: n not in CHILD_BENCHES)
    print("name,metric,value")
    for name in names:
        BENCHES[name]()
    os.makedirs(OUT, exist_ok=True)
    # smoke numbers are for execution coverage only: never overwrite the
    # recorded full-size results with them
    out_name = "bench_results_smoke.csv" if SMOKE else "bench_results.csv"
    path = os.path.join(OUT, out_name)
    ran = {name for name, _, _ in RESULTS}
    kept = []
    if args.only and os.path.exists(path):
        # a partial (--only) run refreshes ITS benches' rows and keeps the
        # rest of the recorded results instead of clobbering them
        with open(path) as f:
            kept = [ln.rstrip("\n") for ln in f.readlines()[1:]
                    if ln.split(",", 1)[0] not in ran]
    with open(path, "w") as f:
        f.write("name,metric,value\n")
        for ln in kept:
            f.write(ln + "\n")
        for row in RESULTS:
            f.write(",".join(str(r) for r in row) + "\n")
    write_bench_consensus_json()
    if FAILED:
        raise SystemExit(f"benchmark child process(es) failed: {FAILED}")


def write_bench_consensus_json() -> None:
    """Machine-readable consensus-perf trajectory: whenever the
    consensus_backends / compressed_consensus benchmarks ran, dump their
    rows (per-backend wall-clock + peak RSS, simulated vs physical wire
    bytes and ratios, the HLO cross-check booleans) to
    experiments/BENCH_consensus.json so the numbers are diffable across
    PRs — the CSV is for humans, this file is the datapoint."""
    import json

    tracked = ("consensus_backends", "compressed_consensus",
               "byzantine_consensus", "overlapped_consensus", "obs_phases")
    per_bench = {name: {m: v for n, m, v in RESULTS if n == name}
                 for name in tracked}
    per_bench = {k: v for k, v in per_bench.items() if v}
    if not per_bench:
        return
    out_name = ("BENCH_consensus_smoke.json" if SMOKE
                else "BENCH_consensus.json")
    path = os.path.join(OUT, out_name)
    if os.path.exists(path):
        # KEY-level merge with the recorded datapoint: a partial (--only)
        # run refreshes its benches' metrics, and a bench whose subprocess
        # died mid-run (only an _error row landed) keeps the surviving
        # backends' fresh numbers WITHOUT dropping the dead backend's last
        # good metrics — the trajectory file must never lose a datapoint
        # to one crashed child
        try:
            with open(path) as f:
                old = json.load(f).get("benchmarks", {})
            for name in tracked:
                merged = dict(old.get(name, {}))
                merged.update(per_bench.get(name, {}))
                if merged:
                    per_bench[name] = merged
            per_bench = {k: v for k, v in per_bench.items() if v}
        except (ValueError, OSError):
            pass
    payload = {"smoke": SMOKE, "benchmarks": per_bench}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
