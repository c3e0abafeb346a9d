"""DFL trainer driver: the smoke preset runs on the CPU, ``--full`` trains
the published widths on a TPU (``chip_smoke.py`` runs smollm-360m through
``train_dynamic`` on one v5e chip, at a federation shape that fits it).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
        --servers 2 --clients 2 --t-client 4 --t-server 5 --epochs 3

With at least M devices (a four-chip host at M=4) and no fault schedule the
dynamic driver places one server per device: the DFL state and the batches
shard over a ('server',) mesh, so each device trains its own server's
clients.

Runs the paper's Algorithm 1 end to end: T_C local SGD steps per client on
per-client synthetic LM shards, per-server aggregation, T_S gossip rounds,
broadcast — logging loss / server disagreement / client drift (the Lemma 1
and Lemma 3 quantities) every epoch, with checkpointing.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_arch, get_smoke
from repro.core.engine import worst_client
from repro.core import (DFLConfig, FLTopology, build_dfl_epoch_step,
                        init_dfl_state, make_engine, ByzantineSchedule,
                        FaultSchedule, ParticipationSchedule, SigmaTracker,
                        TopologySchedule, load_participation_trace)
from repro.data import DataConfig, FLDataPipeline
from repro.launch import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.obs import (ConsoleSink, JSONLSink, MetricsHub, Observability,
                       Sink, Tracer)
from repro.optim import sgd

CONSENSUS_BACKENDS = ("auto", "einsum", "blocked", "shard_map")


def server_mesh(m: int) -> jax.sharding.Mesh:
    """A ('server',) mesh over the first ``m`` devices: one server each."""
    return jax.sharding.Mesh(np.array(jax.devices()[:m]).reshape(m),
                             ("server",))


def resolve_consensus_backend(backend: str, consensus_mode: str,
                              topo: FLTopology, params, *,
                              compression: str = "none",
                              error_feedback: bool = False,
                              wire: str = "simulated",
                              staleness: int = 0,
                              ) -> Tuple[str, Optional[object]]:
    """Map the ``--consensus-backend`` CLI flag to the DFLConfig pair
    ``(consensus_mode, consensus_backend)``.

    ``auto`` keeps ``consensus_mode`` as given; ``einsum`` forces the
    per-leaf reference path ('gossip'); ``blocked`` forces the streamed
    'gossip_blocked' path; ``shard_map`` builds the explicit-collective
    ``consensus.ShardMapBackend`` over a ('server',)-axis mesh — that
    needs at least M devices (on CPU set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=M``).
    ``compression``/``error_feedback``/``wire`` only matter for the
    mesh-aware shard_map case (the wrap happens at construction there); the
    string paths are wrapped later by ``dfl.build_dfl_epoch_step`` from
    ``DFLConfig.compression`` / ``DFLConfig.wire``."""
    if backend not in CONSENSUS_BACKENDS:
        raise ValueError(f"unknown consensus backend {backend!r}; choose "
                         f"one of {CONSENSUS_BACKENDS}")
    if backend == "auto":
        return consensus_mode, None
    gossip_family = consensus_mode in ("gossip", "gossip_blocked")
    if not gossip_family:
        raise ValueError(
            f"--consensus-backend {backend} re-executes the T_S-round "
            f"gossip schedule and is undefined for consensus_mode="
            f"{consensus_mode!r}; use --consensus-backend auto there")
    if backend == "einsum":
        return "gossip", None
    if backend == "blocked":
        return "gossip_blocked", None
    m = topo.num_servers
    ndev = jax.device_count()
    if ndev < m:
        raise ValueError(
            f"the shard_map backend gossips over a physical 'server' mesh "
            f"axis of size M={m} but only {ndev} device(s) are visible; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={m} "
            f"on CPU")
    mesh = server_mesh(m)
    server_abs = jax.eval_shape(
        lambda p: jax.tree.map(
            lambda x: jnp.zeros((m,) + x.shape, x.dtype), p), params)
    return "gossip", shd.fl_consensus_backend(topo, mesh, server_abs,
                                              tp_axis=None,
                                              compression=compression,
                                              error_feedback=error_feedback,
                                              wire=wire,
                                              staleness=staleness)


def _setup_lm(arch_id, smoke, servers, clients, t_client, t_server, graph,
              gamma, seq_len, per_client_batch, seed, attn_impl,
              mixing="symmetric", num_layers=None):
    """Shared trainer scaffolding: arch config, topology, loss, optimizer,
    data pipeline (used by both the static and the dynamic driver).

    ``mixing`` is the DFLConfig interpretation (symmetric | row_stochastic |
    push_sum); the directed paths need row-stochastic out-degree weights on
    the topology, symmetric gossip needs Metropolis weights.
    ``num_layers`` cuts the depth and keeps every width."""
    cfg = get_smoke(arch_id) if smoke else get_arch(arch_id)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    topo_mixing = "out_degree" if mixing != "symmetric" else "metropolis"
    topo = FLTopology(num_servers=servers, clients_per_server=clients,
                      t_client=t_client, t_server=t_server, graph_kind=graph,
                      mixing=topo_mixing)
    opts = tf.ApplyOptions(remat=False, attn_impl=attn_impl)
    loss_fn = tf.make_loss_fn(cfg, opts)
    optimizer = sgd(gamma)
    pipe = FLDataPipeline(topo, DataConfig(seq_len=seq_len,
                                           per_client_batch=per_client_batch,
                                           vocab_size=cfg.vocab_size,
                                           seed=seed), arch=cfg)
    return cfg, topo, loss_fn, optimizer, pipe


def _make_observability(*, log_every: int = 1,
                        telemetry_jsonl: Optional[str] = None,
                        chrome_trace: Optional[str] = None,
                        run_info: Optional[dict] = None,
                        sinks: Sequence[Sink] = ()) -> Observability:
    """The trainers' standard obs bundle: a ConsoleSink (the one place the
    old ``epoch ... loss=...`` prints now live), an optional JSONL
    telemetry stream, the caller's own ``sinks``, an optional span tracer
    for a Chrome trace export, and the convergence watchdogs — see
    docs/observability.md."""
    hub = MetricsHub([ConsoleSink(log_every=log_every)])
    if telemetry_jsonl:
        hub.add_sink(JSONLSink(telemetry_jsonl, run_info=run_info))
    for sink in sinks:
        hub.add_sink(sink)
    return Observability(hub=hub,
                         tracer=Tracer() if chrome_trace else None,
                         monitor=True)


def _run_epochs(epochs: int, run_one: Callable[[int], dict],
                obs: Observability, *, observe: bool,
                ckpt_save: Optional[Callable[[int], None]] = None) -> dict:
    """The ONE trainer loop both drivers share (previously each hand-rolled
    its own history accumulation and print formatting): ``run_one(epoch)``
    returns the epoch's record dict, every record flows through the obs
    bundle, and the returned ``history`` keeps its historical shape —
    metric name -> per-epoch list.  ``observe=False`` when ``run_one``
    already observes internally (the dynamic engine's ``run_epoch`` does,
    with per-link / per-server labels and spans the static path lacks)."""
    history: dict = {}
    for epoch in range(epochs):
        if observe:
            with obs.span("epoch", epoch=epoch):
                rec = run_one(epoch)
            obs.observe(epoch, rec)
        else:
            rec = run_one(epoch)
        for k, v in rec.items():
            history.setdefault(k, []).append(v)
        if ckpt_save is not None:
            ckpt_save(epoch)
    return history


def train(arch_id: str, *, smoke: bool = True, servers: int = 2,
          clients: int = 2, t_client: int = 4, t_server: int = 5,
          epochs: int = 3, seq_len: int = 128, per_client_batch: int = 2,
          gamma: float = 0.05, graph: str = "ring",
          consensus_mode: str = "gossip", mixing: str = "symmetric",
          consensus_backend: str = "auto",
          compression: str = "none", error_feedback: bool = False,
          wire: str = "simulated",
          ckpt_dir: Optional[str] = None, seed: int = 0,
          log_every: int = 1, attn_impl: str = "reference",
          telemetry_jsonl: Optional[str] = None,
          chrome_trace: Optional[str] = None) -> dict:
    cfg, topo, loss_fn, optimizer, pipe = _setup_lm(
        arch_id, smoke, servers, clients, t_client, t_server, graph, gamma,
        seq_len, per_client_batch, seed, attn_impl, mixing=mixing)
    params = tf.init_params(jax.random.key(seed), cfg)
    consensus_mode, backend = resolve_consensus_backend(
        consensus_backend, consensus_mode, topo, params,
        compression=compression, error_feedback=error_feedback, wire=wire)
    dfl_cfg = DFLConfig(topology=topo, consensus_mode=consensus_mode,
                        mixing=mixing, consensus_backend=backend,
                        compression=compression,
                        error_feedback=error_feedback, wire=wire)
    step = jax.jit(build_dfl_epoch_step(dfl_cfg, loss_fn, optimizer),
                   donate_argnums=(0,))

    state = init_dfl_state(dfl_cfg, params, optimizer, jax.random.key(seed + 1))
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    ledger = _make_bytes_tracker(dfl_cfg, params)
    obs = _make_observability(
        log_every=log_every, telemetry_jsonl=telemetry_jsonl,
        chrome_trace=chrome_trace,
        run_info={"arch": cfg.name, "driver": "train", "servers": servers})
    # metric-key parity with the dynamic engine's record (documented in the
    # JSONL schema, docs/observability.md): the static path is the dynamic
    # path with full participation, the fixed graph, and no surgery
    sigma = SigmaTracker(topo.num_servers,
                         mode="push_sum" if mixing == "push_sum"
                         else "average")
    a_np = (topo.mixing_matrix() if topo.num_servers > 1
            else np.ones((1, 1)))

    def run_one(epoch: int) -> dict:
        nonlocal state
        batches = pipe.epoch_batches(epoch)
        state, metrics = step(state, batches)
        record = {
            "loss": float(metrics.loss[-1].mean()),
            "disagreement": float(metrics.server_disagreement),
            "drift": float(metrics.client_drift),
            "participation": 1.0,
            "num_servers": float(topo.num_servers),
            "sigma_prod": sigma.update(a_np, topo.t_server),
        }
        if state.psum_weight is not None:
            record["psum_min_weight"] = float(jnp.min(state.psum_weight))
        if ledger is not None:
            record["wire_mb"] = ledger.update() / 1e6
            record["wire_ratio"] = ledger.tracker.ratio()
        record.update(worst_client(jax.device_get(metrics.aux),
                                   np.ones((servers, clients))))
        return record

    def ckpt_save(epoch: int) -> None:
        if ckpt is not None:
            ckpt.save(epoch, state.client_params,
                      meta={"arch": cfg.name, "epoch": epoch})

    history = _run_epochs(epochs, run_one, obs, observe=True,
                          ckpt_save=ckpt_save)
    obs.close()
    if chrome_trace:
        obs.tracer.save_chrome(chrome_trace)
    return {"state": state, "history": history, "topology": topo,
            "cfg": cfg, "obs": obs}


class _StaticWireLedger:
    """Static-trainer wire ledger: a ``comm.accounting.BytesTracker`` bound
    to the fixed topology and model shapes (the dynamic engine carries its
    own per-M version)."""

    def __init__(self, dfl_cfg, params, compressor):
        from repro.comm.accounting import (
            BytesTracker, tree_physical_wire_bytes_per_server)
        from repro.comm.compressors import (tree_message_elems,
                                            tree_wire_bytes_per_server)
        from repro.core.dfl import active_wire
        topo = dfl_cfg.topology
        server_abs = jax.eval_shape(
            lambda p: jax.tree.map(
                lambda x: jnp.zeros((topo.num_servers,) + x.shape, x.dtype),
                p), params)
        wire, wire_block = active_wire(dfl_cfg)
        if wire == "physical":
            # the ledger counts the padded per-block codes + scales the
            # collectives actually gather, not the unpadded metadata form
            self._row = tree_physical_wire_bytes_per_server(
                compressor, server_abs, wire_block)
        else:
            self._row = tree_wire_bytes_per_server(compressor, server_abs)
        self._elems = tree_message_elems(server_abs)
        self._a = (topo.mixing_matrix() if topo.num_servers > 1
                   else np.ones((1, 1)))
        self._t_s = topo.t_server
        self.tracker = BytesTracker(compressor,
                                    push_sum=dfl_cfg.mixing == "push_sum")

    def update(self) -> float:
        return self.tracker.update(self._a, self._t_s, row_bytes=self._row,
                                   elems_per_row=self._elems)


def _make_bytes_tracker(dfl_cfg, params) -> Optional[_StaticWireLedger]:
    from repro.core.dfl import active_compressor
    compressor = active_compressor(dfl_cfg)
    if compressor is None:
        return None
    return _StaticWireLedger(dfl_cfg, params, compressor)


def train_dynamic(arch_id: str, *, smoke: bool = True, servers: int = 2,
                  clients: int = 2, t_client: int = 4, t_server: int = 5,
                  epochs: int = 3, seq_len: int = 128, per_client_batch: int = 2,
                  gamma: float = 0.05, graph: str = "ring",
                  consensus_mode: str = "gossip", mixing: str = "symmetric",
                  consensus_backend: str = "auto",
                  compression: str = "none", error_feedback: bool = False,
                  wire: str = "simulated",
                  superepoch: int = 1, staleness: int = 0,
                  participation_rate: float = 1.0,
                  participation_kind: str = "bernoulli",
                  edge_drop_prob: float = 0.0,
                  straggler_weaken: float = 0.0,
                  asymmetric_drop_prob: float = 0.0,
                  faults: str = "",
                  byzantine: str = "",
                  participation_trace: str = "",
                  ckpt_dir: Optional[str] = None,
                  seed: int = 0, log_every: int = 1,
                  attn_impl: str = "reference",
                  telemetry_jsonl: Optional[str] = None,
                  chrome_trace: Optional[str] = None,
                  sinks: Sequence[Sink] = (),
                  num_layers: Optional[int] = None) -> dict:
    """Dynamic-federation LM training: the same Algorithm-1 cycle driven by
    the scenario engine — partial client participation, per-epoch degraded
    server graphs, scheduled server failure/rejoin (``faults`` is the
    ``"drop:EPOCH:SERVER,rejoin:EPOCH:SERVER"`` CLI syntax), and directed
    degradation (``asymmetric_drop_prob`` fails individual link DIRECTIONS
    per epoch; pair it with ``mixing="push_sum"`` for unbiased consensus).

    ``byzantine`` is the ``"sign_flip:0.1,scaled_noise:0.1:10"`` attack-spec
    syntax (``ByzantineSchedule.parse``); pair it with a robust
    ``consensus_mode`` (``trimmed_mean[:f]`` | ``median`` | ``clipped[:mult]``)
    to keep the honest servers converging.  ``participation_trace`` replays a
    recorded JSONL availability log (``load_participation_trace``) instead of
    sampling participation stochastically.

    ``superepoch=K > 1`` fuses K epochs per compiled dispatch (history
    element-identical at any K; checkpoint cadence coarsens to block
    boundaries); ``staleness=s > 0`` lets gossip round t mix codes from
    round t-s, overlapping each round's collective with its compute
    (changes the consensus operator — see docs/dynamic_federation.md).

    With at least ``servers`` devices and no ``faults`` each server gets its
    own device (``server_mesh``); otherwise the servers share device 0.
    ``sinks`` are extra metric sinks (``repro.obs.Sink``) that see every
    epoch event.
    ``num_layers`` cuts the model's depth at its published widths: a
    four-chip run of the physical wire fits smollm-360m's 32 layers in no
    chip's memory (see ``chip_smoke.py``)."""
    cfg, topo, loss_fn, optimizer, pipe = _setup_lm(
        arch_id, smoke, servers, clients, t_client, t_server, graph, gamma,
        seq_len, per_client_batch, seed, attn_impl, mixing=mixing,
        num_layers=num_layers)
    params = tf.init_params(jax.random.key(seed), cfg)
    consensus_mode, backend = resolve_consensus_backend(
        consensus_backend, consensus_mode, topo, params,
        compression=compression, error_feedback=error_feedback, wire=wire,
        staleness=staleness)

    if participation_trace:
        part = ParticipationSchedule(
            kind="trace", trace=load_participation_trace(participation_trace))
    elif participation_rate >= 1.0:
        part = ParticipationSchedule()                     # full
    elif participation_kind == "bernoulli":
        part = ParticipationSchedule(kind="bernoulli",
                                     rate=participation_rate, seed=seed)
    else:  # fixed_k / round_robin: rate -> clients per server per epoch
        part = ParticipationSchedule(
            kind=participation_kind,
            k=max(1, round(participation_rate * clients)), seed=seed)
    directed_sched = (asymmetric_drop_prob > 0.0
                      or (straggler_weaken > 0.0 and mixing != "symmetric"))
    if directed_sched:
        # --straggler-weaken composes with the directed schedule: weaken
        # individual link DIRECTIONS (topology.weaken_directed_links)
        # instead of symmetric edges; with --mixing push_sum and no drop
        # prob this is the pure directed-straggler scenario.
        tsched = TopologySchedule(kind="asymmetric",
                                  drop_prob=asymmetric_drop_prob,
                                  weaken=straggler_weaken,
                                  seed=seed + 1)
    elif edge_drop_prob > 0.0:
        tsched = TopologySchedule(kind="edge_drop", drop_prob=edge_drop_prob,
                                  seed=seed + 1)
    elif straggler_weaken > 0.0:
        tsched = TopologySchedule(kind="straggler", weaken=straggler_weaken,
                                  seed=seed + 1)
    else:
        tsched = TopologySchedule()                        # static
    fault_schedule = FaultSchedule.parse(faults)
    obs = _make_observability(
        log_every=log_every, telemetry_jsonl=telemetry_jsonl,
        chrome_trace=chrome_trace,
        run_info={"arch": cfg.name, "driver": "train_dynamic",
                  "servers": servers},
        sinks=sinks)
    engine = make_engine(topo, loss_fn, optimizer,
                         consensus_mode=consensus_mode, mixing=mixing,
                         consensus_backend=backend,
                         compression=compression,
                         error_feedback=error_feedback, wire=wire,
                         participation=part, topology_schedule=tsched,
                         faults=fault_schedule,
                         byzantine=(ByzantineSchedule.parse(byzantine,
                                                            seed=seed)
                                    if byzantine else None),
                         obs=obs, superepoch=superepoch,
                         staleness=staleness)

    state = init_dfl_state(engine.cfg, params, optimizer,
                           jax.random.key(seed + 1))
    # the client copies are all the run needs: at published widths the
    # initial tree would hold another full model of device memory
    del params
    # fault surgery changes M, which a server mesh cannot follow: a fault
    # scenario runs unplaced
    if not fault_schedule.events and 1 < servers <= jax.device_count():
        mesh = server_mesh(servers)
        state_sh = shd.named(shd.fl_state_specs(state, mesh, tp_axis=None),
                             mesh)
        batch_sh = jax.sharding.NamedSharding(
            mesh, shd.fl_batch_spec(mesh, batch_div_replica=False))
        state = jax.device_put(state, state_sh)
        engine = dataclasses.replace(engine, shardings=(state_sh, batch_sh))

    def batch_fn(epoch, alive):
        return pipe.epoch_batches(epoch, server_ids=alive)

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None

    def run_one(epoch: int) -> dict:
        nonlocal state
        state, rec = engine.run_epoch(state, epoch, batch_fn)
        return rec

    def ckpt_save(epoch: int) -> None:
        if ckpt is not None:
            ckpt.save(epoch, state.client_params,
                      meta={"arch": cfg.name, "epoch": epoch,
                            "alive": list(engine.alive)})

    if superepoch > 1:
        # superepoch dispatch: the engine runs K-epoch blocks, observing
        # each epoch internally; checkpoint cadence coarsens to block
        # boundaries (the state only materializes host-side post-block —
        # per-epoch saves would all snapshot the block-final state)
        history = {}
        for epoch0, kblk in engine._plan_blocks(epochs):
            state, recs = engine.run_superepoch(state, epoch0, kblk,
                                                batch_fn)
            for rec in recs:
                for k, v in rec.items():
                    history.setdefault(k, []).append(v)
            ckpt_save(epoch0 + kblk - 1)
    else:
        # observe=False: run_epoch observes internally, with the per-link /
        # per-server labels and span structure the host loop cannot see
        history = _run_epochs(epochs, run_one, obs, observe=False,
                              ckpt_save=ckpt_save)
    obs.close()
    if chrome_trace:
        obs.tracer.save_chrome(chrome_trace)
    return {"state": state, "history": history, "engine": engine,
            "cfg": cfg, "obs": obs}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-360m")
    p.add_argument("--smoke", action="store_true", default=True)
    p.add_argument("--full", dest="smoke", action="store_false",
                   help="published widths (runs on one TPU v5e chip; "
                        "see chip_smoke.py for a shape that fits)")
    p.add_argument("--servers", type=int, default=2)
    p.add_argument("--clients", type=int, default=2)
    p.add_argument("--t-client", type=int, default=4)
    p.add_argument("--t-server", type=int, default=5)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--gamma", type=float, default=0.05)
    p.add_argument("--graph", default="ring",
                   choices=("ring", "complete", "star", "line", "erdos_renyi",
                            "directed_ring", "random_orientation"))
    p.add_argument("--consensus-mode", default="gossip",
                   help="inter-server mixing: gossip | gossip_blocked | "
                        "collapsed | chebyshev | exact_mean | none, or a "
                        "robust screening variant trimmed_mean[:f] | median "
                        "| clipped[:mult] (validated by "
                        "consensus.make_backend)")
    p.add_argument("--consensus-backend", default="auto",
                   choices=CONSENSUS_BACKENDS,
                   help="consensus execution backend: auto (follow "
                        "--consensus-mode), einsum (per-leaf reference "
                        "gossip), blocked (fixed-block streaming), or "
                        "shard_map (explicit collectives over a physical "
                        "'server' mesh axis; needs >= M devices)")
    p.add_argument("--mixing", default="symmetric",
                   choices=("symmetric", "row_stochastic", "push_sum"),
                   help="consensus interpretation of the mixing matrix: "
                        "symmetric doubly-stochastic gossip (the paper), "
                        "naive row-stochastic gossip (directed, biased), or "
                        "push-sum ratio consensus (directed, unbiased)")
    p.add_argument("--compression", default="none",
                   help="lossy inter-server message compression "
                        "(repro.comm): none | int8[:chunk] | int4[:chunk] "
                        "| top_k:RATIO | random_k:RATIO, e.g. top_k:0.05")
    p.add_argument("--error-feedback", action="store_true",
                   help="carry each server's compression residual and fold "
                        "it into the next period's message (removes the "
                        "bias of top-k/clipping at zero extra wire cost)")
    p.add_argument("--wire", default="simulated",
                   choices=("simulated", "physical"),
                   help="where --compression happens: 'simulated' "
                        "quantizes once per period in-graph (host byte "
                        "ledger, the collectives still move floats); "
                        "'physical' ships int8/packed-int4 codes through "
                        "the collectives themselves, re-quantizing every "
                        "gossip hop (quantizers + gossip/gossip_blocked/"
                        "shard_map backends only)")
    p.add_argument("--superepoch", type=int, default=1,
                   help="epochs fused per compiled dispatch (the megastep "
                        "K): the host loop, schedule generation, and the "
                        "metric readback run once per K epochs; history is "
                        "element-identical at any K (dynamic engine only)")
    p.add_argument("--staleness", type=int, default=0,
                   help="bounded gossip staleness s: round t mixes peer "
                        "codes from round t-s, so each round's collective "
                        "overlaps the next rounds' compute; 0 = the "
                        "synchronous path, bitwise (gossip/gossip_blocked "
                        "modes, and the delta-coded physical wire)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--log-every", type=int, default=1,
                   help="console epoch-line cadence (ConsoleSink log_every)")
    p.add_argument("--telemetry-jsonl", default=None,
                   help="write the full metric-event stream (schema v1, "
                        "docs/observability.md) to this JSONL path")
    p.add_argument("--chrome-trace", default=None,
                   help="record host-side spans and write a Chrome "
                        "trace-event JSON (load in Perfetto / "
                        "chrome://tracing) to this path")
    dyn = p.add_argument_group(
        "dynamic federation (any of these switches to the scenario engine)")
    dyn.add_argument("--participation-rate", type=float, default=1.0,
                     help="fraction of clients training each epoch (<1 "
                          "enables partial participation)")
    dyn.add_argument("--participation-kind", default="bernoulli",
                     choices=("bernoulli", "fixed_k", "round_robin"))
    dyn.add_argument("--edge-drop-prob", type=float, default=0.0,
                     help="per-epoch probability that each server link fails")
    dyn.add_argument("--straggler-weaken", type=float, default=0.0,
                     help="weight fraction removed from one random link "
                          "per epoch (slow links); with --mixing "
                          "push_sum/row_stochastic or alongside "
                          "--asymmetric-drop-prob it weakens individual "
                          "link DIRECTIONS instead (directed stragglers)")
    dyn.add_argument("--asymmetric-drop-prob", type=float, default=0.0,
                     help="per-epoch probability that each link DIRECTION "
                          "fails independently (directed degradation; "
                          "combine with --mixing push_sum, and optionally "
                          "--straggler-weaken for per-direction weakening)")
    dyn.add_argument("--faults", default="",
                     help="server fault schedule, e.g. 'drop:5:1,rejoin:9:1'")
    dyn.add_argument("--byzantine", default="",
                     help="Byzantine attack schedule, e.g. "
                          "'sign_flip:0.1' or "
                          "'sign_flip:0.1,scaled_noise:0.1:10'; attacked "
                          "servers replace their aggregate before gossip "
                          "(pair with a robust --consensus-mode)")
    dyn.add_argument("--participation-trace", default="",
                     help="JSONL availability-trace path (see "
                          "schedule.save_participation_trace); replays the "
                          "recorded per-epoch client masks instead of "
                          "sampling --participation-rate")
    return p


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    kw = dict(smoke=args.smoke, servers=args.servers, clients=args.clients,
              t_client=args.t_client, t_server=args.t_server,
              epochs=args.epochs, seq_len=args.seq_len,
              per_client_batch=args.batch, gamma=args.gamma,
              graph=args.graph, consensus_mode=args.consensus_mode,
              consensus_backend=args.consensus_backend,
              mixing=args.mixing, compression=args.compression,
              error_feedback=args.error_feedback, wire=args.wire,
              ckpt_dir=args.ckpt_dir, log_every=args.log_every,
              telemetry_jsonl=args.telemetry_jsonl,
              chrome_trace=args.chrome_trace)
    dynamic = (args.participation_rate < 1.0 or args.edge_drop_prob > 0.0
               or args.straggler_weaken > 0.0
               or args.asymmetric_drop_prob > 0.0 or bool(args.faults)
               or bool(args.byzantine) or bool(args.participation_trace)
               # superepoch fusion and bounded staleness live in the
               # dynamic engine / its consensus backends
               or args.superepoch > 1 or args.staleness > 0)
    if dynamic:
        train_dynamic(args.arch,
                      superepoch=args.superepoch, staleness=args.staleness,
                      participation_rate=args.participation_rate,
                      participation_kind=args.participation_kind,
                      edge_drop_prob=args.edge_drop_prob,
                      straggler_weaken=args.straggler_weaken,
                      asymmetric_drop_prob=args.asymmetric_drop_prob,
                      faults=args.faults, byzantine=args.byzantine,
                      participation_trace=args.participation_trace, **kw)
    else:
        train(args.arch, **kw)


if __name__ == "__main__":
    main()
