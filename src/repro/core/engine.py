"""The dynamic-federation engine: the host-side loop that drives the
jit-compiled dynamic epoch step through a scenario.

Split of responsibilities:

* anything that keeps array shapes fixed — partial participation, per-epoch
  mixing matrices — is a TRACED operand (``schedule.EpochSchedule``) of the
  one compiled ``dfl`` epoch step;
* anything that changes shapes — a server dying or rejoining — is host-side
  graph surgery between epochs: slice (or insert) the failed server's row
  out of every ``(M, N, *w)`` leaf, rebuild the topology via
  ``FLTopology.drop_server`` / ``rejoin_server``, and re-jit the step for
  the new M (cached per M, so a drop/rejoin cycle compiles twice, total).

A rejoining server re-enters with the mean of the survivors' models (the
natural 'state transfer from peers' bootstrap) and its clients broadcast
from it, exactly like an end-of-epoch broadcast.

The engine reports per-epoch history including the participating-client
loss, Lemma-1/3 diagnostics, and the host-side product contraction
``sigma_prod`` (``schedule.SigmaTracker``) of the time-varying gossip.

Superepoch dispatch (``superepoch=K > 1``): ``run`` becomes an event-driven
scheduler over K-epoch blocks — host-side schedules (participation masks,
mixing matrices, byzantine codes, batches) are pre-materialized per block,
stacked into one ``overlap.EpochScheduleBatch``, and dispatched through the
fused K-epoch megastep (``overlap.build_dfl_superepoch_step``, jitted with
donation and cached per (M, K)); the stacked ``DFLMetrics`` come back in
ONE ``jax.device_get``.  Blocks split at fault epochs, where graph surgery
changes shapes.  History is element-identical to the barrier loop — the
scan body is the unchanged epoch step (``tests/test_overlap.py``).  All
host metric readbacks (at any K, including the K=1 barrier path) flow
through the injectable ``_device_get`` hook, so tests can count device
syncs per dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.accounting import (BytesTracker,
                                   tree_bucketed_wire_bytes_per_server)
from repro.comm.compressors import tree_wire_bytes_per_server
from repro.core import dfl
from repro.core import overlap
from repro.core import topology as tp
from repro.core.schedule import (EpochSchedule, FaultSchedule,
                                 ParticipationSchedule, SigmaTracker,
                                 TopologySchedule)
from repro.core.topology import FLTopology
from repro.obs import OBS_OFF
from repro.optim import Optimizer

# batch_fn(epoch, alive_original_server_ids) -> batch pytree with leaves
# (T_C, M_alive, N, ...).  Data follows ORIGINAL server identity, so a
# server that drops and rejoins gets its own clients' shards back.
BatchFn = Callable[[int, Tuple[int, ...]], Any]


@dataclasses.dataclass
class DynamicFederationEngine:
    """Drives DFL training under participation/topology/fault schedules."""

    cfg: dfl.DFLConfig
    loss_fn: dfl.LossFn
    optimizer: Optimizer
    participation: ParticipationSchedule = ParticipationSchedule()
    topology_schedule: TopologySchedule = TopologySchedule()
    faults: FaultSchedule = FaultSchedule()
    # observability bundle (repro.obs.Observability) or None for the no-op
    # null bundle.  HARD CONTRACT: attaching one is bitwise inert on
    # training numerics — the instrumentation only reads already-computed
    # values and the compiled programs are identical with obs on or off
    # (asserted in tests/test_obs.py).  Either way every host phase below
    # is a jax.profiler annotation (``obs.span``).
    obs: Any = None
    # superepoch length K: run() dispatches K epochs per compiled program
    # (overlap.build_dfl_superepoch_step) and reads K epochs of metrics
    # back in one transfer.  1 = the per-epoch barrier loop (unchanged).
    superepoch: int = 1
    # ``(state shardings, batch sharding)``: a NamedSharding pytree for the
    # DFLState and one NamedSharding for every batch leaf, placing both on
    # a ('server',) mesh, one server per device
    # (``launch.sharding.fl_state_specs`` / ``fl_batch_spec``).  The jitted
    # steps take and return the state so placed, so each device trains its
    # own server's clients.  None leaves placement to the inputs (one
    # device).  Set it with ``dataclasses.replace`` once the state exists.
    shardings: Optional[Tuple[Any, Any]] = None

    def __post_init__(self):
        if self.obs is None:
            self.obs = OBS_OFF
        if self.superepoch < 1:
            raise ValueError(
                f"superepoch must be >= 1, got {self.superepoch}")
        if not self.cfg.dynamic:
            self.cfg = dataclasses.replace(self.cfg, dynamic=True)
        if (self.topology_schedule.kind == "asymmetric"
                and self.cfg.mixing == "symmetric"):
            raise ValueError(
                "TopologySchedule(kind='asymmetric') emits row-stochastic "
                "A_p: the symmetric gossip path would silently converge to "
                "a biased average — use DFLConfig(mixing='push_sum') or "
                "mixing='row_stochastic'")
        self.topo: FLTopology = self.cfg.topology
        # fail at construction, not mid-run: every fault event must name an
        # ORIGINAL server id (data shards are keyed by original identity)
        self.faults.validate(self.topo.num_servers)
        # ... and the byzantine populations must leave an honest majority
        # candidate (at least one honest server)
        if self.cfg.byzantine is not None:
            self.cfg.byzantine.validate(self.topo.num_servers)
        if self.faults.events and (
                self.shardings is not None
                or getattr(self.cfg.consensus_backend, "mesh_bound", False)):
            raise ValueError(
                "a mesh-bound consensus backend (shard_map) or a server "
                "placement cannot survive fault surgery: the server axis is "
                "a physical mesh axis and cannot change size with M — use "
                "consensus_mode='gossip_blocked' on one device for fault "
                "scenarios")
        # original server ids still alive, in row order of the state arrays
        self.alive: List[int] = list(range(self.topo.num_servers))
        self._initial_m: int = self.topo.num_servers
        self._steps: Dict[int, Callable] = {}
        # fused K-epoch megasteps, cached per (M, K) — K varies at block
        # boundaries (fault epochs and the run tail)
        self._super_steps: Dict[Tuple[int, int], Callable] = {}
        # ALL host metric readbacks flow through this injectable hook —
        # one call per dispatch (run_epoch or superepoch block), which the
        # device-sync regression test counts by swapping it out
        self._device_get: Callable = jax.device_get
        self._tracker = self._fresh_tracker()
        # compressed-gossip wire accounting (None when the wire is exact):
        # one ledger across the whole run — bytes accumulate through fault
        # surgery, unlike the contraction trackers which reset with M
        self._compressor = dfl.active_compressor(self.cfg)
        # the tracker is wire-aware: on the physical wire push-sum's (M,)
        # weight never crosses a collective (it mixes via the in-graph
        # replicated matvec), so its +4 B/message applies only simulated
        self._bytes = (BytesTracker(self._compressor,
                                    push_sum=self.cfg.mixing == "push_sum",
                                    wire=dfl.active_wire(self.cfg)[0])
                       if self._compressor is not None else None)
        self._row_bytes: Dict[int, Tuple[int, int]] = {}  # M -> (bytes, elems)
        # spectral backends (chebyshev) consume a host-side per-epoch
        # |lambda_2(A_p)| alongside the traced matrix
        backend = self.cfg.consensus_backend
        self._needs_spectral = (self.cfg.consensus_mode == "chebyshev"
                                or getattr(backend, "needs_spectral", False))

    def _fresh_tracker(self) -> SigmaTracker:
        mode = "push_sum" if self.cfg.mixing == "push_sum" else "average"
        return SigmaTracker(self.topo.num_servers, mode=mode,
                            staleness=self.cfg.staleness)

    def _reset_psum_weight(self, state: dfl.DFLState) -> dfl.DFLState:
        """Push-sum weights are per-server mass fractions of the CURRENT
        federation (positive, summing to M): after drop/rejoin surgery the
        old weights describe a federation that no longer exists, so they
        reset to 1 — consistent with every consensus period starting from
        unit weight anyway (``consensus.init_push_sum``)."""
        if self.cfg.mixing != "push_sum":
            return state
        return state._replace(
            psum_weight=jnp.ones((self.topo.num_servers,), jnp.float32))

    def _reset_ef_residual(self, state: dfl.DFLState) -> dfl.DFLState:
        """Compression error-feedback residuals are per-server WIRE state of
        the old federation (what each server still owes its peers): after
        drop/rejoin surgery they reset to zero at the new M, mirroring the
        push-sum weight reset — a rejoined server owes nothing, and a
        dropped server's debt left with it."""
        if not dfl.wants_error_feedback(self.cfg):
            return state
        ef = jax.tree.map(lambda x: jnp.zeros_like(x[:, 0]),
                          state.client_params)
        return state._replace(ef_residual=ef)

    def _wire_row_bytes(self, state: dfl.DFLState) -> Tuple[int, int]:
        """(compressed bytes, elements) of one server's message at the
        current federation size, cached per M.  Simulated wire: compressor
        metadata over the server-tree shapes (unpadded payload flooding).
        Physical wire: the BUCKETED padded codes + scales the collectives
        actually gather each round (``comm.accounting.
        tree_bucketed_wire_bytes_per_server`` — one code buffer + one
        scale buffer for the whole tree) — the ledger then reports bytes
        the interconnect really moved, cross-checked against compiled-HLO
        operand shapes in ``tests/test_wire.py``."""
        m = self.topo.num_servers
        if m not in self._row_bytes:
            server_abs = jax.eval_shape(
                lambda t: jax.tree.map(lambda x: x[:, 0], t),
                state.client_params)
            wire, wire_block = dfl.active_wire(self.cfg)
            if wire == "physical":
                row = tree_bucketed_wire_bytes_per_server(
                    self._compressor, server_abs, wire_block)
            else:
                row = tree_wire_bytes_per_server(self._compressor,
                                                 server_abs)
            self._row_bytes[m] = (
                row,
                sum(int(np.prod(l.shape[1:]))
                    for l in jax.tree.leaves(server_abs)))
        return self._row_bytes[m]

    # -- compiled-step cache -------------------------------------------------
    def _step(self) -> Callable:
        m = self.topo.num_servers
        if m not in self._steps:
            cfg = dataclasses.replace(self.cfg, topology=self.topo)
            # donate the carried state: without this every dynamic epoch
            # holds TWO full copies of client params + optimizer state (the
            # static trainer path has always donated — train.py)
            self._steps[m] = self._jit(dfl.build_dfl_epoch_step(
                cfg, self.loss_fn, self.optimizer), 0)
        return self._steps[m]

    def _batch_sharding(self, k: int) -> Any:
        """``self.shardings``' batch placement under ``k`` stacked epoch
        axes (0: one epoch's batch, 1: a superepoch block's)."""
        s = self.shardings[1]
        return jax.sharding.NamedSharding(
            s.mesh, jax.sharding.PartitionSpec(*(None,) * k, *s.spec))

    def _place(self, batches: Any, k: int) -> Any:
        """Put the batch where the placed step takes it.  An explicit put:
        jit would move an uncommitted batch itself, but under a second
        cache entry that ``compile_counts`` would read as a retrace."""
        if self.shardings is None:
            return batches
        return jax.device_put(batches, self._batch_sharding(k))

    def _to_step(self, state: dfl.DFLState) -> dfl.DFLState:
        """The state as the jitted step takes it.  Placed, the PRNG key
        crosses the jit boundary as raw key data, put at exactly the
        state's sharding for it.  A typed key on a multi-device sharding
        gives jit's dispatch cache a second entry at the third call, and so
        does raw data whose spec merely equals the step's (``P(None)``
        against ``P()``): still one trace and one compile, but
        ``compile_counts`` would read a retrace."""
        if self.shardings is None:
            return state
        return state._replace(rng=jax.device_put(
            jax.random.key_data(state.rng), self.shardings[0].rng))

    def _from_step(self, state: dfl.DFLState) -> dfl.DFLState:
        return state if self.shardings is None else _typed_key(state)

    def _jit(self, step: Callable, k: int) -> Callable:
        """jit an epoch step (``k=0``) or a superepoch megastep (``k=1``:
        its batch stacks an epoch axis), donating the carried state.
        Placed, the state goes in and out and the batch goes in on the
        server mesh, and the key as ``_to_step`` gives it; the schedule
        operands and the metrics are left to the compiler."""
        if self.shardings is None:
            return jax.jit(step, donate_argnums=(0,))
        state_sh = self.shardings[0]

        def placed(state, *rest):
            out = step(_typed_key(state), *rest)
            return (_key_data(out[0]), *out[1:])
        return jax.jit(placed, donate_argnums=(0,),
                       in_shardings=(state_sh, self._batch_sharding(k), None),
                       out_shardings=(state_sh,) + (None,) * (1 + k))

    def _super_step(self, k: int) -> Callable:
        """The jitted fused K-epoch megastep for the current federation
        size, cached per (M, K) — same donation as ``_step`` (the carried
        state is consumed by the scan)."""
        m = self.topo.num_servers
        key = (m, k)
        if key not in self._super_steps:
            cfg = dataclasses.replace(self.cfg, topology=self.topo)
            self._super_steps[key] = self._jit(
                overlap.build_dfl_superepoch_step(
                    cfg, self.loss_fn, self.optimizer, k), 1)
        return self._super_steps[key]

    def compile_counts(self) -> Dict[int, int]:
        """Per federation size M, how many distinct programs the cached
        epoch step has traced.  The dynamic-mode contract is EXACTLY 1:
        the EpochSchedule operand is traced, so mask/mixing/byz variation
        must never change the trace signature.  A count above 1 means a
        schedule operand leaked into trace structure (weak-type flip,
        rank change, Python scalar) and every epoch silently recompiles —
        the regression ``analysis.contracts.audit_engine_retrace`` gates
        on this surface."""
        return {m: int(step._cache_size())
                for m, step in self._steps.items()}

    def superepoch_compile_counts(self) -> Dict[Tuple[int, int], int]:
        """Per (M, K), how many distinct programs the cached megastep has
        traced — the superepoch twin of ``compile_counts`` with the same
        EXACTLY-1 contract: the stacked ``EpochScheduleBatch`` is traced,
        so no block's operand values may change the trace signature."""
        return {key: int(step._cache_size())
                for key, step in self._super_steps.items()}

    # -- fault surgery -------------------------------------------------------
    def _drop(self, state: dfl.DFLState, server: int) -> dfl.DFLState:
        """Remove ORIGINAL server id ``server`` from the federation."""
        if server not in self.alive:
            raise ValueError(f"server {server} is not alive")
        pos = self.alive.index(server)
        self.topo, keep = self.topo.drop_server(pos)
        self.alive.pop(pos)
        keep = np.asarray(keep)

        def leaf(x):
            if x.ndim >= 1 and x.shape[0] == keep.size + 1:
                return x[keep]
            return x
        state = dfl.DFLState(
            jax.tree.map(leaf, state.client_params),
            jax.tree.map(leaf, state.opt_state),
            state.epoch, state.rng)
        self._tracker = self._fresh_tracker()
        return self._reset_ef_residual(self._reset_psum_weight(state))

    def _rejoin(self, state: dfl.DFLState, server: Optional[int]) -> dfl.DFLState:
        """ORIGINAL server ``server`` re-enters with the survivor-mean
        model.  Fresh ids are rejected: client data ownership is keyed by
        original identity (``BatchFn``), so a server that never existed has
        no data shard — admitting one would crash (or silently alias
        another server's shard) at the first ``batch_fn`` call."""
        if server is None or not 0 <= server < self._initial_m:
            raise ValueError(
                f"rejoin needs an ORIGINAL server id in [0, "
                f"{self._initial_m}) — got {server!r}; a fresh server has "
                f"no data shard (data follows original identity, see "
                f"FaultSchedule.validate)")
        if server in self.alive:
            raise ValueError(f"server {server} is already alive")
        self.topo, idx = self.topo.rejoin_server()
        self.alive.append(server)

        def leaf(x):
            if x.ndim >= 1 and x.shape[0] == idx:
                new_row = x.mean(axis=0, keepdims=True).astype(x.dtype)
                return jnp.concatenate([x, new_row], axis=0)
            return x
        state = dfl.DFLState(
            jax.tree.map(leaf, state.client_params),
            jax.tree.map(leaf, state.opt_state),
            state.epoch, state.rng)
        self._tracker = self._fresh_tracker()
        return self._reset_ef_residual(self._reset_psum_weight(state))

    def apply_faults(self, state: dfl.DFLState, epoch: int) -> dfl.DFLState:
        for ev in self.faults.at(epoch):
            if ev.kind == "drop":
                state = self._drop(state, ev.server)
            else:
                state = self._rejoin(state, ev.server)
        return state

    # -- observability -------------------------------------------------------
    def _compile_event(self, steps: Dict[Any, Callable], key: Any,
                       known: bool, programs_before: int, **args: Any
                       ) -> None:
        """Emit the ``compile`` event (an attached tracer records it) if
        the dispatch of the cached step ``steps[key]`` just traced a new
        program, with its cause."""
        if steps[key]._cache_size() <= programs_before:
            return
        if not known and len(steps) == 1:
            cause = "first_trace"
        elif not known:
            cause = "federation_size_change"
        else:
            # a schedule operand leaked into trace structure — the
            # compile-once contract (compile_counts) is being violated
            cause = "retrace"
        self.obs.compile_event(cause, m=self.topo.num_servers,
                               programs=int(steps[key]._cache_size()),
                               **args)

    def _schedule(self, epoch: int):
        """Host-side operands of one epoch at the current federation size:
        ``(mask, mixing, lam2, byzantine codes)`` as numpy (lam2 and the
        codes None where unused)."""
        m, n = self.topo.num_servers, self.topo.clients_per_server
        mask_np = self.participation.mask(epoch, m, n)
        a_np = self.topology_schedule.mixing(self.topo, epoch)
        lam2 = (np.float32(tp.lambda_2(a_np)) if self._needs_spectral
                else None)
        byz_np = None
        if self.cfg.byzantine is not None and self.cfg.byzantine.attacks:
            # per-row attack codes over the CURRENT federation: original
            # attacker ids (stable across surgery — drawn over the
            # ORIGINAL size) mapped through the alive row order.  The
            # array is passed every epoch, all-zero included, so the
            # compiled step's operand structure never changes.
            byz_np = self.cfg.byzantine.codes(epoch, tuple(self.alive),
                                              self._initial_m)
        return mask_np, a_np, lam2, byz_np

    @staticmethod
    def _epoch_schedule(mask_np, a_np, lam2, byz_np) -> EpochSchedule:
        """The traced ``EpochSchedule`` operand of the epoch step."""
        return EpochSchedule(jnp.asarray(mask_np, jnp.float32),
                             jnp.asarray(a_np, jnp.float32),
                             None if lam2 is None else jnp.float32(lam2),
                             None if byz_np is None
                             else jnp.asarray(byz_np, jnp.int32))

    def epoch_program(self, state: dfl.DFLState, epoch: int,
                      batch_fn: BatchFn) -> jax.stages.Compiled:
        """The compiled epoch program at the current federation size,
        lowered from the operands ``run_epoch(state, epoch, batch_fn)``
        would pass (nothing runs, nothing is donated).  Its ``as_text()``
        names the ops a device trace of the program shows, with the
        ``jax.named_scope`` phases on each op's metadata, and its
        ``memory_analysis()`` gives the program's bytes.  The program is
        the one ``run_epoch`` runs, so after an epoch has run this
        compiles nothing new."""
        sched = self._epoch_schedule(*self._schedule(epoch))
        batches = self._place(batch_fn(epoch, tuple(self.alive)), 0)
        return self._step().lower(self._to_step(state), batches,
                                  sched).compile()

    # -- the loop ------------------------------------------------------------
    def run_epoch(self, state: dfl.DFLState, epoch: int,
                  batch_fn: BatchFn) -> Tuple[dfl.DFLState, Dict[str, float]]:
        """One epoch: fault surgery, the host schedule, the batch, the
        compiled epoch step, ONE read-back of its metrics, the record.
        Each phase is an ``obs.span`` (a profiler annotation), so a device
        trace shows what the host was doing while the chip idled."""
        obs = self.obs
        with obs.span("epoch", epoch=epoch):
            with obs.span("fault-surgery", epoch=epoch):
                state = self.apply_faults(state, epoch)
            with obs.span("schedule", epoch=epoch):
                m = self.topo.num_servers
                mask_np, a_np, lam2, byz_np = self._schedule(epoch)
                sigma_prod = self._tracker.update(a_np, self.topo.t_server)
                sched = self._epoch_schedule(mask_np, a_np, lam2, byz_np)
                epoch_wire_bytes = None
                if self._bytes is not None:
                    row_bytes, elems = self._wire_row_bytes(state)
                    epoch_wire_bytes = self._bytes.update(
                        a_np, self.topo.t_server, row_bytes=row_bytes,
                        elems_per_row=elems)
            with obs.span("batch", epoch=epoch):
                batches = self._place(batch_fn(epoch, tuple(self.alive)), 0)
            with obs.span("dispatch", epoch=epoch):
                # enqueues the program; the host waits in ``readback``
                m_known = m in self._steps
                step = self._step()
                programs_before = step._cache_size()
                state, metrics = step(self._to_step(state), batches, sched)
                state = self._from_step(state)
            self._compile_event(self._steps, m, m_known, programs_before,
                                epoch=epoch)
            with obs.span("readback", epoch=epoch):
                # ONE device->host transfer for the whole metrics struct:
                # the old per-field float(...)/np.asarray reads each issued
                # their own blocking transfer (5 syncs per epoch on the
                # push-sum + screen path) — everything below is numpy
                metrics_h, psw_h = self._device_get(
                    (metrics, state.psum_weight))
            with obs.span("host-aggregation", epoch=epoch):
                # participant-weighted loss of the last local iteration
                last = np.asarray(metrics_h.loss[-1], np.float32)
                w = mask_np if mask_np.sum() else np.ones_like(mask_np)
                record = {
                    "loss": float((last * w).sum() / w.sum()),
                    "disagreement": float(metrics_h.server_disagreement),
                    "drift": float(metrics_h.client_drift),
                    "participation": float(mask_np.mean()),
                    "num_servers": float(m),
                    "sigma_prod": sigma_prod,
                }
                if byz_np is not None:
                    # fraction of the CURRENT federation attacking this
                    # epoch — the honest-metric masks in tests/benchmarks
                    # key off this
                    record["byzantine"] = float((byz_np > 0).mean())
                if psw_h is not None:
                    # ratio-consensus conditioning: a terminal weight near
                    # 0 means that server's num/w read-out amplified
                    # rounding error
                    record["psum_min_weight"] = float(np.min(psw_h))
                if epoch_wire_bytes is not None:
                    # this epoch's on-wire consensus traffic + the
                    # cumulative compression ratio vs f32 replicas over the
                    # same links.  THIS epoch's update() return, never
                    # history[-1]: an epoch with zero gossip rounds
                    # (t_server=0, or M==1 after drop surgery) still
                    # records its true 0.0 rather than a stale entry — and
                    # never touches an empty history
                    record["wire_mb"] = epoch_wire_bytes / 1e6
                    record["wire_ratio"] = self._bytes.ratio()
                screen_per_round = None
                if metrics_h.screen_rejected is not None:
                    # robust-screen activity, normalised per gossip round;
                    # the per-server breakdown goes to the hub as a
                    # labelled histogram below
                    rounds = max(self.topo.t_server, 1)
                    screen_per_round = (
                        np.asarray(metrics_h.screen_rejected, np.float32)
                        / rounds)
                    record["screen_rejected"] = float(
                        screen_per_round.sum())
                record.update(worst_client(metrics_h.aux, mask_np))
                obs.observe(
                    epoch, record, servers=tuple(self.alive),
                    per_link=(self._bytes.per_link
                              if self._bytes is not None else None),
                    screen_rejected=screen_per_round)
        return state, record

    # -- superepoch dispatch -------------------------------------------------
    def _plan_blocks(self, epochs: int) -> List[Tuple[int, int]]:
        """Cut ``[0, epochs)`` into superepoch dispatch blocks: maximal runs
        of at most ``self.superepoch`` epochs that contain no fault epoch in
        their interior.  Fault surgery changes array shapes, so a fault
        epoch must sit at a block START (where ``run_superepoch`` applies
        surgery before materializing the block's operands) — the tail block
        and the pre-fault remainder are simply shorter, hitting a smaller-K
        megastep cache entry."""
        cuts = {0, epochs}
        cuts.update(ev.epoch for ev in self.faults.events
                    if 0 < ev.epoch < epochs)
        blocks: List[Tuple[int, int]] = []
        ordered = sorted(cuts)
        for lo, hi in zip(ordered[:-1], ordered[1:]):
            e = lo
            while e < hi:
                k = min(self.superepoch, hi - e)
                blocks.append((e, k))
                e += k
        return blocks

    def run_superepoch(
            self, state: dfl.DFLState, epoch0: int, k: int,
            batch_fn: BatchFn) -> Tuple[dfl.DFLState, List[Dict[str, float]]]:
        """Dispatch epochs ``[epoch0, epoch0 + k)`` as ONE fused megastep.

        Host-side schedule generation runs up front for the whole block —
        participation masks, mixing matrices, byzantine codes, contraction
        tracking, batches — then the stacked operands cross to the device
        once, K epochs execute inside one compiled program, and the stacked
        metrics come back in one ``_device_get``.  The per-epoch records
        are built from the SAME formulas as ``run_epoch`` over the stacked
        arrays, so ``run(superepoch=K)`` history is element-identical to
        the barrier loop's (``tests/test_overlap.py``)."""
        obs = self.obs
        with obs.span("superepoch", epoch=epoch0, k=k):
            with obs.span("fault-surgery", epoch=epoch0):
                state = self.apply_faults(state, epoch0)
            m = self.topo.num_servers
            # pre-materialize the block: one host-side pass per epoch, no
            # device work — the schedules are plain numpy until stacked
            with obs.span("schedule", epoch=epoch0, k=k):
                scheds: List[EpochSchedule] = []
                sigma_list: List[float] = []
                for i in range(k):
                    sched_np = EpochSchedule(*self._schedule(epoch0 + i))
                    sigma_list.append(self._tracker.update(
                        sched_np.mixing, self.topo.t_server))
                    scheds.append(sched_np)
                sb = overlap.stack_epoch_schedules(scheds)
                sched = overlap.EpochScheduleBatch(
                    jnp.asarray(sb.mask), jnp.asarray(sb.mixing),
                    None if sb.lam2 is None else jnp.asarray(sb.lam2),
                    None if sb.byz is None else jnp.asarray(sb.byz))
                wire = None
                if self._bytes is not None:
                    row_bytes, elems = self._wire_row_bytes(state)
                    wire = self._bytes.update_many(
                        [s.mixing for s in scheds], self.topo.t_server,
                        row_bytes=row_bytes, elems_per_row=elems)
            with obs.span("batch", epoch=epoch0, k=k):
                batch_list = [batch_fn(epoch0 + i, tuple(self.alive))
                              for i in range(k)]
                batches = self._place(
                    jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list), 1)
            with obs.span("dispatch", epoch=epoch0, k=k):
                m_known = (m, k) in self._super_steps
                step = self._super_step(k)
                programs_before = step._cache_size()
                state, metrics, psw = step(self._to_step(state), batches,
                                           sched)
                state = self._from_step(state)
            self._compile_event(self._super_steps, (m, k), m_known,
                                programs_before, epoch=epoch0, superepoch=k)
            with obs.span("readback", epoch=epoch0, k=k):
                # the block's ONLY device->host transfer: K epochs of
                # stacked metrics + the (K, M) push-sum weight trace
                metrics_h, psw_h = self._device_get((metrics, psw))
            records: List[Tuple[Dict[str, float], Optional[np.ndarray]]] = []
            with obs.span("host-aggregation", epoch=epoch0, k=k):
                rounds = max(self.topo.t_server, 1)
                for i in range(k):
                    mask_np = scheds[i].mask
                    byz_np = scheds[i].byz
                    last = np.asarray(metrics_h.loss[i][-1], np.float32)
                    w = (mask_np if mask_np.sum()
                         else np.ones_like(mask_np))
                    record = {
                        "loss": float((last * w).sum() / w.sum()),
                        "disagreement": float(
                            metrics_h.server_disagreement[i]),
                        "drift": float(metrics_h.client_drift[i]),
                        "participation": float(mask_np.mean()),
                        "num_servers": float(m),
                        "sigma_prod": sigma_list[i],
                    }
                    if byz_np is not None:
                        record["byzantine"] = float((byz_np > 0).mean())
                    if psw_h is not None:
                        record["psum_min_weight"] = float(
                            np.min(psw_h[i]))
                    if wire is not None:
                        epoch_bytes, ratio_after, _ = wire[i]
                        record["wire_mb"] = epoch_bytes / 1e6
                        record["wire_ratio"] = ratio_after
                    screen_per_round = None
                    if metrics_h.screen_rejected is not None:
                        screen_per_round = (
                            np.asarray(metrics_h.screen_rejected[i],
                                       np.float32) / rounds)
                        record["screen_rejected"] = float(
                            screen_per_round.sum())
                    record.update(worst_client(
                        jax.tree.map(lambda x, i=i: x[i], metrics_h.aux),
                        mask_np))
                    records.append((record, screen_per_round))
                for i, (record, screen_per_round) in enumerate(records):
                    obs.observe(
                        epoch0 + i, record, servers=tuple(self.alive),
                        per_link=(wire[i][2] if wire is not None else None),
                        screen_rejected=screen_per_round)
        return state, [r for r, _ in records]

    def run(self, state: dfl.DFLState, epochs: int,
            batch_fn: BatchFn) -> Tuple[dfl.DFLState, Dict[str, List[float]]]:
        history: Dict[str, List[float]] = {}
        if self.superepoch <= 1:
            for epoch in range(epochs):
                state, rec = self.run_epoch(state, epoch, batch_fn)
                for key, v in rec.items():
                    history.setdefault(key, []).append(v)
            return state, history
        for epoch0, k in self._plan_blocks(epochs):
            state, recs = self.run_superepoch(state, epoch0, k, batch_fn)
            for rec in recs:
                for key, v in rec.items():
                    history.setdefault(key, []).append(v)
        return state, history


def worst_client(aux: Optional[Dict[str, Any]], mask: np.ndarray
                 ) -> Dict[str, float]:
    """Each entry of the loss's per-client aux scalars (``DFLMetrics.aux``,
    (M, N) each) at its largest over the participating clients (over all
    of them when none participates)."""
    if not aux:
        return {}
    live = mask > 0 if mask.any() else np.ones(mask.shape, bool)
    return {k: float(np.asarray(v, np.float32)[live].max())
            for k, v in aux.items()}


def _key_data(state: dfl.DFLState) -> dfl.DFLState:
    return state._replace(rng=jax.random.key_data(state.rng))


def _typed_key(state: dfl.DFLState) -> dfl.DFLState:
    return state._replace(rng=jax.random.wrap_key_data(state.rng))


def make_engine(topology: FLTopology, loss_fn: dfl.LossFn,
                optimizer: Optimizer, *,
                consensus_mode: str = "gossip",
                participation: Optional[ParticipationSchedule] = None,
                topology_schedule: Optional[TopologySchedule] = None,
                faults: Optional[FaultSchedule] = None,
                obs: Optional[Any] = None,
                superepoch: int = 1,
                **cfg_kw) -> DynamicFederationEngine:
    """Convenience constructor mirroring ``DFLConfig`` defaults.

    Any extra keyword (``mixing``, ``metrics``, ``grad_microbatches``, ...)
    is forwarded to ``DFLConfig``; ``dynamic=True`` is always set.  Typical
    usage on the paper's Sec.-IV regression task::

        from repro.core import (FLTopology, FaultSchedule,
                                ParticipationSchedule, TopologySchedule,
                                init_dfl_state, make_engine)
        from repro.data import make_regression_task
        from repro.optim import sgd
        import jax, jax.numpy as jnp

        topo = FLTopology(num_servers=5, clients_per_server=5,
                          t_client=25, t_server=10, graph_kind="ring")
        task = make_regression_task(topo, seed=0)
        engine = make_engine(
            topo, task["loss_fn"], sgd(1e-3),
            participation=ParticipationSchedule(kind="bernoulli", rate=0.5),
            topology_schedule=TopologySchedule(kind="edge_drop",
                                              drop_prob=0.3),
            faults=FaultSchedule.parse("drop:10:2,rejoin:25:2"))
        state = init_dfl_state(engine.cfg, jnp.zeros((2,)), sgd(1e-3),
                               jax.random.key(0))
        state, history = engine.run(state, epochs=40, batch_fn=task["batch_fn"])

    ``history`` maps metric name -> per-epoch list (loss, disagreement,
    drift, participation, num_servers, sigma_prod, psum_min_weight under
    ``mixing="push_sum"``, wire_mb / wire_ratio under compressed
    consensus — ``DFLConfig.compression`` — and byzantine, the attacking
    fraction, under a ``byzantine=ByzantineSchedule(...)`` keyword, which
    forwards to ``DFLConfig.byzantine`` like any other config field).

    ``obs`` attaches a ``repro.obs.Observability`` bundle (span tracing +
    metric sinks + convergence watchdogs); omitted, the engine runs with
    the no-op null bundle — see docs/observability.md.

    ``superepoch=K`` is an ENGINE knob, not a ``DFLConfig`` field: it fuses
    K epochs per compiled dispatch (``overlap.build_dfl_superepoch_step``)
    without changing the per-epoch math — history is element-identical at
    any K.  Contrast ``staleness`` (a ``DFLConfig`` field forwarded through
    ``cfg_kw``), which DOES change the consensus operator."""
    cfg = dfl.DFLConfig(topology=topology, consensus_mode=consensus_mode,
                        dynamic=True, **cfg_kw)
    return DynamicFederationEngine(
        cfg, loss_fn, optimizer,
        participation=participation or ParticipationSchedule(),
        topology_schedule=topology_schedule or TopologySchedule(),
        faults=faults or FaultSchedule(), obs=obs, superepoch=superepoch)
