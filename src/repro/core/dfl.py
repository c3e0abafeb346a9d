"""The DFL algorithm (Algorithm 1) as a composable JAX training step.

One *epoch step* is the paper's full cycle, compiled as a single jitted
program so that XLA schedules the local compute and the two communication
phases (client->server aggregation, server<->server gossip) together:

    1. local period     — lax.scan of T_C per-client SGD steps, vmapped over
                          the (M, N) client grid          (Eq. 3)
    2. aggregation      — mean over the client axis       (Eq. 4)
    3. consensus period — T_S gossip rounds  W <- A W     (Eq. 5/7)
    4. broadcast        — server model back to its N clients

State layout: every parameter leaf carries leading axes ``(M, N, *w)``
sharded over the mesh axes ``("server", "client")`` — each device holds only
its own client's copy, so per-client models cost no per-device memory over
plain data parallelism.  Optimizer state follows the same layout and stays
client-local (the paper's SGD is stateless; for stateful optimizers this is
the natural privacy-preserving choice — moments never leave the client).

``consensus_mode``:
    "gossip"         faithful T_S-round schedule (the paper)
    "gossip_blocked" same schedule streamed over fixed-size parameter blocks
                     (the memory-deterministic production form)
    "collapsed"      beyond-paper: one round with A_eff = A^{T_S} (identical math)
    "chebyshev"      beyond-paper: accelerated polynomial gossip
    "exact_mean"     idealised sigma_A=0 limit == hierarchical FL with a root
                     aggregator (the baseline the paper argues against)
    "none"           no inter-server communication (fully local ablation)
    "trimmed_mean[:f]" / "median" / "clipped[:mult]"
                     Byzantine-robust neighbor screening in place of the
                     weighted round (consensus.py; pair with
                     DFLConfig.byzantine to actually be attacked)

Execution is delegated to a ``consensus.ConsensusBackend`` resolved from
``consensus_mode`` (or injected via ``DFLConfig.consensus_backend`` for
mesh-aware strategies like ``consensus.ShardMapBackend``); every backend
accepts the traced per-epoch ``A_p`` of dynamic mode and implements a
push-sum variant, so every execution path serves every scenario.

Directed federation (``DFLConfig.mixing``): when degraded links make the
server graph directed, Eq. 6's doubly-stochastic A may not exist on its
support.  ``mixing="push_sum"`` replaces the consensus period with ratio
consensus (``consensus.gossip_push_sum``): numerator and a per-server scalar
weight both mixed by the column-stochastic A', read out as the unbiased
ratio; the terminal weights ride along in ``DFLState.psum_weight``.
``mixing="row_stochastic"`` keeps the naive (biased) W <- A W update as the
baseline.  See docs/dynamic_federation.md for why naive row-stochastic
gossip is biased.

Dynamic federation (``DFLConfig.dynamic=True``): the compiled epoch step
additionally takes a ``schedule.EpochSchedule`` operand — a per-epoch
``(M, N)`` participation mask and a per-epoch ``(M, M)`` mixing matrix —
so partial participation and time-varying server graphs run through the
SAME compiled program as the static paper setting (all-ones mask + the
static ``A`` reproduces it exactly).  See ``masked_server_mean`` for the
masked Eq. 4 semantics; server failure/rejoin changes array shapes and is
host-side graph surgery (``engine.DynamicFederationEngine``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as cns
from repro.core.topology import FLTopology
from repro.optim import Optimizer

LossFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, Any]]
# (params, batch, rng) -> (scalar loss, aux)


class DFLState(NamedTuple):
    """Carried across epochs. ``client_params`` leaves: (M, N, *w).

    ``psum_weight`` is only populated under ``DFLConfig(mixing="push_sum")``:
    the ``(M,)`` per-server push-sum weight at the END of the last consensus
    period (positive, sums to M).  It is a directed-gossip diagnostic — a
    weight near 0 means that server's ratio read-out num/w was
    ill-conditioned this epoch — and the state the engine must reset on
    server drop/rejoin; each consensus period itself restarts from weight 1
    (see ``consensus.init_push_sum`` for why).  ``None`` in every other
    mixing mode.

    ``ef_residual`` is only populated under compressed consensus with error
    feedback (``DFLConfig.compression`` + ``error_feedback``): the
    per-server compression residual pytree (leaves ``(M, *w)``, mirroring
    the server aggregates) of ``comm.error_feedback`` — what each server
    withheld from the wire last period and re-offers next period.  Like the
    push-sum weight it is per-server wire state, reset to zero on
    drop/rejoin surgery by the engine.  ``None`` otherwise."""

    client_params: Any
    opt_state: Any
    epoch: jax.Array          # int32 scalar
    rng: jax.Array
    psum_weight: Optional[jax.Array] = None   # (M,) or None
    ef_residual: Optional[Any] = None         # server-tree pytree or None


class DFLMetrics(NamedTuple):
    loss: jax.Array                 # (T_C, M, N) per local step per client
    server_disagreement: jax.Array  # ||W - 1 wbar'||_F after consensus (Lemma 1 LHS)
    client_drift: jax.Array         # max_ij ||w^{ij} - w^i_p|| before aggregation (Lemma 3 LHS)
    grad_norm: jax.Array            # mean per-client grad norm of last local step
    # (M,) per-SOURCE robust-screen activity: how many of server j's values
    # the receivers' trimmed_mean/median/clipped screens discarded this
    # epoch's consensus period.  Populated only under a robust backend with
    # metrics="full" (a static fact of the config, NOT of whether an
    # observer is attached — so obs on/off runs the same compiled program);
    # None everywhere else.
    screen_rejected: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class DFLConfig:
    topology: FLTopology
    consensus_mode: str = "gossip"   # gossip | gossip_blocked | collapsed | chebyshev | exact_mean | none
    # How the mixing matrix is interpreted by the consensus period:
    #   "symmetric"       the paper: A doubly stochastic (Eq. 6), plain
    #                     gossip W <- A W preserves the mean.
    #   "row_stochastic"  naive directed gossip: apply a row-stochastic A
    #                     (topology.mixing="out_degree") with the SAME
    #                     W <- A W update.  Converges to the BIASED
    #                     Perron-weighted average pi' W — kept as the
    #                     baseline that shows why push-sum is needed.
    #   "push_sum"        directed gossip done right: ratio consensus with
    #                     numerator + weight mixed by A' (column
    #                     stochastic); unbiased on any strongly-connected
    #                     digraph.  The epoch step carries the per-server
    #                     weights in DFLState.psum_weight.
    mixing: str = "symmetric"
    chebyshev_rounds: Optional[int] = None  # default: ceil(sqrt(T_S * gap stuff)) picked by caller
    param_dtype: Any = jnp.float32
    # NamedSharding for the flattened (M, D) gossip matrix in
    # consensus_mode="gossip_blocked" (e.g. P("server", ("replica","model"))).
    gossip_flat_sharding: Optional[Any] = None
    # Explicit consensus execution backend (consensus.ConsensusBackend).
    # None: resolved from consensus_mode via consensus.make_backend.  Set by
    # the launcher for mesh-aware strategies (consensus.ShardMapBackend via
    # launch.sharding.fl_consensus_backend) — same math as "gossip", with
    # the per-epoch A_p still a traced operand in dynamic mode.
    consensus_backend: Optional[Any] = None
    # "full": compute the Lemma-1/Lemma-3 diagnostics (server disagreement,
    # client drift, grad norm) every epoch — the right setting for the
    # paper-scale simulations and tests.  "light": skip them (zeros) — at
    # 100B+ scale each is a full-parameter-tree reduction whose f32
    # intermediates rival the model itself in HBM.
    metrics: str = "full"
    # Gradient accumulation: each local iteration's per-client batch is
    # processed in this many sequential microbatches with the summed (mean)
    # gradient applied once — identical math to Eq. 3's full-batch gradient,
    # 1/n the activation footprint.  The per-device activation knob for the
    # 100B+ archs (DESIGN.md §2).
    grad_microbatches: int = 1
    # Dynamic federation: the epoch step takes an extra EpochSchedule operand
    # (participation mask + per-epoch mixing matrix + optional spectral
    # estimate for chebyshev) — see module docstring.
    dynamic: bool = False
    # Lossy inter-server compression (the repro.comm subsystem): a
    # comm.compressors.make_compressor spec — "none" | "int8[:chunk]" |
    # "int4[:chunk]" | "top_k:<ratio>" | "random_k:<ratio>".  Anything but
    # "none" wraps the resolved backend in consensus.CompressedBackend, so
    # the consensus period mixes the wire-decompressed messages;
    # "none" builds NO wrapper at all — that path is bitwise the
    # uncompressed program.
    compression: str = "none"
    # Error feedback for the compression above: carry each server's
    # compression residual in DFLState.ef_residual and fold it into the
    # next period's message (comm.error_feedback) — removes the persistent
    # bias of top-k/clipping at zero extra wire cost.  Ignored when
    # compression == "none".
    error_feedback: bool = False
    # Where the compression above happens (consensus.CompressedBackend):
    #   "simulated"  quantize ONCE per period in-graph (payload flooding)
    #                and let the collectives move floats — bytes are a
    #                host-side ledger (the PR-4 wire model).
    #   "physical"   the codes ARE the wire: every gossip round quantizes
    #                before the collective (int8 / packed-int4 all-gathers
    #                and ppermutes) and dequantizes after, so BytesTracker
    #                reports bytes the collectives actually move.  Needs a
    #                quantizer compressor and a per-round gossip schedule
    #                (gossip / gossip_blocked / shard_map).
    # Ignored when compression == "none".
    wire: str = "simulated"
    # Bounded-staleness consensus (consensus.gossip_scan_stale and the
    # software-pipelined wire bodies): gossip round t mixes with neighbor
    # messages from round t - staleness, so the round-t collective overlaps
    # the round-t compute instead of serializing in front of it.  In exact
    # arithmetic the period contracts as A^(T_S // (staleness+1)) — the
    # augmented operator schedule.SigmaTracker(staleness=...) monitors.
    # staleness=0 is BITWISE today's synchronous path (the build branches
    # to the literally unchanged code).  Carried by the literal T_S-round
    # schedules only (gossip / gossip_blocked / the shard_map codec wire);
    # incompatible with mixing="push_sum" and with robust/spectral modes.
    staleness: int = 0
    # Adversarial-server scenario (schedule.ByzantineSchedule or None):
    # marked servers replace their Eq.-4 aggregate with an attack
    # (apply_byzantine) BEFORE the consensus period, so the robust
    # consensus backends (trimmed_mean / median / clipped) are what stands
    # between one attacker and the whole federation.  Dynamic mode only:
    # the per-epoch attack codes ride the EpochSchedule operand.
    byzantine: Optional[Any] = None


# ---------------------------------------------------------------------------
# helpers on the (M, N, ...) layout
# ---------------------------------------------------------------------------


def replicate_to_clients(params: Any, m: int, n: int) -> Any:
    """Initial broadcast: shared w_0 across all servers and clients."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None, None], (m, n) + p.shape), params)


def server_mean(client_tree: Any) -> Any:
    """Eq. 4: w^i = (1/N) sum_j w^{ij}  — mean over the client axis."""
    return jax.tree.map(lambda x: x.mean(axis=1), client_tree)


def broadcast_to_clients(server_tree: Any, n: int) -> Any:
    """End-of-epoch broadcast: every client restarts from its server model."""
    return jax.tree.map(
        lambda s: jnp.broadcast_to(s[:, None], s.shape[:1] + (n,) + s.shape[1:]),
        server_tree)


def global_mean(client_tree: Any) -> Any:
    """w̄ — mean over all servers and clients (analysis quantity)."""
    return jax.tree.map(lambda x: x.mean(axis=(0, 1)), client_tree)


def masked_server_mean(client_tree: Any, mask: jax.Array) -> Any:
    """Eq. 4 under partial participation:

        w^i = (1/|S_p^i|) sum_{j in S_p^i} w^{ij}

    where ``S_p^i = {j : mask[i, j] = 1}`` is server i's participating set
    this epoch — a masked, weight-renormalised mean over the client axis.
    Non-participants contribute nothing and carry their broadcast model
    forward unchanged (enforced by ``carry_forward`` before this is called),
    so a fully-idle server (|S_p^i| = 0) degenerates to the plain mean of N
    identical broadcast copies == its previous model: the server simply
    holds its state through the epoch.  An all-ones mask reproduces the
    paper's Eq. 4 exactly."""
    cnt = mask.sum(axis=1)                                    # (M,)
    safe = jnp.maximum(cnt, 1.0)

    def leaf(x):
        mk = mask.reshape(mask.shape + (1,) * (x.ndim - 2)).astype(x.dtype)
        s = (x * mk).sum(axis=1)
        c = safe.reshape((-1,) + (1,) * (s.ndim - 1)).astype(x.dtype)
        sel = (cnt > 0).reshape((-1,) + (1,) * (s.ndim - 1))
        return jnp.where(sel, s / c, x.mean(axis=1))

    return jax.tree.map(leaf, client_tree)


def carry_forward(mask: jax.Array, new_tree: Any, old_tree: Any) -> Any:
    """Per-client participation select: leaves with a leading ``(M, N)``
    client grid take ``new`` where ``mask`` is set and ``old`` (the epoch's
    broadcast model / pre-epoch optimizer state) where it is not; shared
    leaves (e.g. the scalar step count) always advance."""
    grid = mask.shape

    def leaf(nl, ol):
        if nl.ndim >= 2 and nl.shape[:2] == grid:
            mk = mask.reshape(grid + (1,) * (nl.ndim - 2))
            return jnp.where(mk > 0, nl, ol)
        return nl

    return jax.tree.map(leaf, new_tree, old_tree)


def apply_byzantine(server_tree: Any, codes: jax.Array, key: jax.Array,
                    attacks: Tuple[Any, ...]) -> Any:
    """Inject the scheduled attacks into the pre-gossip server tree.

    ``codes`` is the traced (M,) int32 per-row attack marking of
    ``schedule.ByzantineSchedule.codes`` (0 = honest, k+1 = attacks[k]);
    ``attacks`` is the STATIC tuple of ``schedule.ByzantineAttack`` — the
    attack kinds/scales are compiled in, only who attacks is traced, so
    one program serves every epoch's attacker set.  Pure function of
    ``(tree, codes, key)``: honest rows pass through bitwise untouched.

    Attack semantics (per ``schedule.ByzantineAttack``): ``sign_flip``
    transmits ``-scale * w``; ``scaled_noise`` transmits ``w + scale *
    N(0, I)`` (one fresh key per leaf); ``inlier_shift`` transmits the
    honest coordinatewise envelope's ``scale``-quantile corner ``h_min +
    scale * (h_max - h_min)`` — a collusion that stays inside the honest
    range (computed over ``codes == 0`` rows; if no honest row exists the
    attacker keeps its value, guarding the inf - inf NaN)."""
    honest = codes == 0

    def leaf_fn(leaf, leaf_key):
        out = leaf
        code_b = codes.reshape((-1,) + (1,) * (leaf.ndim - 1))
        for idx, atk in enumerate(attacks):
            if atk.kind == "sign_flip":
                attacked = (-atk.scale) * leaf
            elif atk.kind == "scaled_noise":
                # fold in the attack index: two scaled_noise entries in one
                # schedule must not draw the SAME noise from the leaf key
                attacked = leaf + atk.scale * jax.random.normal(
                    jax.random.fold_in(leaf_key, idx), leaf.shape,
                    leaf.dtype)
            else:  # inlier_shift
                hmask = honest.reshape((-1,) + (1,) * (leaf.ndim - 1))
                hmin = jnp.where(hmask, leaf,
                                 jnp.asarray(jnp.inf, leaf.dtype)).min(0)
                hmax = jnp.where(hmask, leaf,
                                 jnp.asarray(-jnp.inf, leaf.dtype)).max(0)
                target = jnp.broadcast_to(
                    hmin + atk.scale * (hmax - hmin), leaf.shape)
                attacked = jnp.where(honest.any(), target, leaf)
            out = jnp.where(code_b == idx + 1, attacked.astype(leaf.dtype),
                            out)
        return out

    leaves, treedef = jax.tree.flatten(server_tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten(
        [leaf_fn(l, k) for l, k in zip(leaves, keys)])


def _tree_sq_norm(tree: Any) -> jax.Array:
    # reduce with an f32 accumulator WITHOUT first materialising an f32
    # copy of each (possibly multi-GB bf16) leaf
    return sum(jnp.sum(jnp.square(l), dtype=jnp.float32)
               for l in jax.tree.leaves(tree))


def disagreement_norm(server_tree: Any) -> jax.Array:
    """||W - 1 wbar'||_F over the stacked server models (Lemma 1 LHS).

    Uses sum_i ||w_i||^2 - M ||wbar||^2 (per leaf) instead of materialising
    the (M, ...) deviation tensor: under pjit the naive form all-gathers an
    f32 copy of every parameter leaf across the server axis (~2 GB/leaf at
    27B), whereas this form is shard-local squares + one tiny all-reduce."""
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(server_tree):
        m = leaf.shape[0]
        s_sq = jnp.sum(jnp.square(leaf), dtype=jnp.float32)
        mean = leaf.mean(axis=0, dtype=jnp.float32)
        total += s_sq - m * jnp.sum(jnp.square(mean))
    return jnp.sqrt(jnp.maximum(total, 0.0))


def max_client_drift(client_tree: Any, server_tree: Any) -> jax.Array:
    """max_{ij} ||w^{ij} - w^i|| (Lemma 3 LHS).

    ||c - s||^2 = sum c^2 - 2 sum c*s + sum s^2 per (i, j): three bf16
    elementwise products reduced with f32 accumulators — no (M, N, params)
    f32 deviation tensor (the naive form held ~8 f32 expert-table copies)."""
    sq = None
    for c, s in zip(jax.tree.leaves(client_tree),
                    jax.tree.leaves(server_tree)):
        axes = tuple(range(2, c.ndim))
        sb = s[:, None]
        term = (jnp.sum(jnp.square(c), axis=axes, dtype=jnp.float32)
                - 2.0 * jnp.sum(c * sb, axis=axes, dtype=jnp.float32)
                + jnp.sum(jnp.square(sb), axis=axes, dtype=jnp.float32))
        sq = term if sq is None else sq + term
    return jnp.sqrt(jnp.maximum(jnp.max(sq), 0.0))


# ---------------------------------------------------------------------------
# compressed-consensus config resolution (shared with engine / launcher)
# ---------------------------------------------------------------------------


def active_compressor(cfg: "DFLConfig"):
    """The compressor this config's consensus period runs through, or
    ``None`` when the wire is exact — resolved from an injected
    ``consensus.CompressedBackend`` first (the launcher's mesh-aware path),
    then from ``cfg.compression``.  Single source of truth for the engine's
    byte accounting and the EF-state plumbing."""
    backend = cfg.consensus_backend
    if backend is not None:
        if getattr(backend, "compressed", False):
            return backend.compressor
        return None
    if cfg.compression != "none" and cfg.consensus_mode != "none":
        from repro.comm.compressors import make_compressor
        return make_compressor(cfg.compression)
    return None


def wants_error_feedback(cfg: "DFLConfig") -> bool:
    """Whether this config carries an EF residual in ``DFLState`` — must
    agree between ``init_dfl_state`` and the built epoch step (the residual
    is part of the carried pytree)."""
    backend = cfg.consensus_backend
    if backend is not None:
        return bool(getattr(backend, "compressed", False)
                    and backend.error_feedback)
    return (cfg.compression != "none" and cfg.error_feedback
            and cfg.consensus_mode != "none")


def resolve_backend(cfg: "DFLConfig"):
    """The ``consensus.ConsensusBackend`` this config's consensus period
    executes through: the injected ``cfg.consensus_backend`` if any, else
    one built from ``cfg.consensus_mode`` over the static topology matrix
    (``None`` for consensus_mode='none').  Shared by the epoch-step
    builder and ``active_compressor`` so both see the SAME execution
    strategy."""
    topo = cfg.topology
    if cfg.consensus_backend is not None:
        return cfg.consensus_backend
    if cfg.consensus_mode == "none":
        return None
    m = topo.num_servers
    a_np = topo.mixing_matrix() if m > 1 else np.ones((1, 1))
    return cns.make_backend(
        cfg.consensus_mode, a_np, topo.t_server,
        chebyshev_rounds=cfg.chebyshev_rounds,
        gossip_flat_sharding=cfg.gossip_flat_sharding,
        compression=cfg.compression,
        error_feedback=cfg.error_feedback,
        wire=cfg.wire,
        staleness=cfg.staleness)


def active_wire(cfg: "DFLConfig") -> Tuple[str, int]:
    """``(wire mode, wire block)`` of the active compression layer —
    resolved from an injected ``consensus.CompressedBackend`` first, then
    from ``cfg.wire``.  The block is the physical byte-layout partitioning
    (``consensus.DEFAULT_GOSSIP_BLOCK`` on the string paths): the engine's
    byte ledger needs it to count the BUCKETED padded codes + scales the
    collectives actually gather under ``wire='physical'`` (``comm.
    accounting.tree_bucketed_wire_bytes_per_server``), and its tracker
    needs the mode to know that push-sum's weight scalar never crosses a
    physical collective."""
    backend = cfg.consensus_backend
    if backend is not None and getattr(backend, "compressed", False):
        return backend.wire, backend.wire_block
    return cfg.wire, cns.DEFAULT_GOSSIP_BLOCK


# ---------------------------------------------------------------------------
# the epoch step builder
# ---------------------------------------------------------------------------


def build_dfl_epoch_step(
    cfg: DFLConfig,
    loss_fn: LossFn,
    optimizer: Optimizer,
) -> Callable[[DFLState, Any], Tuple[DFLState, DFLMetrics]]:
    """Return ``epoch_step(state, batches) -> (state, metrics)``.

    ``batches`` leaves are ``(T_C, M, N, *per_client_batch)`` — one
    microbatch per client per local iteration.  The returned function is NOT
    jitted; callers wrap it in jax.jit with the desired shardings (and
    donation — see ``engine.DynamicFederationEngine._step`` and
    ``launch.train.train``).
    """
    topo = cfg.topology
    m, n = topo.num_servers, topo.clients_per_server
    if cfg.mixing not in ("symmetric", "row_stochastic", "push_sum"):
        raise ValueError(f"unknown mixing interpretation {cfg.mixing!r}")
    if cfg.mixing == "symmetric" and topo.mixing == "out_degree" and m > 1:
        raise ValueError(
            "topology.mixing='out_degree' emits a row-stochastic (generally "
            "not doubly stochastic) A: running it through the symmetric "
            "gossip path would silently converge to the biased "
            "Perron-weighted average — choose DFLConfig(mixing='push_sum') "
            "(unbiased) or mixing='row_stochastic' (the explicit biased "
            "baseline)")
    if cfg.staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {cfg.staleness}")
    if cfg.staleness and cfg.mixing == "push_sum":
        raise ValueError(
            "bounded staleness is undefined under mixing='push_sum': the "
            "exact (M,) weight recursion has no delayed twin, so a stale "
            "numerator over a fresh weight breaks mass conservation — use "
            "staleness=0 or a symmetric/row_stochastic mixing")
    if cfg.staleness and cfg.consensus_mode == "none" \
            and cfg.consensus_backend is None:
        raise ValueError("staleness > 0 with consensus_mode='none' is "
                         "meaningless: there are no gossip rounds to delay")
    backend = resolve_backend(cfg)
    if backend is not None and cfg.consensus_backend is not None \
            and getattr(backend, "staleness", 0) != cfg.staleness:
        raise ValueError(
            f"DFLConfig.staleness={cfg.staleness} disagrees with the "
            f"injected consensus backend's staleness="
            f"{getattr(backend, 'staleness', 0)}: the SigmaTracker "
            f"contraction and the compiled wire program must see the same "
            f"depth — build the backend with the same staleness")
    if backend is not None:
        if cfg.mixing != "symmetric" and not backend.supports_directed:
            raise ValueError(
                f"consensus backend {backend.name!r} is undefined for "
                f"mixing={cfg.mixing!r}: the directed paths need the "
                f"literal W <- A W / ratio-consensus update — use one of "
                f"('gossip', 'gossip_blocked', 'collapsed', 'shard_map', "
                f"'none')")
        if cfg.dynamic and not backend.supports_traced:
            raise ValueError(
                f"consensus backend {backend.name!r} cannot consume a "
                f"traced per-epoch A_p; use 'gossip', 'gossip_blocked', "
                f"'collapsed', 'chebyshev' or a shard_map backend")
    # byzantine injection: the attack kinds/scales are static facts of the
    # compiled program; WHO attacks is the traced EpochSchedule.byz operand
    byz_attacks = (tuple(cfg.byzantine.attacks)
                   if cfg.byzantine is not None else ())
    if byz_attacks and not cfg.dynamic:
        raise ValueError(
            "DFLConfig.byzantine needs dynamic=True: the per-epoch "
            "attacker codes ride the EpochSchedule operand (use "
            "engine.make_engine, which sets it)")
    # compression wire state: static facts of the compiled program (when
    # False, nothing below touches the rng stream or the residual — the
    # compression="none" program is bitwise the pre-compression one)
    compressed = (backend is not None
                  and getattr(backend, "compressed", False)
                  and m > 1 and topo.t_server > 0)
    # robust screen-activity readout: a STATIC fact of the config (robust
    # backend + full metrics), never of whether an observer is attached —
    # the obs-on and obs-off programs must stay byte-identical.  On the
    # plain paths mix_stats is never called, so nothing changes there
    # either.
    screen_stats = (backend is not None
                    and getattr(backend, "robust", False)
                    and cfg.metrics == "full")

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    # vmap over clients within a server, then over servers
    client_grad = jax.vmap(jax.vmap(grad_fn))

    n_micro = max(cfg.grad_microbatches, 1)

    def local_step(carry, batch_t):
        params, opt_state, rng = carry
        rng, sub = jax.random.split(rng)
        keys = jax.random.split(sub, (m, n))  # typed keys: pass jax.random.key()
        if n_micro == 1:
            (loss, _aux), grads = client_grad(params, batch_t, keys)
        else:
            # split the per-client batch dim (axis 2 after (M, N)) into
            # n_micro sequential microbatches; average the gradients.
            def split(leaf):
                b = leaf.shape[2]
                assert b % n_micro == 0, (leaf.shape, n_micro)
                mb = leaf.reshape(leaf.shape[:2] + (n_micro, b // n_micro)
                                  + leaf.shape[3:])
                return jnp.moveaxis(mb, 2, 0)     # (n_micro, M, N, b/n, ...)
            micro_batches = jax.tree.map(split, batch_t)

            # accumulate in the PARAM dtype: an f32 accumulator doubles to
            # 2x params f32 once the while-loop double-buffers it; scaling
            # each microgradient by 1/n first keeps bf16 accumulation well-
            # conditioned (grads are same-scale summands).
            def micro_step(g_acc, mb):
                (mloss, _maux), g = client_grad(params, mb, keys)
                g_acc = jax.tree.map(
                    lambda a, x: a + (x / n_micro).astype(a.dtype), g_acc, g)
                return g_acc, mloss

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, cfg.param_dtype),
                              params)
            grads, mlosses = jax.lax.scan(micro_step, g0, micro_batches)
            loss = mlosses.mean(axis=0)
        with jax.named_scope("sgd_update"):
            params, opt_state = optimizer.update(grads, opt_state, params)
        if cfg.metrics == "full":
            with jax.named_scope("epoch_metrics"):
                gnorm = jnp.sqrt(_tree_sq_norm(grads) / (m * n))
        else:
            gnorm = jnp.zeros((), jnp.float32)
        return (params, opt_state, rng), (loss, gnorm)

    def apply_consensus(server_tree, a_p=None, psum_weight=None,
                        ef_residual=None, key=None, lam2=None):
        """Run the consensus period through the resolved ConsensusBackend.
        ``a_p``: optional traced per-epoch mixing matrix (dynamic mode);
        ``None`` selects the static topology's A held by the backend.
        ``ef_residual``/``key``: the error-feedback residual tree and the
        stochastic-rounding key, threaded only under compressed consensus;
        ``lam2``: the per-epoch spectral hint for spectral backends.
        Returns ``(server_tree, psum_weight, ef_residual, screen)`` — the
        weight is the terminal push-sum weight under mixing='push_sum',
        the residual the post-transmission EF state (both pass through
        unchanged when their feature is off), and ``screen`` the per-source
        robust screen-activity counts (``(M,)`` under a robust backend
        with full metrics, ``None`` otherwise — see DFLMetrics)."""
        screen0 = (jnp.zeros((m,), jnp.float32) if screen_stats else None)
        if m == 1 or topo.t_server == 0 or backend is None:
            return server_tree, psum_weight, ef_residual, screen0
        if cfg.mixing == "push_sum":
            # each consensus period is a fresh ratio consensus: numerator =
            # this epoch's server aggregates, weight reset to 1 (the carried
            # DFLState.psum_weight is last period's terminal weight, kept as
            # a diagnostic — see init_push_sum for why it must not seed the
            # next period)
            ps0 = cns.init_push_sum(server_tree)
            if compressed:
                ps, ef_residual = backend.mix_push_sum_compressed(
                    ps0, a_p, residual=ef_residual, key=key)
            else:
                ps = backend.mix_push_sum(ps0, a_p)
            return ps.ratio(), ps.weight, ef_residual, screen0
        if compressed:
            mixed, ef_residual = backend.mix_compressed(
                server_tree, a_p, residual=ef_residual, key=key, lam2=lam2)
            return mixed, psum_weight, ef_residual, screen0
        if screen_stats:
            mixed, screen = backend.mix_stats(server_tree, a_p, lam2=lam2)
            return mixed, psum_weight, ef_residual, screen
        return backend.mix(server_tree, a_p, lam2=lam2), psum_weight, \
            ef_residual, screen0

    def epoch_drift(params, start_params):
        """Lemma-3 drift of each client from its start-of-epoch server
        model (the broadcast client params at epoch entry)."""
        if cfg.metrics != "full":
            return jnp.zeros((), jnp.float32)
        with jax.named_scope("epoch_metrics"):
            return max_client_drift(
                params, jax.tree.map(lambda x: x[:, 0], start_params))

    def epoch_disagreement(server):
        if cfg.metrics != "full":
            return jnp.zeros((), jnp.float32)
        with jax.named_scope("epoch_metrics"):
            return disagreement_norm(server)

    def epoch_step(state: DFLState, batches: Any) -> Tuple[DFLState, DFLMetrics]:
        # ---- 1. local period: T_C client SGD iterations (Eq. 3) ----
        carry = (state.client_params, state.opt_state, state.rng)
        with jax.named_scope("local_period"):
            (params, opt_state, rng), (losses, gnorms) = jax.lax.scan(
                local_step, carry, batches)

        # ---- 2. aggregation at each server (Eq. 4) ----
        with jax.named_scope("aggregate"):
            server = server_mean(params)
        drift = epoch_drift(params, state.client_params)

        # ---- 3. consensus period: T_S gossip rounds (Eq. 5/7) ----
        if compressed:
            rng, ckey = jax.random.split(rng)
        else:
            ckey = None
        with jax.named_scope("gossip_period"):
            server, psw, ef_res, screen = apply_consensus(
                server, psum_weight=state.psum_weight,
                ef_residual=state.ef_residual, key=ckey)
        disagreement = epoch_disagreement(server)

        # ---- 4. broadcast w^i_p back to C_i ----
        with jax.named_scope("broadcast"):
            params = broadcast_to_clients(server, n)

        new_state = DFLState(params, opt_state, state.epoch + 1, rng, psw,
                             ef_res)
        metrics = DFLMetrics(loss=losses, server_disagreement=disagreement,
                             client_drift=drift, grad_norm=gnorms[-1],
                             screen_rejected=screen)
        return new_state, metrics

    def epoch_step_dynamic(state: DFLState, batches: Any,
                           sched: Any) -> Tuple[DFLState, DFLMetrics]:
        """Dynamic variant: ``sched`` is an ``EpochSchedule(mask, mixing[,
        lam2])`` of traced operands — one compiled program serves every
        participation mask and mixing matrix of this shape."""
        mask, a_p = sched.mask, sched.mixing
        lam2 = getattr(sched, "lam2", None)
        # ---- 1. local period (Eq. 3) — all clients traced; the mask is
        # applied afterwards, which is mathematically identical (clients are
        # independent during the local period) and keeps the scan dense.
        carry = (state.client_params, state.opt_state, state.rng)
        with jax.named_scope("local_period"):
            (params, opt_state, rng), (losses, gnorms) = jax.lax.scan(
                local_step, carry, batches)
        # ---- 2. masked aggregation (Eq. 4 over the participating set);
        # non-participants carry their broadcast model (and optimizer
        # state) through the epoch untouched ----
        with jax.named_scope("aggregate"):
            params = carry_forward(mask, params, state.client_params)
            opt_state = carry_forward(mask, opt_state, state.opt_state)
            server = masked_server_mean(params, mask)
        drift = epoch_drift(params, state.client_params)

        # ---- 2b. adversarial injection: marked servers replace their
        # aggregate BEFORE gossip — this is the message the federation
        # actually receives, and what robust consensus must screen ----
        if byz_attacks:
            rng, bkey = jax.random.split(rng)
            server = apply_byzantine(server, getattr(sched, "byz"), bkey,
                                     byz_attacks)

        # ---- 3. consensus over this epoch's graph A_p (Eq. 5/7) ----
        if compressed:
            rng, ckey = jax.random.split(rng)
        else:
            ckey = None
        with jax.named_scope("gossip_period"):
            server, psw, ef_res, screen = apply_consensus(
                server, a_p, psum_weight=state.psum_weight,
                ef_residual=state.ef_residual, key=ckey, lam2=lam2)
        disagreement = epoch_disagreement(server)

        # ---- 4. broadcast (every client, participant or not) ----
        with jax.named_scope("broadcast"):
            params = broadcast_to_clients(server, n)

        new_state = DFLState(params, opt_state, state.epoch + 1, rng, psw,
                             ef_res)
        metrics = DFLMetrics(loss=losses, server_disagreement=disagreement,
                             client_drift=drift, grad_norm=gnorms[-1],
                             screen_rejected=screen)
        return new_state, metrics

    return epoch_step_dynamic if cfg.dynamic else epoch_step


def init_dfl_state(cfg: DFLConfig, params: Any, optimizer: Optimizer,
                   rng: jax.Array) -> DFLState:
    """Replicate shared w_0 (Alg. 1 'Initialize') and build optimizer state.
    Under ``mixing='push_sum'`` the state additionally carries a unit
    per-server push-sum weight; under compressed consensus with error
    feedback, a zero per-server compression residual (leaves ``(M, *w)``)."""
    topo = cfg.topology
    client_params = replicate_to_clients(params, topo.num_servers,
                                         topo.clients_per_server)
    opt_state = optimizer.init(client_params)
    psw = (jnp.ones((topo.num_servers,), jnp.float32)
           if cfg.mixing == "push_sum" else None)
    ef = None
    if wants_error_feedback(cfg) and topo.num_servers > 1 \
            and topo.t_server > 0:
        ef = jax.tree.map(
            lambda p: jnp.zeros((topo.num_servers,) + p.shape, p.dtype),
            params)
    return DFLState(client_params, opt_state,
                    jnp.zeros((), jnp.int32), rng, psw, ef)


# ---------------------------------------------------------------------------
# baselines the paper compares against (conceptually)
# ---------------------------------------------------------------------------


def build_fedavg_epoch_step(topology: FLTopology, loss_fn: LossFn,
                            optimizer: Optimizer) -> Callable:
    """Classic single-server FedAvg: same local period, aggregation is a
    global mean (the single central server), no gossip.  Implemented as DFL
    with consensus_mode='exact_mean' — the sigma_A=0 idealisation that
    Theorem 1's epsilon collapses to."""
    cfg = DFLConfig(topology=topology, consensus_mode="exact_mean")
    return build_dfl_epoch_step(cfg, loss_fn, optimizer)


def build_local_only_epoch_step(topology: FLTopology, loss_fn: LossFn,
                                optimizer: Optimizer) -> Callable:
    """No-communication ablation (lower bound on agreement)."""
    cfg = DFLConfig(topology=topology, consensus_mode="none")
    return build_dfl_epoch_step(cfg, loss_fn, optimizer)
