"""PartitionSpec resolver: parameter-leaf paths -> shardings.

Weights are sharded two ways on top of the FL (server, client) layout:

* **TP** over the "model" axis — the head / expert / feature dimension the
  leaf's table entry names, with a fallback dimension when the preferred one
  is not divisible by the axis size (e.g. kv-heads=8 on a 16-wide model
  axis: fall back to the head_dim).
* **FSDP** over the "replica" axis (train, R>1) or the "data" axis (serve) —
  a second weight dimension, ZeRO-3 style; XLA inserts the per-layer
  all-gathers.

Rules are *name-keyed and right-aligned*: a leaf path's last weight-name
component selects (tp_dims, fsdp_dims) as negative dim indices, so the same
table covers plain leaves (d, h, hd), scanned stacks (periods, d, h, hd) and
DFL client copies (M, N, periods, d, h, hd).  Any leading dims not claimed
by the table get the *lead spec* — ("server", "client") for DFL state, ()
for serve — and everything else is replicated.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# name -> (tp candidate dims, fsdp candidate dims), negative = from the right
_RULES: Dict[str, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {
    "embed":   ((-2,), (-1,)),
    "head":    ((-1,), (-2,)),
    "w_q":     ((-2,), (-3,)),
    "w_k":     ((-2, -1), (-3,)),
    "w_v":     ((-2, -1), (-3,)),
    "w_o":     ((-3,), (-1,)),
    "b_q":     ((-2,), ()),
    "b_k":     ((-2,), ()),
    "b_v":     ((-2,), ()),
    "gate":    ((-1,), (-2,)),
    "up":      ((-1,), (-2,)),
    "down":    ((-2,), (-1,)),
    # MoE expert tables: expert-parallel first, feature-parallel fallback
    "w_gate":  ((-3, -1), (-2,)),
    "w_up":    ((-3, -1), (-2,)),
    "w_down":  ((-3, -2), (-1,)),
    # MLA
    "w_dq":    ((-1,), (-2,)),
    "w_uq":    ((-2,), (-3,)),
    "w_dkv":   ((), (-2,)),          # shared latent projection: TP-replicated
    "w_ukv":   ((-2,), (-3,)),
    # Mamba
    "in_proj": ((-1,), (-2,)),
    "conv_w":  ((-1,), ()),
    "conv_b":  ((-1,), ()),
    "out_proj": ((-2,), (-1,)),
}
# everything else (norm scales, router, biases, a_log, dt_bias, d_skip,
# scalar counters) is replicated beyond the lead spec.

_LAST_NAME = re.compile(r"([A-Za-z_]\w*)(?:\[|$)")


def _leaf_name(path: Tuple) -> str:
    """Last dict-key component of a tree path."""
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _spec_for_leaf(name: str, ndim: int, shape: Tuple[int, ...],
                   lead: Tuple[Optional[str], ...], tp_axis: Optional[str],
                   tp_size: int, fsdp_axis: Optional[str], fsdp_size: int,
                   mesh_shape: Dict[str, int]) -> P:
    entry = [None] * ndim
    for i, ax in enumerate(lead):
        if i < ndim and ax is not None:
            entry[i] = ax
    n_lead = len(lead)
    tp_dims, fsdp_dims = _RULES.get(name, ((), ()))

    def place(axis: Optional[str], size: int, cands: Sequence[int]) -> None:
        if axis is None or size <= 1:
            return
        for c in cands:
            i = ndim + c
            if i < n_lead or i < 0:
                continue
            if entry[i] is None and shape[i] % size == 0:
                entry[i] = axis
                return

    place(tp_axis, tp_size, tp_dims)
    place(fsdp_axis, fsdp_size, fsdp_dims)
    return P(*entry)


_ATTN_LEAVES = frozenset(
    ("w_q", "w_k", "w_v", "w_o", "b_q", "b_k", "b_v"))


def _tree_specs(tree: Any, lead: Tuple[Optional[str], ...],
                mesh: Mesh, tp_axis: Optional[str],
                fsdp_axis: Optional[str],
                attn_tp: bool = True) -> Any:
    """``attn_tp=False`` replicates the attention projections instead of TP:
    for archs whose head count does not divide the model axis, the hd-dim
    fallback would leave K/V head-dim-sharded and every score contraction
    becomes a (b, h, s, chunk) all-reduce — measured 8.2 TB/device on
    smollm prefill."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = shape.get(tp_axis, 1) if tp_axis else 1
    fs = shape.get(fsdp_axis, 1) if fsdp_axis else 1
    # a lead axis the mesh lacks (e.g. 'client' on a ('server',) mesh,
    # one server per chip) replicates that dim
    lead = tuple(ax if ax in shape else None for ax in lead)

    def leaf_spec(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return P()
        name = _leaf_name(path)
        use_tp = tp_axis if (attn_tp or name not in _ATTN_LEAVES) else None
        return _spec_for_leaf(name, leaf.ndim, leaf.shape,
                              lead, use_tp, tp, fsdp_axis, fs, shape)

    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


# ---------------------------------------------------------------------------
# public resolvers
# ---------------------------------------------------------------------------


def fl_param_specs(params: Any, mesh: Mesh, *,
                   tp_axis: Optional[str] = "model") -> Any:
    """DFL client params: leaves (M, N, *w) on the FL mesh."""
    return _tree_specs(params, ("server", "client"), mesh,
                       tp_axis=tp_axis, fsdp_axis="replica")


def serve_param_specs(params: Any, mesh: Mesh, *,
                      fsdp: bool = True, attn_tp: bool = True) -> Any:
    """Serving params on the ("data","model") mesh: TP over "model" always;
    2-D (FSDP over "data") only when ``fsdp`` — small models replicate over
    "data" instead (weight-gather traffic isn't worth <2 GB of savings, and
    FSDP'd weights fight data-sharded batches at every matmul)."""
    return _tree_specs(params, (), mesh, tp_axis="model",
                       fsdp_axis="data" if fsdp else None, attn_tp=attn_tp)


def fl_batch_spec(mesh: Mesh, batch_div_replica: bool,
                  batch_over_model: bool = False) -> P:
    """Per-epoch batch leaves (T_C, M, N, b, ...); axes the mesh lacks are
    left out (on a ('server',) mesh: ``P(None, 'server', None, None)``)."""
    names = set(mesh.axis_names)
    axes = []
    if batch_div_replica and "replica" in names:
        axes.append("replica")
    if batch_over_model and "model" in names:
        axes.append("model")
    b_axis = tuple(axes) if axes else None
    lead = [ax if ax in names else None for ax in ("server", "client")]
    return P(None, *lead, b_axis)


def fl_state_specs(state: Any, mesh: Mesh, *,
                   tp_axis: Optional[str] = "model") -> Any:
    """Shardings for a DFLState pytree (params + opt + scalars).

    The compression error-feedback residual (``DFLState.ef_residual``) is
    SERVER-level wire state — leaves ``(M, *w)`` with no client axis — so
    it gets the ``('server',)`` lead of the server aggregates rather than
    the client grid lead (which would scatter a weight dim over the
    'client' mesh axis)."""
    specs = _tree_specs(state, ("server", "client"), mesh,
                        tp_axis=tp_axis, fsdp_axis="replica")
    ef = getattr(state, "ef_residual", None)
    if ef is not None and hasattr(specs, "_replace"):
        specs = specs._replace(ef_residual=_tree_specs(
            ef, ("server",), mesh, tp_axis=tp_axis, fsdp_axis="replica"))
    return specs


def fl_server_specs(server_tree: Any, mesh: Mesh, *,
                    tp_axis: Optional[str] = "model") -> Any:
    """Server-aggregate tree (leaves ``(M, *w)``): leading 'server' axis
    plus the same name-keyed TP/FSDP placement as the client tree — the
    leaf specs a shard_map consensus backend gossips over."""
    return _tree_specs(server_tree, ("server",), mesh,
                       tp_axis=tp_axis, fsdp_axis="replica")


def fl_consensus_backend(topo: Any, mesh: Mesh, server_tree: Any, *,
                         tp_axis: Optional[str] = "model",
                         block: Optional[int] = None,
                         compression: str = "none",
                         error_feedback: bool = False,
                         wire: str = "simulated",
                         staleness: int = 0,
                         compression_flat_sharding=None) -> Any:
    """Mesh-aware consensus-backend construction (the production path).

    Builds a ``consensus.ShardMapBackend`` gossiping ``server_tree``-shaped
    aggregates over the mesh's 'server' axis with ``fl_server_specs``
    placement, seeded with the topology's static mixing matrix (a traced
    per-epoch ``A_p`` still overrides it in dynamic mode).  A non-"none"
    ``compression`` spec (``comm.compressors.make_compressor``) wraps the
    result in a ``consensus.CompressedBackend`` — the same wrap
    ``consensus.make_backend`` applies to the string-selected paths, done
    here because the mesh-aware backend never goes through the registry.
    ``wire="physical"`` makes the wrapped shard_map program gather the
    int8 / packed-int4 codes themselves (``ShardMapBackend.wire_runner``)
    instead of simulating the quantization in-graph — in the BUCKETED
    layout: the device's whole local tree rides as one padded code buffer,
    one s8 + one f32 all-gather per round regardless of leaf count
    (``consensus.gossip_scan_wire_bucketed`` is the bit-exact in-graph
    reference; both int8 and packed int4 ship at engine level).
    ``staleness=s > 0`` software-pipelines the wire rounds (consume codes
    from round ``t - s``, so round t's gather overlaps round t's mix) —
    it requires the delta-coded physical wire, i.e. a non-"none"
    ``compression`` AND ``wire="physical"``; the wrapped backends raise
    otherwise (``consensus.ShardMapBackend`` / ``CompressedBackend``).
    Inject the result via
    ``DFLConfig.consensus_backend``; selection between this,
    'gossip_blocked' and plain 'gossip' is per deployment plan
    (``launch.plans.DeploymentPlan.consensus_backend``)."""
    import numpy as np

    from repro.core import consensus as cns

    a_np = (topo.mixing_matrix() if topo.num_servers > 1
            else np.ones((1, 1)))
    specs = fl_server_specs(server_tree, mesh, tp_axis=tp_axis)
    kw = {} if block is None else {"block": block}
    backend = cns.ShardMapBackend(mesh, a_np, topo.t_server, specs,
                                  staleness=staleness, **kw)
    if compression != "none":
        from repro.comm.compressors import make_compressor
        backend = cns.CompressedBackend(
            backend, make_compressor(compression),
            error_feedback=error_feedback,
            flat_sharding=compression_flat_sharding,
            wire=wire)
    return backend


def named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# serving cache specs
# ---------------------------------------------------------------------------


def serve_cache_specs(cache: Any, mesh: Mesh, batch: int,
                      attn_tp: bool = True) -> Any:
    """KV / SSM cache shardings.

    batch > 1: shard batch over "data" (heads/features over "model").
    batch == 1 (long_500k): shard the *sequence* dim of length-proportional
    caches over "data" — blockwise/ring-style decode attention; state-shaped
    leaves (SSM) shard heads over "model".
    """
    data = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
    model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    b_axis = "data" if (batch > 1 and batch % data == 0) else None

    def leaf_spec(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return P()
        name = _leaf_name(path)
        nd = leaf.ndim
        entry = [None] * nd
        # batch dim: caches built under scan carry (periods, b, ...) or
        # (b, ...) — find the dim whose size == batch (first match).
        b_dim = next((i for i, s in enumerate(leaf.shape) if s == batch), None)
        if b_dim is not None and b_axis is not None:
            entry[b_dim] = b_axis
        if name in ("k", "v"):                       # (.., b, n, kvh, hd)
            if leaf.shape[-2] % model == 0:
                entry[nd - 2] = "model"
            elif attn_tp and leaf.shape[-1] % model == 0:
                # hd-sharded cache only when the attention itself is TP'd;
                # otherwise it back-propagates hd-sharding into K/V and
                # every score contraction all-reduces (smollm: 8.3 TB/dev)
                entry[nd - 1] = "model"
            if b_axis is None and batch == 1 and leaf.shape[-3] % data == 0:
                entry[nd - 3] = "data"               # seq-sharded cache
        elif name in ("c_kv", "k_rope"):             # MLA latent (.., b, n, r)
            # latent has no head axis; shard the rank dim over "model"
            # (512/16=32 for deepseek) — the per-layer latent cache at
            # decode_32k is ~250 GB total and must use both mesh axes.
            if leaf.shape[-1] % model == 0:
                entry[nd - 1] = "model"
            if b_axis is None and batch == 1 and leaf.shape[-2] % data == 0:
                entry[nd - 2] = "data"
        elif name == "conv":                         # (.., b, w-1, ch)
            if leaf.shape[-1] % model == 0:
                entry[nd - 1] = "model"
        elif name == "ssm":                          # (.., b, nh, ds, hd)
            if leaf.shape[-3] % model == 0:
                entry[nd - 3] = "model"
        elif name == "pos":                          # (.., b, n)
            if b_axis is None and batch == 1 and leaf.shape[-1] % data == 0:
                entry[nd - 1] = "data"
        elif name in ("cross_k", "cross_v"):         # (.., b, enc, kvh, hd)
            if leaf.shape[-2] % model == 0:
                entry[nd - 2] = "model"
            elif leaf.shape[-1] % model == 0:
                entry[nd - 1] = "model"
        return P(*entry)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)
