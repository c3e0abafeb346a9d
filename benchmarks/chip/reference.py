"""Plain reference of the first epochs of a DFL training cell.

Written from the published descriptions alone and importing nothing of the
trainer under test: a Llama-style decoder (RMSNorm, rotary attention with
grouped key/value heads, SiLU-gated MLP, tied or untied head) with
next-token cross-entropy, SGD on every client of the ``(M, N)`` grid,
Eq.-4 aggregation (mean over a server's clients), and T_S gossip rounds
``W <- A W`` with the graph's Metropolis weights.  Where the configuration
ships gossip over the int8 physical wire, each server's message is the
error-feedback corrected model, each round is delta-coded against the
receivers' decoded copy, and the codes are int8 with one absmax scale per
256-element chunk of the whole flattened model (stochastic rounding); the
residual is what round 0 withheld.

It runs in float32 with ``highest`` matmul precision.  ``dtype=bfloat16``
computes the same epochs in bfloat16 throughout: that is the control that
the comparison has to reject.

The weights come from ``init_weights``, which the benchmark also hands to
the trainer, so both start from the same seeded model; the tokens come
from ``traffic.epoch_tokens``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 256          # int8 wire: elements per absmax scale
QMAX = 127.0
# column block of the flattened (M, D) wire matrix processed at once; a
# chunk multiple, so blocks quantize independently
WIRE_BLOCK = 1 << 22


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes under short names, from the configuration file."""
    h = cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], L=cfg["num_hidden_layers"], h=h,
                kvh=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", cfg["hidden_size"] // h),
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
                tied=cfg["tie_word_embeddings"],
                std=cfg["initializer_range"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed >> 31)


def weight_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes in the trainer's parameter layout: ``stack`` holds one
    block whose leaves carry a leading layer axis."""
    m = dims(cfg)
    d, L, h, kvh, hd, ff, V = (m[k] for k in
                               ("d", "L", "h", "kvh", "hd", "ff", "V"))
    shapes = {
        "embed": (V, d),
        "final_norm": {"scale": (d,)},
        "stack": ({
            "ffn": {"down": (L, ff, d), "gate": (L, d, ff),
                    "up": (L, d, ff)},
            "ln1": {"scale": (L, d)},
            "ln2": {"scale": (L, d)},
            "mixer": {"w_k": (L, d, kvh, hd), "w_o": (L, h, hd, d),
                      "w_q": (L, d, h, hd), "w_v": (L, d, kvh, hd)},
        },),
    }
    if not m["tied"]:
        shapes["head"] = (d, V)
    return shapes


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_weights(key: jax.Array, cfg: Dict[str, Any]) -> Any:
    """Normal(0, initializer_range) matrices and unit norm scales, float32.
    Jit it whole: one program makes the model on the device."""
    std = dims(cfg)["std"]
    shapes = weight_shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths[0]):
        if getattr(path[-1], "key", None) == "scale":
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                                  shape, jnp.float32))
    return jax.tree.unflatten(paths[1], leaves)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, theta):
    """Rotary embedding, ``x``: (b, s, heads, hd); rotate-half form."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, axis=-1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.asarray(cos, x.dtype) + rot * jnp.asarray(sin, x.dtype)


def _layer(x, p, m):
    h = _rmsnorm(x, p["ln1"]["scale"], m["eps"])
    a = p["mixer"]
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["w_q"]), m["theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, a["w_k"]), m["theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, a["w_v"])
    rep = m["h"] // m["kvh"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = x.shape[1]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.asarray(
        math.sqrt(m["hd"]), x.dtype)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, jnp.asarray(-1e30, x.dtype)
                       if x.dtype == jnp.float32
                       else jnp.finfo(x.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    x = x + jnp.einsum("bqhk,hkd->bqd", att, a["w_o"])
    h = _rmsnorm(x, p["ln2"]["scale"], m["eps"])
    f = p["ffn"]
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, f["gate"]))
    u = jnp.einsum("bsd,df->bsf", h, f["up"])
    return x + jnp.einsum("bsf,fd->bsd", g * u, f["down"])


def loss(w, tokens, m):
    """Mean next-token cross-entropy of ``tokens`` (b, s) under ``w``."""
    x = w["embed"][tokens]

    def body(x, p):
        return _layer(x, p, m), None

    x, _ = jax.lax.scan(body, x, w["stack"][0])
    x = _rmsnorm(x, w["final_norm"]["scale"], m["eps"])
    head = w["embed"].T if m["tied"] else w["head"]
    logits = jnp.einsum("bsd,dv->bsv", x[:, :-1], head)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def client_period(w, tokens, gamma, m):
    """T_C SGD steps of one client; ``tokens`` (T_C, b, s).  Returns the
    new weights and the loss of the last step."""
    grad = jax.value_and_grad(loss)

    def step(w, tok):
        val, g = grad(w, tok, m)
        w = jax.tree.map(lambda p, gp: p - jnp.asarray(gamma, p.dtype) * gp,
                         w, g)
        return w, val

    w, vals = jax.lax.scan(step, w, tokens)
    return w, vals[-1]


# ---------------------------------------------------------------------------
# gossip
# ---------------------------------------------------------------------------


def mixing_matrix(m_servers: int, graph: str) -> np.ndarray:
    """Metropolis weights of the server graph: a_ij = 1 / (1 + max(d_i,
    d_j)) on each edge, the rest on the diagonal."""
    if graph != "ring":
        raise ValueError(f"the reference knows the ring graph, not {graph!r}")
    adj = np.zeros((m_servers, m_servers))
    for i in range(m_servers):
        for j in ((i - 1) % m_servers, (i + 1) % m_servers):
            if j != i:
                adj[i, j] = 1.0
    deg = adj.sum(1)
    a = np.zeros_like(adj)
    for i in range(m_servers):
        for j in range(m_servers):
            if adj[i, j]:
                a[i, j] = 1.0 / (1.0 + max(deg[i], deg[j]))
        a[i, i] = 1.0 - a[i].sum()
    return a


def mix(a: np.ndarray, trees: List[Any]) -> List[Any]:
    """One exact round: server i gets sum_j a_ij W_j."""
    return [jax.tree.map(lambda *xs, row=row: sum(
        float(c) * x for c, x in zip(row, xs)), *trees) for row in a]


def quantize(x, u):
    """int8 round trip, one absmax scale per CHUNK along the last axis,
    stochastic rounding with dither ``u`` in [0, 1)."""
    c = x.reshape(x.shape[:-1] + (-1, CHUNK))
    amax = jnp.max(jnp.abs(c), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / QMAX, 1.0).astype(x.dtype)
    q = jnp.clip(jnp.floor(c / s + u.reshape(c.shape)), -QMAX, QMAX)
    return (q * s).reshape(x.shape)


def wire_block(w, res, a, key, t_server: int):
    """One column block of the int8 wire period: ``w``, ``res`` (M, n).
    Returns the mixed block and the new error-feedback residual."""
    corrected = w + res
    ref = jnp.zeros_like(corrected)
    acc = jnp.zeros_like(corrected)
    x = corrected
    shipped = None
    for t in range(t_server):
        u = jax.random.uniform(jax.random.fold_in(key, t), x.shape,
                               jnp.float32).astype(x.dtype)
        dq = quantize(x - ref, u)
        if t == 0:
            shipped = dq
        ref = ref + dq
        acc = acc + jnp.einsum("ij,jn->in", a.astype(x.dtype), dq)
        x = acc
    return x, corrected - shipped


# ---------------------------------------------------------------------------
# the epochs
# ---------------------------------------------------------------------------


class Reference:
    """The reference federation for one cell; ``run`` replays its first
    epochs on the same weights and tokens the trainer got."""

    def __init__(self, fed: Dict[str, Any], traffic: Dict[str, Any],
                 dtype=jnp.float32):
        self.m = dims(fed)
        self.fed = fed
        self.traffic = traffic
        self.dtype = dtype
        self.a = mixing_matrix(fed["servers"], fed["graph"])
        self.wire = fed["compression"] != "none"
        if self.wire and (fed["compression"] != "int8"
                          or fed["wire"] != "physical"
                          or not fed["error_feedback"]):
            raise ValueError("the reference's wire is int8, physical, with "
                             "error feedback")
        m = self.m
        self._period = jax.jit(
            lambda w, tok: client_period(w, tok, fed["gamma"], m))
        t_s = traffic["t_server"]
        self._wire = jax.jit(
            lambda w, r, a, k: wire_block(w, r, a, k, t_s),
            donate_argnums=(0, 1))
        self._mix = jax.jit(lambda ts: mix(self.a, ts), donate_argnums=(0,))
        # the exact mean of the servers, every server's copy
        self._mean = jax.jit(lambda ts: [jax.tree.map(
            lambda *xs: sum(xs) / len(xs), *ts)] * len(ts))

    def _precision(self):
        return jax.default_matmul_precision(
            "highest" if self.dtype == jnp.float32 else "default")

    def _gossip(self, servers: List[Any], res: Optional[jax.Array],
                key: jax.Array) -> Tuple[List[Any], Optional[jax.Array]]:
        t_s = self.traffic["t_server"]
        if len(servers) == 1 or t_s == 0:
            return servers, res
        if self.fed["consensus_mode"] == "exact_mean":
            return self._mean(servers), res
        if not self.wire:
            for _ in range(t_s):
                servers = self._mix(servers)
            return servers, res
        leaves0, treedef = jax.tree.flatten(servers[0])
        sizes = [x.size for x in leaves0]
        flat = jnp.stack([jnp.concatenate(
            [x.reshape(-1) for x in jax.tree.leaves(s)]) for s in servers])
        servers.clear()                  # the caller's list: free the trees
        d = flat.shape[1]
        blk = min(WIRE_BLOCK, -(-d // CHUNK) * CHUNK)
        nb = -(-d // blk)
        flat = jnp.pad(flat, ((0, 0), (0, nb * blk - d)))
        if res is None:
            res = jnp.zeros_like(flat)
        a = jnp.asarray(self.a, jnp.float32)
        out, new_res = [], []
        for b in range(nb):
            sl = slice(b * blk, (b + 1) * blk)
            o, r = self._wire(flat[:, sl], res[:, sl], a,
                              jax.random.fold_in(key, b))
            out.append(o)
            new_res.append(r)
        del flat, res
        out = jnp.concatenate(out, axis=1)
        res = jnp.concatenate(new_res, axis=1)
        servers = []
        for i in range(out.shape[0]):
            leaves, off = [], 0
            for x, n in zip(leaves0, sizes):
                leaves.append(out[i, off:off + n].reshape(x.shape))
                off += n
            servers.append(jax.tree.unflatten(treedef, leaves))
        return servers, res

    def run(self, w0: Any, tokens_fn, seed: int, steps: int,
            capture: Tuple[int, ...]) -> Dict[str, Any]:
        """Train ``steps`` epochs from ``w0``; ``tokens_fn(epoch)`` gives
        (T_C, M, N, b, s) tokens.  Returns each epoch's loss (mean over
        clients of the last local step) and, after each epoch in
        ``capture`` (0-based), every leaf's distance from ``w0`` per
        server: ``{epoch: {leaf path: (M,) norms}}``."""
        fed = self.fed
        m_srv, n_cli = fed["servers"], fed["clients_per_server"]
        w0 = jax.tree.map(lambda x: x.astype(self.dtype), w0)
        servers = [w0] * m_srv
        res = None
        key = jax.random.fold_in(seed_key(seed), 0x5EED)
        losses, norms = [], {}
        for epoch in range(steps):
            tok = np.asarray(tokens_fn(epoch))
            new, vals = [], []
            with self._precision():
                for i in range(m_srv):
                    acc = None
                    for j in range(n_cli):
                        w, val = self._period(servers[i], tok[:, i, j])
                        vals.append(float(val))
                        acc = w if acc is None else jax.tree.map(
                            jnp.add, acc, w)
                    new.append(acc if n_cli == 1 else jax.tree.map(
                        lambda x: x / n_cli, acc))
                del servers
                servers, res = self._gossip(new, res,
                                            jax.random.fold_in(key, epoch))
                del new
            losses.append(float(np.mean(vals)))
            if epoch in capture:
                norms[epoch] = leaf_norms(servers, w0)
        return {"loss": losses, "norms": norms}


def leaf_norms(servers: List[Any], w0: Any) -> Dict[str, np.ndarray]:
    """``{leaf path: (M,) L2 norms of (server model - w0)}`` in float32."""
    out = {}
    flat0 = jax.tree_util.tree_flatten_with_path(w0)[0]
    per_server = [jax.tree.leaves(s) for s in servers]
    for li, (path, x0) in enumerate(flat0):
        out[jax.tree_util.keystr(path)] = np.array([
            float(jnp.linalg.norm((ls[li] - x0).astype(jnp.float32)))
            for ls in per_server])
    return out
