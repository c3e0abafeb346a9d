"""Plug-in of the Llama-style decoder block (``models/__init__.py`` states
the contract): RMSNorm, rotary attention with grouped key/value heads,
SiLU-gated MLP, tied or untied head, next-token cross-entropy.  Written
from the published description alone; imports nothing of the trainer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops

# the configuration file's model keys, and the trainer's names for them
ARCH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
             "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings",
             "attention_bias": "use_bias"}
# the trainer's program opens no scope for this block beyond phases.SCOPES
SCOPES = ()


def arch_config(config: Dict[str, Any], base):
    """``base`` (the trainer's configuration of ``program_arch``) with the
    file's sizes; refuses any block but the plain Llama-style one."""
    if (base.family != "dense" or base.moe or base.mla or base.mamba
            or base.encdec or base.frontend or base.qk_norm
            or tuple(base.layer_pattern) != ("global",)
            or base.attn_logit_softcap or base.final_logit_softcap
            or config["hidden_act"] != "silu"):
        raise ValueError(f"{config['program_arch']} is not the plain "
                         f"Llama-style block that models/llama.py computes")
    kw = {dst: config[src] for src, dst in ARCH_KEYS.items()}
    kw["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    return dataclasses.replace(base, act="silu", **kw)


def apply_options(config: Dict[str, Any]) -> Dict[str, Any]:
    """No rematerialisation, jnp attention."""
    return {"remat": False, "attn_impl": "reference"}


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


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters in matmuls a token: the layers' projections and the head
    (tied or not, counted once)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = cfg.get("head_dim", d // h)
    per_layer = d * hd * (2 * h + 2 * kvh) + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> int:
    """``flops.training`` with queries, keys and values of the head size."""
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // h)
    return flops.training(matmul_params(cfg),
                          cfg["num_hidden_layers"] * h * 2 * hd, seq_len)


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


def loss(w, tokens, cfg: Dict[str, Any]):
    """Mean next-token cross-entropy of ``tokens`` (b, s) under ``w``."""
    m = dims(cfg)
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
