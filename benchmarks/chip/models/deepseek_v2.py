"""Plug-in of DeepSeek-V2's block (``models/__init__.py`` states the
contract), written from the published description alone (the model card's
``config.json`` and ``modeling_deepseek.py`` of
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite); imports nothing of
the trainer.

The model: RMSNorm before each sublayer; multi-head latent attention with
a direct query projection (no q-LoRA), the keys' and values' latent
(``kv_lora_rank``) normed and projected up per head, one rope key shared
by the heads, the rope dimensions de-interleaved (even ones, then odd)
before the rotate-half rotation, YaRN frequencies and the softmax scale
1/sqrt(query-key size) times mscale(factor, mscale_all_dim) squared; then
a dense SiLU-gated MLP in the first ``first_k_dense_replace`` layers and a
routed MLP after: a float32 softmax router over every routed expert,
greedy top-k, gates the top-k probabilities (renormalised only if
``norm_topk_prob``) times ``routed_scaling_factor``, SiLU-gated experts,
and the shared experts as one MLP of ``n_shared_experts`` times the
expert width; untied head; next-token cross-entropy plus the per-sequence
balance loss (``seq_aux``) alpha * sum_e f_e * P_e of every routed layer,
averaged over the batch.

Departures from the published model, each the configuration file's
``reduced`` or the deployment it states:

* The chip holds ``n_routed_experts`` of the ``published`` count, from
  ``first_held_expert``: the router keeps every output and its top-k, and
  a token-slot routed to an expert held elsewhere adds nothing here (one
  chip's share of an expert-parallel layer; the exchange between chips is
  left out, in the trainer and here alike).  The balance loss still sees
  every router output.
* The vocabulary is a slice: ``vocab_size`` rows of embedding and head,
  and the traffic draws its ids from the slice.
* ``num_hidden_layers`` layers: the dense ones and the routed ones after.
* The published code adds the balance loss to the gradient only
  (``AddAuxiliaryLoss``); here, as in the trainer, it is added to the
  loss's value too.
* No dropout, no attention or MLP biases (``attention_bias`` false).

Experts are computed densely over every token, each weighted by its gate
where it was picked: plain, and no grouping or sorting as the trainer's
grouped matmul does.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops

# the trainer's program names these parts of the block (``modules.py``)
SCOPES = ("latent_kv", "router", "experts")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The model's sizes under short names, from the configuration file."""
    rs = cfg["rope_scaling"]
    return dict(
        d=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        h=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        r=cfg["kv_lora_rank"], ff=cfg["intermediate_size"],
        eff=cfg["moe_intermediate_size"], shared=cfg["n_shared_experts"],
        E=cfg["published"]["n_routed_experts"], held=cfg["n_routed_experts"],
        first=cfg["first_held_expert"], k=cfg["num_experts_per_tok"],
        dense=cfg["first_k_dense_replace"], V=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"], rs=rs,
        norm_topk=cfg["norm_topk_prob"],
        scaling=cfg["routed_scaling_factor"],
        alpha=cfg["aux_loss_alpha"], std=cfg["initializer_range"])


def _refuse(cfg: Dict[str, Any]) -> None:
    want = {"q_lora_rank": None, "hidden_act": "silu",
            "topk_method": "greedy", "scoring_func": "softmax",
            "moe_layer_freq": 1,
            "first_k_dense_replace": 1, "seq_aux": True,
            "tie_word_embeddings": False, "attention_bias": False}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad or cfg["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"models/deepseek_v2.py computes the "
                         f"DeepSeek-V2-Lite block; this configuration "
                         f"differs: {bad}")


def arch_config(config: Dict[str, Any], base):
    """``base`` (the trainer's configuration of ``program_arch``) with the
    file's sizes, its held experts and its vocabulary slice; refuses a
    block that this reference does not compute."""
    _refuse(config)
    if base.mla is None or base.moe is None or base.rope_scaling is None:
        raise ValueError(f"{config['program_arch']} has no latent attention, "
                         f"routed experts and rotary scaling")
    m = dims(config)
    rs = {k: v for k, v in m["rs"].items() if k != "type"}
    return dataclasses.replace(
        base, num_layers=m["L"], d_model=m["d"], num_heads=m["h"],
        num_kv_heads=config["num_key_value_heads"], d_ff=m["ff"],
        vocab_size=m["V"], norm_eps=m["eps"], rope_theta=m["theta"],
        tie_embeddings=False, use_bias=False, act="silu",
        rope_scaling=dataclasses.replace(base.rope_scaling, **rs),
        mla=dataclasses.replace(
            base.mla, kv_lora_rank=m["r"], q_lora_rank=None,
            qk_nope_head_dim=m["nope"], qk_rope_head_dim=m["rope"],
            v_head_dim=m["vd"]),
        moe=dataclasses.replace(
            base.moe, num_experts=m["E"], top_k=m["k"], d_ff_expert=m["eff"],
            num_shared_experts=m["shared"], d_ff_shared=m["eff"],
            router_aux_weight=m["alpha"], layer_pattern="all_but_first",
            norm_topk_prob=m["norm_topk"],
            routed_scaling_factor=m["scaling"], seq_aux=True,
            num_held_experts=m["held"], first_held_expert=m["first"]))


def apply_options(config: Dict[str, Any]) -> Dict[str, Any]:
    """No rematerialisation, jnp attention, drop-free experts."""
    return {"remat": False, "attn_impl": "reference", "moe_no_drop": True}


# ---------------------------------------------------------------------------
# FLOPs and bytes
# ---------------------------------------------------------------------------


def _mla_params(m) -> int:
    q = m["d"] * m["h"] * (m["nope"] + m["rope"])
    kv_down = m["d"] * (m["r"] + m["rope"])
    kv_up = m["r"] * m["h"] * (m["nope"] + m["vd"])
    return q + kv_down + kv_up + m["h"] * m["vd"] * m["d"]


def expert_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters in matmuls a token: every layer's attention, the dense
    MLPs, and of a routed layer the router, the shared experts and the held
    experts at the share of a token they expect (top-k x held / routed),
    and the head."""
    m = dims(cfg)
    moe_layers = m["L"] - m["dense"]
    routed = (expert_params(cfg) * m["k"] * m["held"]) // m["E"]
    per_moe = (routed + 3 * m["d"] * m["eff"] * m["shared"]
               + m["d"] * m["E"])
    return (m["L"] * _mla_params(m) + m["dense"] * 3 * m["d"] * m["ff"]
            + moe_layers * per_moe + m["d"] * m["V"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> int:
    """``flops.training`` with the attention width of every layer's heads
    at their query-key and value sizes."""
    m = dims(cfg)
    width = m["L"] * m["h"] * (m["nope"] + m["rope"] + m["vd"])
    return flops.training(matmul_params(cfg), width, seq_len)


def expert_flops(cfg: Dict[str, Any], epoch: Dict[str, int]) -> int:
    """Operations the held experts' matmuls need in one epoch: each
    client-step routes the share top-k x held / routed of its token-slots
    to them (expected), each slot through gate, up and down, 2 operations
    a weight forward and 4 backward.  ``epoch`` gives ``servers``,
    ``clients_per_server``, ``per_client_batch``, ``seq_len`` and
    ``t_client``."""
    m = dims(cfg)
    steps = epoch["servers"] * epoch["clients_per_server"] * epoch["t_client"]
    rows = (epoch["per_client_batch"] * epoch["seq_len"] * m["k"] * m["held"]
            // m["E"])
    return 6 * steps * (m["L"] - m["dense"]) * rows * expert_params(cfg)


def expert_weight_bytes(cfg: Dict[str, Any], epoch: Dict[str, int]) -> int:
    """Bytes the held experts' weights must move in one epoch: of every
    held expert of every routed layer, each client-step reads its weights
    in bfloat16 forward and again for the input gradient, and writes its
    weight gradient at least in bfloat16: 6 bytes a weight.  ``epoch``
    gives ``servers``, ``clients_per_server`` and ``t_client``."""
    m = dims(cfg)
    steps = epoch["servers"] * epoch["clients_per_server"] * epoch["t_client"]
    return (6 * steps * (m["L"] - m["dense"]) * m["held"]
            * expert_params(cfg))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _mixer_shapes(m, lead=()) -> Dict[str, Any]:
    return {"kv_norm": {"scale": lead + (m["r"],)},
            "w_dkv": lead + (m["d"], m["r"] + m["rope"]),
            "w_o": lead + (m["h"], m["vd"], m["d"]),
            "w_q": lead + (m["d"], m["h"], m["nope"] + m["rope"]),
            "w_ukv": lead + (m["r"], m["h"], m["nope"] + m["vd"])}


def weight_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes in the trainer's parameter layout: ``prefix`` holds the
    dense layer, ``stack`` one routed block whose leaves carry a leading
    layer axis."""
    m = dims(cfg)
    d, L, n = m["d"], m["L"] - m["dense"], m["held"]
    sh = m["eff"] * m["shared"]
    dense = {"ffn": {"down": (m["ff"], d), "gate": (d, m["ff"]),
                     "up": (d, m["ff"])},
             "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
             "mixer": _mixer_shapes(m)}
    routed = {"ffn": {"router": (L, d, m["E"]),
                      "shared": {"down": (L, sh, d), "gate": (L, d, sh),
                                 "up": (L, d, sh)},
                      "w_down": (L, n, m["eff"], d),
                      "w_gate": (L, n, d, m["eff"]),
                      "w_up": (L, n, d, m["eff"])},
              "ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
              "mixer": _mixer_shapes(m, (L,))}
    return {"embed": (m["V"], d), "final_norm": {"scale": (d,)},
            "head": (d, m["V"]), "prefix": (dense,), "stack": (routed,)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_weights(key: jax.Array, cfg: Dict[str, Any]) -> Any:
    """Normal(0, initializer_range) matrices and unit norm scales, float32.
    Jit it whole: one program makes the model on the device."""
    std = dims(cfg)["std"]
    paths = jax.tree_util.tree_flatten_with_path(weight_shapes(cfg),
                                                 is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths[0]):
        if getattr(path[-1], "key", None) == "scale":
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                                  shape, jnp.float32))
    return jax.tree.unflatten(paths[1], leaves)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(rs: Dict[str, Any], dim: int, theta: float):
    """The published ``yarn_find_correction_range``: (low, high)."""
    def at(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(at(rs["beta_fast"])), 0),
            min(math.ceil(at(rs["beta_slow"])), dim - 1))


def rope_tables(m, seq_len: int):
    """The published YaRN rotary's cos and sin, (s, rope), float64."""
    dim, theta, rs = m["rope"], m["theta"], m["rs"]
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    low, high = yarn_range(rs, dim, theta)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    keep = 1.0 - ramp                       # the published inv_freq_mask
    inv = inter * (1 - keep) + extra * keep
    ang = np.outer(np.arange(seq_len, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    mag = (yarn_mscale(rs["factor"], rs["mscale"])
           / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    return np.cos(emb) * mag, np.sin(emb) * mag


def softmax_scale(m) -> float:
    rs = m["rs"]
    scale = (m["nope"] + m["rope"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, cos, sin):
    """``x`` (b, s, heads, rope): de-interleave, then rotate-half."""
    b, s, h, dim = x.shape
    x = x.reshape(b, s, h, dim // 2, 2).swapaxes(3, 4).reshape(b, s, h, dim)
    rot = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    c = jnp.asarray(cos[None, :, None, :], x.dtype)
    sn = jnp.asarray(sin[None, :, None, :], x.dtype)
    return x * c + rot * sn


def _attention(x, a, m):
    b, s, _ = x.shape
    cos, sin = rope_tables(m, s)
    q = jnp.einsum("bsd,dhk->bshk", x, a["w_q"])
    q_nope, q_pe = q[..., :m["nope"]], q[..., m["nope"]:]
    ckv = jnp.einsum("bsd,dr->bsr", x, a["w_dkv"])
    c, k_pe = ckv[..., :m["r"]], ckv[..., m["r"]:]
    kv = jnp.einsum("bsr,rhk->bshk", _rmsnorm(c, a["kv_norm"]["scale"],
                                              m["eps"]), a["w_ukv"])
    k_nope, v = kv[..., :m["nope"]], kv[..., m["nope"]:]
    q = jnp.concatenate([q_nope, _rope(q_pe, cos, sin)], axis=-1)
    k_pe = jnp.broadcast_to(_rope(k_pe[:, :, None, :], cos, sin),
                            (b, s, m["h"], m["rope"]))
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    scores = (jnp.einsum("bqhk,bshk->bhqs", q, k)
              * jnp.asarray(softmax_scale(m), x.dtype))
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, jnp.finfo(x.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
        x.dtype)
    att = jnp.einsum("bhqs,bshk->bqhk", probs, v)
    return jnp.einsum("bqhk,hkd->bqd", att, a["w_o"])


def _mlp(x, f):
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, f["gate"]))
    return jnp.einsum("bsf,fd->bsd", g * jnp.einsum("bsd,df->bsf", x, f["up"]),
                      f["down"])


def route(x, f, m):
    """Router probabilities (b, s, E), gates and expert ids (b, s, k)."""
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, f["router"]).astype(
        jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    return probs, vals * m["scaling"], idx


def routed(x, f, m):
    """The held experts' part of a routed layer's output, and the layer's
    balance loss.  Every held expert runs on every token; a token takes
    its output weighted by the expert's gate, zero where not picked."""
    probs, gates, idx = route(x, f, m)
    held = m["first"] + np.arange(m["held"])
    w = jnp.sum(jnp.where(idx[..., None] == held, gates[..., None], 0.0),
                axis=-2).astype(x.dtype)                        # (b, s, n)
    g = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, f["w_gate"]))
    u = jnp.einsum("bsd,edf->bsef", x, f["w_up"])
    y = jnp.einsum("bsef,efd,bse->bsd", g * u, f["w_down"], w)
    s = x.shape[1]
    counts = jnp.sum(jax.nn.one_hot(idx, m["E"], dtype=jnp.float32),
                     axis=(1, 2))                               # (b, E)
    f_e = counts * m["E"] / (s * m["k"])
    aux = m["alpha"] * jnp.mean(jnp.sum(f_e * probs.mean(1), -1))
    return y, aux.astype(x.dtype)


def _layer(x, p, m, moe: bool):
    x = x + _attention(_rmsnorm(x, p["ln1"]["scale"], m["eps"]), p["mixer"],
                       m)
    h = _rmsnorm(x, p["ln2"]["scale"], m["eps"])
    if not moe:
        return x + _mlp(h, p["ffn"]), jnp.zeros((), x.dtype)
    y, aux = routed(h, p["ffn"], m)
    return x + y + _mlp(h, p["ffn"]["shared"]), aux


def loss(w, tokens, cfg: Dict[str, Any]):
    """Mean next-token cross-entropy of ``tokens`` (b, s) under ``w``, plus
    every routed layer's balance loss."""
    m = dims(cfg)
    x = w["embed"][tokens]
    aux = jnp.zeros((), x.dtype)
    for p in w["prefix"]:
        x, _ = _layer(x, p, m, moe=False)

    def body(carry, p):
        x, aux = carry
        x, a = _layer(x, p, m, moe=True)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, aux), w["stack"][0])
    x = _rmsnorm(x, w["final_norm"]["scale"], m["eps"])
    logits = jnp.einsum("bsd,dv->bsv", x[:, :-1], w["head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked) + aux
