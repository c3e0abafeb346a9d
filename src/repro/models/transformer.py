"""Composable decoder / encoder-decoder transformer over the module zoo.

Layer stacking uses ``lax.scan`` over *periods* (one period = one cycle of
``cfg.layer_pattern`` × the MoE alternation), so a 72-layer hybrid compiles
the block body once.  Heterogeneous-within-period blocks (e.g. jamba's
7 Mamba + 1 attention) are unrolled *inside* the period body.

Public API
----------
    init_params(key, cfg, dtype)                  -> params pytree
    forward(params, cfg, batch)                   -> (logits, aux_loss)
    make_loss_fn(cfg)                             -> loss_fn(params, batch, rng)
    init_cache(cfg, batch, max_len, dtype)        -> cache pytree
    prefill(params, cfg, batch)                   -> (logits, cache)
    decode_step(params, cfg, token, cache, pos)   -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import mamba as mamba_mod
from repro.models import modules as nn


# ---------------------------------------------------------------------------
# stack plan: prefix blocks + scanned periods
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ApplyOptions:
    """Knobs threaded through the apply path (no param-structure impact)."""

    attn_impl: str = "reference"     # reference | pallas
    remat: bool = True
    # exact (capacity=t) MoE — tests/serving; a layer holding a share of
    # the experts is always drop-free (the grouped matmul)
    moe_no_drop: bool = False
    capacity_factor: float = 1.25
    # Megatron-style sequence parallelism: a NamedSharding for the logical
    # residual stream (b, s, d), applied at every layer-stack boundary so
    # the activations saved by scan-backward are sharded (e.g. seq over the
    # "model" axis).  None = let the partitioner decide.
    act_sharding: Optional[Any] = None
    # Token groups of the capacity dispatch (expert parallelism): tokens
    # are split into ``moe_groups`` groups, each with its own capacity; the
    # group->expert reshard is the all-to-all of a2a expert parallelism.
    # moe_group_sharding: NamedSharding for the grouped (G, t/G, d) tokens.
    moe_groups: int = 1
    moe_group_sharding: Optional[Any] = None
    # SSD (Mamba2) scan: override the intra-chunk quadratic block length for
    # training lowerings (the L matrix is O(b * nh * s * chunk) — chunk 64
    # keeps it ~1 GB/device for jamba where the config default 256 is 4x
    # that); None keeps cfg.mamba.chunk_size.
    ssd_chunk: Optional[int] = None
    # NamedSharding for SSD per-head streams (b, s, nh, hd): shard heads
    # over "model", batch over the DP axes.
    ssd_head_sharding: Optional[Any] = None
    # NamedSharding for attention q/k/v (b, s, h, hd) after GQA expansion —
    # pins heads to "model" (critical for MLA's 128 expanded heads).
    attn_head_sharding: Optional[Any] = None

    def constrain(self, x: jax.Array) -> jax.Array:
        if self.act_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.act_sharding)


DEFAULT_OPTS = ApplyOptions()


@dataclasses.dataclass(frozen=True)
class StackPlan:
    num_prefix: int          # unscanned leading layers (deepseek dense layer 0)
    period: int              # layers per scanned step
    n_periods: int

    def kinds(self, cfg: ArchConfig, base_idx: int) -> Tuple[str, ...]:
        return tuple(cfg.pattern_for_layer(base_idx + i) for i in range(self.period))


def stack_plan(cfg: ArchConfig) -> StackPlan:
    moe_period = {"all": 1, "every_2": 2, "all_but_first": 1, None: 1}[
        cfg.moe.layer_pattern if cfg.moe else None]
    num_prefix = 1 if (cfg.moe and cfg.moe.layer_pattern == "all_but_first") else 0
    period = math.lcm(len(cfg.layer_pattern), moe_period)
    rest = cfg.num_layers - num_prefix
    assert rest % period == 0, (cfg.name, rest, period)
    return StackPlan(num_prefix, period, rest // period)


def _layer_flags(cfg: ArchConfig, abs_idx: int) -> Tuple[str, bool]:
    """(kind, is_moe) for absolute layer index."""
    return cfg.pattern_for_layer(abs_idx), cfg.is_moe_layer(abs_idx)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def block_init(key, cfg: ArchConfig, kind: str, is_moe: bool,
               cross: bool = False, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": nn.rmsnorm_init(d, dtype),
                         "ln2": nn.rmsnorm_init(d, dtype)}
    if kind == "mamba":
        p["mixer"] = mamba_mod.mamba_init(ks[0], cfg, dtype)
    elif cfg.mla is not None:
        p["mixer"] = nn.mla_init(ks[0], cfg, dtype)
    else:
        p["mixer"] = nn.attention_init(ks[0], cfg, dtype)
    if is_moe:
        p["ffn"] = nn.moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff > 0 and kind != "mamba_only":
        p["ffn"] = nn.mlp_init(ks[1], d, cfg.d_ff, dtype)
    if cfg.final_logit_softcap is not None:  # gemma2 family: post-norms
        p["post_ln1"] = nn.rmsnorm_init(d, dtype)
        p["post_ln2"] = nn.rmsnorm_init(d, dtype)
    if cross:
        p["cross_ln"] = nn.rmsnorm_init(d, dtype)
        p["cross_attn"] = nn.attention_init(ks[2], cfg, dtype, cross=True)
    return p


def block_apply(params: Dict, x: jax.Array, cfg: ArchConfig, kind: str,
                is_moe: bool, *, memory: Optional[jax.Array] = None,
                opts: ApplyOptions = DEFAULT_OPTS,
                causal: bool = True
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence block. Returns (x, aux_loss, load_max), the last the
    MoE layer's routing counter (0 elsewhere).  The sequence mixer
    (attention, or a Mamba mixer) with its norm and residual runs under the
    ``attention`` named scope and the FFN sublayer under ``mlp``, so a
    device trace of the compiled program attributes each op to one; the
    modules name their own parts inside (``latent_kv``, ``router``,
    ``experts``)."""
    aux = jnp.zeros((), jnp.float32)
    load = jnp.zeros((), jnp.float32)
    with jax.named_scope("attention"):
        h = nn.rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
        if kind == "mamba":
            mix = mamba_mod.mamba_apply(
                params["mixer"], h, cfg,
                impl=opts.attn_impl
                if opts.attn_impl == "pallas" else "reference",
                chunk_override=opts.ssd_chunk,
                head_sharding=opts.ssd_head_sharding)
        elif cfg.mla is not None:
            mix = nn.mla_apply(params["mixer"], h, cfg,
                               head_sharding=opts.attn_head_sharding)
        else:
            mix = nn.attention_apply(params["mixer"], h, cfg,
                                     layer_kind=kind, causal=causal,
                                     attn_impl=opts.attn_impl,
                                     head_sharding=opts.attn_head_sharding)
        if "post_ln1" in params:
            mix = nn.rmsnorm_apply(params["post_ln1"], mix, cfg.norm_eps)
        x = x + mix
    if memory is not None and "cross_attn" in params:
        h = nn.rmsnorm_apply(params["cross_ln"], x, cfg.norm_eps)
        mem_mask = jnp.ones((x.shape[1], memory.shape[1]), bool)
        x = x + nn.attention_apply(params["cross_attn"], h, cfg,
                                   kv_override=(memory, mem_mask))
    if "ffn" in params:
        with jax.named_scope("mlp"):
            h = nn.rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
            if is_moe:
                ff, aux, load = nn.moe_apply(
                    params["ffn"], h, cfg,
                    capacity_factor=opts.capacity_factor,
                    no_drop=opts.moe_no_drop, groups=opts.moe_groups,
                    group_sharding=opts.moe_group_sharding)
            else:
                ff = nn.mlp_apply(params["ffn"], h, cfg.act)
            if "post_ln2" in params:
                ff = nn.rmsnorm_apply(params["post_ln2"], ff, cfg.norm_eps)
            x = x + ff
    return x, aux, load


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig, dtype=jnp.float32) -> Dict:
    plan = stack_plan(cfg)
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    vp = cfg.padded_vocab_size
    params: Dict[str, Any] = {
        "embed": nn._dense_init(keys[0], (vp, d), dtype, scale=0.02),
        "final_norm": nn.rmsnorm_init(d, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = nn._dense_init(keys[1], (d, vp), dtype)

    cross = cfg.encdec is not None

    def period_init(k):
        sub = jax.random.split(k, plan.period)
        blocks = []
        for i in range(plan.period):
            kind, is_moe = _layer_flags(cfg, plan.num_prefix + i)
            blocks.append(block_init(sub[i], cfg, kind, is_moe, cross=cross,
                                     dtype=dtype))
        return tuple(blocks)

    params["stack"] = jax.vmap(period_init)(
        jax.random.split(keys[2], plan.n_periods))

    if plan.num_prefix:
        # deepseek-style dense first layer(s)
        pk = jax.random.split(keys[3], plan.num_prefix)
        prefix = []
        for i in range(plan.num_prefix):
            kind, _ = cfg.pattern_for_layer(i), False
            blk = block_init(pk[i], cfg, cfg.pattern_for_layer(i), False,
                             cross=cross, dtype=dtype)
            # dense first layer uses the wide dense d_ff
            blk["ffn"] = nn.mlp_init(pk[i], cfg.d_model, cfg.d_ff or
                                     cfg.moe.d_ff_expert * 8, dtype)
            prefix.append(blk)
        params["prefix"] = tuple(prefix)

    if cfg.encdec is not None:
        ec = cfg.encdec

        def enc_period_init(k):
            return (block_init(k, cfg, "global", False, cross=False,
                               dtype=dtype),)

        params["encoder"] = {
            "stack": jax.vmap(enc_period_init)(
                jax.random.split(keys[4], ec.num_encoder_layers)),
            "final_norm": nn.rmsnorm_init(d, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# full-sequence forward (training / prefill trunk)
# ---------------------------------------------------------------------------


def _embed(params, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if cfg.final_logit_softcap is not None:  # gemma scales embeddings
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = nn.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
    logits = nn.softcap(logits, cfg.final_logit_softcap)
    if cfg.padded_vocab_size != cfg.vocab_size:   # mask vocab-padding ids
        pad_ids = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1) >= cfg.vocab_size
        logits = jnp.where(pad_ids, jnp.asarray(-1e30, logits.dtype), logits)
    return logits


def _run_stack(params, cfg: ArchConfig, x: jax.Array, *,
               memory=None, causal=True, opts: ApplyOptions = DEFAULT_OPTS
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (x, summed aux loss, largest MoE load_max over layers)."""
    plan = stack_plan(cfg)
    aux = jnp.zeros((), jnp.float32)
    load = jnp.zeros((), jnp.float32)
    for i, blk in enumerate(params.get("prefix", ())):
        kind, _ = _layer_flags(cfg, i)
        x, a, _ = block_apply(blk, x, cfg, kind, False, memory=memory,
                              opts=opts, causal=causal)
        aux = aux + a

    def period_body(carry, period_params):
        x, aux, load = carry
        x = opts.constrain(x)        # shard the scan-carry residual stream
        for i in range(plan.period):
            kind, is_moe = _layer_flags(cfg, plan.num_prefix + i)
            x, a, l = block_apply(period_params[i], x, cfg, kind, is_moe,
                                  memory=memory, opts=opts,
                                  causal=causal)
            aux, load = aux + a, jnp.maximum(load, l)
        return (x, aux, load), None

    body = jax.checkpoint(period_body) if opts.remat else period_body
    (x, aux, load), _ = jax.lax.scan(body, (opts.constrain(x), aux, load),
                                     params["stack"])
    return x, aux, load


def encode(params, cfg: ArchConfig, frames: jax.Array,
           opts: ApplyOptions = DEFAULT_OPTS) -> jax.Array:
    """Encoder for enc-dec archs. ``frames``: precomputed frontend embeddings
    (the stub carve-out), (b, enc_len, d)."""
    enc = params["encoder"]
    plan = StackPlan(0, 1, cfg.encdec.num_encoder_layers)

    def body(carry, period_params):
        x, = carry
        x = opts.constrain(x)
        x, _, _ = block_apply(period_params[0], x, cfg, "global", False,
                              causal=False, opts=opts)
        return (x,), None

    (x,), _ = jax.lax.scan(jax.checkpoint(body), (opts.constrain(frames),),
                           enc["stack"])
    return nn.rmsnorm_apply(enc["final_norm"], x, cfg.norm_eps)


def forward_hidden(params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
                   opts: ApplyOptions = DEFAULT_OPTS
                   ) -> Tuple[jax.Array, jax.Array]:
    """Trunk only: final hidden states over the token positions (pre-head)
    and the aux loss."""
    x, aux, _ = _trunk(params, cfg, batch, opts)
    return x, aux


def _trunk(params, cfg: ArchConfig, batch: Dict[str, jax.Array],
           opts: ApplyOptions) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``forward_hidden`` with the MoE layers' largest ``load_max``.

    ``batch`` keys by family:
       text:  tokens (b, s)
       vlm:   patch_embeds (b, p, d) + tokens (b, s-p)
       audio: frames (b, enc_len, d) + tokens (b, dec_len)
    """
    tokens = batch["tokens"]
    memory = None
    if cfg.encdec is not None:
        memory = encode(params, cfg, batch["frames"], opts)
    x = _embed(params, cfg, tokens)
    n_text = x.shape[1]
    if cfg.frontend is not None and cfg.frontend.kind == "vision_patches":
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    x, aux, load = _run_stack(params, cfg, x, memory=memory, opts=opts)
    return x[:, -n_text:], aux, load


def forward(params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
            opts: ApplyOptions = DEFAULT_OPTS) -> Tuple[jax.Array, jax.Array]:
    """Training forward: (logits over the token part, aux loss)."""
    x, aux = forward_hidden(params, cfg, batch, opts=opts)
    return _head(params, cfg, x), aux


LOSS_CHUNK = 512     # sequence positions per head/loss chunk


def make_loss_fn(cfg: ArchConfig, opts: ApplyOptions = DEFAULT_OPTS,
                 loss_chunk: int = LOSS_CHUNK):
    """Next-token cross-entropy. Signature matches ``repro.core.dfl.LossFn``.

    Two structural choices keep the head from dominating memory at
    256k-vocab scale:

    * **Chunked head** — the unembedding matmul + logsumexp run under a
      rematted lax.scan over ``loss_chunk``-position slices, so the peak
      logits tensor is (b, chunk, v/TP) instead of (b, s, v/TP); the
      backward recomputes each chunk's logits instead of saving them.
    * **Partitioner-friendly CE** — with the unembedding sharded over the
      "model" axis the chunk logits stay *vocab-sharded*: logsumexp
      partially reduces per shard (small (b, chunk) all-reduce), and the
      target logit is a one-hot contraction instead of take_along_axis
      (whose gather would force a full-vocab all-gather).

    The head and the cross-entropy run under the ``lm_head`` named scope.
    The aux dict holds the cross-entropy (``nll``), the balance loss
    (``aux``) and, for an MoE model, ``moe_load_max``: over the held
    experts of every MoE layer, the largest count of routed token-slots
    over their mean.
    """

    def loss_fn(params, batch, rng):
        del rng
        x, aux, load = _trunk(params, cfg, batch, opts)
        with jax.named_scope("lm_head"):
            nll_mean = chunked_nll(params, batch, x)
        loss = nll_mean + aux
        out = {"nll": nll_mean, "aux": aux}
        if cfg.moe is not None:
            out["moe_load_max"] = load
        return loss, out

    def chunked_nll(params, batch, x):
        xs = x[:, :-1]                                       # predict t+1
        targets = batch["tokens"][:, 1:]
        b, sm1, d = xs.shape
        chunk = min(loss_chunk, sm1)
        pad = (-sm1) % chunk
        if pad:
            xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)),
                              constant_values=-1)            # masked
        nc = (sm1 + pad) // chunk
        xc = jnp.moveaxis(xs.reshape(b, nc, chunk, d), 1, 0)
        tc = jnp.moveaxis(targets.reshape(b, nc, chunk), 1, 0)

        def body(total, inp):
            x_c, t_c = inp
            logits = _head(params, cfg, x_c).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)          # (b, chunk)
            vocab_ids = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, logits.ndim - 1)
            tgt = jnp.sum(jnp.where(vocab_ids == t_c[..., None], logits, 0.0),
                          axis=-1)
            valid = t_c >= 0
            nll = jnp.where(valid, lse - tgt, 0.0)
            return total + nll.sum(), None

        total, _ = jax.lax.scan(jax.checkpoint(body),
                                jnp.zeros((), jnp.float32), (xc, tc))
        return total / (b * sm1)

    return loss_fn


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _block_cache_init(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      dtype, cross: bool) -> Dict:
    c: Dict[str, Any] = {}
    if kind == "mamba":
        c["mixer"] = mamba_mod.mamba_cache_init(cfg, batch, dtype)
    elif cfg.mla is not None:
        c["mixer"] = nn.mla_cache_init(cfg, batch, max_len, dtype)
    else:
        c["mixer"] = nn.attention_cache_init(cfg, batch, max_len, kind, dtype)
    if cross:
        hd = cfg.resolved_head_dim()
        enc_len = int(max_len * cfg.encdec.encoder_len_ratio)
        c["cross_k"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, hd), dtype)
        c["cross_v"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, hd), dtype)
    return c


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    plan = stack_plan(cfg)
    cross = cfg.encdec is not None
    cache: Dict[str, Any] = {"position": jnp.zeros((), jnp.int32)}
    cache["prefix"] = tuple(
        _block_cache_init(cfg, cfg.pattern_for_layer(i), batch, max_len,
                          dtype, cross)
        for i in range(plan.num_prefix))

    def one_period(_):
        return tuple(
            _block_cache_init(cfg, _layer_flags(cfg, plan.num_prefix + i)[0],
                              batch, max_len, dtype, cross)
            for i in range(plan.period))

    cache["stack"] = jax.vmap(one_period)(jnp.arange(plan.n_periods))
    return cache


def _block_decode(params, cache, x, cfg: ArchConfig, kind: str, is_moe: bool,
                  position) -> Tuple[jax.Array, Dict]:
    new_cache = dict(cache)
    h = nn.rmsnorm_apply(params["ln1"], x, cfg.norm_eps)
    if kind == "mamba":
        mix, new_cache["mixer"] = mamba_mod.mamba_decode_step(
            params["mixer"], h, cache["mixer"], cfg)
    elif cfg.mla is not None:
        # absorbed attention (W_UK/W_UV folded into q/out): attends the
        # compact latent cache directly — the naive path re-expands
        # (b, S, h, hd) K/V per layer per step (~80 GB/device at 32k).
        mix, new_cache["mixer"] = nn.mla_decode_step(
            params["mixer"], h, cache["mixer"], position, cfg, absorbed=True)
    else:
        mix, new_cache["mixer"] = nn.attention_decode_step(
            params["mixer"], h, cache["mixer"], position, cfg, layer_kind=kind)
    if "post_ln1" in params:
        mix = nn.rmsnorm_apply(params["post_ln1"], mix, cfg.norm_eps)
    x = x + mix
    if "cross_attn" in params and "cross_k" in cache:
        h = nn.rmsnorm_apply(params["cross_ln"], x, cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, params["cross_attn"]["w_q"])
        out = nn.mha_attend(q, cache["cross_k"].astype(h.dtype),
                            cache["cross_v"].astype(h.dtype), None,
                            attn_softcap=None)
        x = x + jnp.einsum("bshk,hkd->bsd", out.astype(h.dtype),
                           params["cross_attn"]["w_o"])
    if "ffn" in params:
        h = nn.rmsnorm_apply(params["ln2"], x, cfg.norm_eps)
        if is_moe:
            ff, _, _ = nn.moe_apply(params["ffn"], h, cfg, no_drop=True)
        else:
            ff = nn.mlp_apply(params["ffn"], h, cfg.act)
        if "post_ln2" in params:
            ff = nn.rmsnorm_apply(params["post_ln2"], ff, cfg.norm_eps)
        x = x + ff
    return x, new_cache


def decode_step(params, cfg: ArchConfig, token: jax.Array, cache: Dict,
                ) -> Tuple[jax.Array, Dict]:
    """One synchronous decode step. token: (b, 1) int32."""
    plan = stack_plan(cfg)
    position = cache["position"]
    x = _embed(params, cfg, token)
    new_cache = dict(cache)
    new_prefix = []
    for i, blk in enumerate(params.get("prefix", ())):
        kind, _ = _layer_flags(cfg, i)
        x, c = _block_decode(blk, cache["prefix"][i], x, cfg, kind, False,
                             position)
        new_prefix.append(c)
    new_cache["prefix"] = tuple(new_prefix)

    def body(x, scanned):
        period_params, period_cache = scanned
        new_pc = []
        for i in range(plan.period):
            kind, is_moe = _layer_flags(cfg, plan.num_prefix + i)
            x, c = _block_decode(period_params[i], period_cache[i], x, cfg,
                                 kind, is_moe, position)
            new_pc.append(c)
        return x, tuple(new_pc)

    x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
    new_cache["stack"] = new_stack
    new_cache["position"] = position + 1
    return _head(params, cfg, x), new_cache


def prefill(params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
            max_len: Optional[int] = None, cache_dtype=jnp.bfloat16,
            opts: ApplyOptions = DEFAULT_OPTS) -> Tuple[jax.Array, Dict]:
    """Run the full prompt, build a cache ready for decode.

    For simplicity and FLOPs-faithfulness the prefill trunk is the full
    forward; KV extraction re-runs projections per layer into the cache via a
    dedicated pass (kept O(prompt) — acceptable; real deployments fuse it).
    Here we take the standard approach: run per-layer apply while recording
    K/V.  For the dry-run what matters is that the compiled program has
    prefill cost + cache writes, which this does.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_len = max_len or s
    memory = None
    if cfg.encdec is not None:
        memory = encode(params, cfg, batch["frames"], opts)
    x = _embed(params, cfg, tokens)
    if cfg.frontend is not None and cfg.frontend.kind == "vision_patches":
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)

    cache = init_cache(cfg, b, max_len, cache_dtype)
    plan = stack_plan(cfg)
    seq = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq), (b, seq))

    def fill_block(blk_params, blk_cache, x, kind, is_moe):
        h = nn.rmsnorm_apply(blk_params["ln1"], x, cfg.norm_eps)
        new_c = dict(blk_cache)
        if kind == "mamba":
            mix, new_c["mixer"] = mamba_mod.mamba_prefill(
                blk_params["mixer"], h, cfg,
                conv_cache_dtype=blk_cache["mixer"]["conv"].dtype,
                chunk_override=opts.ssd_chunk,
                head_sharding=opts.ssd_head_sharding)
        elif cfg.mla is not None:
            q, c_kv, k_rope = nn._mla_qkv(blk_params["mixer"], h, cfg, positions)
            mix = nn._mla_attend(blk_params["mixer"], q, c_kv, k_rope,
                                 None, cfg, causal=True,
                                 head_sharding=opts.attn_head_sharding)
            m = cfg.mla
            new_c["mixer"] = {
                "c_kv": _pad_to(c_kv, max_len).astype(cache_dtype),
                "k_rope": _pad_to(k_rope, max_len).astype(cache_dtype),
                "pos": _pad_to(positions.astype(jnp.int32), max_len, fill=-1),
            }
        else:
            window = cfg.sliding_window if kind == "local" else None
            q, k, v = nn._project_qkv(blk_params["mixer"], h, h, cfg,
                                      positions, positions, use_rope=True)
            out = nn.dispatch_attend(q, k, v, causal=True, window=window,
                                     attn_softcap=cfg.attn_logit_softcap,
                                     attn_impl=opts.attn_impl,
                                     head_sharding=opts.attn_head_sharding)
            mix = jnp.einsum("bshk,hkd->bsd", out.astype(h.dtype),
                             blk_params["mixer"]["w_o"])
            if "b_o" in blk_params["mixer"]:
                mix = mix + blk_params["mixer"]["b_o"]
            n = blk_cache["mixer"]["k"].shape[1]
            if n >= seq:
                new_c["mixer"] = {
                    "k": _pad_to(k, n).astype(cache_dtype),
                    "v": _pad_to(v, n).astype(cache_dtype),
                    "pos": _pad_to(positions.astype(jnp.int32), n, fill=-1),
                }
            else:  # sliding-window ring: keep last n, slot = pos % n
                new_c["mixer"] = _ring_pack(k, v, positions, n, cache_dtype)
        if "post_ln1" in blk_params:
            mix = nn.rmsnorm_apply(blk_params["post_ln1"], mix, cfg.norm_eps)
        x = x + mix
        if "cross_attn" in blk_params and memory is not None:
            hh = nn.rmsnorm_apply(blk_params["cross_ln"], x, cfg.norm_eps)
            mem_mask = jnp.ones((x.shape[1], memory.shape[1]), bool)
            x = x + nn.attention_apply(blk_params["cross_attn"], hh, cfg,
                                       kv_override=(memory, mem_mask))
            ck = jnp.einsum("bsd,dhk->bshk", memory,
                            blk_params["cross_attn"]["w_k"])
            cv = jnp.einsum("bsd,dhk->bshk", memory,
                            blk_params["cross_attn"]["w_v"])
            new_c["cross_k"] = ck.astype(cache_dtype)
            new_c["cross_v"] = cv.astype(cache_dtype)
        if "ffn" in blk_params:
            h = nn.rmsnorm_apply(blk_params["ln2"], x, cfg.norm_eps)
            if is_moe:
                ff, _, _ = nn.moe_apply(blk_params["ffn"], h, cfg,
                                     capacity_factor=opts.capacity_factor,
                                     no_drop=opts.moe_no_drop,
                                     groups=opts.moe_groups,
                                     group_sharding=opts.moe_group_sharding)
            else:
                ff = nn.mlp_apply(blk_params["ffn"], h, cfg.act)
            if "post_ln2" in blk_params:
                ff = nn.rmsnorm_apply(blk_params["post_ln2"], ff, cfg.norm_eps)
            x = x + ff
        return x, new_c

    new_prefix = []
    for i, blk in enumerate(params.get("prefix", ())):
        kind, _ = _layer_flags(cfg, i)
        x, c = fill_block(blk, cache["prefix"][i], x, kind, False)
        new_prefix.append(c)

    def body(x, scanned):
        period_params, period_cache = scanned
        new_pc = []
        for i in range(plan.period):
            kind, is_moe = _layer_flags(cfg, plan.num_prefix + i)
            x, c = fill_block(period_params[i], period_cache[i], x, kind,
                              is_moe)
            new_pc.append(c)
        return x, tuple(new_pc)

    x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
    logits = _head(params, cfg, x[:, -1:])
    return logits, {"position": jnp.asarray(seq, jnp.int32),
                    "prefix": tuple(new_prefix), "stack": new_stack}


def _pad_to(arr: jax.Array, n: int, fill=0):
    if arr.shape[1] == n:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[1] = (0, n - arr.shape[1])
    return jnp.pad(arr, pad, constant_values=fill)


def _ring_pack(k, v, positions, n, cache_dtype):
    """Pack the last ``n`` keys of a longer prompt into ring order."""
    seq = k.shape[1]
    kk, vv, pp = k[:, -n:], v[:, -n:], positions[:, -n:]
    # slot for position p is p % n: rotate so that entry j sits at slot pp[j]%n
    slots = pp[0] % n
    order = jnp.argsort(slots)
    return {"k": kk[:, order].astype(cache_dtype),
            "v": vv[:, order].astype(cache_dtype),
            "pos": pp[:, order].astype(jnp.int32)}
