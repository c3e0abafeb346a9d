"""Architecture / run configuration dataclasses.

Every assigned architecture gets a module ``src/repro/configs/<id>.py``
exposing ``CONFIG: ArchConfig`` (the full published size, exercised only via
the dry-run) and ``smoke_config()`` (a reduced member of the same family for
CPU smoke tests: <=2 layers, d_model<=512, <=4 experts).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Sequence

# ---------------------------------------------------------------------------
# sub-configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN."""

    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    d_ff_shared: int = 0            # per shared expert
    router_aux_weight: float = 0.01  # load-balance loss weight (kept client-local)
    # which decoder layers are MoE: "all" | "every_2" | "all_but_first"
    layer_pattern: str = "all"
    # the routed experts this device holds: ``num_held_experts`` of them
    # from ``first_held_expert`` (None: all).  The router keeps every
    # output; token-slots routed to an expert held elsewhere add nothing
    # here (one device's share of an expert-parallel layer).
    num_held_experts: Optional[int] = None
    first_held_expert: int = 0
    # gate weights: the top-k router probabilities, renormalised to sum to
    # one when ``norm_topk_prob``, times ``routed_scaling_factor``
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # balance loss per sequence, averaged over the batch (DeepSeek's
    # ``seq_aux``), instead of over the whole batch at once
    seq_aux: bool = False

    @property
    def held(self) -> int:
        """How many routed experts this device holds."""
        return (self.num_experts if self.num_held_experts is None
                else self.num_held_experts)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention."""

    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536   # None: a direct query projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling`` of type "yarn"):
    frequencies between the two correction dimensions are blended from
    interpolated (divided by ``factor``) to extrapolated along a linear
    ramp, cos/sin are multiplied by mscale(mscale) / mscale(mscale_all_dim)
    and the softmax scale by mscale(mscale_all_dim) squared, where
    mscale(m) = 0.1 m ln(factor) + 1."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    """Mamba2 / SSD block."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    """Encoder-decoder extras (seamless-m4t)."""

    num_encoder_layers: int = 24
    # ratio of encoder input length to the nominal shape seq_len
    encoder_len_ratio: float = 1.0


@dataclasses.dataclass(frozen=True)
class FrontendStub:
    """Modality frontend carve-out: precomputed embeddings of this shape are
    fed by ``input_specs`` instead of raw pixels / waveforms."""

    kind: str                 # "vision_patches" | "audio_frames"
    num_tokens: int           # patches or frames prepended / encoded
    embed_dim: int            # must equal d_model after the (stubbed) projector


# ---------------------------------------------------------------------------
# main architecture config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    source: str                      # citation from the assignment table
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    # attention flavour ------------------------------------------------------
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    # per-layer attention pattern, cycled over layers. entries:
    #   "global" (full causal), "local" (sliding window), "mamba"
    layer_pattern: Sequence[str] = ("global",)
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = None   # MLA's rope dimensions
    use_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    act: str = "silu"                # silu | gelu
    # optional sub-systems ---------------------------------------------------
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    encdec: Optional[EncDecConfig] = None
    frontend: Optional[FrontendStub] = None
    # which input shapes this arch supports for decode at 500k context
    supports_long_context: bool = False
    long_context_skip_reason: str = ""

    # -- derived -------------------------------------------------------------
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def padded_vocab_size(self) -> int:
        """Embedding/unembedding tables round the vocab up to a multiple of
        128 so the vocab dim shards cleanly over a 16-wide TP axis (seamless
        256206 -> 256256, mamba2 50280 -> 50304, internvl 151655 -> 151680).
        Logits for the padding ids are masked to -inf in the head; token ids
        never reach them."""
        return ((self.vocab_size + 127) // 128) * 128

    def pattern_for_layer(self, idx: int) -> str:
        return self.layer_pattern[idx % len(self.layer_pattern)]

    def is_moe_layer(self, idx: int) -> bool:
        if self.moe is None:
            return False
        p = self.moe.layer_pattern
        if p == "all":
            return True
        if p == "every_2":
            return idx % 2 == 1
        if p == "all_but_first":
            return idx > 0
        raise ValueError(p)

    def param_count(self) -> int:
        """Total parameters (embedding + blocks + head), analytic."""
        return _param_count(self)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed top-k + shared)."""
        return _param_count(self, active_only=True)


def _attn_params(cfg: ArchConfig) -> int:
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    if cfg.mla is not None:
        m = cfg.mla
        q_head = m.qk_nope_head_dim + m.qk_rope_head_dim
        if m.q_lora_rank is None:
            p = d * cfg.num_heads * q_head                                      # q
        else:
            p = d * m.q_lora_rank + m.q_lora_rank * cfg.num_heads * q_head      # q down/up
        p += d * (m.kv_lora_rank + m.qk_rope_head_dim)                          # kv down (+shared rope)
        p += m.kv_lora_rank * cfg.num_heads * (m.qk_nope_head_dim + m.v_head_dim)  # kv up
        p += cfg.num_heads * m.v_head_dim * d                                    # out proj
        return p
    q = d * cfg.num_heads * hd
    kv = 2 * d * cfg.num_kv_heads * hd
    o = cfg.num_heads * hd * d
    return q + kv + o


def _mlp_params(d_model: int, d_ff: int) -> int:
    return 3 * d_model * d_ff  # gated: gate, up, down


def _mamba_params(cfg: ArchConfig) -> int:
    m = cfg.mamba
    d = cfg.d_model
    di = m.d_inner(d)
    nh = m.num_heads(d)
    # standard mamba2 in_proj size: d -> (2*di + 2*n_groups*d_state + nh)
    n_groups = 1
    p = d * (2 * di + 2 * n_groups * m.d_state + nh)
    p += m.d_conv * (di + 2 * n_groups * m.d_state)  # conv1d over x,B,C
    p += nh * 2                                       # A_log, D
    p += di                                           # norm
    p += di * d                                       # out_proj
    return p


def _block_params(cfg: ArchConfig, idx: int, active_only: bool) -> int:
    d = cfg.d_model
    pat = cfg.pattern_for_layer(idx)
    p = 2 * d  # two rmsnorms
    if pat == "mamba":
        p += _mamba_params(cfg)
    else:
        p += _attn_params(cfg)
    if cfg.is_moe_layer(idx):
        moe = cfg.moe
        n_live = (moe.top_k if active_only else moe.held)
        p += n_live * _mlp_params(d, moe.d_ff_expert)
        p += moe.num_shared_experts * _mlp_params(d, moe.d_ff_shared or moe.d_ff_expert)
        p += d * moe.num_experts  # router
    elif pat != "mamba" or cfg.d_ff > 0:
        if cfg.d_ff > 0:
            p += _mlp_params(d, cfg.d_ff)
    return p


def _param_count(cfg: ArchConfig, active_only: bool = False) -> int:
    d = cfg.d_model
    p = cfg.vocab_size * d  # embedding
    if not cfg.tie_embeddings:
        p += cfg.vocab_size * d
    p += d  # final norm
    for i in range(cfg.num_layers):
        p += _block_params(cfg, i, active_only)
    if cfg.encdec is not None:
        # encoder blocks (full attention, no moe) + cross-attn in decoder
        for _ in range(cfg.encdec.num_encoder_layers):
            p += 2 * d + _attn_params(cfg) + _mlp_params(d, cfg.d_ff)
        p += cfg.num_layers * (d + _attn_params(cfg))  # cross-attn + its norm
    return p


# ---------------------------------------------------------------------------
# input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS = (
    "mixtral_8x22b",
    "gemma2_27b",
    "seamless_m4t_large_v2",
    "internvl2_1b",
    "jamba_1_5_large_398b",
    "command_r_35b",
    "smollm_360m",
    "qwen3_1_7b",
    "mamba2_780m",
    "deepseek_v2_236b",
    "deepseek_v2_lite",
)


def get_arch(arch_id: str) -> ArchConfig:
    """Load ``CONFIG`` from ``repro.configs.<arch_id>`` (dashes ok)."""
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.CONFIG


def get_smoke(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.smoke_config()
