"""Per-architecture deployment plans (hardware adaptation, DESIGN.md §2).

The paper's protocol requires each client to hold a full model copy; on a
16 GB-HBM v5e that forces a per-arch trade between the number of FL clients
(M*N) and the intra-client shard degree (R*TP):

    bytes/device ~= param_bytes / (R * TP)   (+ grads of the same size
                    + remat'd activations)

Small archs use the paper-like M=4, N=4 (16 clients); the 100B+ archs scale
clients down and FSDP up (M=2, N=1, R=8..16).  dtype is bf16 for the big
archs (mixed-precision deployment; the paper's SGD is stateless so there is
no optimizer-moment memory either way) and f32 for the small ones (matches
the theory-faithful configuration).

``plan_for(arch, multi_pod)`` is the single lookup the launcher uses.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch.mesh import FLMeshSpec


@dataclasses.dataclass(frozen=True)
class DeploymentPlan:
    arch_id: str
    # FL refinement of the replica axes (train_4k / the DFL epoch step)
    single_pod: FLMeshSpec
    multi_pod: FLMeshSpec
    param_dtype: str = "float32"      # "float32" | "bfloat16"
    # per-client microbatch is derived: global_batch / (M*N)
    t_client_dry: int = 2             # scan body compiles once; see DESIGN §5
    t_server: int = 25                # the paper's T_S
    # Archs whose head count does not divide the 16-wide "model" axis
    # (smollm: 15 heads, internvl: 14) use the model axis as *intra-client
    # data parallelism* instead of TP: weights replicate (they are tiny),
    # the per-client batch shards 16-way, and the client-local gradient
    # all-reduce rides fast intra-group ICI.
    batch_over_model: bool = False
    # Gradient-accumulation microbatches per local step (DFLConfig pass-
    # through); sized so per-device activations fit alongside params+grads.
    grad_microbatches: int = 1
    # Serving: 2-D (data x model) weight sharding only pays for big models;
    # small ones replicate over "data" — FSDP'd weights + data-sharded
    # batches otherwise fight at every matmul (the partitioner can resolve
    # it only with per-layer gathers it does not always choose).
    serve_fsdp: bool = False
    # Megatron-SP on/off (None = auto: on unless MLA/batch_over_model).
    # §Perf hillclimbs A/B measured SP net-NEGATIVE at per-device batches
    # of 1-2 sequences under full remat (the bwd re-gathers outweigh the
    # boundary-save sharding): command-r -39%, jamba -64% collective with
    # SP off + more grad-accumulation steps.
    seq_parallel: Optional[bool] = None
    # Same knob for the serve/prefill path (mixtral measured -66%
    # collective and -33% peak with SP off at prefill_32k, while its train
    # shape prefers SP for the memory win — the knobs are independent).
    serve_seq_parallel: Optional[bool] = None
    # Consensus execution path for the train shape (the launcher's default;
    # an explicit build_train_lowering(consensus_mode=...) overrides):
    # "gossip_shardmap" = explicit blocked shard_map collectives
    # (consensus.ShardMapBackend — deterministic memory, u16 wire),
    # "gossip_blocked" = pjit blocked streaming, "gossip" = per-leaf einsum.
    consensus_backend: str = "gossip_shardmap"
    # Inter-server message compression for the train shape
    # (DFLConfig.compression, the repro.comm subsystem).  Gossip cost is
    # pure inter-server bandwidth — one full replica per live edge per
    # round — so the 140-400B archs (whose consensus periods ship hundreds
    # of GB per epoch even over a single ring edge) default to int8 with
    # error feedback: ~3.9x fewer wire bytes at a consensus-error cost the
    # compressed_consensus benchmark shows is inside the paper's fig-3
    # tolerance.  Small/mid archs keep the exact paper protocol.
    compression: str = "none"
    error_feedback: bool = False
    # Where the compression happens (DFLConfig.wire).  The 140-400B archs
    # run wire="physical": their consensus backend is gossip_shardmap, so
    # the int8 codes + per-chunk scales are the literal all-gather
    # operands — the 3.9x BytesTracker ratio becomes actual ICI traffic
    # instead of a host-side ledger over bf16 collectives.  "simulated"
    # everywhere the wire is exact anyway (compression="none").
    wire: str = "simulated"

    def serve_dtype(self):
        return jnp.bfloat16          # deployment dtype for all archs

    def fl_spec(self, multi_pod: bool) -> FLMeshSpec:
        return self.multi_pod if multi_pod else self.single_pod

    def dtype(self):
        return jnp.bfloat16 if self.param_dtype == "bfloat16" else jnp.float32


_SMALL_SP = FLMeshSpec(num_servers=4, clients_per_server=4, fsdp=1, tp=16)
_SMALL_MP = FLMeshSpec(num_servers=4, clients_per_server=8, fsdp=1, tp=16)
_MID_SP = FLMeshSpec(num_servers=2, clients_per_server=2, fsdp=4, tp=16)
_MID_MP = FLMeshSpec(num_servers=2, clients_per_server=4, fsdp=4, tp=16)
_BIG_SP = FLMeshSpec(num_servers=2, clients_per_server=1, fsdp=8, tp=16)
_BIG_MP = FLMeshSpec(num_servers=2, clients_per_server=1, fsdp=16, tp=16)

PLANS: Dict[str, DeploymentPlan] = {
    # ~0.4-2B: plenty of room -> paper-like 16 clients, f32
    "smollm_360m": DeploymentPlan("smollm_360m", _SMALL_SP, _SMALL_MP,
                                  batch_over_model=True),
    "qwen3_1_7b": DeploymentPlan("qwen3_1_7b", _SMALL_SP, _SMALL_MP),
    "mamba2_780m": DeploymentPlan("mamba2_780m", _SMALL_SP, _SMALL_MP),
    "internvl2_1b": DeploymentPlan("internvl2_1b", _SMALL_SP, _SMALL_MP,
                                   batch_over_model=True),
    "seamless_m4t_large_v2": DeploymentPlan("seamless_m4t_large_v2",
                                            _SMALL_SP, _SMALL_MP),
    # ~16-35B: bf16 + R=2 (1.7-1.9 GB params/device; deepseek-v2-lite 0.5)
    "gemma2_27b": DeploymentPlan("gemma2_27b", _MID_SP, _MID_MP,
                                 param_dtype="bfloat16",
                                 grad_microbatches=8, serve_fsdp=True),
    "deepseek_v2_lite": DeploymentPlan("deepseek_v2_lite", _MID_SP, _MID_MP,
                                       param_dtype="bfloat16",
                                       grad_microbatches=8, serve_fsdp=True),
    "command_r_35b": DeploymentPlan("command_r_35b", _MID_SP, _MID_MP,
                                    param_dtype="bfloat16",
                                    grad_microbatches=16, serve_fsdp=True,
                                    seq_parallel=False),
    # 140-400B: bf16 + R=8/16, 2 servers x 1 client (the scalability edge
    # case: DFL still applies — consensus over M=2 is one gossip edge)
    "mixtral_8x22b": DeploymentPlan("mixtral_8x22b", _BIG_SP, _BIG_MP,
                                    param_dtype="bfloat16",
                                    grad_microbatches=16, serve_fsdp=True,
                                    serve_seq_parallel=False,
                                    compression="int8", error_feedback=True,
                                    wire="physical"),
    "deepseek_v2_236b": DeploymentPlan("deepseek_v2_236b", _BIG_SP, _BIG_MP,
                                       param_dtype="bfloat16",
                                       grad_microbatches=16, serve_fsdp=True,
                                       compression="int8",
                                       error_feedback=True,
                                       wire="physical"),
    "jamba_1_5_large_398b": DeploymentPlan("jamba_1_5_large_398b", _BIG_SP,
                                           _BIG_MP, param_dtype="bfloat16",
                                           grad_microbatches=16, serve_fsdp=True,
                                           seq_parallel=False,
                                           serve_seq_parallel=False,
                                           compression="int8",
                                           error_feedback=True,
                                           wire="physical"),
}


def plan_for(arch_id: str) -> DeploymentPlan:
    return PLANS[arch_id.replace("-", "_").replace(".", "_")]
