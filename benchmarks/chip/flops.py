"""Model FLOPs of training, and the chips' published peaks.

Convention: forward plus backward is 6 x the parameters that enter a
matrix multiplication (the tied embedding counts once, as the head; the
embedding lookup, norms and elementwise work count zero), plus attention's
score and value products at the full sequence length: 12 x layers x heads
x head size x sequence per token (the causal mask is not halved: the
trainer computes every score).  Recomputation counts zero, and so do
aggregation and gossip.
"""
from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
    hd = cfg.get("head_dim", d // h)
    per_layer = d * hd * (2 * h + 2 * kvh) + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    attn = 12 * cfg["num_hidden_layers"] * h * hd * seq_len
    return 6 * matmul_params(cfg) + attn


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table knows {sorted(table)}")
    return table[device_kind]
