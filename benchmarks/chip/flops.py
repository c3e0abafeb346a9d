"""The FLOPs convention of training, and the chips' published peaks.

Convention: forward plus backward is 6 x the parameters that enter a
matrix multiplication for a token (the tied embedding counts once, as the
head; the embedding lookup, norms and elementwise work count zero; of an
expert layer only the experts a token is routed to), plus attention's
score and value products at the full sequence length: 6 x sequence x the
attention width, the sum over layers of heads x (query-key size + value
size) (the causal mask is not halved: the trainer computes every score).
Recomputation counts zero, and so do aggregation and gossip.  Each model
plug-in (``models/``) counts its own parameters and width by this rule.
"""
from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def training(matmul_params: int, attention_width: int, seq_len: int) -> int:
    """Model FLOPs of training one token at ``seq_len`` positions."""
    return 6 * (matmul_params + seq_len * attention_width)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table knows {sorted(table)}")
    return table[device_kind]
