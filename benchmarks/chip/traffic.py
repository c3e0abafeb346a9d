"""Token streams for training cells, made on the host from the seed.

A traffic file (``traffic/<name>.json``) gives the epoch schedule (T_C
local steps, T_S gossip rounds) and the token distribution.  The one kind
so far, ``zipf_bigram``, draws Zipf(alpha) token ids over the vocabulary
and replaces each with ``(previous * mult + add) % vocab`` with
probability ``bigram_share``, so a model has structure to learn.  Every
(epoch, local step, server, client, row) draws its own tokens: no two rows
of a run repeat.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


class TokenStream:
    """``epoch_tokens(epoch)`` -> int32 (T_C, M, N, b, s) for one seed."""

    def __init__(self, traffic: Dict[str, Any], vocab: int,
                 shape: Tuple[int, int, int, int, int], seed: int):
        spec = traffic["tokens"]
        if spec["kind"] != "zipf_bigram":
            raise ValueError(f"unknown token kind {spec['kind']!r}")
        self.spec = spec
        self.vocab = vocab
        self.shape = shape
        self.seed = seed
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(spec["alpha"])
        self.cdf = np.cumsum(p / p.sum())

    def epoch_tokens(self, epoch: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, epoch])
        base = np.searchsorted(self.cdf, rng.random(self.shape),
                               side="right")
        base = np.minimum(base, self.vocab - 1)
        rolled = (np.roll(base, 1, axis=-1) * self.spec["mult"]
                  + self.spec["add"]) % self.vocab
        mix = rng.random(self.shape) < self.spec["bigram_share"]
        return np.where(mix, rolled, base).astype(np.int32)
