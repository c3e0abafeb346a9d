"""Tiny cells for the benchmark's CPU tests: the benchmark's own
configuration files cut to a size the CPU trains in seconds."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=512,
            seq_len=16)


def tiny_cell(name: str) -> harness.Cell:
    """The cell ``name`` at the tiny size, with its own limits; on one
    CPU device the shard_map wire runs as the in-graph einsum wire."""
    cell = harness.load_cell(name)
    config = dict(cell.config, **TINY)
    if config["consensus_backend"] == "shard_map":
        config.update(consensus_backend="einsum", placed=False)
    traffic = dict(cell.traffic, t_client=2)
    return harness.Cell(cell.name, 1, config, traffic, cell.limits,
                        cell.end_to_end, cell.per_layer)

# Limits at the tiny size.  On the CPU the trainer's float32 matches the
# reference to about 1e-7 where gossip is exact; through the int8 wire the
# two draw different stochastic rounding, and a tiny model's few small
# leaves read that noise as gaps of up to about 0.25 (seeds 3 and 4).
TINY_LIMITS = {
    "smollm-360m.m2-mean.local10": {"loss_gap": 1e-4, "update_gap": 1e-3,
                               "change_gap": 1e-3},
    "smollm-360m-8l.m4-int8.ts6": {"loss_gap": 4e-4, "update_gap": 0.6,
                                   "change_gap": 0.6},
}
# the faults each cell can have (a one-chip cell has no exchange between
# chips)
CELL_FAULTS = {
    "smollm-360m.m2-mean.local10": ("unchanged", "half_batch"),
    "smollm-360m-8l.m4-int8.ts6": ("unchanged", "half_batch",
                                   "no_exchange"),
}


def tiny_limited(name: str) -> harness.Cell:
    cell = tiny_cell(name)
    cell.limits = TINY_LIMITS[name]
    return cell
