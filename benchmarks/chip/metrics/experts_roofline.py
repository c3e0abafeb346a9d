"""Share of the roofline that the held experts' grouped matmuls reach, in
%: the least time their work needs in an epoch over ``experts_ms``.  That
time is the larger of the model plug-in's ``expert_flops`` over the bf16
peak and its ``expert_weight_bytes`` (6 bytes a held expert weight a
client-step: a bfloat16 read forward, one for the input gradient and at
least a bfloat16 write of its gradient) over the HBM bytes/s; at about 24
token-slots an expert a step the bytes bound it.  The configuration is the
one cell this metric lists, found through ``harness.load_cell`` in this
checkout; a run whose model counts other operations a token than that
cell's (``ctx["flops_per_token"]``) is another configuration, and gets
nothing."""
import pathlib

from benchmarks.chip import harness, models

CELL = "deepseek-v2-lite-5l-8e.m2-mean.local10"
HOME = pathlib.Path(__file__).resolve().parents[1]


def read(ctx):
    ms = ctx["scope_ms"].get("experts")
    if not ms or ctx["peak"] is None:
        return None
    cell = harness.load_cell(CELL, HOME.parents[1])
    plugin = models.load(cell.config, HOME / "models")
    if ctx["flops_per_token"] != plugin.train_flops_per_token(
            cell.config, cell.config["seq_len"]):
        return None
    epoch = dict(cell.config, t_client=cell.traffic["t_client"])
    peak = ctx["peak"]
    least_s = max(plugin.expert_flops(cell.config, epoch)
                  / peak["bf16_flops_per_s"],
                  plugin.expert_weight_bytes(cell.config, epoch)
                  / peak["hbm_bytes_per_s"])
    return 100 * least_s / (ms * 1e-3)
