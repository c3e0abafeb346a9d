"""The DeepSeek-V2-Lite cell (``models/deepseek_v2.py``) at a tiny size on
the CPU: a sound run is correct, planted faults and the bfloat16 control
are not; its FLOP and byte counts are the hand counts; its readers read
what the run gives them."""
import time

import jax.numpy as jnp
import pytest

from bench_tiny import harness
from benchmarks.chip import faults, models

NAME = "deepseek-v2-lite-5l-8e.m2-mean.local10"
SEED = 2**31 + 17
# widths that the grouped matmul's tiling takes, 3 layers (the dense one
# and two routed), 4 of 16 experts held, 32 positions
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=2,
            num_key_value_heads=2, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, moe_intermediate_size=128,
            num_hidden_layers=3, n_routed_experts=4, vocab_size=512,
            seq_len=32)
# On the CPU the trainer matches the float32 reference to about 1e-6 in
# the loss and 1e-5 in the updates' norms (its expert matmuls take
# bfloat16 operands, as on the chip); the bfloat16 control reads 1.6e-3,
# 0.15 and 0.10, and the faults more.
TINY_LIMITS = {"loss_gap": 1e-4, "update_gap": 1e-3, "change_gap": 1e-3}


def tiny_cell() -> harness.Cell:
    cell = harness.load_cell(NAME)
    config = dict(cell.config, **TINY,
                  published=dict(cell.config["published"],
                                 n_routed_experts=16))
    return harness.Cell(cell.name, 1, config, dict(cell.traffic, t_client=2),
                        dict(TINY_LIMITS), cell.end_to_end, cell.per_layer)


def test_sound_run_is_correct():
    r = harness.run(tiny_cell(), SEED, 0.3, False, time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct(fault):
    r = harness.run(tiny_cell(), SEED, 0.3, False, time.perf_counter(),
                    plant=faults.FAULTS[fault])
    assert not r["correct"], r["checks"]


def test_bfloat16_control_is_not_correct():
    cell = tiny_cell()
    w0 = harness.Federation(cell.config, cell.traffic).weights(SEED)
    ref = harness.run_reference(cell, w0, SEED)
    ctrl = harness.run_reference(cell, w0, SEED, dtype=jnp.bfloat16)
    got = harness.readings(ctrl, ref)
    assert any(got[k] > cell.limits[k] for k in cell.limits), got


def test_traced_run_reads_the_routing_counter():
    """The CPU has no device plane: of the cell's metrics only the
    routing counter, from the engine's records, has something to read."""
    cell = tiny_cell()
    r = harness.run(cell, 5, 0.2, True, time.perf_counter())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"moe_load_max"}
    assert r["metrics"]["moe_load_max"]["value"] >= 1.0


def test_flops_and_expert_bytes_are_the_hand_counts():
    cell = harness.load_cell(NAME)
    plugin = models.load(cell.config)
    # 5 x 13,762,560 attention, 67,239,936 dense MLP, 4 x (6/64 x 8 x
    # 8,650,752 held experts + 17,301,504 shared + 131,072 router),
    # 26,214,400 head; attention width 5 x 16 x (192 + 128) x 256
    assert plugin.train_flops_per_token(cell.config, 256) == 1_587_019_776
    epoch = dict(cell.config, t_client=cell.traffic["t_client"])
    # 6 bytes x 2 servers x 1 client x 10 steps x 4 layers x 69,206,016
    assert plugin.expert_weight_bytes(cell.config, epoch) == 33_218_887_680
    # 6 x 20 client-steps x 4 layers x 192 expected held slots x 8,650,752
    assert plugin.expert_flops(cell.config, epoch) == 797_253_304_320


def test_roofline_reader_is_the_byte_floor_over_the_scope_time():
    """The bytes bound the held experts here: 40.6 ms against 4.0 ms of
    operations at the bf16 peak.  A run of another configuration (another
    count of operations a token) reads nothing."""
    read = harness._reader("experts_roofline", harness.CHIP / "metrics")
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    floor_ms = 33_218_887_680 / 819e9 * 1e3
    ctx = {"scope_ms": {"experts": 2 * floor_ms}, "peak": peak,
           "flops_per_token": 1_587_019_776}
    assert read(ctx) == pytest.approx(50.0)
    assert read(dict(ctx, flops_per_token=778_567_680)) is None
    assert read(dict(ctx, scope_ms={})) is None
    assert read(dict(ctx, scope_ms={"experts": 1.0}, peak=None)) is None
