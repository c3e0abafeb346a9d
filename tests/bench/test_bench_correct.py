"""The check that decides ``correct``, at a tiny size on the CPU: a sound
run passes, every planted fault and the bfloat16 control fail."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import CELL_FAULTS, harness, tiny_limited
from benchmarks.chip import faults

SEED = 2**31 + 17          # past 32 signed bits, as the driver's seeds are


@pytest.mark.parametrize("name", sorted(CELL_FAULTS))
def test_sound_run_is_correct(name):
    r = harness.run(tiny_limited(name), SEED, 0.3, False,
                    time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "peak_hbm_gb", "setup_s"}


@pytest.mark.parametrize("name,fault", [(n, f) for n, fs in
                                        sorted(CELL_FAULTS.items())
                                        for f in fs])
def test_planted_fault_is_not_correct(name, fault):
    r = harness.run(tiny_limited(name), SEED, 0.3, False,
                    time.perf_counter(), plant=faults.FAULTS[fault])
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", sorted(CELL_FAULTS))
def test_bfloat16_control_is_not_correct(name):
    cell = tiny_limited(name)
    fed = harness.Federation(cell.config, cell.traffic)
    w0 = fed.weights(SEED)
    ref = harness.run_reference(cell, w0, SEED)
    ctrl = harness.run_reference(cell, w0, SEED, dtype=jnp.bfloat16)
    got = harness.readings(ctrl, ref)
    assert any(got[k] > cell.limits[k] for k in cell.limits), got


def test_traced_run_reports_per_layer_metrics_only():
    cell = tiny_limited("smollm-360m.m2-mean.local10")
    r = harness.run(cell, 5, 0.2, True, time.perf_counter())
    # the CPU has no device plane to reduce: only counters and rates
    assert set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "mfu" not in r["metrics"] or r["device"]["platform"] == "tpu"


def test_compile_watch_counts_a_compile_in_its_block():
    with harness.compile_watch() as compiles:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    assert compiles[0] >= 1
    before = compiles[0]
    jax.jit(lambda x: x * 5 + 2)(jnp.ones(3))
    assert compiles[0] == before
