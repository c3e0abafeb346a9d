"""The reduction from a profiler trace to device metrics, on hand-made
events and on a small trace recorded on a TPU v5e (two epochs of a
two-layer model at M=2, T_C=2, T_S=1)."""
import pathlib

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from benchmarks.chip import trace_reduce as tr

SMALL = (pathlib.Path(tr.__file__).parent / "testdata"
         / "small.xplane.pb")


def _synthetic():
    chip = tr.Chip(
        ops=[("fusion.1", 10, 20), ("fusion.2", 15, 30),
             ("control:while.1", 10, 30), ("all-gather.1", 50, 60),
             ("fusion.3", 95, 120)],
        modules=[("jit_step", 10, 30), ("jit_step", 50, 60),
                 ("jit_small", 95, 96)],
        async_ops=[("all-gather-start.2", 55, 70)])
    host = [("engine", 0, 40), ("input", 1, 5), ("engine", 45, 100)]
    return tr.Trace({0: chip}, host)


def test_interval_arithmetic():
    assert tr.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.covered([(0, 3), (5, 9)], 2, 6) == 2


def test_reduce_hand_made_trace():
    t = _synthetic()
    red = tr.reduce(t)[0]
    assert red.window_ns == 100                   # first to last engine span
    assert red.busy_ns == 20 + 10 + 5             # [10,30], [50,60], [95,100]
    assert red.idle_share == pytest.approx(0.65)
    assert red.step_module == "jit_step" and red.steps == 2
    assert red.step_ns == 30
    assert red.gaps_ns == [20]                    # idle from 30 to 50
    assert red.collective_ns == 20                # [50,70] in flight
    assert "control:while.1" not in red.op_ns
    bd = tr.breakdown(t, {0: red})
    assert bd["device_ops"][0][0] == "fusion.2"
    assert bd["device_ops"][0][1] == pytest.approx(15e-9)
    # the longest idle gap, 60 to 95, falls under the second engine span
    assert bd["idle_gaps"][0][0] == "engine"
    assert bd["idle_gaps"][0][1] == pytest.approx(35e-9)
    assert len(bd["idle_gaps"]) == 3


def test_op_names_keep_the_instruction():
    assert tr.op_name("%fusion.585 = f32[2,256]{1,0} fusion(f32[2] %x)") \
        == "fusion.585"
    assert tr.op_name("%while.3 = (s32[]) while((s32[]) %t), body=%b") \
        == "control:while.3"


@pytest.mark.skipif(not SMALL.is_file(), reason="no recorded trace")
def test_reduce_recorded_chip_trace():
    t = tr.load(str(SMALL))
    assert sorted(t.chips) == [0]
    assert [n for n, _, _ in t.host].count("engine") == 2
    assert [n for n, _, _ in t.host].count("input") == 2
    red = tr.reduce(t)[0]
    assert red.step_module.startswith("jit_epoch_step")
    assert red.steps == 2 and len(red.gaps_ns) == 1
    assert 0 < red.busy_ns < red.window_ns
    # a module's span also holds the idle bubbles between its ops
    assert 0 < red.busy_ns / 2 < red.step_ns < red.window_ns
    assert red.collective_ns == 0                 # one chip, no exchange
    bd = tr.breakdown(t, {0: red})
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(not n.startswith(tr.CONTROL_MARK) and s > 0
               for n, s in bd["device_ops"])
    assert {n for n, _ in bd["idle_gaps"]} <= {"engine", "input", "host"}
