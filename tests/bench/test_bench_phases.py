"""The epoch's phases: the named scopes of the compiled epoch program, the
engine's host spans on the profiler's clock, and ``phases.py``'s
reduction of both, on hand-made HLO text and events, on the tiny cells,
and on small traces of both cells recorded on TPU v5e chips."""
import glob
import gzip
import os
import pathlib
import re
import subprocess
import sys
import tempfile

import jax
import pytest

from bench_tiny import ROOT, tiny_cell
from benchmarks.chip import harness, phases
from benchmarks.chip import trace_reduce as tr

TESTDATA = pathlib.Path(tr.__file__).parent / "testdata"
MODEL_SCOPES = ("local_period", "embed", "attention", "mlp", "lm_head",
                "sgd_update", "aggregate", "gossip_period", "epoch_metrics",
                "broadcast")
WIRE_SCOPES = ("wire_pack", "wire_encode", "wire_gather", "wire_decode_mix")

HLO = """\
HloModule jit_epoch_step_dynamic

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %sub.1 = f32[4]{0} subtract(%p, %p), metadata={op_name="jit(epoch_step_dynamic)/local_period/while/body/sgd_update/sub"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(epoch_step_dynamic)/local_period/while/body/transpose(jvp(attention))/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(epoch_step_dynamic)/local_period/while/body/sgd_update/sub"}
  %all-gather-start.3 = (f32[4]{0}, f32[16]{0}) all-gather-start(%x), metadata={op_name="jit(epoch_step_dynamic)/gossip_period/shard_map/wire_gather/all_gather"}
  %copy.4 = f32[4]{0} copy(%x)
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %copy.8 = f32[4]{0:T(128)} copy(%fusion.2)
  %while.5 = f32[4]{0} while(%x), body=%b, metadata={op_name="jit(epoch_step_dynamic)/local_period/while"}
  ROOT %add.6 = f32[4]{0} add(%x, %x), metadata={op_name="jit(epoch_step_dynamic)/add"}
}
"""


def test_scope_map_reads_the_op_name_paths():
    smap = phases.scope_map(HLO)
    assert smap["fusion.1"] == ("local_period", "attention")
    assert smap["fusion.2"] == ("local_period", "sgd_update")
    assert smap["sub.1"] == ("local_period", "sgd_update")
    assert smap["all-gather-start.3"] == ("gossip_period", "wire_gather")
    assert smap["add.6"] == () and smap["copy.4"] == ()
    # what the compiler left without metadata: a fusion takes its fused
    # root's path, a layout copy its operand's, a parameter's copy none
    assert smap["fusion.7"] == ("local_period", "sgd_update")
    assert smap["copy.8"] == ("local_period", "sgd_update")
    assert phases.path_scopes(
        "jit(f)/transpose(jvp(mlp))/jvp(lm_head)/x") == ("mlp", "lm_head")


def _hand_made():
    chip = tr.Chip(
        ops=[("fusion.1", 10, 20), ("fusion.2", 20, 25),
             ("control:while.5", 10, 25), ("add.6", 25, 28),
             ("copy.4", 28, 30), ("fusion.9", 50, 52),
             ("fusion.1", 60, 70), ("fusion.2", 70, 75),
             ("add.6", 75, 80)],
        modules=[("jit_epoch_step_dynamic", 10, 30), ("jit_other", 50, 52),
                 ("jit_epoch_step_dynamic", 60, 80)],
        async_ops=[("all-gather-start.3", 18, 22)])
    host = [("engine", 0, 40), ("engine", 45, 90)]
    return tr.Trace({0: chip}, host)


def test_scope_attribution_nests_and_names_the_unscoped():
    t = _hand_made()
    red = tr.reduce(t)
    assert red[0].steps == 2
    ns = phases.scope_ns(t, red, phases.scope_map(HLO))[0]
    # an op counts toward every scope on its path; the in-flight gather
    # overlaps the attention op, and the union counts the overlap once
    assert ns["attention"] == 20
    assert ns["sgd_update"] == 10
    assert ns["local_period"] == 30
    assert ns["wire_gather"] == 4 and ns["gossip_period"] == 4
    # ops with no scope; another program's op (fusion.9) and the loop
    # that encloses the body are left out
    assert ns["unscoped"] == 3 + 2 + 5
    assert ns["program"] == 40                    # its busy time
    assert set(ns) == {"local_period", "attention", "sgd_update",
                       "gossip_period", "wire_gather", "unscoped",
                       "program"}
    assert phases.per_epoch_ms(red, {0: ns}, "local_period") \
        == pytest.approx(15e-6)
    assert phases.per_epoch_ms(red, {0: ns}, "mlp") is None


def test_host_turnaround_and_idle_time_by_engine_span():
    host = [("engine", 0, 40), ("epoch", 1, 39), ("dispatch", 5, 8),
            ("readback", 8, 30), ("host-aggregation", 30, 38),
            ("engine", 45, 90), ("epoch", 46, 89), ("schedule", 47, 55),
            ("dispatch", 56, 58), ("readback", 58, 85)]
    assert phases.host_turnaround_ns(host) == 56 - 30
    t = _hand_made()
    red = tr.reduce(t)[0]
    assert red.idle == [(0, 10), (30, 50), (52, 60), (80, 90)]
    # each piece of idle time goes to the innermost span over it
    assert phases.idle_by_span(host, red.idle) == {
        "engine": 1 + 1 + 1 + 1, "epoch": 4 + 1 + 1 + 1 + 4,
        "dispatch": 3 + 2, "readback": 2 + 2 + 5, "host-aggregation": 8,
        "host": 5, "schedule": 3 + 3}


def test_engine_spans_under_the_profiler_give_a_turnaround():
    """A tiny engine traced on the CPU: every engine span once per epoch,
    and a finite host turnaround."""
    cell = tiny_cell("smollm-360m.m2-mean.local10")
    fed = harness.Federation(cell.config, cell.traffic)
    batch_fn = harness.batches(cell, 5)
    state = fed.new_state(5)
    state, _ = fed.engine.run_epoch(state, 0, batch_fn)
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    for e in range(1, 4):
        state, _ = fed.engine.run_epoch(state, e, batch_fn)
    jax.profiler.stop_trace()
    host = phases.load_host(
        glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0])
    names = [n for n, _, _ in host]
    for span in phases.ENGINE_SPANS:
        assert names.count(span) == 3, span
    turn = phases.host_turnaround_ns(host)
    assert turn is not None and 0 < turn < float("inf")


def test_epoch_program_carries_every_model_scope():
    cell = tiny_cell("smollm-360m.m2-mean.local10")
    fed = harness.Federation(cell.config, cell.traffic)
    state = fed.new_state(3)
    program = fed.engine.epoch_program(state, 0, harness.batches(cell, 3))
    text = program.as_text()
    assert isinstance(program, jax.stages.Compiled)
    assert program.memory_analysis().temp_size_in_bytes > 0
    scopes = set(s for sc in phases.scope_map(text).values() for s in sc)
    assert scopes == set(MODEL_SCOPES)
    # the backward pass keeps the names: ops of the transposed layer scan
    # still sit under attention and mlp
    ops = re.findall(r'op_name="([^"]*)"', text)
    for sc in ("attention", "mlp"):
        assert any("transpose(" in o.split(f"/{sc}/")[0]
                   for o in ops if f"/{sc}/" in o), sc


_WIRE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path[:0] = ["tests/bench"]
from bench_tiny import TINY
from benchmarks.chip import harness, phases

cell = harness.load_cell("smollm-360m-8l.m4-int8.ts6")
config = dict(cell.config, **TINY)
assert config["consensus_backend"] == "shard_map" and config["placed"]
fed = harness.Federation(config, dict(cell.traffic, t_client=2))
batch_fn = harness.batches(harness.Cell(cell.name, 4, config,
                                        fed.traffic, {}, [], []), 3)
state = fed.new_state(3)
text = fed.engine.epoch_program(state, 0, batch_fn).as_text()
smap = phases.scope_map(text)
# with one client a server, the broadcast back is a free relayout
for sc in set(phases.SCOPES) - {"broadcast"}:
    assert any(sc in v for v in smap.values()), sc
for sc in phases.SCOPES:
    if sc.startswith("wire_"):
        assert any(v[:1] == ("gossip_period",) and sc in v
                   for v in smap.values()), sc
gathers = {n: v for n, v in smap.items()
           if n.startswith(("all-gather", "all_gather"))}
assert gathers and all(v == ("gossip_period", "wire_gather")
                       for v in gathers.values()), gathers
state, rec = fed.engine.run_epoch(state, 0, batch_fn)
assert rec["loss"] == rec["loss"]
print("OK")
"""


def test_wire_epoch_program_carries_the_wire_scopes():
    """The int8 physical wire through shard_map on four CPU devices: the
    compiled epoch program names the wire's four phases inside the
    gossip period, the all-gathers under ``wire_gather``."""
    r = subprocess.run([sys.executable, "-c", _WIRE], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}"})
    assert "OK" in r.stdout, r.stderr[-3000:]


RECORDED = TESTDATA / "phases_local10.xplane.pb"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_phases_of_a_recorded_chip_trace():
    """Two traced epochs of the one-chip cell at the tiny size on a TPU
    v5e, with the epoch program's HLO text."""
    t = tr.load(str(RECORDED))
    host = phases.load_host(str(RECORDED))
    names = [n for n, _, _ in host]
    for span in phases.ENGINE_SPANS:
        assert names.count(span) == 2, span
    hlo = gzip.open(TESTDATA / "phases_local10.hlo.txt.gz", "rt").read()
    out = phases.reduce_phases(t, host, hlo)
    red = tr.reduce(t)[0]
    ns = phases.scope_ns(t, {0: red}, phases.scope_map(hlo))[0]
    # every scope of the dense program ran; no wire in this cell
    assert set(ns) == set(MODEL_SCOPES) | {"unscoped", "program"}
    assert out["encode_ms"] is None and out["decode_mix_ms"] is None
    # nesting: a scope inside the local period takes part of it
    for sc in ("embed", "attention", "mlp", "lm_head", "sgd_update"):
        assert 0 < ns[sc] <= ns["local_period"], sc
    assert ns["local_period"] + ns["gossip_period"] <= red.step_ns
    assert out["local_ms"] <= out["step_device_ms"]
    # all but a few copies of the program's busy time carry a scope
    assert out["top_share"] > 0.9
    assert ns["unscoped"] < 0.1 * ns["program"] <= red.step_ns
    assert 0 < out["host_turnaround_ms"] < 1e3
    # idle time goes to the host's phases, not to the harness's span
    idle = out["idle_ms_by_span"]
    assert max(idle, key=idle.get) in phases.ENGINE_SPANS


WIRE_TRACE = TESTDATA / "phases_ts6.xplane.pb"


@pytest.mark.skipif(not WIRE_TRACE.is_file(), reason="no recorded trace")
def test_wire_phases_of_a_recorded_four_chip_trace():
    """Two traced epochs of the four-chip int8-wire cell at the tiny size
    on four TPU v5e chips: the wire's phases sit inside the gossip period
    on every chip, the all-gathers in flight count under ``wire_gather``."""
    t = tr.load(str(WIRE_TRACE))
    hlo = gzip.open(TESTDATA / "phases_ts6.hlo.txt.gz", "rt").read()
    red = tr.reduce(t)
    assert sorted(red) == [0, 1, 2, 3]
    ns = phases.scope_ns(t, red, phases.scope_map(hlo))
    for chip in ns.values():
        wire = sum(chip[sc] for sc in WIRE_SCOPES)
        assert all(chip[sc] > 0 for sc in WIRE_SCOPES)
        assert 0.9 * chip["gossip_period"] <= wire
        assert chip["local_period"] + chip["gossip_period"] <= chip["program"]
        assert chip["unscoped"] < 0.1 * chip["program"]
    out = phases.reduce_phases(t, phases.load_host(str(WIRE_TRACE)), hlo)
    assert out["gossip_ms"] > out["decode_mix_ms"] > 0
    assert out["encode_ms"] > 0 and out["top_share"] > 0.9
