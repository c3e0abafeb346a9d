"""Model plug-ins (``benchmarks/chip/models/``): the Llama plug-in keeps
the reference's numbers to the bit, refuses a block it does not compute,
and a new plug-in and a new scope reader are taken up from new files
alone."""
import gzip
import json
import os
import re
import subprocess
import sys
import time

import pytest

from bench_tiny import ROOT, TINY, harness, tiny_limited
from benchmarks.chip import models, phases
from benchmarks.chip import trace_reduce as tr

CHIP = ROOT / "benchmarks" / "chip"
TESTDATA = CHIP / "testdata"
SEED = 2**31 + 17

# The reference's epochs and the compared numbers of bench_tiny's cells,
# written by the code before the model moved into its plug-in, in one
# process held to one CPU core (XLA's CPU backend splits its reductions
# by the cores it may use, and the int8 wire's rounding carries a last
# bit into the next epoch).  ``ref_norms``: SHA-256 of every captured
# epoch's per-leaf norms (float64 bytes, by epoch, then leaf path).
FROZEN = {
    "smollm-360m.m2-mean.local10": {
        "ref_loss": ["0x1.9027940000000p+2", "0x1.8d33870000000p+2",
                     "0x1.8330b00000000p+2"],
        "ref_norms": "f1712bcc7e2e78c16c7cc16197757cfe"
                     "83e5e97f0ec71ea8cc0019caa9070699",
        "readings": {"loss_gap": "0x1.49fd26666f221p-25",
                     "update_gap": "0x1.b95039b9a435fp-24",
                     "change_gap": "0x1.55c1b7b4c7fa8p-24"}},
    "smollm-360m-8l.m4-int8.ts6": {
        "ref_loss": ["0x1.911f098000000p+2", "0x1.8eedf20000000p+2",
                     "0x1.8e191f0000000p+2"],
        "ref_norms": "e314c438187cba209c0bc95185ae61df"
                     "aeb37e540f999f15114f7c0592db145b",
        "readings": {"loss_gap": "0x1.51ed3e55aa81fp-15",
                     "update_gap": "0x1.1794ab22e1defp-3",
                     "change_gap": "0x1.93aa7ef2e709dp-4"}},
}

_FREEZE = r"""
import hashlib, json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
sys.path[:0] = ["tests/bench"]
import numpy as np
from bench_tiny import harness, tiny_limited

SEED = %d
out = {}
for name in %r:
    cell = tiny_limited(name)
    fed = harness.Federation(cell.config, cell.traffic)
    batch_fn = harness.batches(cell, SEED)
    state = fed.new_state(SEED)
    state, prog = harness.first_steps(fed, state, SEED, batch_fn)
    del state
    ref = harness.run_reference(cell, fed.weights(SEED), SEED)
    h = hashlib.sha256()
    for step in sorted(ref["norms"]):
        for k in sorted(ref["norms"][step]):
            h.update(k.encode())
            h.update(np.asarray(ref["norms"][step][k], np.float64).tobytes())
    out[name] = {"ref_loss": [x.hex() for x in ref["loss"]],
                 "ref_norms": h.hexdigest(),
                 "readings": {k: float(v).hex() for k, v in
                              harness.readings(prog, ref).items()}}
print(json.dumps(out))
""" % (SEED, sorted(FROZEN))


def test_llama_plugin_keeps_the_reference_numbers_to_the_bit():
    r = subprocess.run([sys.executable, "-c", _FREEZE], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.splitlines()[-1]) == FROZEN


@pytest.mark.parametrize("block", ["mixtral_8x22b", "deepseek_v2_236b",
                                   "smollm_360m+mla", "smollm_360m+moe"])
def test_llama_plugin_refuses_an_expert_or_latent_block(block):
    import dataclasses

    from repro.configs import get_arch

    llama = models.load({})
    config = harness.load_cell("smollm-360m.m2-mean.local10").config
    arch, _, part = block.partition("+")
    base = get_arch(arch)
    if part:
        base = dataclasses.replace(
            base, **{part: getattr(get_arch("deepseek_v2_236b"), part)})
    with pytest.raises(ValueError, match="not the plain Llama-style block"):
        llama.arch_config(dict(config, program_arch=arch), base)


# A second model, as a later configuration would bring it: its plug-in,
# configuration, traffic, limits and readers, and its entries in
# BENCHMARK.json, all new files in a checkout of their own.  The plug-in
# computes the Llama block (it loads that plug-in), names a scope of its
# own and counts FLOPs its own way.
_TOY_MODEL = '''
from benchmarks.chip import models

_llama = models.load({})
arch_config = _llama.arch_config
apply_options = _llama.apply_options
init_weights = _llama.init_weights
loss = _llama.loss
SCOPES = ("toy_scope",)


def train_flops_per_token(config, seq_len):
    return 12345 * seq_len
'''
_TOY_READERS = {
    "toy_scope_ms": 'def read(ctx):\n'
                    '    return ctx["scope_ms"].get("toy_scope")\n',
    "toy_flops": 'def read(ctx):\n    return ctx["flops_per_token"]\n',
}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    home = root / "benchmarks" / "chip"
    for sub in ("models", "metrics", "configs", "traffic", "limits"):
        (home / sub).mkdir(parents=True)
    (home / "models" / "toy.py").write_text(_TOY_MODEL)
    for name, src in _TOY_READERS.items():
        (home / "metrics" / f"{name}.py").write_text(src)
    config = dict(harness.load_cell("smollm-360m.m2-mean.local10").config,
                  reference_model="toy", **TINY)
    (home / "configs" / "toy.json").write_text(json.dumps(config))
    traffic = json.loads((CHIP / "traffic" / "local10.json").read_text())
    (home / "traffic" / "toy2.json").write_text(
        json.dumps(dict(traffic, t_client=2)))
    (home / "limits" / "toy.toy2.json").write_text(json.dumps(
        tiny_limited("smollm-360m.m2-mean.local10").limits))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "a test",
                            "file": "benchmarks/chip/configs/toy.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "toy.toy2", "config": "toy",
                              "traffic": "toy2", "chips": 1,
                              "why": "a test"})
    spec["per_layer"] += [
        {"name": n, "unit": "ms", "better": "lower", "source": "device_trace",
         "layer": "model", "moves": "tokens_per_s",
         "workloads": ["toy.toy2"]} for n in _TOY_READERS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_plugin_and_readers_are_taken_up_from_new_files(toy_root):
    cell = harness.load_cell("toy.toy2", root=toy_root)
    assert cell.home == toy_root / "benchmarks" / "chip"
    assert [m["name"] for m in cell.per_layer][-2:] == list(_TOY_READERS)
    r = harness.run(cell, SEED, 0.2, True, time.perf_counter())
    assert r["correct"], r["checks"]
    # the plug-in's FLOPs reach the readers; the CPU has no device plane,
    # so its scope reader finds nothing to read and is left out
    assert r["metrics"]["toy_flops"]["value"] == 12345 * TINY["seq_len"]
    assert "toy_scope_ms" not in r["metrics"]


def _renamed(hlo, old, new):
    """``hlo`` with the scope ``old`` on every op_name path named ``new``."""
    return re.sub(rf"(?<=[/(]){old}(?=[/)])", new, hlo)


def test_a_plugin_scope_is_measured_and_read_from_new_files(toy_root):
    """The recorded one-chip trace, its program's ``mlp`` scope renamed to
    one that only the toy plug-in names: the new reader reads what
    ``mlp`` read, and ``phases.SCOPES`` alone finds nothing of it."""
    t = tr.load(str(TESTDATA / "phases_local10.xplane.pb"))
    red = tr.reduce(t)
    hlo = gzip.open(TESTDATA / "phases_local10.hlo.txt.gz", "rt").read()
    toy = models.load({"reference_model": "toy"},
                      toy_root / "benchmarks" / "chip" / "models")
    renamed = _renamed(hlo, "mlp", "toy_scope")
    ctx = phases.trace_context(t, red, renamed, toy.SCOPES)
    plain = phases.trace_context(t, red, hlo)["scope_ms"]
    read = harness._reader("toy_scope_ms",
                           toy_root / "benchmarks" / "chip" / "metrics")
    assert read(ctx) == plain["mlp"] > 0
    assert "mlp" not in ctx["scope_ms"]
    assert "toy_scope" not in phases.trace_context(t, red, renamed)[
        "scope_ms"]


def test_a_named_scope_of_a_cpu_program_maps_to_its_ops():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("toy_scope"):
            y = jnp.tanh(x @ x)
        return y.sum()

    text = f.lower(jnp.ones((8, 8))).compile().as_text()
    extra = phases.scope_map(text, phases.SCOPES + ("toy_scope",))
    assert any(v == ("toy_scope",) for v in extra.values())
    assert all(v == () for v in phases.scope_map(text).values())


PHASE_METRICS = ("local_ms", "sgd_update_ms", "attention_ms", "mlp_ms",
                 "lm_head_ms", "gossip_ms", "host_turnaround_ms",
                 "encode_ms", "decode_mix_ms")


@pytest.mark.parametrize("recorded", ["phases_local10", "phases_ts6"])
def test_phase_readers_give_what_phases_gives(recorded):
    """The nine phase readers over the harness's context of a recorded
    chip trace read exactly ``phases.reduce_phases``' numbers."""
    t = tr.load(str(TESTDATA / f"{recorded}.xplane.pb"))
    red = tr.reduce(t)
    hlo = gzip.open(TESTDATA / f"{recorded}.hlo.txt.gz", "rt").read()
    ctx = {"trace": red, **phases.trace_context(t, red, hlo)}
    want = phases.reduce_phases(t, t.host, hlo)
    for name in PHASE_METRICS:
        got = harness._reader(name, CHIP / "metrics")(ctx)
        assert got == want[name], name
    wire = recorded == "phases_ts6"
    assert (want["encode_ms"] is not None) == wire
    assert all(want[n] is not None for n in PHASE_METRICS[:7])


def test_program_afresh_compiles_though_the_program_has_run():
    import jax

    cell = tiny_limited("smollm-360m.m2-mean.local10")
    fed = harness.Federation(cell.config, cell.traffic)
    batch_fn = harness.batches(cell, 3)
    state = fed.new_state(3)
    state, _ = fed.engine.run_epoch(state, 0, batch_fn)
    compiles = []

    def listen(event, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        fed.engine.epoch_program(state, 1, batch_fn)
        assert not compiles          # the program that ran, from memory
        text, memory = harness.program_afresh(fed, state, 1, batch_fn)
        assert compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert set(memory) == {"argument_bytes", "output_bytes", "temp_bytes"}
    assert memory["temp_bytes"] > 0
    assert "local_period" in text
