"""Compile the chip's programs for a described TPU v5e, with no chip attached.

Each test lowers and compiles with the TPU compiler for a topology that is
described, not present (``jax.experimental.topologies``): the compiler then
refuses what the chip would refuse — an unaligned kernel tile, a program
larger than the 15.75 GB of HBM it may use — at no chip time.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and pytest-xdist imports
every test file in every worker.  Everything built from the topology is
built in fixtures or tests.  The persistent compilation cache is off
around these compiles: an entry written here cannot be read back without a
chip.
"""
import dataclasses
import importlib
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import init_dfl_state, make_engine
from repro.core.schedule import EpochSchedule
from repro.launch import sharding as shd
from repro.launch.train import _setup_lm
from repro.models import transformer as tf

HBM_BYTES = 15.75e9          # what the v5e compiler lets one program use
ARCH = "smollm-360m"


def _chip_smoke():
    """``chip_smoke.py``'s module, for the shapes its phases run."""
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _chip_smoke()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


def _step_inputs(servers, num_layers=None, backend_fn=None, **engine_kw):
    """The trainer's engine at ``chip_smoke.py``'s shape, with the
    abstract state, batch and schedule its epoch step takes.
    ``backend_fn(topology, params)`` builds a mesh-bound backend."""
    shape = SMOKE.SHAPE
    cfg, topo_fl, loss_fn, opt, _ = _setup_lm(
        ARCH, False, servers, shape["clients"], shape["t_client"],
        shape["t_server"], "ring", SMOKE.GAMMA, shape["seq_len"],
        shape["per_client_batch"], 0, "reference", num_layers=num_layers)
    params = jax.eval_shape(lambda: tf.init_params(jax.random.key(0), cfg))
    if backend_fn is not None:
        engine_kw["consensus_backend"] = backend_fn(topo_fl, params)
    eng = make_engine(topo_fl, loss_fn, opt, **engine_kw)
    state = jax.eval_shape(
        lambda p: init_dfl_state(eng.cfg, p, opt, jax.random.key(1)), params)
    m, n = servers, shape["clients"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (shape["t_client"], m, n, shape["per_client_batch"],
         shape["seq_len"]), jnp.int32)}
    sched = EpochSchedule(jax.ShapeDtypeStruct((m, n), jnp.float32),
                          jax.ShapeDtypeStruct((m, m), jnp.float32))
    return eng, state, batch, sched


def test_full_width_epoch_step_fits_one_chip(one_chip):
    """smollm-360m at its published widths and depth, M=2 servers of one
    client, batch 1, sequence 256: the one-chip smoke run's epoch step."""
    eng, state, batch, sched = _step_inputs(2)
    compiled = eng._step().lower(
        *(_shaped(t, jax.tree.map(lambda _: one_chip, t))
          for t in (state, batch, sched))).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < HBM_BYTES, peak


@pytest.mark.parametrize("backend", ["shard_map", "einsum"])
def test_four_chip_placed_step_fits_each_chip(topo, backend):
    """M=4 servers, one per chip of the 2x2 mesh, int8 physical wire with
    error feedback: the four-chip smoke phase's epoch step, at its depth.
    Every chip's share must fit its HBM."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("server",))
    wire = dict(SMOKE.WIRE)
    if backend == "shard_map":
        def build(topo_fl, params):
            server = jax.eval_shape(lambda p: jax.tree.map(
                lambda x: jnp.zeros((4,) + x.shape, x.dtype), p), params)
            return shd.fl_consensus_backend(topo_fl, mesh, server,
                                            tp_axis=None, **wire)
        kw = {"backend_fn": build}
    else:
        kw = wire
    eng, state, batch, sched = _step_inputs(
        4, num_layers=SMOKE.FOUR_CHIP_LAYERS, **kw)
    state_sh = shd.named(shd.fl_state_specs(state, mesh, tp_axis=None), mesh)
    batch_sh = NamedSharding(mesh, shd.fl_batch_spec(mesh, False))
    eng = dataclasses.replace(eng, shardings=(state_sh, batch_sh))
    # placed, the step takes the PRNG key as raw key data (engine._to_step)
    state = state._replace(rng=jax.eval_shape(jax.random.key_data, state.rng))
    rep = NamedSharding(mesh, P())
    compiled = eng._step().lower(
        _shaped(state, state_sh),
        _shaped(batch, jax.tree.map(lambda _: batch_sh, batch)),
        _shaped(sched, jax.tree.map(lambda _: rep, sched))).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < HBM_BYTES, peak


def _kernel(name):
    # repro.kernels re-exports functions under the module names, so the
    # modules themselves are reached by their dotted path
    return importlib.import_module(f"repro.kernels.{name}")


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads", [15, 5])
def test_flash_attention_compiles_at_model_widths(one_chip, heads):
    fa = _kernel("flash_attention")
    q = jax.ShapeDtypeStruct((1, heads, 256, 64), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 5, 256, 64), jnp.float32,
                              sharding=one_chip)
    _assert_kernel(jax.jit(lambda q, k, v: fa.flash_attention_bhsd(
        q, k, v, interpret=False)).lower(q, kv, kv).compile())


def test_rmsnorm_compiles_at_model_width(one_chip):
    rn = _kernel("rmsnorm")
    x = jax.ShapeDtypeStruct((256, 960), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((960,), jnp.float32, sharding=one_chip)
    _assert_kernel(jax.jit(lambda x, s: rn.rmsnorm_2d(
        x, s, interpret=False)).lower(x, s).compile())


def test_bucketed_gossip_round_compiles_at_model_flat_size(one_chip):
    """One physical-wire round over smollm-360m's whole flat model for
    M=2 servers.  The TPU lowering needs the per-tile scale block to be a
    multiple of 128 chunks, so the tile is 128 x 256 = 32768 elements (the
    2048 default is refused)."""
    cm = _kernel("consensus_mix")
    m, chunk, block_d = 2, 256, 32768
    cfg = _setup_lm(ARCH, False, m, 1, 1, 1, "ring", 0.05, 8, 1, 0,
                    "reference")[0]
    n_params = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: tf.init_params(jax.random.key(0), cfg))))
    d = -(-n_params // block_d) * block_d

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _assert_kernel(jax.jit(lambda *a: cm.bucketed_gossip_round_2d(
        *a, chunk=chunk, block_d=block_d, interpret=False)).lower(
            s((m, m), jnp.float32), s((m, d), jnp.int8),
            s((m, d // chunk), jnp.float32), s((m, d), jnp.float32),
            s((m, d), jnp.float32), s((m, d), jnp.float32)).compile())
