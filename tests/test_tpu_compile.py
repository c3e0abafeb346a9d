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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.comm.compressors import bucket_block
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


def _placed_four_chip_step(topo, backend):
    """Compile chip_smoke's four-chip phase's epoch step: M=4 servers, one
    per chip of the 2x2 mesh, int8 physical wire with error feedback, at
    its depth.  Returns the compiled step and, for the shard_map wire,
    the element count of one round's gathered codes (M x the padded
    bucket)."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("server",))
    wire = dict(SMOKE.WIRE)
    gathered = None
    if backend == "shard_map":
        def build(topo_fl, params):
            nonlocal gathered
            server = jax.eval_shape(lambda p: jax.tree.map(
                lambda x: jnp.zeros((4,) + x.shape, x.dtype), p), params)
            be = shd.fl_consensus_backend(topo_fl, mesh, server,
                                          tp_axis=None, **wire)
            blk, nb = bucket_block(
                sum(x.size for x in jax.tree.leaves(params)),
                be.inner.block, be.compressor.chunk)
            gathered = 4 * blk * nb
            return be
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
    return compiled, gathered


@pytest.fixture(scope="module")
def placed_step(topo):
    """``_placed_four_chip_step`` by backend, compiled once per module."""
    cache = {}

    def get(backend):
        if backend not in cache:
            cache[backend] = _placed_four_chip_step(topo, backend)
        return cache[backend]
    return get


@pytest.mark.parametrize("backend", ["shard_map", "einsum"])
def test_four_chip_placed_step_fits_each_chip(placed_step, backend):
    """M=4 servers, one per chip of the 2x2 mesh, int8 physical wire with
    error feedback: the four-chip smoke phase's epoch step, at its depth.
    Every chip's share must fit its HBM."""
    compiled, _ = placed_step(backend)
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < HBM_BYTES, peak


# The same shard_map step's per-chip peak before the wire decoded and
# mixed in the bucket's chunk view (commit 6bc7583, JAX 0.9.0 with libtpu
# 0.0.34, compiled as in ``_placed_four_chip_step``): 12,165,821,440
# bytes, most of it f32 copies of the gathered codes.
F32_DECODE_PEAK_BYTES = 12_165_821_440


def test_four_chip_wire_never_decodes_the_gather_to_f32(placed_step):
    """The shard_map wire decodes and mixes the gathered int8 codes in one
    elementwise pass: the compiled four-chip step holds no f32 buffer the
    size of the gathered codes (M x the padded bucket), and each chip's
    peak is at least 2 GB under the program that converted them."""
    compiled, gathered = placed_step("shard_map")
    hlo = compiled.as_text()
    sizes = {int(np.prod([int(n) for n in dims.split(",")]))
             for dims in re.findall(r"f32\[([\d,]+)\]", hlo)}
    assert max(sizes) < gathered, (max(sizes), gathered)
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= F32_DECODE_PEAK_BYTES - 2e9, peak


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


DEEPSEEK_CELL = "deepseek-v2-lite-5l-8e.m2-mean.local10"


def test_deepseek_cell_epoch_step_fits_through_the_grouped_matmul(one_chip):
    """The benchmark cell ``deepseek-v2-lite-5l-8e.m2-mean.local10``'s
    epoch step, built as its run builds it (M=2 servers of one client, B=1,
    S=256, T_C=10; 5 layers, 8 of 64 experts held): it fits one chip, runs
    the held experts through the grouped-matmul kernel, and holds no
    (held, tokens, hidden) capacity buffer."""
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.chip import harness
    from repro.core.schedule import EpochSchedule as Sched

    cell = harness.load_cell(DEEPSEEK_CELL)
    c = cell.config
    fed = harness.Federation(c, cell.traffic)
    params = jax.eval_shape(fed._weights, jax.random.key(0))
    state = jax.eval_shape(lambda p: init_dfl_state(
        fed.engine.cfg, p, fed.optimizer, jax.random.key(1)), params)
    m, n = c["servers"], c["clients_per_server"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cell.traffic["t_client"], m, n, c["per_client_batch"],
         c["seq_len"]), jnp.int32)}
    sched = Sched(jax.ShapeDtypeStruct((m, n), jnp.float32),
                  jax.ShapeDtypeStruct((m, m), jnp.float32))
    compiled = fed.engine._step().lower(
        *(_shaped(t, jax.tree.map(lambda _: one_chip, t))
          for t in (state, batch, sched))).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < HBM_BYTES, peak
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    held = fed.arch.moe.held
    tokens = c["per_client_batch"] * c["seq_len"]
    capacity = rf"\[(?:\d+,)*{held},{tokens},{c['hidden_size']}\]"
    assert not re.search(capacity, hlo), re.search(capacity, hlo).group()


@pytest.fixture(scope="module")
def pod_topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:16x16")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:16x16 topology can be described here: {e}")


def test_moe_decode_32k_partitions_over_the_serving_mesh(pod_topo,
                                                         monkeypatch):
    """DeepSeek-V2-Lite's ``decode_32k`` serve step, built as
    ``launch/specs.py`` builds it, over a described 16x16 v5e serving mesh
    with the expert tables sharded over "model".  Its layers hold every
    expert, so they keep the capacity dispatch: the SPMD partitioner
    refuses to split a Mosaic kernel, and the grouped matmul must not
    appear.  Each chip's share fits its HBM."""
    from repro.configs import INPUT_SHAPES
    from repro.launch import specs

    mesh = Mesh(np.array(pod_topo.devices).reshape(16, 16),
                ("data", "model"))
    monkeypatch.setattr(specs, "make_serve_mesh",
                        lambda multi_pod=False: mesh)
    bundle = specs.build_decode_lowering("deepseek_v2_lite",
                                         INPUT_SHAPES["decode_32k"])
    compiled = bundle.jitted.lower(*bundle.args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < HBM_BYTES, peak
