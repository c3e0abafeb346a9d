"""Launcher invariants: plans, sharding resolver, roofline parser,
supported-pair registry.  (The actual 512-device compiles live in
launch/dryrun.py — these tests cover the pure-python layers.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.launch import roofline as rl
from repro.launch.mesh import FLMeshSpec
from repro.launch.plans import PLANS, plan_for
from repro.launch.specs import supported_pairs


def test_every_arch_has_a_plan():
    for arch_id in ARCH_IDS:
        assert plan_for(arch_id) is not None


@pytest.mark.parametrize("multi_pod", [False, True])
def test_plans_fill_the_mesh(multi_pod):
    target = 512 if multi_pod else 256
    for plan in PLANS.values():
        spec = plan.fl_spec(multi_pod)
        assert spec.total_devices() == target, plan.arch_id
        if multi_pod:
            assert spec.num_servers % 2 == 0 or spec.num_servers == 2


def test_plans_param_budget():
    """params/device (bf16/f32 per plan) fits alongside grads in 16 GB."""
    for arch_id in ARCH_IDS:
        plan = plan_for(arch_id)
        cfg = get_arch(arch_id)
        spec = plan.fl_spec(False)
        bytes_per = 2 if plan.param_dtype == "bfloat16" else 4
        per_dev = cfg.param_count() * bytes_per / (spec.fsdp * spec.tp)
        assert per_dev * 2 < 16e9, (arch_id, per_dev / 1e9)


def test_supported_pairs_count():
    pairs = supported_pairs()
    assert len(pairs) == 37          # 11 x 3 + 4 long-context archs
    longs = [a for a, s in pairs if s == "long_500k"]
    assert sorted(longs) == sorted([
        "mixtral_8x22b", "gemma2_27b", "jamba_1_5_large_398b",
        "mamba2_780m"])


def test_collective_parser_on_synthetic_hlo():
    hlo = """
ENTRY %main (a: f32[128,256]) -> f32[128,256] {
  %ag = f32[128,256]{1,0} all-gather(%a), dimensions={0}
  %ar = bf16[64,64]{1,0} all-reduce(%b)
  %rs = f32[32]{0} reduce-scatter(%c), dimensions={0}
  %cp = u16[16,16]{1,0} collective-permute(%d)
  %a2a = f32[8,8]{1,0} all-to-all(%e), dimensions={1}
}
"""
    stats = rl.collective_bytes(hlo)
    assert stats.bytes_by_kind["all-gather"] == 128 * 256 * 4
    assert stats.bytes_by_kind["all-reduce"] == 64 * 64 * 2 * 2    # x2
    assert stats.bytes_by_kind["reduce-scatter"] == 32 * 4
    assert stats.bytes_by_kind["collective-permute"] == 16 * 16 * 2
    assert stats.bytes_by_kind["all-to-all"] == 8 * 8 * 4
    assert stats.total_bytes == sum(stats.bytes_by_kind.values())


def test_roofline_terms():
    meta = {"arch": "qwen3_1_7b", "shape": "train_4k", "multi_pod": False,
            "M": 2, "N": 1, "R": 8, "TP": 16, "per_client_batch": 128,
            "t_client": 2, "t_server": 25, "params": int(2e9),
            "dtype": "bfloat16", "active_params": 1e9}
    cost = {"flops": 1e12, "bytes accessed": 1e11}
    coll = rl.CollectiveStats({"all-gather": int(2e10)}, {"all-gather": 3})
    rep = rl.roofline(meta, 256, cost, coll)
    tokens = 2 * 2 * 1 * 128 * 4096          # T_C * M * N * b * seq
    assert rep.model_flops == pytest.approx(6 * 1e9 * tokens)
    assert rep.compute_s == pytest.approx(6 * 1e9 * tokens / 256 /
                                          rl.PEAK_FLOPS)
    assert rep.collective_s == pytest.approx(2e10 / rl.ICI_BW)
    assert rep.hlo_flops_per_device == 1e12


def test_sharding_resolver_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as shd
    mesh = jax.sharding.Mesh(
        np.array(jax.devices() * 1).reshape(1, 1, 1, 1),
        ("server", "client", "replica", "model"))
    params = {
        # DFL layout: every leaf carries (M, N) client axes
        "embed": jnp.zeros((2, 2, 128, 64)),
        "stack": {"w_q": jnp.zeros((2, 2, 9, 64, 16, 8))},
        "norm": {"scale": jnp.zeros((2, 2, 64))},
    }
    specs = shd.fl_param_specs(params, mesh)
    assert specs["embed"][0] == "server" and specs["embed"][1] == "client"
    assert specs["stack"]["w_q"][0] == "server"
    assert specs["stack"]["w_q"][1] == "client"
    assert specs["norm"]["scale"][:2] == ("server", "client")


def test_mesh_specs_validate():
    spec = FLMeshSpec(num_servers=4, clients_per_server=4, fsdp=1, tp=16)
    assert spec.total_devices() == 256
    assert spec.devices_per_client == 16


# ---------------------------------------------------------------------------
# one server per device, the compile cache, and the chip entry point
# ---------------------------------------------------------------------------

REPO = __import__("pathlib").Path(__file__).resolve().parents[1]


def test_server_mesh_specs_replicate_absent_axes():
    """On a ('server',) mesh (one server per chip) the client grid dim and
    the batch's client dim replicate instead of naming a missing axis."""
    from jax.sharding import PartitionSpec as P

    from repro.launch import sharding as shd
    from repro.launch.train import server_mesh
    mesh = server_mesh(1)
    state = {"w": jnp.zeros((1, 2, 3, 4)), "count": jnp.zeros(())}
    specs = shd.fl_state_specs(state, mesh, tp_axis=None)
    assert specs["w"] == P("server", None, None, None)
    assert specs["count"] == P()
    assert shd.fl_batch_spec(mesh, batch_div_replica=True,
                             batch_over_model=True) == P(None, "server",
                                                         None, None)
    shd.named(specs, mesh)          # every named axis exists on the mesh


def test_compile_cache_dir_honours_environment(monkeypatch, tmp_path):
    from repro.launch.compile_cache import compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_the_ignored_checkout_dir(monkeypatch):
    from repro.launch.compile_cache import compile_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == str(REPO / ".jax_cache")
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_enable_compile_cache_points_jax_there(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_without_a_tpu(capsys, monkeypatch):
    """Run as a script on a host whose JAX has no TPU, chip_smoke.py exits
    nonzero before any work and prints no result line."""
    import runpy
    import sys
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU: the smoke run would train")
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(str(REPO / "chip_smoke.py"), run_name="__main__")
    assert exc.value.code not in (0, None)
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
