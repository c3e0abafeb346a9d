"""DeepSeek-V2-Lite's block in the trainer against the benchmark's plain
reference (``benchmarks/chip/models/deepseek_v2.py``), on seeded random
weights at a small size on the CPU: latent attention without q-LoRA and
with YaRN, one dense and two routed layers of 16 experts, 4 held, top-6,
the grouped matmul in interpret mode.

Both sides run in float32 at ``highest`` matmul precision, so they differ
by the order of their sums alone: about 1e-6 relative in the loss and in
every gradient leaf.  The tolerances below (1e-5 and 1e-4) leave ten and
a hundred times that, and a layer that renormalises its top-k or holds
the wrong experts misses them by orders of magnitude.
"""
import dataclasses
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import models  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import init_dfl_state, make_engine  # noqa: E402
from repro.launch.train import _setup_lm  # noqa: E402
from repro.models import modules as nn  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

CELL = json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                   "deepseek-v2-lite-5l-8e.m2-mean.json").read_text())
# every width a multiple of 128 or under it, as the kernel's tiling takes;
# 2 x 32 tokens x top-6 = 384 token-slots, three row tiles
SMALL = dict(CELL, hidden_size=128, intermediate_size=256,
             num_attention_heads=2, num_key_value_heads=2, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             moe_intermediate_size=128, num_hidden_layers=3,
             n_routed_experts=4, first_held_expert=0, vocab_size=512,
             published=dict(CELL["published"], n_routed_experts=16))
B, S = 2, 32
LOSS_TOL, GRAD_TOL = 1e-5, 1e-4
PLUGIN = models.load(CELL)


def _arch(cfg, **moe):
    arch = PLUGIN.arch_config(cfg, get_arch("deepseek_v2_lite"))
    return dataclasses.replace(arch, moe=dataclasses.replace(arch.moe, **moe))


def _setup(cfg=SMALL, seed=3):
    w = jax.jit(lambda k: PLUGIN.init_weights(k, cfg))(jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(seed + 1), (B, S), 0,
                                cfg["vocab_size"])
    return w, tokens


def _gaps(arch, cfg, w, tokens):
    """The relative gap of the loss and the largest relative gap of a
    gradient leaf between the trainer (``arch``) and the reference
    (``cfg``)."""
    loss_fn = tf.make_loss_fn(arch, tf.ApplyOptions(
        **PLUGIN.apply_options(cfg)))
    with jax.default_matmul_precision("highest"):
        (got, _), g_got = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(w, {"tokens": tokens}, None)
        want, g_want = jax.jit(jax.value_and_grad(
            lambda w: PLUGIN.loss(w, tokens, cfg)))(w)
    grad = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
               for a, b in zip(jax.tree.leaves(g_got),
                               jax.tree.leaves(g_want)))
    return abs(float(got - want)) / abs(float(want)), grad


def test_weights_have_the_trainers_layout():
    w, _ = _setup()
    want = jax.eval_shape(lambda k: tf.init_params(k, _arch(SMALL)),
                          jax.random.key(0))
    assert jax.tree.structure(w) == jax.tree.structure(want)
    assert ([x.shape for x in jax.tree.leaves(w)]
            == [x.shape for x in jax.tree.leaves(want)])


def test_loss_and_gradients_match_the_reference():
    w, tokens = _setup()
    loss_gap, grad_gap = _gaps(_arch(SMALL), SMALL, w, tokens)
    assert loss_gap < LOSS_TOL and grad_gap < GRAD_TOL, (loss_gap, grad_gap)


@pytest.mark.parametrize("wrong", [{"norm_topk_prob": True},
                                   {"first_held_expert": 4}])
def test_a_wrong_routed_layer_fails_the_comparison(wrong):
    """Renormalising the top-k gates, or holding experts 4-7 in place of
    0-3, is caught by the same tolerances."""
    w, tokens = _setup()
    loss_gap, grad_gap = _gaps(_arch(SMALL, **wrong), SMALL, w, tokens)
    assert loss_gap > LOSS_TOL or grad_gap > GRAD_TOL, (loss_gap, grad_gap)


def _layer(seed, e):
    """One routed layer's weights with ``e`` experts, and its input."""
    cfg = dict(SMALL, n_routed_experts=e)
    w, _ = _setup(cfg, seed)
    layer = jax.tree.map(lambda x: x[0], w["stack"][0]["ffn"])
    x = jax.random.normal(jax.random.key(seed + 7), (B, S, SMALL[
        "hidden_size"]), jnp.float32)
    return layer, x


def _reference_layer(layer, x, cfg):
    m = PLUGIN.dims(cfg)
    y, _ = PLUGIN.routed(x, layer, m)
    return y + PLUGIN._mlp(x, layer["shared"])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each share holds 4 of the 16 experts; the routed parts of all four,
    with the shared experts counted once, give the whole layer."""
    layer, x = _layer(11, 16)
    shared = nn.mlp_apply(layer["shared"], x)
    total = shared
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            part = dict(layer, **{k: layer[k][4 * i:4 * i + 4]
                                  for k in ("w_gate", "w_up", "w_down")})
            arch = _arch(SMALL, first_held_expert=4 * i)
            y, _, _ = nn.moe_apply(part, x, arch, no_drop=True)
            total = total + (y - shared)
        whole = _reference_layer(
            layer, x, dict(SMALL, n_routed_experts=16))
    err = float(jnp.linalg.norm(total - whole) / jnp.linalg.norm(whole))
    assert err < LOSS_TOL, err


def test_a_layer_holding_every_expert_keeps_the_exact_capacity_dispatch():
    """A whole layer (every expert held, as the sharded serving plans hold
    it) with ``no_drop`` takes the capacity dispatch at capacity t, which
    the SPMD partitioner can split, and no grouped-matmul kernel; it is the
    reference's uncut layer."""
    layer, x = _layer(17, 16)
    arch = dataclasses.replace(_arch(SMALL), moe=dataclasses.replace(
        _arch(SMALL).moe, num_experts=16, num_held_experts=None))
    jaxpr = str(jax.make_jaxpr(
        lambda p, x: nn.moe_apply(p, x, arch, no_drop=True))(layer, x))
    assert "pallas_call" not in jaxpr
    with jax.default_matmul_precision("highest"):
        y, _, _ = nn.moe_apply(layer, x, arch, no_drop=True)
        want = _reference_layer(layer, x, dict(SMALL, n_routed_experts=16))
    err = float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))
    assert err < LOSS_TOL, err


def test_drop_free_when_every_token_picks_one_held_expert():
    """A router that sends every token to expert 1 first: that group holds
    all 64 tokens, over twice the 30 rows that a capacity factor of 1.25
    gives an expert, and nothing is dropped."""
    layer, x = _layer(13, 4)
    x = x.at[..., 0].set(10.0)
    layer["router"] = layer["router"].at[0, 1].set(5.0)
    arch = _arch(SMALL)
    with jax.default_matmul_precision("highest"):
        probs, _, idx = nn.moe_route(layer, x, arch)
        assert bool((idx[..., 0] == 1).all())
        y, _, load = nn.moe_apply(layer, x, arch, no_drop=True)
        want = _reference_layer(layer, x, SMALL)
    err = float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))
    assert err < LOSS_TOL, err
    counts = np.bincount(np.asarray(idx).ravel(), minlength=16)[:4]
    assert counts[1] == B * S > 1.25 * B * S * 6 / 16
    assert float(load) == pytest.approx(counts.max() / counts.mean())


def test_yarn_at_the_published_sizes():
    arch = get_arch("deepseek_v2_lite")
    rs = CELL["rope_scaling"]
    assert nn.yarn_correction_range(arch.rope_scaling, 64, 1e4) == (10, 23)
    assert PLUGIN.yarn_range(rs, 64, 1e4) == (10, 23)
    scale = 1 / math.sqrt(192) * 1.589627
    assert nn.mla_softmax_scale(arch) == pytest.approx(scale, rel=1e-6)
    assert nn.mla_softmax_scale(arch) == pytest.approx(0.114722, abs=1e-6)
    assert PLUGIN.softmax_scale(PLUGIN.dims(CELL)) == pytest.approx(
        nn.mla_softmax_scale(arch), rel=1e-12)
    # the cos/sin magnitude mscale(40, 0.707) / mscale(40, 0.707) is 1
    cos, sin = PLUGIN.rope_tables(PLUGIN.dims(CELL), CELL["seq_len"])
    freqs = np.asarray(nn.rope_frequencies(64, 1e4, arch.rope_scaling))
    ang = np.outer(np.arange(CELL["seq_len"]), freqs)
    # float32 frequencies: about 1e-5 of angle at 256 positions
    np.testing.assert_allclose(cos[:, :32], np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(sin[:, 32:], np.sin(ang), atol=2e-5)


def test_setup_lm_trains_through_the_engine():
    """``_setup_lm``'s loss for the smoke configuration (2 of 4 experts
    held, so the grouped-matmul path) through the engine's epoch program:
    the loss falls and the record carries the routing counter."""
    cfg, topo, loss_fn, opt, pipe = _setup_lm(
        "deepseek_v2_lite", True, 2, 1, 3, 1, "ring", 0.1, 32, 2, 0,
        "reference")
    assert cfg.moe.held < cfg.moe.num_experts
    eng = make_engine(topo, loss_fn, opt)
    state = init_dfl_state(eng.cfg, tf.init_params(jax.random.key(0), cfg),
                           opt, jax.random.key(1))

    def batch_fn(epoch, alive):
        return pipe.epoch_batches(0, server_ids=alive)

    recs = []
    for epoch in range(3):
        state, rec = eng.run_epoch(state, epoch, batch_fn)
        recs.append(rec)
    assert recs[-1]["loss"] < recs[0]["loss"]
    assert all(r["moe_load_max"] >= 1.0 for r in recs)
    assert {"nll", "aux"} <= set(recs[0])
